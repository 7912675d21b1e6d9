"""The trace reduction on a synthetic trace built to the TPU schema:
planes ``/device:TPU:<id>`` with an ``XLA Ops`` line whose events are
named by their HLO text (a ``while`` enclosing its body's operations),
and the host's ``python3`` line with the harness's ``bench.*`` spans."""
import pytest

from bench import xtrace


def xspace(planes) -> bytes:
    """``planes``: {plane name: {line name: [(event name, start_ns,
    duration_ns)]}} -> a serialized XSpace."""
    from jax.profiler import ProfileData
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        body = ""
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {d * 1000} }}\n" for n, s, d in evs)
            body += (f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                     f"{ev}}}\n")
        out.append(f'planes {{ id: {pid} name: "{pname}"\n{body}{meta}}}\n')
    return ProfileData.text_proto_to_serialized_xspace("".join(out))


WHILE = "%while.3 = (s32[]) while(s32[] %p)"
DOT = "%fusion.7 = bf16[8,128]{1,0} fusion(bf16[8,128] %a)"
KERN = ('%fused_dsgd_pallas.2 = bf16[8,128]{1,0} custom-call(bf16[8,128] %x),'
        ' custom_call_target=\\"tpu_custom_call\\"')
CP = "%collective-permute-done.1 = f32[8,128]{1,0} collective-permute-done(f32[8,128] %s)"


def trace(tmp_path):
    dev0 = [(WHILE, 1000, 400), (DOT, 1000, 150), (KERN, 1200, 200),
            (CP, 1500, 100)]
    dev1 = [(DOT, 1000, 300), (KERN, 1400, 100)]
    start = CP.replace("done", "start")
    host = [("bench.feed", 1000, 100), ("bench.dispatch", 1100, 50),
            ("token_batches", 1420, 40), ("bench.wait", 1400, 500)]
    data = xspace({
        "/device:TPU:0": {"XLA Ops": dev0,
                          "XLA Modules": [("jit__step(123)", 1000, 600)]},
        "/device:TPU:1": {"XLA Ops": dev1,
                          "Async XLA Ops": [(start, 1250, 150)]},
        "/host:CPU": {"python3": host}})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(data)
    return str(path)


def test_reduce_busy_kernels_collectives(tmp_path):
    s = xtrace.reduce(trace(tmp_path), [0, 1])
    # window: first bench span 1000 ns to the last's end 1900 ns
    assert s["window_s"] == pytest.approx(900e-9)
    # chip 0 busy 1000-1400 (the while and its body) and 1500-1600; chip
    # 1 1000-1300 and 1400-1500 (an asynchronous transfer is no operation)
    assert s["busy_s"] == pytest.approx((500e-9 + 400e-9) / 2)
    k = s["kernels"]["fused_dsgd_pallas"]
    assert k["count"] == 2 and k["seconds"] == pytest.approx(300e-9)
    assert s["collective_s"] == pytest.approx([100e-9, 150e-9])
    # chip 0's permute (1500-1600) overlaps no compute; chip 1's
    # asynchronous one (1250-1400) overlaps the fusion until 1300
    assert s["collective_exposed_s"] == pytest.approx([100e-9, 100e-9])
    assert s["modules"] == {"jit__step": 1}


def test_breakdown_names_ops_and_gaps(tmp_path):
    s = xtrace.reduce(trace(tmp_path), [0, 1])
    ops = dict(s["breakdown"]["device_ops"])
    # the enclosing while is not an operation of its own
    assert not any(n.startswith("while") for n in ops)
    assert ops["fusion.7 bf16[8,128]"] == pytest.approx((150e-9 + 300e-9) / 2)
    gaps = s["breakdown"]["idle_gaps"]
    # chip 1 idles 1500-1900 and chip 0 1600-1900 under bench.wait; chip
    # 0's 1400-1500 falls in token_batches, chip 1's 1300-1400 in no span
    assert gaps[0] == ["bench.wait at +0.000s", pytest.approx(400e-9)]
    assert gaps[1] == ["bench.wait at +0.000s", pytest.approx(300e-9)]
    assert sorted(n.split(" at ")[0] for n, _ in gaps[2:]) == [
        "no host span", "token_batches"]


def test_innermost_span_names_the_gap():
    spans = [("bench.wait", 0, 100), ("token_batches", 10, 20)]
    assert xtrace.name_gap(spans, 15) == "token_batches"
    assert xtrace.name_gap(spans, 50) == "bench.wait"
    assert xtrace.name_gap(spans, 500) == "no host span"


def test_interval_helpers():
    assert xtrace.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert xtrace.subtract([[0, 10]], [[2, 3], [5, 6]]) == 8
    assert xtrace.op_base(KERN.replace('\\"', '"')) == "fused_dsgd_pallas"
    assert xtrace.op_base(CP) == "collective-permute-done"
