"""The reduction of the program's own names in a trace (``bench/scopes``)
on synthetic traces built to the TPU schema, its metrics' readers, and
traced runs of the test cells that read every new metric.

A device operation's op name is the ``tf_op`` stat of its ``XLA Ops``
event, as a TPU v5e trace gives it."""
import json

import pytest

from conftest import TINY_LIMITS, tiny_bench

import run
from bench import scopes, spec, xtrace


def xspace(planes) -> bytes:
    """``planes``: {plane: {line: [(name, start_ns, duration_ns[, op
    name])]}} -> a serialized XSpace.  As on the chip, the op name is
    the ``tf_op`` stat of the event's metadata (one per event name),
    beside a stat of another name, and the events carry stats of their
    own."""
    from jax.profiler import ProfileData
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        ops = {ev[0]: ev[3] if len(ev) > 3 else None
               for evs in lines.values() for ev in evs}
        ids = {n: i for i, n in enumerate(sorted(ops), 1)}
        meta = ""
        for n, i in ids.items():
            stat = ('stats { metadata_id: 2 str_value: "fusion" } '
                    f'stats {{ metadata_id: 1 str_value: "{ops[n]}:" }}'
                    if ops[n] is not None else "")
            meta += (f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{n}" {stat} }} }}\n')
        meta += ('stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
                 'stat_metadata { key: 2 value { id: 2 name: "hlo_category"'
                 ' } }\n')
        body = ""
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {d * 1000} stats {{ metadata_id: 2 "
                f"int64_value: 0 }} }}\n" for n, s, d, *_ in evs)
            body += (f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                     f"{ev}}}\n")
        out.append(f'planes {{ id: {pid} name: "{pname}"\n{body}{meta}}}\n')
    return ProfileData.text_proto_to_serialized_xspace("".join(out))


FWD = "jit(_step)/shard_map/vmap(jvp(forward))"
BWD = "jit(_step)/shard_map/vmap(transpose(jvp(forward)))"
REMAT = "jit(_step)/checkpoint/rematted_computation/dot_general"
UPD = "jit(_step)/shard_map/update/mul"
GOS = "jit(_step)/shard_map/update/gossip/cond/branch_0_fun/ppermute"

WHILE = "%while.3 = (s32[]) while(s32[] %p)"
DOT = "%fusion.7 = bf16[8,128]{1,0} fusion(bf16[8,128] %a)"
KERN = "%flash_attention_pallas.2 = bf16[8,128]{1,0} custom-call(bf16[8] %x)"
UKERN = "%fused_dsgd_pallas.3 = bf16[8,128]{1,0} custom-call(bf16[8,128] %x)"
CAST = "%convert.9 = f32[8,128]{1,0} convert(bf16[8,128] %x)"
CP = ("%collective-permute-done.1 = f32[8,128]{1,0} "
      "collective-permute-done((f32[8,128], f32[8,128]) %s)")
CPS = ("%collective-permute-start.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}, "
       "u32[], u32[]) collective-permute-start(f32[8,128] %x)")
COS = "%cosine.4 = f32[64]{0} cosine(f32[64] %t)"


def test_classification_order_and_markers():
    assert scopes.OP_NAME_STAT == "tf_op"
    assert scopes.classify(GOS) == "gossip"
    assert scopes.classify(UPD) == "update"
    assert scopes.classify(BWD + "/dot_general") == "backward"
    # the remat recompute is backward even outside a transpose
    assert scopes.classify(REMAT) == "backward"
    assert scopes.classify(BWD + "/checkpoint/rematted_computation/x") \
        == "backward"
    assert scopes.classify(FWD + "/while/body/closed_call/pallas_call") \
        == "forward"
    # innermost wins: gossip inside update, update before the markers
    assert scopes.classify("jit(f)/update/transpose(jvp(g))/gossip/x") \
        == "gossip"
    assert scopes.classify("jit(f)/update/transpose(jvp(g))/x") == "update"
    # a fusion's op names joined by ';'
    assert scopes.classify("jit(f)/cos;" + FWD + "/mul") == "forward"
    for other in ("jit(_step)/cos", "", "jit(f)/forwarding/x",
                  "jit(f)/updates/x"):
        assert scopes.classify(other) == "unscoped"


def test_result_bytes_from_the_hlo_text():
    assert scopes.result_bytes(CP) == 8 * 128 * 4
    assert scopes.result_bytes(CPS) == 2 * 8 * 128 * 4 + 2 * 4
    assert scopes.result_bytes(
        "%collective-permute.2 = (bf16[4,2]{1,0}, f32[3]{0}) "
        "collective-permute(bf16[4,2] %a, f32[3] %b)") == 16 + 12
    assert scopes.result_bytes(COS) == 256


def train_trace(tmp_path, scoped=True):
    op = (lambda name: name) if scoped else (lambda name: "jit(_step)/x")
    # chip 0: a forward loop (its own time 1000-1050) around a forward
    # fusion and a backward kernel, the update, the gossip's cast and
    # permute, and an unscoped op; chip 1 the same at half the length
    dev0 = [(WHILE, 1000, 400, op(FWD + "/while")),
            (DOT, 1050, 150, op(FWD + "/dot_general")),
            (KERN, 1200, 200, op(BWD + "/pallas_call")),
            (UKERN, 1400, 100, op(UPD)),
            (CAST, 1500, 50, op(GOS)),
            (CPS, 1550, 10, op(GOS)), (CP, 1640, 20, op(GOS)),
            (COS, 1700, 100, "jit(_step)/cos")]
    dev1 = [(n, 1000 + (s - 1000) // 2, d // 2, o) for n, s, d, o in dev0]
    host = [("bench.feed", 1000, 100), ("bench.dispatch", 1100, 50),
            ("bench.wait", 1400, 600)]
    data = xspace({
        "/device:TPU:0": {"XLA Ops": dev0,
                          "Async XLA Ops": [(CPS, 1560, 80, op(GOS))]},
        "/device:TPU:1": {"XLA Ops": dev1},
        "/host:CPU": {"python3": host}})
    path = tmp_path / ("t.xplane.pb" if scoped else "u.xplane.pb")
    path.write_bytes(data)
    return str(path)


def test_train_classes_add_up_to_busy_time(tmp_path):
    path = train_trace(tmp_path)
    t = scopes.train(path, [0, 1], steps=1)
    # chip 0 in ns: forward 50 + 150, backward 200, update 100, gossip
    # 50 + 10 + 20, unscoped 100; chip 1 half of each
    want = {"forward": 200, "backward": 200, "update": 100, "gossip": 80,
            "unscoped": 100}
    for c, ns in want.items():
        assert t["ms"][c] == pytest.approx(1.5 * ns / 2 * 1e-6), c
    busy = xtrace.reduce(path, [0, 1])["busy_s"]
    assert sum(t["ms"].values()) * 1e-3 == pytest.approx(busy)
    assert t["top"]["unscoped"][0][0] == "cosine.4 f32[64]"
    # chip 0 sends one f32[8,128] (the done, not the start) over the
    # union of start, transfer and done: 1550-1660 ns
    link = t["link"][0]
    assert link["bytes"] == 4096
    assert link["seconds"] == pytest.approx(110e-9)
    assert link["gbps"] == pytest.approx(4096 / 110e-9 / 1e9)
    # chip 1's permutes (no transfer event) take 1275-1280 and 1320-1330
    assert t["link"][1]["gbps"] == pytest.approx(4096 / 15e-9 / 1e9)


def ctx_train(path, chips=2, steps=1):
    return {"kind": "train", "trace_path": path, "devices": [0, 1][:chips],
            "traced_steps": steps, "chips": chips}


def read(name, ctx, here=run.HERE):
    from pathlib import Path
    return spec.Cell.reader(type("C", (), {"here": Path(here)})(), name)(ctx)


def test_train_readers_and_a_program_without_scopes(tmp_path):
    path = train_trace(tmp_path)
    ctx = ctx_train(path, steps=2)
    assert read("train_forward_ms", ctx) == pytest.approx(1.5e-4 / 2)
    assert read("train_gossip_ms", ctx) == pytest.approx(0.6e-4 / 2)
    rate = read("gossip_link_gbps", ctx)
    assert rate == pytest.approx((4096 / 110e-9 + 4096 / 15e-9) / 2 / 1e9)
    assert read("gossip_link_gbps", ctx_train(path, chips=1)) is None
    bare = ctx_train(train_trace(tmp_path, scoped=False))
    for m in ("train_forward_ms", "train_backward_ms", "train_update_ms",
              "train_gossip_ms", "gossip_link_gbps"):
        assert read(m, bare) is None, m


def serve_trace(tmp_path, spans=True):
    # two iterations: each decodes (device busy under bench.decode), then
    # fetches (serve.sync); the second also prefills first
    host =[("bench.wait", 0, 10),
            ("serve.step", 100, 400), ("bench.decode", 150, 180),
            ("serve.sync", 350, 100),
            ("serve.step", 500, 500), ("bench.prefill", 520, 100),
            ("serve.sync", 620, 30), ("bench.decode", 700, 200),
            ("serve.sync", 900, 60), ("bench.wait", 1000, 100)]
    if not spans:
        host = [h for h in host if not h[0].startswith("serve.")]
    dev = [("%fusion.1 = bf16[8]{0} fusion()", 160, 190),
           ("%fusion.2 = bf16[8]{0} fusion()", 530, 90),
           ("%fusion.3 = bf16[8]{0} fusion()", 710, 190)]
    path = tmp_path / f"s{int(spans)}.xplane.pb"
    path.write_bytes(xspace({"/device:TPU:0": {"XLA Ops": dev},
                             "/host:CPU": {"python3": host}}))
    return str(path)


def test_serve_idle_against_engine_spans(tmp_path):
    s = scopes.serve(serve_trace(tmp_path), 0)
    assert (s["steps"], s["syncs"]) == (2, 3)
    assert s["window_s"] == pytest.approx(1100e-9)
    assert s["idle_s"] == pytest.approx((1100 - 190 - 90 - 190) * 1e-9)
    assert s["idle_in_sync_s"] == pytest.approx(190e-9)
    assert s["idle_in_step_s"] == pytest.approx((900 - 470) * 1e-9)
    ctx = {"kind": "serve", "trace_path": serve_trace(tmp_path),
           "devices": [0]}
    assert read("serve_sync_idle_ms", ctx) == pytest.approx(190e-6 / 3)
    bare = dict(ctx, trace_path=serve_trace(tmp_path, spans=False))
    assert read("serve_sync_idle_ms", bare) is None


def test_engine_host_time_from_the_calls():
    # the harness's records of the untraced window: (kind, dispatch,
    # ready, ...); between the decodes the host took 2, then 3 - 1 (a
    # prefill of 1 in between), then 4 (the trailing prefill is no gap)
    calls = [("prefill", 0.0, 1.0, 16), ("decode", 1.0, 5.0, [], []),
             ("decode", 7.0, 9.0, [], []), ("prefill", 10.0, 11.0, 16),
             ("decode", 12.0, 13.0, [], []), ("decode", 17.0, 18.0, [], []),
             ("prefill", 18.5, 19.0, 16)]
    ctx = {"kind": "serve", "calls": calls}
    assert read("serve_engine_host_ms", ctx) == pytest.approx(1e3 * 8 / 3)
    assert read("serve_engine_host_ms", dict(ctx, calls=calls[:2])) is None
    assert read("serve_engine_host_ms", {"kind": "train"}) is None


# -- traced runs of the test cells ------------------------------------------

NEW = {"tiny.train": ["train_forward_ms", "train_backward_ms",
                      "train_update_ms", "train_gossip_ms"],
       "tiny.serve": ["serve_sync_idle_ms", "serve_engine_host_ms"]}


class _Space:
    """The CPU's trace with a TPU plane put in: the host spans are the
    run's own; the device runs one operation of each train class in
    turn over the window, or is busy under each serving call.  ``ops``
    maps the plane's event names to op names."""

    def __init__(self, space):
        self.space = space
        spans, bench = xtrace.host_spans(space)
        lo, hi = min(s for _, s, _ in bench), max(e for _, _, e in bench)
        calls = [(s, e) for n, s, e in spans
                 if n in ("bench.prefill", "bench.decode")]
        if calls:
            evs = [("%fusion.1 = bf16[8]{0} fusion()", s, e - s)
                   for s, e in calls]
            self.ops = {}
        else:
            names = [FWD + "/dot", BWD + "/dot", UPD, GOS, "x"]
            w = (hi - lo) // len(names)
            evs = [(f"%fusion.{i} = bf16[8]{{0}} fusion()", lo + i * w, w)
                   for i in range(len(names))]
            self.ops = {ev[0]: op for ev, op in zip(evs, names)}
        line = type("L", (), {"name": "XLA Ops", "events": [
            type("E", (), {"name": n, "start_ns": s, "duration_ns": d})
            for n, s, d in evs]})
        self.device = type("P", (), {"lines": [line]})

    def find_plane_with_name(self, name):
        if name.startswith("/device:TPU:"):
            return self.device
        return self.space.find_plane_with_name(name)


@pytest.fixture
def traced(checkout, monkeypatch):
    """The test checkout with the new metrics listed for its cells, and a
    trace reduction that puts a TPU plane in the CPU's trace."""
    root, here = checkout
    bench = tiny_bench()
    for cell, names in NEW.items():
        for name in names:
            bench["per_layer"].append({
                "name": name, "unit": "ms", "better": "lower",
                "source": "device_trace", "layer": "test",
                "moves": "train_tokens_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    real_load, spaces = scopes._load, {}

    def load(path):
        spaces[path] = _Space(real_load(path))
        return spaces[path]

    monkeypatch.setattr(scopes, "_load", load)
    monkeypatch.setattr(scopes, "op_names", lambda path, planes: {
        p: spaces[path].ops for p in planes})

    def reduce(path, device_ids):
        return {"window_s": 1.0, "busy_s": 0.5, "kernels": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}

    monkeypatch.setattr(xtrace, "reduce", reduce)
    return checkout


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_cell_reads_every_new_metric(traced, capsys, cell):
    root, here = traced
    rc = run.main(["--workload", cell, "--seed", str(2**33 + 7),
                   "--seconds", "0.5", "--trace", "1"],
                  root=root, here=here, require_tpu=False)
    out, _ = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"], line
    metrics = line["metrics"]
    for name in NEW[cell]:
        assert isinstance(metrics[name]["value"], float), (name, metrics)
        assert metrics[name]["value"] >= 0
    # the metrics the cell had read what they read before, beside them
    before = {m["name"] for m in tiny_bench()["per_layer"]
              if cell in m["workloads"]}
    assert before <= set(metrics)
    if cell == "tiny.train":
        assert metrics["train_idle_share"]["value"] == 50.0
        # one op of each class, each a fifth of the window
        ms = [metrics[n]["value"] for n in NEW[cell]]
        assert max(ms) == pytest.approx(min(ms), rel=1e-3)
    else:
        assert metrics["serve_sync_idle_ms"]["value"] > 0
    assert set(TINY_LIMITS[cell]) == set(line["checks"])
