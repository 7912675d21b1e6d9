"""Whole runs of cells added by data alone, on the CPU: a sound run is
correct, and each fault planted in the timed path, or the fp8 control
put in the program's place, comes out as not correct.

The four-node cell runs in a child process with four virtual CPU
devices.  XLA's CPU backend may keep a bf16 intermediate in float32
(``--xla_allow_excess_precision``), which on the chip the fused update
kernel never does; the child turns that off so that the program rounds
its half step to the parameters' dtype as the method defines it."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import HERE, TINY_LIMITS

import run
from bench import reference, serve, train


def run_cell(checkout, capsys, name, trace=False, **kw):
    root, here = checkout
    rc = run.main(["--workload", name, "--seed", str(2**33 + 5),
                   "--seconds", "0.5", "--trace", str(int(trace))],
                  root=root, here=here, require_tpu=False, **kw)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_sound_cells_are_correct(checkout, capsys):
    for name, metric in (("tiny.train", "train_tokens_per_s"),
                         ("tiny.serve", "serve_tokens_per_s")):
        rc, line, err = run_cell(checkout, capsys, name)
        assert rc == 0 and line["correct"], line
        assert set(line["metrics"]) == {metric, "setup_s"}
        assert list(line)[-1] == "checks"
        assert set(line["checks"]) == set(TINY_LIMITS[name])
        assert err.strip().splitlines()[-1].startswith("check ")
        assert line["device"]["count"] >= 1 and line["window_compiles"] == 0


def test_window_queues_ahead_and_counts_every_step_it_sent():
    """The window keeps about ``AHEAD_S`` seconds of steps queued ahead
    of the one it waits for, sends nothing once its time is up, counts
    every step it sent, and closes on the last one's result (each step
    takes the one before as input, so all are done then)."""
    log = {"sent": 0, "waited": 0, "most_queued": 0, "last": 0}

    class Out:
        def __init__(self):
            self.n = log["sent"]

        def block_until_ready(self):
            log["waited"] += 1
            log["last"] = self.n
            return self

        def __float__(self):
            return 1.0

    def step(params, opt, batch, idx):
        log["sent"] += 1
        log["most_queued"] = max(log["most_queued"],
                                 log["sent"] - log["waited"])
        return params, opt, Out()

    tc = object.__new__(train.TrainCell)
    tc.step, tc.step_s = step, 0.5
    tc.batch = lambda s, seed: None
    tc.step_index = lambda s: None
    steps, t_open, t_close, loss, _, _, ahead = tc.window(
        ({}, {}), seed=1, seconds=0.05, first=3)
    assert ahead == 12 and steps == log["sent"] > ahead
    assert log["most_queued"] == ahead + 1
    assert log["last"] == log["sent"] and t_close > t_open


def test_traced_run_reads_the_host_clock_in_the_untraced_window(
        checkout, capsys, monkeypatch):
    """``--trace 1`` runs the same untraced window as ``--trace 0`` and
    then a traced part: the host-clock metrics read the first, the trace
    (which on the CPU has no TPU plane to reduce) the second."""
    from bench import xtrace
    seen = []

    def reduce(path, device_ids):
        spans, bench = xtrace.host_spans(
            __import__("jax").profiler.ProfileData.from_file(path))
        seen.append(len(bench))
        return {"window_s": 1.0, "busy_s": 0.5, "kernels": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}

    monkeypatch.setattr(xtrace, "reduce", reduce)
    for name, metric in (("tiny.train", "train_mfu"),
                         ("tiny.serve", "serve_prefill_ms")):
        rc, line, _ = run_cell(checkout, capsys, name, trace=True)
        assert rc == 0 and line["correct"], line
        assert metric in line["metrics"], line["metrics"]
        assert line["metrics"][metric]["value"] > 0
        assert line["device"]["window_s"] > 0 and "breakdown" in line
        assert line["window_compiles"] == 0
    # the traced part recorded the harness's spans
    assert len(seen) == 2 and min(seen) > 0


def test_no_tpu_no_result(checkout, capsys):
    root, here = checkout
    rc = run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                   "1"], root=root, here=here)
    out, err = capsys.readouterr()
    assert rc == 2 and out == "" and "TPU" in err


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files:
    the program is missing, so the run fails and prints no result."""
    root = tmp_path / "alone"
    (root / "benchmarks").mkdir(parents=True)
    import shutil
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(HERE.parents[1] / "BENCHMARK.json", root / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite8b-train-1chip", "--seed", "1", "--seconds", "1"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""


# -- faults planted in the timed path ---------------------------------------

def test_state_returned_unchanged_is_not_correct(checkout, capsys,
                                                 monkeypatch):
    real = train.TrainCell.__init__

    def init(self, *a, **k):
        real(self, *a, **k)
        step = self.step
        self.step = lambda p, o, b, s: (p, o, step(p, o, b, s)[2])

    monkeypatch.setattr(train.TrainCell, "__init__", init)
    rc, line, _ = run_cell(checkout, capsys, "tiny.train")
    assert rc == 0 and not line["correct"]
    assert line["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_not_correct(checkout, capsys, monkeypatch):
    real = train.TrainCell.batch

    def batch(self, step, seed):
        b = real(self, step, seed)
        half = self.mix["rows_per_node"] // 2
        labels = np.array(b["labels"])
        labels[:, half:] = -100          # the mean is over the rest
        return dict(b, labels=__import__("jax").device_put(
            labels, self.batch_sharding["labels"]))

    monkeypatch.setattr(train.TrainCell, "batch", batch)
    rc, line, _ = run_cell(checkout, capsys, "tiny.train")
    assert rc == 0 and not line["correct"]
    assert line["checks"]["loss_gap"]["value"] > 1e-3


def test_altered_token_is_not_correct(checkout, capsys, monkeypatch):
    real = serve.Recorder.decode

    def decode(self, compiled):
        def altered(*args):
            nxt, pools = compiled(*args)
            return (nxt + 1) % 256, pools
        return real(self, altered)

    monkeypatch.setattr(serve.Recorder, "decode", decode)
    rc, line, _ = run_cell(checkout, capsys, "tiny.serve")
    assert rc == 0 and not line["correct"]


FOUR = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{here!r}, {src!r}]
    import run
    from pathlib import Path
    if {fault!r} == "no_exchange":
        import repro.dist.steps as steps

        def no_exchange(*a, **k):
            def mixer(tree, r):
                return tree
            mixer.per_shard = mixer
            return mixer

        steps.make_gossip_mixer = no_exchange
    root = Path({root!r})
    sys.exit(run.main(["--workload", "tiny.train4", "--seed", "77",
                       "--seconds", "0.5"], root=root,
                      here=root / "benchmarks" / "chip", require_tpu=False))
""")


def run_four(checkout, fault):
    root, here = checkout
    code = FOUR.format(here=str(here), src=str(HERE.parents[1] / "src"),
                       root=str(root), fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        "--xla_force_host_platform_device_count=4 "
        "--xla_allow_excess_precision=false"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_nodes_sound_and_exchange_left_out(checkout):
    line = run_four(checkout, None)
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    bad = run_four(checkout, "no_exchange")
    assert not bad["correct"]
    assert bad["checks"]["update_norm_gap"]["value"] > 0.1


# -- the control: the reference in fp8 put in the program's place ----------

def test_fp8_control_is_not_correct(checkout):
    import jax
    root, here = checkout
    cell = cell_of(root, here, "tiny.train")
    limits = TINY_LIMITS["tiny.train"]
    want = reference.Reference(cell.arch, cell.model, cell.mix)
    control = reference.Reference(cell.arch, cell.model, cell.mix, "fp8")
    for seed in (1, 2, 3):
        ref = want.train_readings(seed, jax.devices(), steps=3)
        ctl = control.train_readings(seed, jax.devices(), steps=3)
        nums = train.compare(ctl, ref)
        assert any(nums[k] > lim for k, lim in limits.items()), nums


def test_fp8_control_serving_is_not_correct(checkout):
    import jax
    root, here = checkout
    cell = cell_of(root, here, "tiny.serve")
    sc = serve.ServeCell(cell, jax.devices())
    rec, served, _, prompts = serve.serve_once(sc, 5, 0.5)
    nums, checked = serve.compare(
        reference.Reference(cell.arch, cell.model, cell.mix), cell.mix, 5,
        prompts, served,
        control=reference.Reference(cell.arch, cell.model, cell.mix, "fp8"))
    assert nums["served_logit_gap"] <= TINY_LIMITS["tiny.serve"][
        "served_logit_gap"]
    assert nums["control_logit_gap"] > TINY_LIMITS["tiny.serve"][
        "served_logit_gap"], nums
    assert checked["tokens"] == checked["requests"] * cell.mix["max_new"]


def cell_of(root, here, name):
    from bench import spec
    return spec.Cell(name, root, here)
