"""Architectures as files.  A configuration file names its reference
module under ``archs/``; a module the harness has never seen, added to a
checkout as a file, is found by that name and used for the check of the
program's configuration, the reference's readings and the FLOP count
that ``serve_mfu`` reads.  A mechanism that the named module does not
compute is refused.  The reference compiles its programs once."""
import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import DATA, HERE, TINY_LIMITS

import run
from bench import common, program, reference, spec, xtrace

dense = spec.load_arch("dense_decoder")


def cell_of(checkout, name):
    root, here = checkout
    return spec.Cell(name, root, here)


def test_every_configuration_names_a_module():
    bench = spec.load_json(HERE.parents[1] / "BENCHMARK.json")
    for c in bench["configs"]:
        model = spec.load_json(HERE.parents[1] / c["file"])
        arch = spec.load_arch(model["reference"])
        assert callable(arch.check) and callable(arch.forward_flops)


def test_dense_module_refuses_what_it_does_not_compute():
    from repro.configs.common import LayerSpec, MLAConfig
    from repro.models.moe import MoEConfig
    m = json.loads((DATA / "tiny-dense.json").read_text())
    cfg = program.program_config(m, dense)
    moe = dataclasses.replace(
        cfg, moe=MoEConfig(num_experts=4, top_k=2, d_expert=32),
        pattern=(LayerSpec(ffn="moe"),))
    prologue = dataclasses.replace(cfg, prologue=(LayerSpec(),),
                                   num_blocks=cfg.num_blocks - 1)
    mla = dataclasses.replace(cfg, mla=MLAConfig())
    qk = dataclasses.replace(cfg, qk_norm=True)
    for bad in (moe, prologue, mla, qk):
        with pytest.raises(ValueError, match="not a plain dense decoder"):
            dense.check(bad, m)
    with pytest.raises(ValueError, match="intermediate_size"):
        dense.check(dataclasses.replace(cfg, d_ff=256), m)
    # a file that runs a q/k-normed program under the dense module
    qk_file = json.loads((DATA / "tiny-qk-norm.json").read_text())
    with pytest.raises(ValueError, match="not a plain dense decoder"):
        program.program_config(qk_file, dense)


def test_new_module_is_found_by_name(checkout, capsys, monkeypatch):
    root, here = checkout
    seen = []
    real = run.per_layer

    def per_layer(cell, res):
        seen.append((cell, res["ctx"]))
        return real(cell, res)

    monkeypatch.setattr(run, "per_layer", per_layer)
    monkeypatch.setattr(xtrace, "reduce", lambda path, ids: {
        "window_s": 1.0, "busy_s": 0.5, "kernels": {},
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    lines = {}
    for name, trace in (("tiny.qk.train", 0), ("tiny.qk.serve", 1)):
        rc = run.main(["--workload", name, "--seed", str(2**35 + 9),
                       "--seconds", "0.5", "--trace", str(trace)],
                      root=root, here=here, require_tpu=False)
        out, _ = capsys.readouterr()
        lines[name] = json.loads(out.strip().splitlines()[-1])
        assert rc == 0 and lines[name]["correct"], lines[name]
        assert set(lines[name]["checks"]) == set(TINY_LIMITS[name])
    cell, ctx = seen[-1]
    assert Path(cell.arch.__file__) == here / "archs" / "qk_norm_decoder.py"
    assert ctx["arch"] is cell.arch
    # serve_mfu counts the module's FLOPs, which exceed the dense count
    mfu = lines["tiny.qk.serve"]["metrics"]["serve_mfu"]["value"]
    read = cell.reader("serve_mfu")
    assert mfu == read(ctx) > read(dict(ctx, arch=dense)) > 0


def test_second_seed_compiles_nothing(checkout):
    devices = jax.devices()
    train = cell_of(checkout, "tiny.train")
    ref = reference.Reference(train.arch, train.model, train.mix)
    ctl = reference.Reference(train.arch, train.model, train.mix, "fp8")
    serve = cell_of(checkout, "tiny.serve")
    sref = reference.Reference(serve.arch, serve.model, serve.mix)
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, 256, (2, 48)).astype(np.int32)
    where = np.stack([np.arange(8) + 20] * 2).astype(np.int32)

    def readings(seed):
        ref.train_readings(seed, devices, steps=3)
        ctl.train_readings(seed, devices, steps=3)
        ref.train_readings(seed, devices, steps=3, fault="half_batch")
        sref.serve_gaps(seed, seqs, where, [seqs[:, :8]])

    with common.CompileCounter().watching() as first:
        readings(1)
    with common.CompileCounter().watching() as counter:
        readings(2**40 + 7)
    assert first.count > 0 and counter.count == 0


def test_readings_tool_compiles_once_per_process(checkout):
    tool = spec.load_module(HERE / "tools" / "readings.py", "_readings")
    devices = jax.devices()
    limits = TINY_LIMITS["tiny.train"]
    rows = tool.train_rows(cell_of(checkout, "tiny.train"), [3, 2**33 + 1],
                           True, ["half_batch"], devices)
    with common.CompileCounter().watching() as compiled:
        first = next(rows)
    with common.CompileCounter().watching() as counter:
        second = next(rows)
    assert compiled.count > 0 and counter.count == 0
    for row in (first, second):
        assert all(row["program"][k] <= lim for k, lim in limits.items())
        assert any(row["half_batch"][k] > lim for k, lim in limits.items())
    rows = tool.serve_rows(cell_of(checkout, "tiny.serve"), [5, 6], True,
                           0.5, devices)
    next(rows)
    with common.CompileCounter().watching() as counter:
        row = next(rows)
    assert counter.count == 0
    assert row["numbers"]["served_logit_gap"] <= TINY_LIMITS["tiny.serve"][
        "served_logit_gap"] < row["numbers"]["control_logit_gap"]
