"""Fixtures of the benchmark's own tests: a throwaway checkout on the CPU
that holds the benchmark's code and cells of test size, added by data
alone.  Run them with ``python -m pytest benchmarks/chip/tests``."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

# limits of the test cells, from test-size readings on the CPU: the sound
# program reads loss_gap <= 2e-5, grad_norm_gap <= 1.2e-3 and
# update_norm_gap <= 5e-3; the fp8 control loss_gap >= 7e-5 (four nodes)
# and grad_norm_gap >= 1e-2; served tokens lie at most 0.03 below the
# best logit, the fp8 control's first tokens 0.4 or more
TINY_LIMITS = {
    "tiny.train": {"loss_gap": 8e-5, "grad_norm_gap": 4e-3,
                   "update_norm_gap": 2e-2},
    "tiny.train4": {"loss_gap": 8e-5, "grad_norm_gap": 4e-3,
                    "update_norm_gap": 2e-2},
    "tiny.serve": {"served_logit_gap": 0.15},
    "tiny.qk.train": {"loss_gap": 8e-5, "grad_norm_gap": 4e-3,
                      "update_norm_gap": 2e-2},
    "tiny.qk.serve": {"served_logit_gap": 0.15},
}
CONFIGS = ("tiny-dense.json", "tiny-dense-bias.json", "tiny-qk-norm.json")
ARCHS = ("qk_norm_decoder.py",)


def tiny_bench() -> dict:
    metric = {"unit": "%", "better": "higher", "source": "device_trace",
              "layer": "device"}
    return {
        "command": ["python3", "benchmarks/chip/run.py"],
        "paths": ["benchmarks/chip"],
        "run_seconds": 1,
        "configs": [
            {"name": "tiny-dense", "source": "test",
             "file": "benchmarks/chip/configs/tiny-dense.json", "reduced": [],
             "why": "test"},
            {"name": "tiny-dense-bias", "source": "test",
             "file": "benchmarks/chip/configs/tiny-dense-bias.json",
             "reduced": [], "why": "test"},
            {"name": "tiny-qk-norm", "source": "test",
             "file": "benchmarks/chip/configs/tiny-qk-norm.json",
             "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny.train", "config": "tiny-dense",
             "traffic": "tiny-train-1node", "chips": 1, "why": "test"},
            {"name": "tiny.train4", "config": "tiny-dense",
             "traffic": "tiny-train-4node", "chips": 4, "why": "test"},
            {"name": "tiny.serve", "config": "tiny-dense-bias",
             "traffic": "tiny-serve", "chips": 1, "why": "test"},
            {"name": "tiny.qk.train", "config": "tiny-qk-norm",
             "traffic": "tiny-train-1node", "chips": 1, "why": "test"},
            {"name": "tiny.qk.serve", "config": "tiny-qk-norm",
             "traffic": "tiny-serve", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.train", "tiny.train4", "tiny.qk.train"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.serve", "tiny.qk.serve"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            dict(metric, name="train_idle_share", moves="train_tokens_per_s",
                 workloads=["tiny.train"]),
            dict(metric, name="train_mfu", source="host_clock",
                 moves="train_tokens_per_s", workloads=["tiny.train"]),
            dict(metric, name="serve_prefill_ms", unit="ms", better="lower",
                 source="host_clock", moves="serve_tokens_per_s",
                 workloads=["tiny.serve"]),
            dict(metric, name="serve_mfu", source="host_clock",
                 moves="serve_tokens_per_s", workloads=["tiny.qk.serve"])],
    }


@pytest.fixture
def checkout(tmp_path):
    """A checkout holding the benchmark's directory, its test cells, a
    test architecture module and a ``BENCHMARK.json`` that names them.
    Returns (root, benchmark dir)."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for f in CONFIGS:
        shutil.copy(DATA / f, here / "configs" / f)
    for f in ARCHS:
        shutil.copy(DATA / f, here / "archs" / f)
    for f in ("tiny-train-1node.json", "tiny-train-4node.json",
              "tiny-serve.json"):
        shutil.copy(DATA / f, here / "traffic" / f)
    for cell, limits in TINY_LIMITS.items():
        (here / "limits" / f"{cell}.json").write_text(json.dumps(limits))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(tiny_bench()))
    os.symlink(HERE.parents[1] / "src", tmp_path / "src")
    return tmp_path, here
