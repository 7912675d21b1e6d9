"""Gossip/backward overlap: sequential vs overlapped train-step time on
the 8-virtual-device mesh, plus the gated bit-exactness indicator.

The timings answer "what does splitting the method update + gossip into
per-group chains buy on this machine" — informational only
(UNGATED_TIMING_SUITES: a 2-core CI runner timing a 8-fake-device CPU
mesh is scheduler-jitter dominated, and the CPU backend serialises the
collectives the overlap exists to hide anyway; the real win needs an
accelerator's async collectives).  The gated signal is ``bit_exact``:
after identical step sequences, the overlapped step's params AND method
state must be bit-identical to the sequential step's — the schedule
may differ, the numbers may not (same invariant tests/test_overlap.py
pins per method).

Runs in a subprocess because the virtual-device flag must precede jax
initialisation; the device count is pinned to 8 (the committed
baseline's mesh) regardless of REPRO_TEST_DEVICES.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.topology import spec_from_cli

from .common import emit
from .registry import register

_DEVICES = 8
_NODES = 4
_WARMUP = 2
_ITERS = 6

_SCRIPT = f"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count={_DEVICES}")
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.dist.steps import make_train_step
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.optim.decentralized import make_method

cfg = get_config("granite-8b").reduced()
mesh = make_mesh(({_NODES}, {_DEVICES // _NODES}), ("data", "model"))
n = {_NODES}
params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
params_n = jax.tree.map(
    lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0, params)

def mk_batch(step):
    kk = jax.random.fold_in(jax.random.PRNGKey(7), step)
    toks = jax.random.randint(kk, (n, 2, 16), 0, cfg.vocab_size)
    labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-100)
    return {{"tokens": toks, "labels": labels}}

batches = [mk_batch(s) for s in range({_WARMUP} + {_ITERS})]
method = make_method("dsgdm")
out = {{}}
finals = {{}}
for label, overlap in (("seq", False), ("ovl", True)):
    bundle = make_train_step(cfg, mesh, topology="base", k=1,
                             method_name="dsgdm", eta=0.05,
                             param_dtype=jnp.float32, remat=False,
                             overlap=overlap)
    assert bundle.overlap == overlap
    pn, op = params_n, method.init(params_n)
    for s in range({_WARMUP}):
        pn, op, loss = bundle.step_fn(pn, op, batches[s], jnp.int32(s))
    jax.block_until_ready((pn, op))
    t0 = time.perf_counter()
    for s in range({_WARMUP}, {_WARMUP} + {_ITERS}):
        pn, op, loss = bundle.step_fn(pn, op, batches[s], jnp.int32(s))
    jax.block_until_ready((pn, op))
    out[label + "_us"] = (time.perf_counter() - t0) / {_ITERS} * 1e6
    finals[label] = (pn, op)

exact = all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(finals["seq"]),
                    jax.tree.leaves(finals["ovl"])))
out["bit_exact"] = int(exact)
out["n"] = n
print("RESULT " + json.dumps(out), flush=True)
"""


@register("overlap", fast=True)
def run():
    """Comm/compute overlap: sequential vs per-group-overlapped step
    time on 8 fake devices + the gated bit-exactness indicator."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    # fake CPU devices by design: the child must never reach for a chip
    # the parent (which has touched JAX) already holds
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, env=env,
                       timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"overlap subprocess failed:\n{r.stderr}")
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    data = json.loads(line[len("RESULT "):])

    spec = spec_from_cli("base", n=_NODES, k=1)
    const = f"devices={_DEVICES};nodes={_NODES};method=dsgdm"
    emit("train_step/sequential", data["seq_us"], const, spec=spec)
    emit("train_step/overlap", data["ovl_us"],
         f"{const};bit_exact={data['bit_exact']}", spec=spec)
    return {
        "devices": _DEVICES,
        "nodes": _NODES,
        "seq_us": data["seq_us"],
        "ovl_us": data["ovl_us"],
        "speedup": data["seq_us"] / max(data["ovl_us"], 1e-9),
        "bit_exact": data["bit_exact"],
    }
