"""Batched serving example: prefill + compiled scan generation with a
sharded KV cache on a (data, model) mesh, using a reduced gemma3
(sliding-window + global attention, MQA) model.

The whole decode phase — token loop, cache appends, sampling — is one
compiled executable (``repro.serve.make_engine``); compare the reported
steady-state time against the per-token dispatch loop the serving
benchmark (`benchmarks/serving.py`) keeps as the reference.

    PYTHONPATH=src python examples/serve_batched.py
"""
from repro.launch.env import set_host_device_count

set_host_device_count(8)

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.serve import SamplingParams, make_engine


def main():
    cfg = get_config("gemma3-1b").reduced()
    mesh = make_mesh((4, 2), ("data", "model"))
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)

    B, prompt, gen = 8, 24, 12
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (B, prompt), 0, cfg.vocab_size)}

    for sampling in (SamplingParams(),  # greedy
                     SamplingParams(mode="sample", temperature=0.8,
                                    top_k=40)):
        engine = make_engine(cfg, mesh, batch=B, prompt_len=prompt,
                             max_new=gen, sampling=sampling,
                             param_dtype=jnp.float32,
                             cache_dtype=jnp.float32)
        t0 = time.time()
        out, _ = engine.generate(params, batch, key=jax.random.PRNGKey(2))
        jax.block_until_ready(out)
        t_first = time.time() - t0
        t0 = time.time()
        out, _ = engine.generate(params, batch, key=jax.random.PRNGKey(2))
        jax.block_until_ready(out)
        dt = time.time() - t0
        print(f"[{sampling.mode}] {gen} tokens x {B} seqs: "
              f"first call {t_first:.2f}s (compile), steady {dt:.3f}s "
              f"({B * gen / dt:.0f} tok/s)")
        for r in range(min(4, B)):
            print("  seq", r, list(map(int, out[r])))
    print("OK")


if __name__ == "__main__":
    main()
