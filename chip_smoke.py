"""Smoke run of the main paths on a TPU, at granite-8b's published widths.

    python chip_smoke.py [--seed 0]          # one chip: train + serve
    python chip_smoke.py --chips 4           # four chips: gossip only

One process from start to end (only one process at a time may hold a
chip).  The model is granite-8b (``repro/configs/granite_8b.py``) at its
published widths -- d_model 4096, 32 heads with 8 KV heads of 128, d_ff
14336, vocab 49152 -- cut in depth only, to 2 of its 36 blocks, with
random bf16 weights drawn from ``--seed``.

Phases (one chip):

* device -- the first device must be a TPU; there is no CPU fallback.
* train -- ``make_train_step`` (the factory ``repro.launch.train`` uses)
  on a 1x1 mesh: DSGDm, Base-2 topology, remat, 2 x 2048 synthetic
  tokens; a warm-up step and 3 more.  The Pallas step's first loss is
  checked against a ``ref`` step from the same state.
* serve -- ``make_engine`` (greedy, batch 4, 512-token prompts, 32 new
  tokens), the same engine speculating 3 tokens per round, and
  ``ContinuousEngine`` answering 8 requests of a Poisson trace with
  16-token pages.  Prefill, decode, verify-window and paged-decode
  logits of the Pallas path are checked against ``ref`` on the chip.

Phase (``--chips 4``): a (4, 1) mesh of 4 gossip nodes.  After the Base-2
graph's 2 rounds every node holds the exact node mean (the paper's
finite-time consensus), checked against the mean computed on the chip;
then 3 Pallas DSGDm steps, with each chip holding its own node.

Every phase prints one JSON line; its seconds are smoke timings, not
measurements.  The last line is ``{"ok": true, "device": {...}}``, and
it is printed only when every check passed.  Any failure raises and
exits non-zero before it.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Parity tolerances, Pallas vs ref on the same chip and the same state.
# Both paths keep bf16 weights, caches and activations and accumulate the
# softmax in f32; they differ only in summation order (blocked flash
# tiles vs the chunked reference), which flips single bf16 roundings of
# attention outputs.  One bf16 ulp is 2**-8 of a value; a few such flips
# through 2 blocks move a logit by a few ulps of the largest logit.
LOGIT_RTOL = 2e-2        # max |dlogit| / max |logit|
# The train loss is a mean over 4096 tokens, where those flips average
# out: 1e-3 of the loss is ~0.01 nats at ln(49152) ~ 10.8.
LOSS_RTOL = 1e-3
# Consensus after n_rounds rounds is exact in real arithmetic; in f32
# each of the 2 rounds rounds once per element (and the mean computed
# for comparison once more), so a few ulps of the largest value.
CONSENSUS_RTOL = 2.0 ** -20

BLOCKS = 2   # of granite-8b's 36; the only cut
# executable -> the Mosaic kernel it must hold (the verify window runs
# the flash kernel with per-request query positions)
SERVE_KERNELS = {"plain_prefill": "flash_attention_pallas",
                 "speculative_generate": "flash_attention_pallas",
                 "continuous_decode": "paged_flash_attention_pallas"}


def mosaic_kernels(compiled) -> collections.Counter:
    """Mosaic kernels in a compiled TPU executable, by the name of the
    jitted wrapper that called ``pallas_call``."""
    names = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'jit\((\w+)\)/pallas_call', line)
            names[m.group(1) if m else "unnamed"] += 1
    return names


def _timed_compile(fn, *args):
    """AOT-compile ``fn`` for ``args``; a later call with the same
    shapes reuses the executable."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _rel_err(a, b) -> float:
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                       1e-30))


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip smoke check failed: {msg}")


def _node_batch(step: int, n: int, b: int, seq: int, vocab: int, seed: int):
    import jax.numpy as jnp
    from repro.data.synthetic import token_batches
    raw = token_batches(step, batch=n * b, seq=seq, vocab=vocab, seed=seed)
    return {k: jnp.asarray(v).reshape(n, b, seq) for k, v in raw.items()}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(cfg, mesh, kcfg, ref_kcfg, *, batch: int, seq: int,
                seed: int, steps: int = 3) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.dist.steps import make_train_step

    t_phase = time.perf_counter()
    common = dict(topology="base", k=1, method_name="dsgdm", eta=0.01,
                  param_dtype=jnp.bfloat16, remat=True)
    bundle = make_train_step(cfg, mesh, kernel_config=kcfg, **common)
    ref = make_train_step(cfg, mesh, kernel_config=ref_kcfg, **common)
    n = bundle.n_nodes
    b = batch // n

    key = jax.random.PRNGKey(seed)
    init, c_init = _timed_compile(bundle.init_fn, key)
    params0, opt0 = init(key)
    batch0 = _node_batch(0, n, b, seq, cfg.vocab_size, seed)
    args0 = (params0, opt0, batch0, jnp.int32(0))
    step, c_step = _timed_compile(bundle.step_fn, *args0)
    ref_step, c_ref = _timed_compile(ref.step_fn, *args0)
    kernels = mosaic_kernels(step)

    _, _, loss_ref = ref_step(*args0)
    loss_ref = float(loss_ref)
    watched = jax.tree.leaves(params0)[0]
    params, opt, loss = step(*args0)
    losses = [float(loss)]
    del params0, opt0, args0
    t0 = time.perf_counter()
    for i in range(1, steps + 1):
        params, opt, loss = step(
            params, opt, _node_batch(i, n, b, seq, cfg.vocab_size, seed),
            jnp.int32(i))
        losses.append(float(loss))
    jax.block_until_ready(params)
    t_steps = time.perf_counter() - t0

    _check(all(jnp.isfinite(jnp.asarray(losses))), f"losses {losses}")
    changed = bool(jnp.any(jax.tree.leaves(params)[0] != watched))
    _check(changed, "params did not change over the steps")
    dloss = abs(losses[0] - loss_ref) / abs(loss_ref)
    _check(dloss <= LOSS_RTOL,
           f"first-step loss pallas {losses[0]} vs ref {loss_ref}: "
           f"rel {dloss:.3g} > {LOSS_RTOL}")
    return {"phase": "train", "nodes": n, "tokens_per_step": batch * seq,
            "losses": losses, "loss_ref_first": loss_ref,
            "loss_rel_err": dloss, "loss_rtol": LOSS_RTOL,
            "mosaic_calls": sum(kernels.values()),
            "mosaic_kernels": dict(kernels),
            "smoke_compile_s": c_init + c_step + c_ref,
            "smoke_steps_s": t_steps,
            "smoke_wall_s": time.perf_counter() - t_phase}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _pack_pages(caches, ps: int):
    """Dense ``(L, B, S, KV, hd)`` caches -> page pools with page 0 the
    scratch page and request ``b``'s page ``j`` at ``1 + b*maxp + j``."""
    import jax
    import jax.numpy as jnp

    def pack(a):
        a = jnp.pad(a, [(0, 0), (0, 0), (0, -a.shape[2] % ps)]
                    + [(0, 0)] * (a.ndim - 3))
        L, B, S = a.shape[:3]
        pages = a.reshape((L, B * (S // ps), ps) + a.shape[3:])
        return jnp.concatenate([jnp.zeros_like(pages[:, :1]), pages], 1)

    _check(not caches["prologue"], "page packing covers stacked blocks only")
    blocks = caches["blocks"]
    _, B, S = jax.tree.leaves(blocks)[0].shape[:3]
    table = 1 + jnp.arange(B * -(-S // ps), dtype=jnp.int32).reshape(B, -1)
    return {"prologue": [], "blocks": jax.tree.map(pack, blocks)}, table


def serve_phase(cfg, mesh, kcfg, ref_kcfg, *, batch: int, prompt_len: int,
                max_new: int, speculate_k: int, requests: int,
                page_size: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    from repro.models.model import PagedCacheLayout
    from repro.serve import (ContinuousEngine, decode_logits_scan,
                             make_engine, poisson_trace)

    t_phase = time.perf_counter()
    compile_s = 0.0
    k_init, k_prompt, k_tok = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = jnp.bfloat16
    params = M.init(cfg, k_init, dt)
    prompts = {"tokens": jax.random.randint(k_prompt, (batch, prompt_len), 0,
                                            cfg.vocab_size)}
    eng_kw = dict(batch=batch, prompt_len=prompt_len, max_new=max_new,
                  param_dtype=dt, cache_dtype=dt)
    kernels = {}

    # -- fixed-batch engine: plain greedy, then speculative ------------
    def run_engine(engine, label):
        nonlocal compile_s
        pre, c1 = _timed_compile(engine.prefill_fn, params, prompts)
        logits, caches, _ = pre(params, prompts)
        gen, c2 = _timed_compile(engine.generate_fn, params, logits, caches,
                                 jax.random.PRNGKey(0))
        compile_s += c1 + c2
        kernels[f"{label}_prefill"] = mosaic_kernels(pre)
        kernels[f"{label}_generate"] = mosaic_kernels(gen)
        res = engine.generate_with_state(params, prompts)
        toks = np.asarray(res.tokens)
        _check(toks.shape == (batch, max_new), f"{label} tokens {toks.shape}")
        _check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
               f"{label} token ids out of range")

    plain_engine = make_engine(cfg, mesh, kernel_config=kcfg, **eng_kw)
    run_engine(plain_engine, "plain")
    spec_engine = make_engine(cfg, mesh, kernel_config=kcfg,
                              speculate_k=speculate_k, draft_layers=1,
                              **eng_kw)
    run_engine(spec_engine, "speculative")

    # -- logits parity, Pallas vs ref, from the same state -------------
    parity = {}
    ref_engine = make_engine(cfg, mesh, kernel_config=ref_kcfg, **eng_kw)
    lg_r, caches, _ = ref_engine.prefill_fn(params, prompts)
    lg_p, _, _ = plain_engine.prefill_fn(params, prompts)
    parity["prefill"] = _rel_err(lg_p, lg_r)

    steps = 8
    toks = jax.random.randint(k_tok, (batch, steps), 0, cfg.vocab_size)

    def both(fn, *args):
        # params and caches are arguments, never closed over: a closure
        # would embed them in the executable as constants
        out = {}
        for name, kc in (("pallas", kcfg), ("ref", ref_kcfg)):
            def run(*a, _kc=kc):
                return fn(_kc, *a)
            compiled, c = _timed_compile(jax.jit(run), *args)
            t0 = time.perf_counter()
            out[name] = jax.block_until_ready(compiled(*args))
            out[name + "_s"] = {
                "compile": c, "run": time.perf_counter() - t0,
                "code_bytes":
                    compiled.memory_analysis().generated_code_size_in_bytes}
        return out

    dense = both(lambda kc, p, c, t: decode_logits_scan(
        cfg, p, c, t, prompt_len, kernel_config=kc)[0], params, caches, toks)
    parity["decode"] = _rel_err(dense["pallas"], dense["ref"])

    pos = prompt_len + jnp.arange(batch, dtype=jnp.int32)   # ragged starts
    verify = both(lambda kc, p, c, t, i: M.decode_step(
        cfg, p, c, t, i, kernel_config=kc)[0],
        params, caches, toks[:, :speculate_k + 1], pos)
    parity["verify"] = _rel_err(verify["pallas"], verify["ref"])

    pools, table = _pack_pages(caches, page_size)
    paged = both(lambda kc, p, c, t, i, bt: decode_logits_scan(
        cfg, p, c, t, i, decode_mode="paged", block_table=bt,
        kernel_config=kc)[0],
        params, pools, toks, jnp.full((batch,), prompt_len, jnp.int32),
        table)
    parity["paged_decode"] = _rel_err(paged["pallas"], paged["ref"])
    del caches, pools
    for name, err in parity.items():
        _check(err <= LOGIT_RTOL,
               f"{name} logits pallas vs ref: rel {err:.3g} > {LOGIT_RTOL}")

    # -- continuous engine over a paged cache --------------------------
    max_pages = -(-(prompt_len + max_new) // page_size)
    layout = PagedCacheLayout(page_size=page_size,
                              num_pages=batch * max_pages + 1,
                              max_pages_per_slot=max_pages)
    # prompts in (prompt_len/2, prompt_len]: one bucket, one prefill
    trace = poisson_trace(requests, rate=0.5, seed=seed,
                          min_prompt=prompt_len // 2 + 1,
                          max_prompt=prompt_len, vocab_size=cfg.vocab_size)
    cont = ContinuousEngine(cfg, slots=batch, layout=layout,
                            max_new=max_new, buckets=(prompt_len,),
                            param_dtype=dt, cache_dtype=dt,
                            kernel_config=kcfg)
    dec = cont._get_decode()
    z = jnp.zeros((batch,), jnp.int32)
    dec, c_dec = _timed_compile(
        dec, params, cont.pools, jnp.zeros((batch, max_pages), jnp.int32), z,
        z, jnp.zeros((batch, 2), jnp.uint32))
    compile_s += c_dec
    kernels["continuous_decode"] = mosaic_kernels(dec)
    t0 = time.perf_counter()
    out = cont.run(params, trace)
    t_cont = time.perf_counter() - t0
    got = {rid: len(r.tokens) for rid, r in out["results"].items()}
    _check(sorted(got) == sorted(r.rid for r in trace),
           f"continuous results for {sorted(got)}")
    _check(all(v == max_new for v in got.values()),
           f"continuous token counts {got}")

    return {"phase": "serve", "batch": batch, "prompt_len": prompt_len,
            "max_new": max_new, "speculate_k": speculate_k,
            "continuous_requests": len(got),
            "continuous_tokens": sum(got.values()),
            "continuous_steps": out["stats"]["steps"],
            "logit_rel_err": parity, "logit_rtol": LOGIT_RTOL,
            "mosaic_kernels": {k: dict(v) for k, v in kernels.items()},
            "smoke_compile_s": compile_s,
            "smoke_parity_s": {
                label: {k: d[k + "_s"] for k in ("pallas", "ref")}
                for label, d in (("decode", dense), ("verify", verify),
                                 ("paged_decode", paged))},
            "smoke_continuous_s": t_cont,
            "smoke_wall_s": time.perf_counter() - t_phase}


def check_serve_kernels(result: dict) -> None:
    """The flash (prefill), verify-window and paged kernels must each be
    in the executable that serves them."""
    for label, want in SERVE_KERNELS.items():
        got = result["mosaic_kernels"].get(label, {})
        _check(got.get(want, 0) > 0, f"no {want} kernel in {label}")


# ---------------------------------------------------------------------------
# four chips: gossip consensus + placement
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, mesh, kcfg, *, batch: int, seq: int, seed: int,
                    steps: int = 3) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.dist.gossip import make_gossip_mixer
    from repro.dist.steps import make_train_step
    from repro.models import model as M

    t_phase = time.perf_counter()
    bundle = make_train_step(cfg, mesh, topology="base", k=1,
                             method_name="dsgdm", eta=0.01,
                             param_dtype=jnp.bfloat16, remat=True,
                             kernel_config=kcfg)
    n, rounds = bundle.n_nodes, bundle.n_rounds
    node_devices = list(mesh.devices[:, 0])

    # -- finite-time consensus: distinct f32 params per node ------------
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    t0 = time.perf_counter()
    x0 = jax.jit(jax.vmap(lambda k: M.init(cfg, k, jnp.float32)),
                 out_shardings=bundle.param_shardings)(keys)
    specs = jax.tree.map(lambda s: s.spec, bundle.param_shardings)
    mixer = jax.jit(make_gossip_mixer(mesh, bundle.plan,
                                      bundle.rules.node_axis, specs,
                                      kernel_config=kcfg))
    mean = jax.jit(lambda t: jax.tree.map(lambda a: jnp.mean(a, 0), t))(x0)
    x = x0
    spread = []
    for r in range(rounds):
        x = mixer(x, jnp.int32(r))
        spread.append(max(_rel_err(a, jnp.broadcast_to(m[None], a.shape))
                          for a, m in zip(jax.tree.leaves(x),
                                          jax.tree.leaves(mean))))
    t_mix = time.perf_counter() - t0
    _check(spread[0] > 1e-3, f"one round already agrees ({spread[0]})")
    _check(spread[-1] <= CONSENSUS_RTOL,
           f"after {rounds} rounds nodes are {spread[-1]:.3g} from the "
           f"mean (> {CONSENSUS_RTOL:.3g})")
    del x0, x, mean

    # -- train: each chip holds its own node ----------------------------
    key = jax.random.PRNGKey(seed)
    init, c_init = _timed_compile(bundle.init_fn, key)
    params, opt = init(key)
    b = batch // n
    args = (params, opt, _node_batch(0, n, b, seq, cfg.vocab_size, seed),
            jnp.int32(0))
    step, c_step = _timed_compile(bundle.step_fn, *args)
    kernels = mosaic_kernels(step)
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        params, opt, loss = step(
            params, opt, _node_batch(i, n, b, seq, cfg.vocab_size, seed),
            jnp.int32(i))
        losses.append(float(loss))
    t_steps = time.perf_counter() - t0
    _check(all(jnp.isfinite(jnp.asarray(losses))), f"losses {losses}")
    for leaf in jax.tree.leaves((params, opt)):
        if leaf.ndim == 0 or leaf.shape[0] != n:
            continue
        for shard in leaf.addressable_shards:
            node = node_devices.index(shard.device)
            _check(shard.index[0] == slice(node, node + 1),
                   f"device {shard.device} holds nodes {shard.index[0]}")
    in_use = [d.memory_stats()["bytes_in_use"] for d in node_devices]
    _check(max(in_use) - min(in_use) <= 0.1 * max(in_use),
           f"bytes in use differ across chips: {in_use}")
    return {"phase": "four_chip", "nodes": n, "rounds": rounds,
            "consensus_rel_spread": spread,
            "consensus_rtol": CONSENSUS_RTOL, "losses": losses,
            "bytes_in_use": in_use, "mosaic_calls": sum(kernels.values()),
            "mosaic_kernels": dict(kernels),
            "smoke_compile_s": c_init + c_step, "smoke_mix_s": t_mix,
            "smoke_steps_s": t_steps,
            "smoke_wall_s": time.perf_counter() - t_phase}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip gossip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.configs import get_config
    from repro.kernels.ops import KernelConfig
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r} "
              f"({len(devices)} devices)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"phase": "device", **device}), flush=True)

    pallas = KernelConfig(backend="pallas")
    _check(pallas.use_pallas and not pallas.run_interpret,
           "forced Pallas must run natively on the chip")
    _check(KernelConfig().use_pallas, "auto must pick Pallas on the chip")
    ref = KernelConfig(backend="ref")
    cfg = dataclasses.replace(get_config("granite-8b"), num_blocks=BLOCKS)

    if args.chips == 4:
        mesh = make_mesh((4, 1), ("data", "model"), devices=devices[:4])
        res = four_chip_phase(cfg, mesh, pallas, batch=8, seq=2048,
                              seed=args.seed)
        _check(res["mosaic_calls"] > 0, "no Mosaic kernel in the step")
        print(json.dumps(res), flush=True)
    else:
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
        res = train_phase(cfg, mesh, pallas, ref, batch=2, seq=2048,
                          seed=args.seed)
        _check(res["mosaic_calls"] > 0, "no Mosaic kernel in the step")
        print(json.dumps(res), flush=True)
        res = serve_phase(cfg, mesh, pallas, ref, batch=4, prompt_len=512,
                          max_new=32, speculate_k=3, requests=8,
                          page_size=16, seed=args.seed)
        check_serve_kernels(res)
        print(json.dumps(res), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
