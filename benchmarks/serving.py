"""Serving suite: decode-phase dispatch counts, scan-vs-loop parity,
tokens/s.

The quantity that predicts serving latency at small batch is not FLOPs
but per-token *dispatch* overhead: the historical serving path paid one
XLA executable call plus one device->host sync (the argmax) per
generated token, while the compiled engine (`repro.serve.make_engine`)
issues exactly ONE executable call for the whole decode phase and keeps
every sampling decision on device.  This suite pins that dispatch-count
model with MEASURED counts (deterministic integers, gated by report.py
against the committed baseline), asserts greedy token parity between
the scan engine and the per-token loop, and records tokens/s for both
paths (host timings — informational only, listed in
``UNGATED_TIMING_SUITES`` like the kernels suite).

Dispatch model for generating N tokens from a prefilled prompt:

* per-token loop: ``N - 1`` decode executable calls, plus ``N`` host
  round-trips for the argmax/token handling;
* compiled scan engine: ``1`` executable call, ``0`` per-token host
  syncs (one transfer at the end for the finished token block).

The sustained-throughput section drives the continuous-batching paged
engine (``repro.serve.ContinuousEngine``) over a seeded 32-request
ragged Poisson trace and GATES its deterministic scheduler model: the
lifetime executable count (must stay <= #prompt-buckets + 1 — the
bucketing contract), the per-executable dispatch counts, slot
utilization and the p50/p99 queueing delays in virtual decode-step
units (the trace and scheduler are seed-pinned, so these are exact
reproducibility indicators, not timings).  Wall-clock tokens/s stays
informational like every timing in this suite.

The speculative section (DESIGN.md Sec. 15) extends the dispatch model
to draft-k-verify-once decoding: with per-draft acceptance rate alpha,
a round emits ``(1 - alpha^(k+1)) / (1 - alpha)`` expected tokens for
ONE sequential full-depth pass, so sequential passes per emitted token
drop below 1 whenever ``alpha >= 0.5`` and ``k >= 2`` — the analytic
claim this suite gates, alongside MEASURED deterministic rounds /
acceptance counts and the still-1-executable-call contract of the
speculative scan engine (greedy speculative tokens are asserted
bit-identical to the plain scan).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.dist.steps import make_decode_step, make_prefill
from repro.kernels.ops import KernelConfig
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.model import PagedCacheLayout
from repro.serve import ContinuousEngine, make_engine, poisson_trace

from .common import emit
from .registry import register

B, P, N = 2, 8, 8       # batch, prompt length, generated tokens

# continuous sustained-throughput trace (seed-pinned -> deterministic)
TRACE_REQUESTS, TRACE_RATE, TRACE_SEED = 32, 0.7, 0
SLOTS, BUCKETS, MAX_NEW = 4, (8, 16, 32), 4

# speculative draft depths exercised by the measured section
SPEC_KS = (2, 4)
SPEC_DRAFT_LAYERS = 1


def dispatch_model(n: int) -> dict[str, dict[str, int]]:
    return {"loop": {"executable_calls": n - 1, "host_syncs": n},
            "scan": {"executable_calls": 1, "host_syncs": 0}}


def speculative_model(alpha: float, k: int) -> dict[str, float]:
    """Expected draft-k-verify-once economics at per-draft acceptance
    rate ``alpha``: tokens emitted per round (the truncated geometric
    sum ``1 + alpha + ... + alpha^k``) and its inverse, sequential
    full-depth passes per emitted token (the plain scan pays exactly
    1.0)."""
    tokens_per_round = sum(alpha ** i for i in range(k + 1))
    return {"tokens_per_round": tokens_per_round,
            "passes_per_token": 1.0 / tokens_per_round}


def _best_s(fn, iters: int = 5) -> float:
    fn()  # warmup (compile)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


@register("serving", fast=True)
def run() -> dict:
    cfg = get_config("gemma3-1b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                          cfg.vocab_size)}

    engine = make_engine(cfg, mesh, batch=B, prompt_len=P, max_new=N,
                         param_dtype=jnp.float32, cache_dtype=jnp.float32)
    pre = make_prefill(cfg, mesh, batch=B, seq=P + N,
                       param_dtype=jnp.float32, cache_dtype=jnp.float32)
    dec = make_decode_step(cfg, mesh, batch=B, seq=P + N,
                           param_dtype=jnp.float32, cache_dtype=jnp.float32)

    # --- measured dispatch counts + token parity ----------------------
    before = engine.dispatch_counter[0]
    scan_tokens, _ = engine.generate(params, batch)
    scan_calls = engine.dispatch_counter[0] - before

    loop_calls = 0
    logits, cache, _ = pre.fn(params, batch)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    outs = [tok]
    for i in range(N - 1):
        logits, cache = dec.fn(params, cache, tok, jnp.int32(P + i))
        loop_calls += 1
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    loop_tokens = jnp.concatenate(outs, axis=1)

    model = dispatch_model(N)
    assert scan_calls == model["scan"]["executable_calls"] == 1
    assert loop_calls == model["loop"]["executable_calls"]
    parity = int(np.array_equal(np.asarray(scan_tokens),
                                np.asarray(loop_tokens)))
    assert parity == 1, "scan-decode tokens diverged from the loop"

    emit(f"serving/dispatch/N{N}/loop", 0.0,
         f"executable_calls={loop_calls};"
         f"host_syncs={model['loop']['host_syncs']}")
    emit(f"serving/dispatch/N{N}/scan", 0.0,
         f"executable_calls={scan_calls};host_syncs=0;"
         f"calls_saved={loop_calls - scan_calls}")
    emit(f"serving/parity/N{N}", 0.0, f"tokens_equal={parity}")

    # --- tokens/s (informational; timings ungated for this suite) ----
    def run_scan():
        t, _ = engine.generate(params, batch)
        jax.block_until_ready(t)

    def run_loop():
        logits, cache, _ = pre.fn(params, batch)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        for i in range(N - 1):
            logits, cache = dec.fn(params, cache, tok, jnp.int32(P + i))
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)

    s_scan = _best_s(run_scan)
    s_loop = _best_s(run_loop)
    # derived carries only deterministic counts; the wall time lives in
    # us_per_call, which report.py never gates for this suite
    emit(f"serving/generate/N{N}/scan", s_scan * 1e6, f"tokens={B * N}")
    emit(f"serving/generate/N{N}/loop", s_loop * 1e6, f"tokens={B * N}")

    # --- speculative decoding: model + measured -----------------------
    spec = _run_speculative(cfg, mesh, params, batch,
                            np.asarray(scan_tokens))

    # --- continuous-batching sustained throughput ---------------------
    cont = _run_continuous(cfg, params)

    return {"dispatch_model": model,
            "measured": {"scan_calls": scan_calls, "loop_calls": loop_calls},
            "greedy_parity": bool(parity),
            "tokens_per_s": {"scan": B * N / s_scan, "loop": B * N / s_loop},
            "shape": {"batch": B, "prompt": P, "gen": N},
            "speculative": spec,
            "continuous": cont}


def _run_speculative(cfg, mesh, params, batch, plain_tokens) -> dict:
    """Gate the speculative dispatch model (analytic) and the measured
    deterministic round/acceptance counts of the speculative scan
    engine.  Everything here is exact integers or closed-form floats —
    no timings."""
    # analytic claim: above alpha = 0.5 a draft depth of k >= 2 takes
    # the engine below one sequential full-depth pass per emitted token
    analytic = {}
    for alpha in (0.5, 0.8):
        for k in SPEC_KS:
            m = speculative_model(alpha, k)
            assert m["passes_per_token"] < 1.0, \
                f"speculative model must beat 1 pass/token at " \
                f"alpha={alpha}, k={k}: {m}"
            analytic[f"alpha{alpha}_k{k}"] = m
            emit(f"serving/speculative/model/alpha{alpha}/k{k}", 0.0,
                 f"tokens_per_round={m['tokens_per_round']:.6f};"
                 f"passes_per_token={m['passes_per_token']:.6f}")

    measured = {}
    for k in SPEC_KS:
        eng = make_engine(cfg, mesh, batch=B, prompt_len=P, max_new=N,
                          param_dtype=jnp.float32, cache_dtype=jnp.float32,
                          speculate_k=k, draft_layers=SPEC_DRAFT_LAYERS)
        before = eng.dispatch_counter[0]
        res = eng.generate_with_state(params, batch)
        calls = eng.dispatch_counter[0] - before
        assert calls == 1, \
            "speculate-verify round must stay inside ONE executable"
        parity = int(np.array_equal(np.asarray(res.tokens), plain_tokens))
        assert parity == 1, \
            f"greedy speculative k={k} diverged from the plain scan"
        rounds = int(np.asarray(res.spec.rounds).sum())
        drafted = int(np.asarray(res.spec.drafted).sum())
        accepted = int(np.asarray(res.spec.accepted).sum())
        tokens = int(np.asarray(res.lengths).sum())
        acc_rate = accepted / max(drafted, 1)
        passes = rounds / max(tokens - B, 1)  # first token comes from
        #                                       prefill, not a round
        emit(f"serving/speculative/measured/k{k}", 0.0,
             f"executable_calls={calls};parity={parity};rounds={rounds};"
             f"drafted={drafted};accepted={accepted};tokens={tokens}")
        measured[f"k{k}"] = {
            "rounds": rounds, "drafted": drafted, "accepted": accepted,
            "tokens": tokens, "acceptance_rate": acc_rate,
            "rounds_per_token": passes,
            "draft_layers": SPEC_DRAFT_LAYERS}
    return {"analytic": analytic, "measured": measured}


def _run_continuous(cfg, params) -> dict:
    """Drive the 32-request ragged Poisson trace through the paged
    continuous engine; gate its deterministic scheduler model."""
    layout = PagedCacheLayout(page_size=8, num_pages=SLOTS * 5 + 3,
                              max_pages_per_slot=5)
    engine = ContinuousEngine(cfg, slots=SLOTS, layout=layout,
                              max_new=MAX_NEW, buckets=BUCKETS,
                              cache_dtype=jnp.float32,
                              kernel_config=KernelConfig(backend="ref"))
    trace = poisson_trace(TRACE_REQUESTS, rate=TRACE_RATE, seed=TRACE_SEED,
                          min_prompt=4, max_prompt=30,
                          vocab_size=cfg.vocab_size)
    t0 = time.perf_counter()
    out = engine.run(params, trace)
    wall = time.perf_counter() - t0
    s = out["stats"]

    bound = len(BUCKETS) + 1
    assert s["executables"] <= bound, \
        f"executable count {s['executables']} exceeds bucket bound {bound}"
    assert s["requests"] == TRACE_REQUESTS

    n_prefill = sum(v for k, v in s["dispatches"].items()
                    if k.startswith("prefill_"))
    emit(f"serving/continuous/trace{TRACE_REQUESTS}/executables", 0.0,
         f"executables={s['executables']};bound={bound};"
         f"buckets_used={len(s['buckets_used'])};"
         f"prefill_calls={n_prefill};"
         f"decode_calls={s['dispatches']['decode']}")
    emit(f"serving/continuous/trace{TRACE_REQUESTS}/queueing", 0.0,
         f"wait_p50_steps={s['wait_p50_steps']:.6f};"
         f"wait_p99_steps={s['wait_p99_steps']:.6f};"
         f"slot_utilization={s['slot_utilization']:.6f};"
         f"steps={s['steps']}")
    # wall time is the informational part (UNGATED_TIMING_SUITES);
    # generated_tokens in derived is the deterministic token count
    emit(f"serving/continuous/trace{TRACE_REQUESTS}/throughput", wall * 1e6,
         f"tokens={s['generated_tokens']}")
    return {"executables": s["executables"], "bound": bound,
            "steps": s["steps"],
            "generated_tokens": s["generated_tokens"],
            "slot_utilization": s["slot_utilization"],
            "wait_p50_steps": s["wait_p50_steps"],
            "wait_p99_steps": s["wait_p99_steps"],
            "dispatches": s["dispatches"],
            "tokens_per_s": s["generated_tokens"] / wall,
            "trace": {"requests": TRACE_REQUESTS, "rate": TRACE_RATE,
                      "seed": TRACE_SEED, "slots": SLOTS,
                      "buckets": list(BUCKETS), "max_new": MAX_NEW}}
