"""Continuous-batching serve engine: dense-vs-paged teacher-forced
parity (ref bitwise + pallas-interpret), the slot-refill property (a
request admitted into a recycled slot produces bit-identical tokens to
the same request run alone, and to the fixed-batch dense engine), the
bounded-executable contract over a ragged Poisson trace, and the
``generate_with_state`` caches/lengths satellite."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ops import KernelConfig
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.model import PagedCacheLayout
from repro.serve import (ContinuousEngine, PagePool, Request,
                         SamplingParams, bucket_for, decode_logits_scan,
                         make_engine, poisson_trace, prompt_buckets)

KEY = jax.random.PRNGKey(0)
REF = KernelConfig(backend="ref")
PALLAS = KernelConfig(backend="pallas", interpret=True)


@functools.lru_cache(maxsize=None)
def _setup():
    cfg = get_config("gemma3-1b").reduced()   # windowed + global attn mix
    params = M.init(cfg, KEY, jnp.float32)
    return cfg, params


def _paged_state(cfg, B, layout):
    """Fresh pools + a block table of distinct allocated pages."""
    pools = M.init_paged_cache(cfg, layout, jnp.float32)
    pool = PagePool(layout.num_pages)
    table = np.zeros((B, layout.max_pages_per_slot), np.int32)
    for b in range(B):
        table[b] = pool.alloc(layout.max_pages_per_slot)
    return pools, jnp.asarray(table)


# ---------------------------------------------------------------------------
# host-side helpers
# ---------------------------------------------------------------------------

def test_page_pool_contract():
    pool = PagePool(8)
    assert pool.available == 7           # page 0 reserved scratch
    a = pool.alloc(3)
    assert 0 not in a and len(set(a)) == 3
    with pytest.raises(RuntimeError):
        pool.alloc(5)
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)                     # double free
    assert pool.available == 7


def test_prompt_buckets_policy():
    assert prompt_buckets(48) == (8, 16, 32, 64)
    assert bucket_for(9, (8, 16, 32)) == 16
    assert bucket_for(16, (8, 16, 32)) == 16
    with pytest.raises(ValueError):
        bucket_for(33, (8, 16, 32))


def test_paged_layout_validation():
    with pytest.raises(ValueError):
        PagedCacheLayout(page_size=8, num_pages=4, max_pages_per_slot=4)
    assert PagedCacheLayout(page_size=8, max_pages_per_slot=4).max_seq == 32


def test_poisson_trace_deterministic():
    a = poisson_trace(6, rate=0.5, seed=3)
    b = poisson_trace(6, rate=0.5, seed=3)
    assert a == b
    assert a != poisson_trace(6, rate=0.5, seed=4)
    assert all(x.arrival <= y.arrival for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "mamba2-2.7b",
                                  "seamless-m4t-large-v2"])
def test_paged_cache_rejects_non_attn_families(arch):
    cfg = get_config(arch).reduced()
    with pytest.raises(NotImplementedError):
        M.init_paged_cache(cfg, PagedCacheLayout())


# ---------------------------------------------------------------------------
# dense-vs-paged decode parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kcfg,bitwise", [(REF, True), (PALLAS, False)])
def test_decode_logits_scan_dense_vs_paged(kcfg, bitwise):
    """Teacher-forced scoring over the paged layout == the dense layout:
    bitwise on the ref backend (the gather argument), numerically under
    interpret-mode Pallas."""
    cfg, params = _setup()
    B, T = 2, 6
    layout = PagedCacheLayout(page_size=8, num_pages=12,
                              max_pages_per_slot=4)
    S = layout.max_seq                    # dense cache sized to the view
    tokens = jax.random.randint(jax.random.fold_in(KEY, 7), (B, T), 0,
                                cfg.vocab_size)
    dense = M.init_cache(cfg, B, S, jnp.float32)
    ld, _ = decode_logits_scan(cfg, params, dense, tokens, 0,
                               decode_mode="dus", kernel_config=REF)
    pools, table = _paged_state(cfg, B, layout)
    lp, _ = decode_logits_scan(cfg, params, pools, tokens,
                               jnp.zeros((B,), jnp.int32),
                               decode_mode="paged", block_table=table,
                               kernel_config=kcfg)
    if bitwise:
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(ld))
    else:
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld),
                                   atol=2e-4, rtol=2e-4)


def test_paged_scan_advances_slots_independently():
    """Ragged per-slot start positions: slot 1 scored from position 5
    matches slot 1 of a batch scored uniformly from 5."""
    cfg, params = _setup()
    layout = PagedCacheLayout(page_size=8, num_pages=12,
                              max_pages_per_slot=4)
    B, T = 2, 4
    k = jax.random.fold_in(KEY, 11)
    prefix = jax.random.randint(k, (B, 5), 0, cfg.vocab_size)
    tokens = jax.random.randint(jax.random.fold_in(k, 1), (B, T), 0,
                                cfg.vocab_size)
    pools, table = _paged_state(cfg, B, layout)
    # fill both slots with the prefix, then score with ragged starts
    _, pools = decode_logits_scan(cfg, params, pools, prefix,
                                  jnp.zeros((B,), jnp.int32),
                                  decode_mode="paged", block_table=table,
                                  kernel_config=REF)
    lr, _ = decode_logits_scan(cfg, params, pools, tokens,
                               jnp.array([5, 5], jnp.int32),
                               decode_mode="paged", block_table=table,
                               kernel_config=REF)
    # same state, slot 1 alone (B=1 pools reuse slot 1's pages)
    l1, _ = decode_logits_scan(cfg, params, pools, tokens[1:],
                               jnp.array([5], jnp.int32),
                               decode_mode="paged", block_table=table[1:],
                               kernel_config=REF)
    np.testing.assert_array_equal(np.asarray(lr[1]), np.asarray(l1[0]))


# ---------------------------------------------------------------------------
# continuous engine
# ---------------------------------------------------------------------------

def _engine(slots, *, max_new=4, sampling=SamplingParams(), eos_id=None):
    cfg, params = _setup()
    layout = PagedCacheLayout(page_size=8, num_pages=slots * 5 + 3,
                              max_pages_per_slot=5)
    eng = ContinuousEngine(cfg, slots=slots, layout=layout, max_new=max_new,
                           buckets=(8, 16, 32), sampling=sampling,
                           eos_id=eos_id, cache_dtype=jnp.float32,
                           kernel_config=REF)
    return cfg, params, eng


@pytest.mark.parametrize("sampling", [SamplingParams(),
                                      SamplingParams(mode="sample",
                                                     temperature=0.8)])
def test_slot_refill_bit_identical(sampling):
    """Three requests funneled through ONE slot (forced recycling): the
    later requests, decoded in recycled pages, match the same request
    re-run on the same (dirty) engine alone — and PRNG streams are
    keyed by request id, so the rerun reuses the identical stream."""
    cfg, params, eng = _engine(1, sampling=sampling)
    reqs = [Request(rid=i, tokens=tuple(range(3 + 2 * i)), arrival=0.0)
            for i in range(3)]
    base = jax.random.PRNGKey(42)
    first = eng.run(params, reqs, base_key=base)
    for r in reqs:
        alone = eng.run(params, [r], base_key=base)
        assert alone["results"][r.rid].tokens == \
            first["results"][r.rid].tokens


def test_continuous_matches_dense_engine_greedy():
    """A request served through the continuous paged engine produces
    bit-identical greedy tokens to the fixed-batch dense engine."""
    cfg, params, eng = _engine(2)
    reqs = poisson_trace(3, rate=1.0, seed=5, min_prompt=4, max_prompt=12,
                         vocab_size=cfg.vocab_size)
    out = eng.run(params, reqs)
    mesh = make_mesh((1, 1), ("data", "model"))
    for r in reqs:
        dense = make_engine(cfg, mesh, batch=1, prompt_len=r.prompt_len,
                            max_new=4, param_dtype=jnp.float32,
                            cache_dtype=jnp.float32, kernel_config=REF)
        toks, _ = dense.generate(
            params, {"tokens": jnp.asarray([r.tokens], jnp.int32)})
        assert list(map(int, toks[0])) == out["results"][r.rid].tokens


def test_ragged_trace_bounded_executables():
    """The 32-request ragged Poisson trace acceptance contract:
    executable count <= #prompt-buckets + 1 (pinned by the dispatch
    counter), every request completes, slot utilization is reported."""
    cfg, params, eng = _engine(4, max_new=4, eos_id=1)
    trace = poisson_trace(32, rate=0.7, seed=0, min_prompt=4,
                          max_prompt=30, vocab_size=cfg.vocab_size)
    out = eng.run(params, trace)
    s = out["stats"]
    assert s["requests"] == 32
    assert s["executables"] == eng.num_executables \
        <= len(eng.buckets) + 1
    assert set(s["buckets_used"]) <= set(eng.buckets)
    # dispatch counts pin the model: one prefill per request, one decode
    # per busy step
    n_prefill = sum(v for k, v in s["dispatches"].items()
                    if k.startswith("prefill_"))
    assert n_prefill == 32
    assert 0.0 < s["slot_utilization"] <= 1.0
    assert s["wait_p99_steps"] >= s["wait_p50_steps"] >= 0.0
    for r in trace:
        got = out["results"][r.rid].tokens
        assert 1 <= len(got) <= 4
        if len(got) < 4:
            assert got[-1] == 1          # early exit only via eos


@pytest.mark.parametrize("speculate_k", [0, 2])
def test_run_record_stamps_and_counts(speculate_k):
    """The engine's record of a run: each request's host stamps are in
    order, each call's output is ready after its dispatch, and the
    record's calls and tokens add up to ``stats`` and the dispatch
    counter."""
    cfg, params = _setup()
    layout = PagedCacheLayout(page_size=8, max_pages_per_slot=5)
    eng = ContinuousEngine(cfg, slots=2, layout=layout, max_new=4,
                           buckets=(8, 16, 32), cache_dtype=jnp.float32,
                           kernel_config=REF, speculate_k=speculate_k,
                           prefill_batch=2)
    trace = poisson_trace(6, rate=1.0, seed=2, min_prompt=4, max_prompt=20,
                          vocab_size=cfg.vocab_size)
    out = eng.run(params, trace)
    s, res, rec = out["stats"], out["results"], eng.record
    for r in res.values():
        assert r.t_eligible <= r.t_admitted <= r.t_first <= r.t_done
    assert all(c.t_dispatch <= c.t_ready for c in rec)
    assert all(a.t_ready <= b.t_dispatch for a, b in zip(rec, rec[1:]))
    prefills = [c for c in rec if c.kind == "prefill"]
    decodes = [c for c in rec if c.kind == "decode"]
    assert len(prefills) == sum(v for k, v in s["dispatches"].items()
                                if k.startswith("prefill_"))
    assert len(decodes) == s["dispatches"]["decode"]
    assert sum(c.group for c in prefills) == s["requests"] == len(trace)
    assert sorted(n for c in prefills for n in c.prompt_lens) == \
        sorted(r.prompt_len for r in trace)
    assert all(c.bucket in eng.buckets for c in prefills)
    assert all(c.group == len(c.active) >= 1 for c in decodes)
    if not speculate_k:
        # one token a prefilled request, one an active slot a decode
        assert s["generated_tokens"] == s["requests"] + sum(
            c.group for c in decodes)
    for q in ("wait", "ttft", "itl"):
        assert s[f"{q}_p99_ms"] >= s[f"{q}_p50_ms"] >= 0.0
    assert s["ttft_p50_ms"] >= s["wait_p50_ms"]
    # a later run starts a record of its own
    eng.run(params, trace[:1])
    assert sum(c.kind == "prefill" for c in eng.record) == 1


def test_page_exhaustion_defers_admission():
    """With pages for only one slot-load in the pool, the second request
    waits for the first to retire — and still completes."""
    cfg, params = _setup()
    layout = PagedCacheLayout(page_size=8, num_pages=6,
                              max_pages_per_slot=5)
    eng = ContinuousEngine(cfg, slots=2, layout=layout, max_new=3,
                           buckets=(8, 16, 32), cache_dtype=jnp.float32,
                           kernel_config=REF)
    reqs = [Request(rid=0, tokens=tuple(range(6)), arrival=0.0),
            Request(rid=1, tokens=tuple(range(5)), arrival=0.0)]
    out = eng.run(params, reqs)
    assert sorted(out["results"]) == [0, 1]
    assert out["results"][1].admitted_step > out["results"][0].admitted_step
    assert all(len(r.tokens) == 3 for r in out["results"].values())


# ---------------------------------------------------------------------------
# generate_with_state satellite (dense fixed-batch engine)
# ---------------------------------------------------------------------------

def test_generate_with_state_returns_caches_and_lengths():
    cfg, params = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    B, L, N = 2, 8, 4
    batch = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 3),
                                          (B, L), 0, cfg.vocab_size)}
    eng = make_engine(cfg, mesh, batch=B, prompt_len=L, max_new=N,
                      param_dtype=jnp.float32, cache_dtype=jnp.float32,
                      kernel_config=REF)
    res = eng.generate_with_state(params, batch)
    toks, done = eng.generate(params, batch)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(res.tokens))
    assert list(map(int, res.lengths)) == [N, N]
    want = jax.eval_shape(lambda: M.init_cache(cfg, B, L + N, jnp.float32))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), res.caches)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    # caches really are the post-generation state: teacher-forcing the
    # generated tokens from the prefill cache reproduces them
    _, c0, _ = eng.prefill_fn(params, batch)
    _, replay = decode_logits_scan(cfg, params, c0, res.tokens[:, :-1], L,
                                   decode_mode="dus", kernel_config=REF)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(replay)[0]),
        np.asarray(jax.tree.leaves(res.caches)[0]))


def test_generate_with_state_eos_lengths():
    cfg, params = _setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    B, L, N = 2, 8, 4
    batch = {"tokens": jax.random.randint(jax.random.fold_in(KEY, 4),
                                          (B, L), 0, cfg.vocab_size)}
    free = make_engine(cfg, mesh, batch=B, prompt_len=L, max_new=N,
                       param_dtype=jnp.float32, cache_dtype=jnp.float32,
                       kernel_config=REF)
    first = int(free.generate(params, batch)[0][0, 0])
    eng = make_engine(cfg, mesh, batch=B, prompt_len=L, max_new=N,
                      eos_id=first, param_dtype=jnp.float32,
                      cache_dtype=jnp.float32, kernel_config=REF)
    res = eng.generate_with_state(params, batch)
    assert int(res.lengths[0]) == 1 and bool(res.done[0])
    assert all(int(t) == first for t in res.tokens[0])   # frozen at eos
    assert int(res.lengths[1]) <= N
