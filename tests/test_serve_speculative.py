"""Speculative decoding correctness (DESIGN.md Sec. 15).

The load-bearing contract is LOSSLESSNESS: greedy speculative decoding
must be BIT-identical to the plain greedy scan — the draft can only
change how fast tokens appear, never which tokens — across draft
depths, architectures (attention / GQA / MLA), kernel backends and
both engines (dense fixed-batch, paged continuous).  The second
contract is ROLLBACK: rejected draft rows must leave the KV cache
bit-identical to never having drafted (pinned against the untouched
init bits past the committed frontier, dense and paged).  Sampling-law
tests cover top-p nucleus truncation and the residual-rejection
acceptance rule.
"""
import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.ops import KernelConfig
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.model import PagedCacheLayout
from repro.serve import (ContinuousEngine, Request, SamplingParams,
                         make_engine, sample_token)
from repro.serve.sampling import fold_pos_keys, speculative_accept

KEY = jax.random.PRNGKey(0)
REF = KernelConfig(backend="ref")
PALLAS = KernelConfig(backend="pallas", interpret=True)

B, P, N = 2, 4, 5   # batch, prompt, max_new — tiny: ~20 engine compiles

# (arch, backend, k) — every axis of the lossless matrix is covered:
# k in {1,2,4,8}, attention (gemma3: softcap + sliding window), GQA
# (granite), MLA (deepseek, MoE-isolated), ref and pallas-interpret
CASES = [
    ("gemma3-1b", "ref", 1),
    ("gemma3-1b", "ref", 2),
    ("gemma3-1b", "ref", 4),
    ("gemma3-1b", "ref", 8),
    ("gemma3-1b", "pallas", 2),
    ("granite-8b", "ref", 2),
    ("granite-8b", "ref", 8),
    ("granite-8b", "pallas", 4),
    ("deepseek-v3-671b", "ref", 2),
    ("deepseek-v3-671b", "ref", 4),
    ("deepseek-v3-671b", "pallas", 1),
]
KC = {"ref": REF, "pallas": PALLAS}

_setup_cache: dict = {}


def _setup(arch):
    """Reduced config + params + prompt batch (MoE/MTP isolated out of
    deepseek so the MLA cache path is tested without routing
    discontinuities — same rationale as tests/test_serve_engine.py)."""
    if arch in _setup_cache:
        return _setup_cache[arch]
    cfg = get_config(arch).reduced()
    if arch == "deepseek-v3-671b":
        cfg = dataclasses.replace(
            cfg, moe=None, mtp=0,
            pattern=tuple(dataclasses.replace(s, ffn="dense")
                          for s in cfg.pattern),
            prologue=tuple(dataclasses.replace(s, ffn="dense")
                           for s in cfg.prologue))
    params = M.init(cfg, KEY, jnp.float32)
    k1 = jax.random.fold_in(KEY, zlib.crc32(arch.encode()) % 1000)
    batch = {"tokens": jax.random.randint(k1, (B, P), 0, cfg.vocab_size)}
    _setup_cache[arch] = (cfg, params, batch)
    return _setup_cache[arch]


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _plain_tokens(arch, kc_name):
    cfg, params, batch = _setup(arch)
    eng = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                      param_dtype=jnp.float32, cache_dtype=jnp.float32,
                      kernel_config=KC[kc_name])
    t, _ = eng.generate(params, batch)
    return np.asarray(t)


# ---------------------------------------------------------------------------
# lossless greedy speculation: dense fixed-batch engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,kc_name,k", CASES)
def test_greedy_spec_bit_identical_to_plain_scan(arch, kc_name, k):
    cfg, params, batch = _setup(arch)
    plain = _plain_tokens(arch, kc_name)
    eng = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                      param_dtype=jnp.float32, cache_dtype=jnp.float32,
                      kernel_config=KC[kc_name], speculate_k=k,
                      draft_layers=1)
    before = eng.dispatch_counter[0]
    res = eng.generate_with_state(params, batch)
    # the whole speculate-verify generation phase is ONE executable call
    assert eng.dispatch_counter[0] - before == 1
    np.testing.assert_array_equal(np.asarray(res.tokens), plain)
    rounds = np.asarray(res.spec.rounds)
    # every live round emits in [1, k+1] tokens
    assert (rounds >= -(-(N - 1) // (k + 1))).all() and \
        (rounds <= N - 1).all()
    assert (np.asarray(res.spec.accepted)
            <= np.asarray(res.spec.drafted)).all()


def test_full_depth_draft_accepts_everything():
    """draft_layers == num_blocks makes the draft the target: greedy
    drafts always match, so every round accepts all k."""
    cfg, params, batch = _setup("gemma3-1b")
    eng = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                      param_dtype=jnp.float32, cache_dtype=jnp.float32,
                      kernel_config=REF, speculate_k=2,
                      draft_layers=cfg.num_blocks)
    res = eng.generate_with_state(params, batch)
    np.testing.assert_array_equal(np.asarray(res.tokens),
                                  _plain_tokens("gemma3-1b", "ref"))
    acc, drafted = np.asarray(res.spec.accepted), np.asarray(res.spec.drafted)
    # raw per-round acceptance is full; only the budget clips emission
    assert (acc == drafted).all() and (drafted > 0).all()


# ---------------------------------------------------------------------------
# separate-draft-model speculation
# ---------------------------------------------------------------------------

def test_draft_config_spec_is_lossless():
    """A separate draft model — even a randomly-initialized one — never
    changes greedy output; an identical draft accepts everything."""
    cfg, params, batch = _setup("gemma3-1b")
    eng = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                      param_dtype=jnp.float32, cache_dtype=jnp.float32,
                      kernel_config=REF, speculate_k=2, draft_cfg=cfg)
    bad_draft = M.init(cfg, jax.random.fold_in(KEY, 123), jnp.float32)
    res = eng.generate_with_state(params, batch, draft_params=bad_draft)
    np.testing.assert_array_equal(np.asarray(res.tokens),
                                  _plain_tokens("gemma3-1b", "ref"))

    res2 = eng.generate_with_state(params, batch, draft_params=params)
    np.testing.assert_array_equal(np.asarray(res2.tokens),
                                  _plain_tokens("gemma3-1b", "ref"))
    assert (np.asarray(res2.spec.accepted)
            == np.asarray(res2.spec.drafted)).all()

    with pytest.raises(ValueError, match="draft_params"):
        eng.generate_with_state(params, batch)


# ---------------------------------------------------------------------------
# rejection rollback: rejected drafts leave the cache untouched
# ---------------------------------------------------------------------------

def test_rejected_drafts_leave_dense_cache_clean():
    """Final speculative caches == plain-scan caches bit-for-bit on the
    shared range, and every row past the committed frontier still holds
    the init bits (zeros) — a rejected draft's write never survives."""
    cfg, params, batch = _setup("gemma3-1b")
    k = 2
    plain = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                        param_dtype=jnp.float32, cache_dtype=jnp.float32,
                        kernel_config=REF)
    spec = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                       param_dtype=jnp.float32, cache_dtype=jnp.float32,
                       kernel_config=REF, speculate_k=k, draft_layers=1)
    rp = plain.generate_with_state(params, batch)
    rs = spec.generate_with_state(params, batch)
    # cache filled for [0, P + N - 1): the last emitted token's K/V is
    # never written by either engine
    lim = P + N - 1
    # seq axis: prologue leaves are (B, S, ...), blocks (L, B, S, ...)
    for grp, ax in (("prologue", 1), ("blocks", 2)):
        for a, b in zip(jax.tree.leaves(rs.caches[grp]),
                        jax.tree.leaves(rp.caches[grp])):
            a, b = np.asarray(a), np.asarray(b)
            sl = [slice(None)] * a.ndim
            sl[ax] = slice(0, lim)
            np.testing.assert_array_equal(a[tuple(sl)], b[tuple(sl)])
            # beyond the frontier: the spec cache (which drafted and
            # rolled back there) must hold the init bits
            sl[ax] = slice(lim, None)
            assert (a[tuple(sl)] == 0).all(), \
                "rejected draft rows survived past the frontier"


# ---------------------------------------------------------------------------
# eos interaction
# ---------------------------------------------------------------------------

def test_spec_eos_freezes_like_plain():
    cfg, params, batch = _setup("gemma3-1b")
    base = _plain_tokens("gemma3-1b", "ref")
    eos = int(base[0, 1])           # row 0 emits this mid-sequence
    kw = dict(batch=B, prompt_len=P, max_new=N, eos_id=eos,
              param_dtype=jnp.float32, cache_dtype=jnp.float32,
              kernel_config=REF)
    pt, pd = make_engine(cfg, _mesh(), **kw).generate(params, batch)
    st = make_engine(cfg, _mesh(), speculate_k=2, draft_layers=1,
                     **kw).generate_with_state(params, batch)
    np.testing.assert_array_equal(np.asarray(st.tokens), np.asarray(pt))
    np.testing.assert_array_equal(np.asarray(st.done), np.asarray(pd))
    np.testing.assert_array_equal(np.asarray(st.lengths),
                                  np.asarray(
                                      make_engine(cfg, _mesh(), **kw)
                                      .generate_with_state(params, batch)
                                      .lengths))


# ---------------------------------------------------------------------------
# sampled speculation: residual rejection
# ---------------------------------------------------------------------------

def test_sampled_spec_full_depth_accepts_all_and_is_deterministic():
    """With the draft == the target (full-depth early exit), q == p
    bitwise, so residual rejection accepts every draft (u*q <= p
    always); and the whole thing is key-deterministic."""
    cfg, params, batch = _setup("gemma3-1b")
    samp = SamplingParams(mode="sample", temperature=0.8, top_k=16)
    eng = make_engine(cfg, _mesh(), batch=B, prompt_len=P, max_new=N,
                      sampling=samp, param_dtype=jnp.float32,
                      cache_dtype=jnp.float32, kernel_config=REF,
                      speculate_k=2, draft_layers=cfg.num_blocks)
    kk = jax.random.PRNGKey(5)
    r1 = eng.generate_with_state(params, batch, kk)
    r2 = eng.generate_with_state(params, batch, kk)
    np.testing.assert_array_equal(np.asarray(r1.tokens),
                                  np.asarray(r2.tokens))
    assert (np.asarray(r1.spec.accepted)
            == np.asarray(r1.spec.drafted)).all()
    t = np.asarray(r1.tokens)
    assert ((t >= 0) & (t < cfg.vocab_size)).all()


def test_speculative_accept_greedy_rule():
    """Unit-level: acceptance length is the leading argmax-match run and
    the correction token is the target argmax at the first mismatch."""
    V, k = 8, 3
    vl = jax.random.normal(jax.random.fold_in(KEY, 7), (2, k + 1, V))
    t_hat = np.asarray(jnp.argmax(vl, -1))
    drafts = t_hat[:, :k].copy()
    drafts[0, 1] = (drafts[0, 1] + 1) % V       # row 0: mismatch at 1
    acc, toks = speculative_accept(vl, jnp.zeros((2, k, V)),
                                   jnp.asarray(drafts), SamplingParams())
    acc, toks = np.asarray(acc), np.asarray(toks)
    assert acc[0] == 1 and acc[1] == k
    assert toks[0, 0] == drafts[0, 0] and toks[0, 1] == t_hat[0, 1]
    np.testing.assert_array_equal(toks[1, :k], drafts[1])
    assert toks[1, k] == t_hat[1, k]            # all-accept bonus token


def test_speculative_accept_residual_rule_distribution():
    """Sampled acceptance: identical p == q accepts everything; a draft
    with zero target mass is always rejected and the correction comes
    from the residual (never the impossible token)."""
    V, k, Bn = 6, 2, 4
    keys = jax.random.split(jax.random.PRNGKey(3), Bn)
    pos = jnp.zeros((Bn,), jnp.int32)
    params = SamplingParams(mode="sample", temperature=1.0)
    lg = jax.random.normal(jax.random.fold_in(KEY, 9), (Bn, k + 1, V))
    dtk = jnp.asarray(np.asarray(jnp.argmax(lg[:, :k], -1)))
    acc, _ = speculative_accept(lg, lg[:, :k], dtk, params, keys, pos)
    assert (np.asarray(acc) == k).all()

    # target assigns -inf to the drafted token -> p_d = 0 -> reject at 0
    lg2 = lg.at[jnp.arange(Bn), 0, dtk[:, 0]].set(-1e30)
    acc2, toks2 = speculative_accept(lg2, lg[:, :k], dtk, params, keys, pos)
    assert (np.asarray(acc2) == 0).all()
    assert (np.asarray(toks2)[:, 0] != np.asarray(dtk)[:, 0]).all()


# ---------------------------------------------------------------------------
# top-p nucleus sampling laws
# ---------------------------------------------------------------------------

def test_top_p_one_is_exactly_temperature_sampling():
    logits = jax.random.normal(jax.random.fold_in(KEY, 11), (4, 64))
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    a = sample_token(logits, SamplingParams(mode="sample", temperature=0.7),
                     keys)
    b = sample_token(logits, SamplingParams(mode="sample", temperature=0.7,
                                            top_p=1.0), keys)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_top_p_restricts_to_nucleus():
    # probs ~ [0.57, 0.21, 0.21/e, ...]: top_p=0.5 keeps only argmax
    logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0, 0.0, -1.0]] * 3)
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    for i in range(25):
        ks = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, i)
        got = np.asarray(sample_token(
            logits, SamplingParams(mode="sample", top_p=0.5), ks))
        assert (got == 0).all(), got


def test_top_p_composes_with_top_k():
    """top_k truncates first, then the nucleus forms over the
    renormalized survivors: flat logits + top_k=4 + top_p=0.5 keeps the
    first two of the four top-k survivors."""
    logits = jnp.asarray([[1.0, 1.0, 1.0 - 1e-6, 1.0 - 1e-6,
                           1.0 - 2e-6, 1.0 - 2e-6, -50.0, -50.0]] * 2)
    keys = jax.random.split(jax.random.PRNGKey(17), 2)
    for i in range(25):
        ks = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, i)
        got = np.asarray(sample_token(
            logits, SamplingParams(mode="sample", top_k=4, top_p=0.5), ks))
        assert (got < 2).all(), got


def test_top_p_validation():
    with pytest.raises(ValueError):
        SamplingParams(mode="sample", top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(mode="sample", top_p=1.5)


def test_fold_pos_keys_streams_are_disjoint():
    keys = jax.random.split(jax.random.PRNGKey(19), 2)
    pos = jnp.asarray([5, 9], jnp.int32)
    a = np.asarray(fold_pos_keys(keys, pos, 0))
    b = np.asarray(fold_pos_keys(keys, pos, 1))
    assert not (a == b).all()
    # (B, T) positions broadcast per request
    c = np.asarray(fold_pos_keys(keys, pos[:, None] + jnp.arange(3), 0))
    assert c.shape[:2] == (2, 3)
    np.testing.assert_array_equal(c[:, 0], a)


# ---------------------------------------------------------------------------
# engine validation
# ---------------------------------------------------------------------------

def test_spec_engine_validation():
    cfg, _, _ = _setup("gemma3-1b")
    mesh = _mesh()
    kw = dict(batch=B, prompt_len=P, max_new=N, param_dtype=jnp.float32,
              cache_dtype=jnp.float32)
    with pytest.raises(ValueError, match="speculate_k"):
        make_engine(cfg, mesh, speculate_k=-1, **kw)
    with pytest.raises(ValueError, match="not both"):
        make_engine(cfg, mesh, speculate_k=2, draft_layers=1,
                    draft_cfg=cfg, **kw)
    with pytest.raises(ValueError, match="draft_layers"):
        make_engine(cfg, mesh, speculate_k=2,
                    draft_layers=cfg.num_blocks + 1, **kw)
    ssm = get_config("mamba2-2.7b").reduced()
    with pytest.raises(NotImplementedError, match="attn-family"):
        make_engine(ssm, mesh, speculate_k=2, **kw)
    vsmall = dataclasses.replace(cfg, vocab_size=cfg.vocab_size // 2)
    with pytest.raises(ValueError, match="vocab"):
        make_engine(cfg, mesh, speculate_k=2, draft_cfg=vsmall, **kw)


# ---------------------------------------------------------------------------
# continuous engine: paged speculation + batched prefill admission
# ---------------------------------------------------------------------------

def _trace(cfg, n=5, slots_arrive=True):
    rng = np.random.RandomState(7)
    reqs = []
    for rid in range(n):
        pl = int(rng.randint(2, 8))
        reqs.append(Request(
            rid=rid, tokens=rng.randint(0, cfg.vocab_size, size=pl).tolist(),
            arrival=0.0 if slots_arrive else float(rid // 2)))
    return reqs


def _layout():
    return PagedCacheLayout(page_size=4, num_pages=32, max_pages_per_slot=5)


def test_continuous_spec_greedy_parity():
    """Paged speculative decoding emits the exact same per-request
    tokens as the plain lockstep engine — ragged slot advance, window
    rollback over page pools and all."""
    cfg, params, _ = _setup("gemma3-1b")
    kw = dict(slots=3, layout=_layout(), max_new=4, buckets=(4, 8),
              kernel_config=REF, cache_dtype=jnp.float32)
    reqs = _trace(cfg, n=6, slots_arrive=False)
    base = ContinuousEngine(cfg, **kw).run(params, reqs)
    spec = ContinuousEngine(cfg, speculate_k=2, draft_layers=1,
                            **kw).run(params, reqs)
    for rid in base["results"]:
        assert base["results"][rid].tokens == spec["results"][rid].tokens
    st = spec["stats"]["speculative"]
    assert st["rounds"] > 0 and 0.0 <= st["acceptance_rate"] <= 1.0
    # speculation reduces decode steps whenever anything is accepted
    assert spec["stats"]["steps"] <= base["stats"]["steps"]
    # still one decode executable (the spec round replaces it)
    assert spec["stats"]["executables"] <= 2 + 1


def test_continuous_spec_rollback_pools_bitwise():
    """With identical admission (everything arrives at step 0, one
    request per slot, no page reuse) the speculative run's final pools
    are bit-identical to the plain run's outside scratch page 0 —
    rejected drafts left no trace in the paged cache either."""
    cfg, params, _ = _setup("gemma3-1b")
    kw = dict(slots=2, layout=_layout(), max_new=4, buckets=(4, 8),
              kernel_config=REF, cache_dtype=jnp.float32)
    reqs = _trace(cfg, n=2)
    e1 = ContinuousEngine(cfg, **kw)
    e2 = ContinuousEngine(cfg, speculate_k=2, draft_layers=1, **kw)
    r1 = e1.run(params, reqs)
    r2 = e2.run(params, reqs)
    for rid in r1["results"]:
        assert r1["results"][rid].tokens == r2["results"][rid].tokens
    for grp in ("prologue", "blocks"):
        page_ax = 0 if grp == "prologue" else 1
        for a, b in zip(jax.tree.leaves(e1.pools[grp]),
                        jax.tree.leaves(e2.pools[grp])):
            a, b = np.asarray(a), np.asarray(b)
            sl = [slice(None)] * a.ndim
            sl[page_ax] = slice(1, None)   # page 0 = scratch, excluded
            np.testing.assert_array_equal(a[tuple(sl)], b[tuple(sl)])


def test_continuous_prefill_batch_parity_and_executable_bound():
    cfg, params, _ = _setup("gemma3-1b")
    kw = dict(slots=3, layout=_layout(), max_new=4, buckets=(4, 8),
              kernel_config=REF, cache_dtype=jnp.float32)
    reqs = _trace(cfg, n=6, slots_arrive=False)
    base = ContinuousEngine(cfg, **kw).run(params, reqs)
    eng = ContinuousEngine(cfg, prefill_batch=3, **kw)
    out = eng.run(params, reqs)
    for rid in base["results"]:
        assert base["results"][rid].tokens == out["results"][rid].tokens
    s = out["stats"]
    # at least one grouped admission actually happened
    assert any("x" in k for k in s["dispatches"] if k.startswith("prefill"))
    # executables <= #buckets per admission-group size + 1 decode
    assert s["executables"] <= len(kw["buckets"]) * 3 + 1
    # grouped admission must not add decode steps
    assert s["steps"] <= base["stats"]["steps"]


def test_continuous_spec_validation():
    cfg, _, _ = _setup("gemma3-1b")
    with pytest.raises(ValueError, match="draft_layers"):
        ContinuousEngine(cfg, slots=2, layout=_layout(), max_new=4,
                         buckets=(4, 8), draft_layers=1)
    with pytest.raises(ValueError, match="prefill_batch"):
        ContinuousEngine(cfg, slots=2, layout=_layout(), max_new=4,
                         buckets=(4, 8), prefill_batch=0)
    eng = ContinuousEngine(cfg, slots=2, layout=_layout(), max_new=18,
                           buckets=(4, 8), speculate_k=4)
    with pytest.raises(ValueError, match="speculate_k"):
        eng.run(None, [Request(rid=0, tokens=[1, 2], arrival=0.0)])
