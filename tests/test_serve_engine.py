"""Decode-engine correctness: the compiled generation scan is ONE
executable call per generation, bit-identical to the per-token dispatch
loop for greedy decoding on the ref backend; token-by-token scan decode
reproduces full-prefill logits for every arch family under both the ref
and the interpret-mode Pallas flash-attention backends; and the
explicit ``decode_mode`` argument lets bundles with different cache
policies coexist (the retrace-poisoning regression for the deleted
``APPEND_FREE_DECODE`` module global)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.steps import make_decode_step, make_prefill
from repro.kernels.ops import KernelConfig
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.serve import (SamplingParams, decode_logits_scan, make_engine,
                         sample_token)
from repro.serve import engine as engine_mod

KEY = jax.random.PRNGKey(0)
REF = KernelConfig(backend="ref")
PALLAS = KernelConfig(backend="pallas", interpret=True)

# one representative (reduced) arch per family the decode engine serves
FAMILY_ARCHS = [
    ("attention", "granite-8b"),
    ("mla", "deepseek-v3-671b"),
    ("mamba2", "mamba2-2.7b"),
    ("encoder-decoder", "seamless-m4t-large-v2"),
]


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _setup(arch, *, B=2, T=8):
    import zlib   # per-arch fold-in: stable across processes, unlike hash()
    cfg = get_config(arch).reduced()
    if arch == "deepseek-v3-671b":
        # Isolate the MLA cache path: top-k MoE routing is discontinuous,
        # so a ~1e-6 prefill-vs-decode hidden-state difference can flip
        # an expert choice and move logits by 1e-2 — a property of MoE
        # routing, not of the decode path (the full MoE config is pinned
        # bit-exactly scan-vs-loop in
        # test_moe_scan_decode_matches_per_token_loop).
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=None, mtp=0,
            pattern=tuple(dataclasses.replace(s, ffn="dense")
                          for s in cfg.pattern),
            prologue=tuple(dataclasses.replace(s, ffn="dense")
                           for s in cfg.prologue))
    params = M.init(cfg, KEY, jnp.float32)
    k1, k2 = jax.random.split(
        jax.random.fold_in(KEY, zlib.crc32(arch.encode()) % 1000))
    batch = {"tokens": jax.random.randint(k1, (B, T), 0, cfg.vocab_size)}
    if cfg.frontend == "audio":
        from repro.models.frontends import stub_audio_frontend
        batch["frames"] = stub_audio_frontend(k2, B, cfg.d_model,
                                              jnp.float32, frames=8)
    return cfg, params, batch


def _full_logits(cfg, params, batch, kc):
    """Full-forward logits oracle (same backend as the decode side)."""
    enc_out = None
    if cfg.encoder is not None:
        enc_out = M.encode(cfg, params, batch["frames"], kernel_config=kc)
    h, _, _ = M.backbone(cfg, params, batch["tokens"], enc_out=enc_out,
                         kernel_config=kc)
    logits = h @ M._out_proj(cfg, params)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits, enc_out


# ---------------------------------------------------------------------------
# decode-vs-prefill logits parity, per arch family x kernel backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kc", [REF, PALLAS], ids=["ref", "pallas-interp"])
@pytest.mark.parametrize("family,arch", FAMILY_ARCHS)
def test_scan_decode_reproduces_full_prefill_logits(family, arch, kc):
    cfg, params, batch = _setup(arch)
    tokens = batch["tokens"]
    B, T = tokens.shape
    P = T // 2
    full, enc_out = _full_logits(cfg, params, batch, kc)

    pre_batch = dict(batch, tokens=tokens[:, :P])
    logits, caches, enc2 = M.prefill(cfg, params, pre_batch, T, jnp.float32,
                                     kernel_config=kc)
    tol = dict(atol=3e-3, rtol=3e-3) if cfg.family in ("ssm", "hybrid") \
        else dict(atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, P - 1]), **tol)

    ls, _ = decode_logits_scan(cfg, params, caches, tokens[:, P:], P,
                               enc_out=enc2, kernel_config=kc)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(full[:, P:]),
                               **tol)


# ---------------------------------------------------------------------------
# one executable call for the whole decode phase; scan == loop for greedy
# ---------------------------------------------------------------------------

def test_generation_is_one_executable_call_and_matches_loop(monkeypatch):
    traces = [0]
    real = M.decode_step

    def counted(*a, **k):
        traces[0] += 1
        return real(*a, **k)

    monkeypatch.setattr(M, "decode_step", counted)
    make_engine.cache_clear()   # force a fresh trace under the counter

    cfg, params, batch = _setup("gemma3-1b", B=2, T=8)
    mesh = _mesh()
    B, P, N = 2, 8, 6
    engine = make_engine(cfg, mesh, batch=B, prompt_len=P, max_new=N,
                         param_dtype=jnp.float32, cache_dtype=jnp.float32)
    toks, done = engine.generate(params, batch)
    assert toks.shape == (B, N)
    assert engine.dispatch_counter[0] == 1
    # the scan traces decode_step a bounded number of times, NOT once
    # per generated token — the whole phase is one compiled loop
    first_traces = traces[0]
    assert 1 <= first_traces < N

    toks2, _ = engine.generate(params, batch)
    assert engine.dispatch_counter[0] == 2
    assert traces[0] == first_traces, \
        "second generation must reuse the compiled executable (no retrace)"
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))

    # per-token dispatch loop oracle: bit-identical greedy tokens on the
    # (default CPU = ref) backend — the acceptance criterion
    pre = make_prefill(cfg, mesh, batch=B, seq=P + N,
                       param_dtype=jnp.float32, cache_dtype=jnp.float32)
    dec = make_decode_step(cfg, mesh, batch=B, seq=P + N,
                           param_dtype=jnp.float32, cache_dtype=jnp.float32)
    logits, cache, _ = pre.fn(params, batch)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    outs = [tok]
    for i in range(N - 1):
        logits, cache = dec.fn(params, cache, tok, jnp.int32(P + i))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        outs.append(tok)
    loop = jnp.concatenate(outs, axis=1)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(loop))


def test_engine_memoized_on_config():
    cfg, params, batch = _setup("gemma3-1b")
    mesh = _mesh()
    kw = dict(batch=2, prompt_len=8, max_new=4, param_dtype=jnp.float32,
              cache_dtype=jnp.float32)
    e1 = make_engine(cfg, mesh, **kw)
    assert make_engine(cfg, mesh, **kw) is e1
    e2 = make_engine(cfg, mesh, sampling=SamplingParams(mode="sample"),
                     **kw)
    assert e2 is not e1
    e3 = make_engine(cfg, mesh, kernel_config=PALLAS, **kw)
    assert e3 is not e1 and e3.kernel_config == PALLAS


def test_eos_done_mask_freezes_finished_requests():
    cfg, params, batch = _setup("gemma3-1b", B=2, T=8)
    mesh = _mesh()
    B, P, N = 2, 8, 6
    base = make_engine(cfg, mesh, batch=B, prompt_len=P, max_new=N,
                       param_dtype=jnp.float32, cache_dtype=jnp.float32)
    toks0, done0 = base.generate(params, batch)
    assert not bool(np.asarray(done0).any())

    eos = int(toks0[0, 1])          # row 0 emits this at step 1
    eng = make_engine(cfg, mesh, batch=B, prompt_len=P, max_new=N,
                      eos_id=eos, param_dtype=jnp.float32,
                      cache_dtype=jnp.float32)
    toks, done = eng.generate(params, batch)
    t = np.asarray(toks)
    t0 = np.asarray(toks0)
    for b in range(B):
        hits = np.where(t0[b] == eos)[0]
        if len(hits):
            first = hits[0]
            # identical up to and including the first eos, frozen after
            np.testing.assert_array_equal(t[b, :first + 1],
                                          t0[b, :first + 1])
            assert (t[b, first:] == eos).all()
            assert bool(np.asarray(done)[b])
        else:
            np.testing.assert_array_equal(t[b], t0[b])
            assert not bool(np.asarray(done)[b])
    assert bool(np.asarray(done)[0])


def test_generation_with_sampling_and_pallas_backend():
    """Sampled generation through the interpret-mode Pallas decode path
    stays shape-correct, in-vocab, and key-deterministic."""
    cfg, params, batch = _setup("gemma3-1b", B=2, T=8)
    mesh = _mesh()
    eng = make_engine(cfg, mesh, batch=2, prompt_len=8, max_new=4,
                      sampling=SamplingParams(mode="sample",
                                              temperature=0.7, top_k=8),
                      kernel_config=PALLAS, param_dtype=jnp.float32,
                      cache_dtype=jnp.float32)
    k = jax.random.PRNGKey(3)
    t1, _ = eng.generate(params, batch, key=k)
    t2, _ = eng.generate(params, batch, key=k)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert ((np.asarray(t1) >= 0) & (np.asarray(t1) < cfg.vocab_size)).all()


# ---------------------------------------------------------------------------
# decode_mode bundle coexistence (retrace-poisoning regression)
# ---------------------------------------------------------------------------

def test_decode_mode_bundles_coexist_without_retrace_poisoning():
    """Two decode-step bundles with different ``decode_mode``s built from
    the same config must each keep their own traced behaviour across
    interleaved calls.  With the deleted ``APPEND_FREE_DECODE`` module
    global this depended on nobody tracing concurrently; the explicit
    argument makes the mode part of each bundle's closure."""
    cfg, params, batch = _setup("granite-8b", B=2, T=8)
    mesh = _mesh()
    B, S = 2, 10
    pre = make_prefill(cfg, mesh, batch=B, seq=S, param_dtype=jnp.float32,
                       cache_dtype=jnp.float32)
    logits, cache, _ = pre.fn(params, batch)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)

    dus = make_decode_step(cfg, mesh, batch=B, seq=S,
                           param_dtype=jnp.float32, cache_dtype=jnp.float32)
    af = make_decode_step(cfg, mesh, batch=B, seq=S,
                          param_dtype=jnp.float32, cache_dtype=jnp.float32,
                          append_free=True)
    assert dus.decode_mode == "dus" and af.decode_mode == "append_free"

    out_dus1, cache_dus = dus.fn(params, cache, tok, jnp.int32(8))
    out_af1, cache_af1 = af.fn(params, cache, tok, jnp.int32(8))
    # interleaved re-calls: each bundle must reproduce its own first
    # result bit-for-bit (the stale-global failure mode served one
    # bundle's trace to the other)
    out_dus2, _ = dus.fn(params, cache, tok, jnp.int32(8))
    out_af2, cache_af2 = af.fn(params, cache, tok, jnp.int32(8))
    np.testing.assert_array_equal(np.asarray(out_dus1), np.asarray(out_dus2))
    np.testing.assert_array_equal(np.asarray(out_af1), np.asarray(out_af2))

    # same logits within LSE-combine tolerance, distinct cache policies
    np.testing.assert_allclose(np.asarray(out_af1), np.asarray(out_dus1),
                               atol=3e-4, rtol=3e-4)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_af2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    wrote = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_dus)))
    assert wrote, "dus bundle must write the fresh K/V into the cache"


# ---------------------------------------------------------------------------
# sampling layer
# ---------------------------------------------------------------------------

def test_sampling_params_validate():
    with pytest.raises(ValueError):
        SamplingParams(mode="nope")
    with pytest.raises(ValueError):
        SamplingParams(mode="sample", temperature=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=0)


def test_greedy_sampling_is_argmax():
    logits = jax.random.normal(KEY, (4, 64))
    got = sample_token(logits, SamplingParams())
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_top_k_one_equals_greedy():
    logits = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 64))
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    got = sample_token(logits, SamplingParams(mode="sample",
                                              temperature=2.0, top_k=1),
                       keys)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_top_k_restricts_support():
    logits = jnp.asarray([[10.0, 9.0, 8.0, -5.0, -6.0, -7.0]] * 3)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    for i in range(20):
        ks = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, i)
        got = np.asarray(sample_token(
            logits, SamplingParams(mode="sample", temperature=5.0, top_k=3),
            ks))
        assert (got < 3).all(), got


def test_moe_scan_decode_matches_per_token_loop():
    """The full MoE + MLA config (routing discontinuities and all): the
    generation scan must agree with the per-token decode loop to f32
    noise — same routing decisions, same cache math."""
    cfg = get_config("deepseek-v3-671b").reduced()
    params = M.init(cfg, KEY, jnp.float32)
    tokens = jax.random.randint(jax.random.fold_in(KEY, 21), (2, 8), 0,
                                cfg.vocab_size)
    _, caches, _ = M.prefill(cfg, params, {"tokens": tokens[:, :4]}, 8,
                             jnp.float32)
    ls, _ = decode_logits_scan(cfg, params, caches, tokens[:, 4:], 4)
    caches2 = caches
    for i in range(4, 8):
        lg, caches2 = M.decode_step(cfg, params, caches2,
                                    tokens[:, i:i + 1], i)
        np.testing.assert_allclose(np.asarray(ls[:, i - 4]),
                                   np.asarray(lg[:, 0]),
                                   atol=2e-5, rtol=2e-5)


def test_teacher_forced_scan_matches_per_token_loop():
    """decode_logits_scan (the scoring building block) == the per-token
    decode loop, bit-for-bit on the default backend."""
    cfg, params, batch = _setup("granite-8b", B=2, T=8)
    tokens = batch["tokens"]
    _, caches, _ = M.prefill(cfg, params, {"tokens": tokens[:, :4]}, 8,
                             jnp.float32)
    ls, _ = decode_logits_scan(cfg, params, caches, tokens[:, 4:], 4)
    caches2 = caches
    for i in range(4, 8):
        step_logits, caches2 = M.decode_step(cfg, params, caches2,
                                             tokens[:, i:i + 1], i)
        np.testing.assert_allclose(np.asarray(ls[:, i - 4]),
                                   np.asarray(step_logits[:, 0]),
                                   atol=1e-5, rtol=1e-5)


def test_engine_module_has_no_mutable_mode_flag():
    """The engine bakes decode_mode/kernel config into the bundle — no
    trace-time module globals (the discipline this PR extends from
    FORCE_PALLAS_INTERPRET to APPEND_FREE_DECODE)."""
    from repro.models import attention as A
    assert not hasattr(A, "APPEND_FREE_DECODE")
    assert not hasattr(engine_mod, "DECODE_MODE")
