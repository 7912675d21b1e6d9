"""Paged (block-table) attention conformance: the ref oracle is
BIT-identical to the dense grouped path over the same cache contents,
and the Pallas kernel (interpret mode) matches the oracle across
ragged ``(Tq, k_valid_len)`` sweeps for the attention / GQA / MQA /
MLA-shaped (hd_v != hd) families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import _CHUNK, paged_flash_attention_pallas
from repro.kernels.ops import KernelConfig, pallas_shape_ok

KEY = jax.random.PRNGKey(0)

# (H, KV, hd, hd_v): GQA, MQA, MHA, and the MLA-shaped head (hd_v != hd
# — the decompressed latent attention the MLA family serves with)
FAMILIES = [
    ("gqa", 8, 2, 32, 32),
    ("mqa", 4, 1, 32, 32),
    ("mha", 4, 4, 32, 32),
    ("mla", 4, 4, 64, 32),
]


def _case(seed, *, B, Tq, H, KV, hd, hd_v, ps, maxp, num_pages, dtype,
          q_start, k_valid):
    """Random paged cache + the dense cache holding the same bits at the
    same logical positions (S = maxp * ps)."""
    assert B * maxp <= num_pages - 1
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    S = maxp * ps
    q = jax.random.normal(ks[0], (B, Tq, H, hd), jnp.float32).astype(dtype)
    kd = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32).astype(dtype)
    vd = jax.random.normal(ks[2], (B, S, KV, hd_v), jnp.float32).astype(dtype)
    # distinct physical pages per (row, logical page), page 0 unused
    perm = np.random.RandomState(seed).permutation(num_pages - 1)[:B * maxp]
    table = (perm + 1).reshape(B, maxp).astype(np.int32)
    kp = np.zeros((num_pages, ps, KV, hd), np.float32)
    vp = np.zeros((num_pages, ps, KV, hd_v), np.float32)
    kd_n, vd_n = np.asarray(kd, np.float32), np.asarray(vd, np.float32)
    for b in range(B):
        for j in range(maxp):
            kp[table[b, j]] = kd_n[b, j * ps:(j + 1) * ps]
            vp[table[b, j]] = vd_n[b, j * ps:(j + 1) * ps]
    return (q, kd, vd, jnp.asarray(kp).astype(dtype),
            jnp.asarray(vp).astype(dtype), jnp.asarray(table),
            jnp.asarray(q_start, jnp.int32), jnp.asarray(k_valid, jnp.int32))


@pytest.mark.parametrize("fam,H,KV,hd,hd_v", FAMILIES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_ref_bitwise_vs_dense_ref(fam, H, KV, hd, hd_v, dtype):
    """Gathering pages is indexing: against a dense cache holding the
    same bits the paged oracle is BIT-identical to grouped_sdpa_ref —
    the acceptance contract behind dense-vs-paged serve parity."""
    B, Tq, ps, maxp = 2, 3, 8, 3
    q, kd, vd, kp, vp, table, qs, kv = _case(
        1, B=B, Tq=Tq, H=H, KV=KV, hd=hd, hd_v=hd_v, ps=ps, maxp=maxp,
        num_pages=8, dtype=dtype, q_start=[5, 5], k_valid=[8, 13])
    got = ref.paged_sdpa_ref(q, kp, vp, table, q_start=qs, k_valid_len=kv)
    want = ref.grouped_sdpa_ref(q, kd, vd, q_pos0=5, k_valid_len=kv)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_ref_ragged_q_start_rows():
    """Per-request ragged q_start == running each row through the dense
    ref with its own scalar q_pos0."""
    B, Tq, H, KV, hd, ps, maxp = 3, 2, 4, 2, 32, 8, 3
    qs, kv = [4, 9, 17], [6, 11, 19]
    q, kd, vd, kp, vp, table, qs_a, kv_a = _case(
        2, B=B, Tq=Tq, H=H, KV=KV, hd=hd, hd_v=hd, ps=ps, maxp=maxp,
        num_pages=12, dtype=jnp.float32, q_start=qs, k_valid=kv)
    got = ref.paged_sdpa_ref(q, kp, vp, table, q_start=qs_a,
                             k_valid_len=kv_a)
    for b in range(B):
        want = ref.grouped_sdpa_ref(q[b:b + 1], kd[b:b + 1], vd[b:b + 1],
                                    q_pos0=qs[b],
                                    k_valid_len=kv_a[b:b + 1])
        np.testing.assert_array_equal(np.asarray(got[b]),
                                      np.asarray(want[0]))


@pytest.mark.parametrize("fam,H,KV,hd,hd_v", FAMILIES)
@pytest.mark.parametrize("Tq,q_start,k_valid", [
    (1, [7, 15], [8, 16]),     # decode: tail page partially filled
    (1, [23, 0], [24, 1]),     # full pages vs nearly empty slot
    (4, [4, 9], [8, 13]),      # multi-row queries, ragged valid prefix
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_pallas_matches_ref(fam, H, KV, hd, hd_v, Tq, q_start,
                                  k_valid, dtype):
    q, _, _, kp, vp, table, qs, kv = _case(
        3, B=2, Tq=Tq, H=H, KV=KV, hd=hd, hd_v=hd_v, ps=8, maxp=3,
        num_pages=8, dtype=dtype, q_start=q_start, k_valid=k_valid)
    got = paged_flash_attention_pallas(
        q.transpose(0, 2, 1, 3), kp, vp, table, qs, kv, interpret=True)
    got = got.transpose(0, 2, 1, 3)
    want = ref.paged_sdpa_ref(q, kp, vp, table, q_start=qs, k_valid_len=kv)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window,softcap", [(None, None), (12, None),
                                            (None, 30.0), (12, 30.0)])
def test_paged_pallas_window_softcap(window, softcap):
    q, _, _, kp, vp, table, qs, kv = _case(
        4, B=2, Tq=2, H=4, KV=2, hd=32, hd_v=32, ps=8, maxp=3,
        num_pages=8, dtype=jnp.float32, q_start=[10, 14], k_valid=[12, 16])
    got = paged_flash_attention_pallas(
        q.transpose(0, 2, 1, 3), kp, vp, table, qs, kv, window=window,
        softcap=softcap, interpret=True).transpose(0, 2, 1, 3)
    want = ref.paged_sdpa_ref(q, kp, vp, table, q_start=qs, k_valid_len=kv,
                              window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# Ragged edges of the (slot, page chunk) grid: ps 4, maxp 10 pages a slot,
# not a multiple of a chunk of min(_CHUNK, maxp) pages (for _CHUNK 4 or
# 8); a valid length of chunk * ps sits on a chunk edge.
# (Tq, q_start, k_valid, window, softcap) per slot.
_PS, _MAXP = 4, 10
_EDGE = min(_CHUNK, _MAXP) * _PS
EDGE_CASES = {
    "chunk_edge": (1, [_EDGE - 1, _EDGE - 2], [_EDGE, _EDGE - 1],
                   None, None),
    "k_valid_1": (1, [0, 0], [1, 1], None, None),
    "last_chunk_partial": (1, [_MAXP * _PS - 3, _EDGE], [_MAXP * _PS - 2,
                                                         _EDGE + 1],
                           None, None),
    "full_vs_one_row": (1, [_MAXP * _PS - 1, 0], [_MAXP * _PS, 1],
                        None, None),
    "verify_straddles_page": (5, [2 * _PS - 2, 5 * _PS - 3],
                              [2 * _PS + 3, 5 * _PS + 2], None, None),
    "window_softcap": (3, [21, 30], [24, 33], 9, 30.0),
}


@pytest.mark.parametrize("fam,H,KV,hd,hd_v", FAMILIES)
@pytest.mark.parametrize("case", list(EDGE_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_pallas_grid_edges(fam, H, KV, hd, hd_v, case, dtype):
    Tq, q_start, k_valid, window, softcap = EDGE_CASES[case]
    q, _, _, kp, vp, table, qs, kv = _case(
        6, B=2, Tq=Tq, H=H, KV=KV, hd=hd, hd_v=hd_v, ps=_PS, maxp=_MAXP,
        num_pages=24, dtype=dtype, q_start=q_start, k_valid=k_valid)
    got = paged_flash_attention_pallas(
        q.transpose(0, 2, 1, 3), kp, vp, table, qs, kv, window=window,
        softcap=softcap, interpret=True).transpose(0, 2, 1, 3)
    want = ref.paged_sdpa_ref(q, kp, vp, table, q_start=qs, k_valid_len=kv,
                              window=window, softcap=softcap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_ops_dispatch_backends_agree():
    """ops.paged_sdpa: the ref backend IS the oracle (bitwise) and the
    interpret-mode Pallas backend matches it numerically."""
    q, _, _, kp, vp, table, qs, kv = _case(
        5, B=2, Tq=1, H=4, KV=2, hd=32, hd_v=32, ps=8, maxp=3,
        num_pages=8, dtype=jnp.float32, q_start=[6, 20], k_valid=[7, 21])
    want = ref.paged_sdpa_ref(q, kp, vp, table, q_start=qs, k_valid_len=kv)
    got_ref = ops.paged_sdpa(q, kp, vp, table, q_start=qs, k_valid_len=kv,
                             config=KernelConfig(backend="ref"))
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(want))
    got_pl = ops.paged_sdpa(q, kp, vp, table, q_start=qs, k_valid_len=kv,
                            config=KernelConfig(backend="pallas",
                                                interpret=True))
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_pallas_shape_ok_paged_kind():
    assert pallas_shape_ok("paged_attention", (1, 24, 32))
    assert not pallas_shape_ok("paged_attention", (0, 24, 32))
