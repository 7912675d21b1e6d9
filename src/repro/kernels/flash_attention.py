"""Pallas TPU kernel: blocked (flash) attention, causal + sliding window.

TPU-native adaptation of flash attention for the long-context configs
(gemma2/gemma3 sliding window, 32k prefill) AND the model stack's
prefill/train path (wired through ``repro.kernels.ops.sdpa``):

  * grid = (batch*heads, q_blocks, kv_blocks); the kv dimension is the
    innermost (sequential on TPU), carrying the running max / denominator /
    accumulator in VMEM scratch across kv steps — the classic streaming
    softmax.
  * GQA-grouped layout: ``k``/``v`` stay at KV heads; the k/v BlockSpec
    index maps fold query head ``h`` onto kv head ``h // (H // KV)``, so
    grouped caches are consumed without materialising the H-head repeat.
  * ragged edges are masked in-kernel (iota position masks): any
    ``Tq``/``Tk`` runs, not just 128-multiples.  Head dims are zero-padded
    to the 128 lane width in the wrapper — exact for the q.k contraction,
    and padded value columns are sliced off the output.
  * per-batch ``q_start`` / ``k_valid_len`` int32 operands (whole in
    SMEM, indexed by ``program_id(0) // H``): decode
    and continued prefill attend a query at absolute position
    ``q_start + i`` against the valid cache prefix ``[0, k_valid_len)``.
    Keys at or beyond ``k_valid_len`` are masked to -inf and their value
    rows zeroed before the accumulate, so garbage in the padded cache
    region can never reach the output.
  * blocks entirely outside the causal/window band or entirely beyond the
    valid cache are *skipped* via ``pl.when`` (the VMEM fetch is still
    scheduled by the grid, but the MXU work — the dominant cost — is
    elided); for a window w << T this makes the kernel O(T*w) compute
    instead of O(T^2).
  * optional logit soft-capping (gemma2) fused before the mask.
  * a paged variant (:func:`paged_flash_attention_pallas`): the KV cache
    is a pool of fixed-size pages plus a per-request int32 block table
    carried as a scalar-prefetch operand; the kv grid dimension walks
    the table, so the gather is resolved by the BlockSpec index maps at
    DMA-schedule time and the body stays the dense streaming-softmax
    body with ``block_k = page_size``.

Validated against ``ref.flash_attention_ref`` / ``ref.grouped_sdpa_ref``
in interpret mode over a shape/dtype/window/GQA sweep
(tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANE = 128


def _flash_kernel(q_start_ref, k_valid_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, causal, window, softcap,
                  block_q, block_k, num_kv_blocks, num_heads, tq):
    b = pl.program_id(0) // num_heads
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions: query row r of this tile sits at position
    # q_start + iq*block_q + r; cache slot s holds position s.
    q_lo = q_start_ref[b] + iq * block_q
    k_valid = k_valid_ref[b]
    k_lo = ik * block_k
    # block-level skip: wholly beyond the valid cache prefix, entirely
    # above the diagonal, or entirely left of the sliding window.
    skip = k_lo >= k_valid
    if causal:
        skip = skip | (k_lo > q_lo + block_q - 1)
    if window is not None:
        skip = skip | (k_lo + block_k - 1 <= q_lo - window)

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, Dv)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kj = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        # validity first: covers both the ragged Tk edge (k_valid <= Tk)
        # and a partially filled cache; masked-out key columns may hold
        # edge-tile garbage, so their value rows are zeroed too.
        mask = kj < k_valid
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
        logits = jnp.where(mask, logits, _NEG_INF)
        kv_rows = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, v.shape[-1]), 0)
        v = jnp.where(kv_rows < k_valid, v, 0.0)

        m_prev = m_ref[:, 0]                          # (bq,)
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)               # <= 1, 0*inf avoided
        p = jnp.exp(logits - m_new[:, None])
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = alpha[:, None] * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
        # zero ragged-edge query rows (their lanes hold garbage) before
        # the dropped out-of-bounds write
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, out.shape, 0)
        o_ref[0, 0] = jnp.where(rows < tq, out, 0.0).astype(o_ref.dtype)


def _pad_lane(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-pad the trailing (head) dim up to the 128 lane width."""
    d = x.shape[-1]
    pad = (-d) % _LANE
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _paged_flash_kernel(table_ref, q_start_ref, k_valid_ref, q_ref, k_ref,
                        v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, causal,
                        window, softcap, block_q, page_size, num_pages,
                        num_heads, tq):
    """Paged twin of :func:`_flash_kernel`: the kv grid dimension walks
    the slot's *block table* instead of a contiguous cache — page ``j``
    of request ``b`` holds absolute positions ``[j*ps, (j+1)*ps)`` but
    lives at physical page ``table[b, j]`` of the pool (the BlockSpec
    index map does the gather; the body only sees the fetched page).
    The masking math is identical to the dense kernel with
    ``block_k = page_size``: ``k_valid_len`` covers the partially
    filled tail page, and pages wholly beyond the valid prefix or the
    causal/window band are skipped via ``pl.when``."""
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    j = pl.program_id(2)
    b = bh // num_heads

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = q_start_ref[b] + iq * block_q
    k_valid = k_valid_ref[b]
    k_lo = j * page_size
    skip = k_lo >= k_valid
    if causal:
        skip = skip | (k_lo > q_lo + block_q - 1)
    if window is not None:
        skip = skip | (k_lo + page_size - 1 <= q_lo - window)

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (ps, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (ps, Dv)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qi = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 0)
        kj = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
        mask = kj < k_valid
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
        logits = jnp.where(mask, logits, _NEG_INF)
        kv_rows = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, v.shape[-1]), 0)
        v = jnp.where(kv_rows < k_valid, v, 0.0)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = alpha[:, None] * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = l_ref[:, 0]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, out.shape, 0)
        o_ref[0, 0] = jnp.where(rows < tq, out, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "interpret"))
def paged_flash_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 block_table: jnp.ndarray,
                                 q_start: jnp.ndarray,
                                 k_valid_len: jnp.ndarray, *,
                                 causal: bool = True,
                                 window: int | None = None,
                                 softcap: float | None = None,
                                 scale: float | None = None,
                                 block_q: int = 128,
                                 interpret: bool = False) -> jnp.ndarray:
    """Flash attention over a paged (block) KV cache.

    q: (B, H, Tq, D); k_pages: (P, ps, KV, D); v_pages: (P, ps, KV, Dv)
    with H % KV == 0; block_table: (B, maxp) int32 — request ``b``'s
    absolute positions ``[j*ps, (j+1)*ps)`` live at physical page
    ``block_table[b, j]``.  ``q_start``/``k_valid_len``: (B,) int32 —
    same semantics as the dense kernel's SMEM operands (query ``i``
    sits at ``q_start[b] + i``; keys at or beyond ``k_valid_len[b]``
    are masked, which covers the partially filled tail page).

    The block table rides in as a scalar-prefetch operand
    (``PrefetchScalarGridSpec``), so the k/v BlockSpec index maps
    resolve the page indirection at DMA-schedule time — the kernel body
    is the dense streaming-softmax body with ``block_k = page_size``.
    Unreferenced table entries must still be valid page ids (callers
    point them at page 0); their fetches are scheduled but their MXU
    work is skipped and their lanes masked.
    """
    B, H, Tq, D = q.shape
    num_pool_pages, ps, KV, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    maxp = block_table.shape[1]
    assert H % KV == 0, (H, KV)
    G = H // KV
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_table = jnp.asarray(block_table, jnp.int32)
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_valid = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(k_valid_len, jnp.int32), (B,)),
        maxp * ps)

    # kernel page layout: (P, KV, ps, D) so a page block's trailing two
    # dims are (ps, lane-padded D) — the same tile shape as the dense
    # kernel's kv blocks
    qp = _pad_lane(q)
    kp = _pad_lane(k_pages.transpose(0, 2, 1, 3))
    vp = _pad_lane(v_pages.transpose(0, 2, 1, 3))
    Dp, Dvp = qp.shape[-1], vp.shape[-1]
    block_q = min(block_q, Tq)
    nq = pl.cdiv(Tq, block_q)
    kernel = functools.partial(
        _paged_flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, page_size=ps, num_pages=maxp,
        num_heads=H, tq=Tq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * H, nq, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dp),
                         lambda bh, iq, j, tbl, qs, kv: (bh // H, bh % H,
                                                         iq, 0)),
            pl.BlockSpec((1, 1, ps, Dp),
                         lambda bh, iq, j, tbl, qs, kv: (tbl[bh // H, j],
                                                         (bh % H) // G,
                                                         0, 0)),
            pl.BlockSpec((1, 1, ps, Dvp),
                         lambda bh, iq, j, tbl, qs, kv: (tbl[bh // H, j],
                                                         (bh % H) // G,
                                                         0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dvp),
                               lambda bh, iq, j, tbl, qs, kv: (bh // H,
                                                               bh % H,
                                                               iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dvp), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, Dvp), q.dtype),
        interpret=interpret,
    )(block_table, q_start, k_valid, qp, kp, vp)
    return out[..., :Dv]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "block_q", "block_k",
    "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window: int | None = None,
                           softcap: float | None = None,
                           scale: float | None = None,
                           q_start: jnp.ndarray | None = None,
                           k_valid_len: jnp.ndarray | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Tq, D); k: (B, KV, Tk, D); v: (B, KV, Tk, Dv) with
    H % KV == 0 (KV == H is the pre-broadcast layout).  Any Tq/Tk/D —
    ragged tiles are masked, head dims zero-padded to the lane width.

    ``q_start``: (B,) absolute position of the first query (default
    ``Tk - Tq``: last query attends to the last key).  ``k_valid_len``:
    (B,) number of valid cache entries (default ``Tk``)."""
    B, H, Tq, D = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    assert H % KV == 0, (H, KV)
    G = H // KV
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if q_start is None:
        q_start = jnp.full((B,), Tk - Tq, jnp.int32)
    else:
        q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    if k_valid_len is None:
        k_valid = jnp.full((B,), Tk, jnp.int32)
    else:
        k_valid = jnp.minimum(
            jnp.broadcast_to(jnp.asarray(k_valid_len, jnp.int32), (B,)), Tk)

    qp, kp, vp = _pad_lane(q), _pad_lane(k), _pad_lane(v)
    Dp, Dvp = qp.shape[-1], vp.shape[-1]
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    nq = pl.cdiv(Tq, block_q)
    nk = pl.cdiv(Tk, block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k,
        num_kv_blocks=nk, num_heads=H, tq=Tq)
    # the (B,) position operands ride whole in SMEM and are indexed by
    # batch in the body: a blocked (1, 1) SMEM window over a (B, 1)
    # array breaks the TPU tiling rule for any B > 1
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            smem, smem,
            pl.BlockSpec((1, 1, block_q, Dp),
                         lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dp),
                         lambda bh, iq, ik: (bh // H, (bh % H) // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dvp),
                         lambda bh, iq, ik: (bh // H, (bh % H) // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dvp),
                               lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, Dvp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dvp), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(q_start, k_valid, qp, kp, vp)
    return out[..., :Dv]
