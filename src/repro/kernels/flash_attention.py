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
  * a paged decode variant (:func:`paged_flash_attention_pallas`): the
    KV cache is a pool of fixed-size pages ``(P, ps, KV, D)`` plus a
    per-request int32 block table.  The grid is (slot, chunk of pages):
    each step takes every query head and query row of the slot, and
    each page of the chunk is a ``(ps, KV, D)`` block read from the pool
    where it lies, in its own layout (no transpose, no lane pad).  A
    per-slot fetch list (scalar prefetch) points pages outside the
    slot's valid span at a block already resident, so they are neither
    fetched nor computed.

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


# pages per grid step of the paged decode kernel: each is a block of
# its own, so the pipeline keeps the next chunk's pages in flight while
# this chunk's are read
_CHUNK = 4


def _page_span(q_start, k_valid, *, page_size, causal, window, tq):
    """Pages ``[lo, hi)`` holding the keys a slot's queries can attend:
    below the valid length (and the causal edge), right of the sliding
    window of its first query.  Scalars in the kernel, (B,) outside."""
    end = k_valid
    if causal:
        end = jnp.minimum(end, q_start + tq)
    lo = jnp.zeros_like(end)
    if window is not None:
        lo = jnp.maximum(q_start - window + 1, 0) // page_size
    return lo, (jnp.maximum(end, 0) + page_size - 1) // page_size


def _paged_decode_kernel(q_start_ref, k_valid_ref, fetch_ref, q_ref, *refs,
                         scale, causal, window, softcap, page_size, chunk,
                         group, tq):
    """Grid step ``(b, c)``: every query head and query row of slot ``b``
    against its pages ``[c*chunk, (c+1)*chunk)``, one block per page.

    ``q_ref``: (1, R, KV, D) with query row ``r = t * group + g`` (query
    ``t``, head ``kv * group + g``).  Pages outside the slot's span are
    not computed (nor fetched: ``fetch_ref`` repeats a block already
    resident).  Scores are computed on the VPU in f32: the ``(ps, KV,
    D)`` K page times the row's ``(KV, D)`` queries, summed over ``D``,
    scores all KV heads at once as ``(ps, KV, 1)``.  Running max, sum
    and accumulator are f32 scratch across the slot's steps."""
    del fetch_ref  # read by the index maps only
    k_refs, v_refs = refs[:chunk], refs[chunk:2 * chunk]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * chunk:]
    b, c = pl.program_id(0), pl.program_id(1)
    q_start, k_valid = q_start_ref[b], k_valid_ref[b]
    lo, hi = _page_span(q_start, k_valid, page_size=page_size,
                        causal=causal, window=window, tq=tq)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def page(k_ref, v_ref, j):
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, k_ref.shape[:2] + (1,), 0)      # (ps, KV, 1)
        k = k_ref[...].astype(jnp.float32)             # (ps, KV, D)
        v = v_ref[...].astype(jnp.float32)             # (ps, KV, Dv)

        def row(r, carry):
            qpos = q_start + r // group
            q = q_ref[0, r].astype(jnp.float32)        # (KV, D)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            mask = kpos < k_valid
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[r][:, :1]                   # (KV, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=0))
            alpha = jnp.exp(m_prev - m_new)
            # masked keys (past the valid length: maybe another
            # request's rows) weigh exactly 0
            p = jnp.where(mask, jnp.exp(s - m_new[None]), 0.0)
            l_new = alpha * l_ref[r][:, :1] + p.sum(axis=0)
            acc_ref[r] = alpha * acc_ref[r] + (p * v).sum(axis=0)
            m_ref[r] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[r] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            return carry

        rows = q_ref.shape[1]
        if rows == 1:
            row(0, 0)
        else:
            jax.lax.fori_loop(0, rows, row, 0)

    for i in range(chunk):
        j = c * chunk + i
        pl.when((j >= lo) & (j < hi))(
            functools.partial(page, k_refs[i], v_refs[i], j))

    @pl.when(c == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[...][..., :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "interpret"))
def paged_flash_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 block_table: jnp.ndarray,
                                 q_start: jnp.ndarray,
                                 k_valid_len: jnp.ndarray, *,
                                 causal: bool = True,
                                 window: int | None = None,
                                 softcap: float | None = None,
                                 scale: float | None = None,
                                 interpret: bool = False) -> jnp.ndarray:
    """Flash attention over a paged (block) KV cache, decode-shaped.

    q: (B, H, Tq, D); k_pages: (P, ps, KV, D); v_pages: (P, ps, KV, Dv)
    with H % KV == 0; block_table: (B, maxp) int32 — request ``b``'s
    absolute positions ``[j*ps, (j+1)*ps)`` live at physical page
    ``block_table[b, j]``.  ``q_start``/``k_valid_len``: (B,) int32 —
    same semantics as the dense kernel's SMEM operands (query ``i``
    sits at ``q_start[b] + i``; keys at or beyond ``k_valid_len[b]``
    are masked, which covers the partially filled tail page).

    The grid is (slot, chunk of ``_CHUNK`` pages); all heads and query
    rows of the slot share each step.  The pools stay in their own
    layout (no transpose, no lane pad): each page of the chunk is its
    own ``(ps, KV, D)`` block, gathered through a per-slot fetch list
    (a scalar-prefetch operand).  The list is the block table with
    every page outside the slot's span replaced by the nearest page
    inside it that the same block already holds, so the pipeline
    fetches nothing there.  Meant for small ``Tq`` (decode, the
    speculative verify window): each query row is a pass over the page.
    """
    B, H, Tq, D = q.shape
    _, ps, KV, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    maxp = block_table.shape[1]
    assert H % KV == 0, (H, KV)
    G = H // KV
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_valid = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(k_valid_len, jnp.int32), (B,)),
        maxp * ps)
    chunk = min(_CHUNK, maxp)
    nc = pl.cdiv(maxp, chunk)

    # block i of chunk c holds page c*chunk + i; outside [lo, hi) it
    # holds its own nearest page inside (same residue mod chunk), which
    # it already has: a repeated block index skips the DMA.  A block
    # with no page of its residue in the span holds page lo.
    lo, hi = _page_span(q_start, k_valid, page_size=ps, causal=causal,
                        window=window, tq=Tq)
    lo, hi = lo[:, None], hi[:, None]
    j = jnp.arange(nc * chunk, dtype=jnp.int32)[None]
    first = lo + (j - lo) % chunk
    last = hi - 1 - (hi - 1 - j) % chunk
    j = jnp.clip(jnp.minimum(jnp.maximum(j, first), last), lo, hi - 1)
    j = jnp.maximum(j, 0)   # an empty span reads entry 0, unused
    fetch = jnp.take_along_axis(jnp.asarray(block_table, jnp.int32), j,
                                axis=1)

    # query rows per slot r = t * G + g against KV heads: (B, Tq*G, KV, D)
    R = Tq * G
    qr = q.reshape(B, KV, G, Tq, D).transpose(0, 3, 2, 1, 4).reshape(
        B, R, KV, D)
    row_map = lambda b, c, *_: (b, 0, 0, 0)  # noqa: E731

    def page_map(i):
        return lambda b, c, qs, kv, f: (f[b, c * chunk + i], 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, page_size=ps, chunk=chunk, group=G, tq=Tq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nc),
        in_specs=[pl.BlockSpec((1, R, KV, D), row_map)]
        + [pl.BlockSpec((None, ps, KV, D), page_map(i))
           for i in range(chunk)]
        + [pl.BlockSpec((None, ps, KV, Dv), page_map(i))
           for i in range(chunk)],
        out_specs=pl.BlockSpec((1, R, KV, Dv), row_map),
        scratch_shapes=[
            pltpu.VMEM((R, KV, Dv), jnp.float32),
            pltpu.VMEM((R, KV, _LANE), jnp.float32),
            pltpu.VMEM((R, KV, _LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, KV, Dv), q.dtype),
        interpret=interpret,
    )(q_start, k_valid, fetch, qr, *[k_pages] * chunk, *[v_pages] * chunk)
    return out.reshape(B, Tq, G, KV, Dv).transpose(0, 3, 2, 1, 4).reshape(
        B, H, Tq, Dv)


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
