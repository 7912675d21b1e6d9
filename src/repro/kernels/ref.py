"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantic ground truth; the Pallas kernels are validated
against them over shape/dtype sweeps in ``tests/test_kernels.py``, and the
CPU execution path (simulation engine, dry-run lowering) uses them
directly via ``ops.py`` dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def gossip_mix_ref(bufs: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Weighted combine of self + received neighbour buffers.

    bufs:    (S, ...) — slot 0 is the node's own parameters, slots 1..S-1
             are buffers received via collective-permute.
    weights: (S,)     — w_self followed by receive weights.
    returns  (...,)   — sum_s weights[s] * bufs[s].
    """
    w = jnp.asarray(weights, jnp.float32).reshape(
        (-1,) + (1,) * (bufs.ndim - 1))
    return jnp.sum(w * bufs.astype(jnp.float32), axis=0).astype(bufs.dtype)


def fused_dsgd_ref(x: jnp.ndarray, u: jnp.ndarray, g: jnp.ndarray,
                   beta: float, eta: float, pre_scale: float = 1.0
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused heavy-ball momentum + SGD step (+ optional gossip self-weight
    pre-scale so the subsequent mix can skip one full HBM pass):

        u' = beta * u + g
        x' = pre_scale * (x - eta * u')

    ``pre_scale`` is a scalar or any array broadcastable against ``x``
    (per-node self-weights arrive shaped ``(n, 1, ..., 1)``).
    """
    xf, uf, gf = (a.astype(jnp.float32) for a in (x, u, g))
    if hasattr(pre_scale, "astype"):
        pre_scale = pre_scale.astype(jnp.float32)
    u_new = beta * uf + gf
    x_new = pre_scale * (xf - eta * u_new)
    return x_new.astype(x.dtype), u_new.astype(u.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True,
                        window: int | None = None,
                        softcap: float | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """Plain-softmax attention oracle.

    q: (B, H, Tq, D);  k, v: (B, H, Tk, D) — callers handling GQA broadcast
    the kv heads before the call.  ``window`` is a sliding-window width: key
    j attends to query i iff i - window < j <= i (when causal).
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qi = jnp.arange(Tq)[:, None] + (Tk - Tq)  # align last q to last k
    kj = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), dtype=bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = _softmax(logits)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def grouped_sdpa_ref(q, k, v, *, causal=True, window=None, softcap=None,
                     scale=None, q_pos0=None, k_valid_len=None,
                     q_chunk: int = 1024) -> jnp.ndarray:
    """Grouped-query attention in the model stack's layout — the
    memory-bounded streaming-softmax reference (scan over query chunks,
    never materialising the full (T, S) logits) that
    ``repro.models.attention`` historically ran inline; it is the
    bit-exact ``ref`` backend behind ``ops.sdpa``.

    q: (B, Tq, H, hd);  k, v: (B, S, KV, hd[, hd_v]) with H % KV == 0.
    ``q_pos0``: absolute position of the first query (queries are
    contiguous: position of query i is ``q_pos0 + i``; defaults to
    ``S - Tq``).  ``k_valid_len``: (B,) number of valid cache entries
    (for decode against a partially filled cache).
    """
    B, Tq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    if q_pos0 is None:
        q_pos0 = S - Tq
    q_positions = q_pos0 + jnp.arange(Tq)
    kpos = jnp.arange(S)

    qg = q.reshape(B, Tq, KV, G, hd)

    def block(qi, qpos_i):
        # qi: (B, t, KV, G, hd) -> out (B, t, KV, G, hd_v)
        logits = jnp.einsum("btkgd,bskd->btkgs", qi.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        m = jnp.ones(jnp.broadcast_shapes(qpos_i[:, None].shape,
                                          kpos[None, :].shape), dtype=bool)
        if causal:
            m &= kpos[None, :] <= qpos_i[:, None]
        if window is not None:
            m &= kpos[None, :] > qpos_i[:, None] - window
        m = m[None, :, None, None, :]               # (1, t, 1, 1, S)
        if k_valid_len is not None:
            valid = kpos[None, :] < k_valid_len[:, None]      # (B, S)
            m = m & valid[:, None, None, None, :]
        logits = jnp.where(m, logits, _NEG_INF)
        mx = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - mx)
        out = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(jnp.float32))
        den = jnp.maximum(p.sum(-1), 1e-30)
        return out / den[..., None]

    if Tq <= q_chunk:
        out = block(qg, q_positions)
    else:
        assert Tq % q_chunk == 0
        nq = Tq // q_chunk
        qs = qg.reshape(B, nq, q_chunk, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
        ps = q_positions.reshape(nq, q_chunk)
        out = jax.lax.map(lambda t: block(*t), (qs, ps))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Tq, KV, G, hd_v)
    return out.reshape(B, Tq, H, hd_v).astype(q.dtype)


def grouped_sdpa_decode_ref(q, k, v, *, q_start, k_valid_len, causal=True,
                            window=None, softcap=None,
                            scale=None) -> jnp.ndarray:
    """Dense-cache decode/verify attention with PER-REQUEST ragged query
    positions — the reference behind ``ops.sdpa_decode`` (the k-token
    speculative-verify entry).

    q: (B, Tq, H, hd);  k, v: (B, S, KV, hd[, hd_v]) with H % KV == 0;
    ``q_start``: (B,) absolute position of each request's FIRST query
    (query i of request b sits at ``q_start[b] + i``);  ``k_valid_len``:
    (B,) valid cache prefix.

    Query rows are computed by a ``lax.map`` of single-row blocks, each
    reproducing the Tq=1 op sequence of :func:`grouped_sdpa_ref`
    verbatim.  That structure is load-bearing: the speculative engine's
    lossless guarantee is that verifying k+1 tokens in ONE call is
    bit-identical to the plain one-token-per-step scan, and for
    ``hd_v != hd`` heads (MLA's absorbed layout) XLA lowers a fused
    (Tq>1, S) contraction with a different reduction order than the
    Tq=1 step in the last ulp — scanning rows keeps the per-row
    reduction order identical by construction.
    """
    B, Tq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_valid = jnp.broadcast_to(jnp.asarray(k_valid_len, jnp.int32), (B,))
    kpos = jnp.arange(S)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = q.reshape(B, Tq, KV, G, hd)

    def row(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i, 1, axis=1)  # (B,1,KV,G,hd)
        qpos = q_start + i                                   # (B,)
        logits = jnp.einsum("btkgd,bskd->btkgs", qi.astype(jnp.float32),
                            kf) * scale
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        m = kpos[None, :] < k_valid[:, None]                 # (B, S)
        if causal:
            m = m & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            m = m & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(m[:, None, None, None, :], logits, _NEG_INF)
        mx = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - mx)
        out = jnp.einsum("btkgs,bskd->btkgd", p, vf)
        den = jnp.maximum(p.sum(-1), 1e-30)
        return out / den[..., None]

    out = jax.lax.map(row, jnp.arange(Tq))      # (Tq, B, 1, KV, G, hd_v)
    out = jnp.moveaxis(out[:, :, 0], 0, 1)      # (B, Tq, KV, G, hd_v)
    return out.reshape(B, Tq, H, hd_v).astype(q.dtype)


def paged_sdpa_ref(q, k_pages, v_pages, block_table, *, q_start,
                   k_valid_len, causal=True, window=None, softcap=None,
                   scale=None) -> jnp.ndarray:
    """Paged-cache attention oracle in the model stack's layout.

    q: (B, Tq, H, hd);  k_pages: (P, ps, KV, hd);  v_pages:
    (P, ps, KV, hd_v) with H % KV == 0;  block_table: (B, maxp) int32 —
    request ``b``'s absolute positions ``[j*ps, (j+1)*ps)`` live at
    physical page ``block_table[b, j]``.  ``q_start``: (B,) absolute
    position of each request's first query (per-request ragged — unlike
    :func:`grouped_sdpa_ref`'s shared scalar ``q_pos0``).
    ``k_valid_len``: (B,) valid cache prefix, masking both retired page
    slack and the partially filled tail page.

    The oracle gathers each request's pages into the dense layout and
    runs exactly the grouped-attention math of :func:`grouped_sdpa_ref`
    — gathering is indexing, so against a dense cache holding the same
    bits at the same positions the result is BIT-identical, which is
    the dense-vs-paged acceptance contract the serve tests pin.
    """
    B, Tq, H, hd = q.shape
    _, ps, KV, _ = k_pages.shape
    hd_v = v_pages.shape[-1]
    maxp = block_table.shape[1]
    S = maxp * ps
    G = H // KV
    if scale is None:
        scale = hd ** -0.5
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_valid = jnp.broadcast_to(jnp.asarray(k_valid_len, jnp.int32), (B,))
    # gather the logical view: (B, maxp, ps, KV, hd) -> (B, S, KV, hd)
    k = k_pages[block_table].reshape(B, S, KV, hd)
    v = v_pages[block_table].reshape(B, S, KV, hd_v)

    qpos = q_start[:, None] + jnp.arange(Tq)[None, :]        # (B, Tq)
    kpos = jnp.arange(S)
    qg = q.reshape(B, Tq, KV, G, hd)
    logits = jnp.einsum("btkgd,bskd->btkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    m = kpos[None, None, :] < k_valid[:, None, None]         # (B, 1, S)
    m = jnp.broadcast_to(m, (B, Tq, S))
    if causal:
        m = m & (kpos[None, None, :] <= qpos[:, :, None])
    if window is not None:
        m = m & (kpos[None, None, :] > qpos[:, :, None] - window)
    logits = jnp.where(m[:, :, None, None, :], logits, _NEG_INF)
    mx = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - mx)
    out = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(jnp.float32))
    den = jnp.maximum(p.sum(-1), 1e-30)
    out = out / den[..., None]
    return out.reshape(B, Tq, H, hd_v).astype(q.dtype)


def _softmax(logits: jnp.ndarray) -> jnp.ndarray:
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # rows that are fully masked
    e = jnp.exp(logits - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    return e / jnp.maximum(s, 1e-30)


# ---------------------------------------------------------------------------
# quantized gossip payloads (repro.compress)
#
# The stochastic-rounding noise is a deterministic per-element hash of
# (key, global element index) rather than a PRNG operand: the simulation
# engine (full node-stacked arrays, row_offset=0) and the distributed
# shard path (per-shard rows, row_offset=node*rows_per_node) then produce
# IDENTICAL payload bits for the same key, which is what makes the
# sim-vs-dist parity tests exact at the payload level.  The same helpers
# are imported by the Pallas kernel (repro.kernels.quantized_gossip) so
# kernel blocks and these full-array references share the math verbatim.
# ---------------------------------------------------------------------------

# per-format max representable magnitude the per-chunk scale maps amax
# to.  _SR_INV_QMAX is the pre-rounded f32 reciprocal: the scale is
# computed as an explicit multiply (never ``amax / QMAX``) because XLA
# strength-reduces constant divisions to reciprocal multiplies in SOME
# lowerings (shape/fusion dependent) — an explicit constant multiply is
# the only form that produces identical scale bits in the Pallas
# kernel, the interpret-mode kernel, and these references.
_SR_QMAX = {"int8": 127.0, "fp8": 448.0}
_SR_INV_QMAX = {"int8": 1.0 / 127.0, "fp8": 1.0 / 448.0}


def sr_key(seed, t) -> jnp.ndarray:
    """Fold (codec seed, step counter) into one uint32 hash key.  ``t``
    may be a traced scalar; the ``| 1`` keeps the key nonzero so the
    per-element hash never degenerates to a pure index hash."""
    s = jnp.asarray(seed).astype(jnp.uint32)
    tt = jnp.asarray(t).astype(jnp.uint32)
    return ((s * jnp.uint32(0x9E3779B1)) ^ (tt * jnp.uint32(0x85EBCA77))) \
        | jnp.uint32(1)


def _sr_bits(key, idx) -> jnp.ndarray:
    """murmur3-finalizer-style uint32 hash of a per-element index grid."""
    h = idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    h = h ^ key
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _u32_to_f32(bits) -> jnp.ndarray:
    """``bits.astype(float32)`` bit for bit, from two exact 16-bit
    halves and one correctly rounded add: Mosaic has no unsigned-to-float
    cast, and the kernel shares this math with the reference."""
    hi = (bits >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def _quantize_core(s, scale, bits, fmt: str):
    """Elementwise payload math shared by the Pallas kernel blocks and
    the full-array reference: returns ``(q, hat)`` with ``hat`` the
    dequantized f32 value ``q * scale``.

    * ``int8``: unbiased stochastic rounding ``floor(v + u)`` with
      ``u in [0, 1)`` from the hash bits.
    * ``fp8`` (e4m3fn): stochastic rounding by injecting 20 hash bits
      below the 3-bit target mantissa and truncating — exact for values
      in fp8's normal range; the final cast handles the subnormal tail
      (round-to-nearest there, documented in DESIGN.md Sec. 13).  The
      clip to +-448 keeps a rounded-up max from overflowing e4m3fn's
      finite range (448 is its largest finite value; 480 encodes NaN).
    """
    v = s / scale
    if fmt == "int8":
        u = _u32_to_f32(bits) * jnp.float32(2.0 ** -32)
        q = jnp.clip(jnp.floor(v + u), -127.0, 127.0).astype(jnp.int8)
    elif fmt == "fp8":
        b = jax.lax.bitcast_convert_type(v, jnp.uint32)
        b = (b + (bits & jnp.uint32(0xFFFFF))) & jnp.uint32(0xFFF00000)
        w = jnp.clip(jax.lax.bitcast_convert_type(b, jnp.float32),
                     -448.0, 448.0)
        q = w.astype(jnp.float8_e4m3fn)
    else:
        raise ValueError(f"unknown quantize format {fmt!r}")
    return q, q.astype(jnp.float32) * scale


def quantize_ef_ref(x: jnp.ndarray, err: jnp.ndarray | None, key,
                    row_offset, *, fmt: str):
    """Quantize one (R, C) chunk-row layout buffer with per-row scales
    and produce the EF21 residual in the same pass.

    x:   (R, C) — the values to transmit (C = the codec chunk size).
    err: (R, C) or None — carried error-feedback residual, added to x
         before quantization (``s = x + err``).
    key: uint32 scalar from :func:`sr_key`.
    row_offset: global index of row 0 (per-shard callers pass
         ``node * rows_per_node`` so bits match the stacked layout).
    returns (q, scale, residual): q (R, C) int8/fp8, scale (R, 1) f32,
         residual (R, C) f32 = s - dequant(q) (exact EF update).
    """
    x = x.astype(jnp.float32)
    s = x if err is None else x + err.astype(jnp.float32)
    amax = jnp.max(jnp.abs(s), axis=1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax * _SR_INV_QMAX[fmt], 1.0)
    R, C = s.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (R, C), 0) \
        + jnp.asarray(row_offset, jnp.int32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    bits = _sr_bits(jnp.asarray(key).astype(jnp.uint32), rows * C + cols)
    q, hat = _quantize_core(s, scale, bits, fmt)
    return q, scale, s - hat


def quantized_gossip_mix_ref(own: jnp.ndarray, q_slots, scale_slots,
                             weights) -> jnp.ndarray:
    """Dequantize-and-combine oracle for one compressed gossip round:

        out = w[0] * own + sum_s w[s+1] * (q_s * scale_s)

    own: (R, C) f32 — the node's own exact values (never quantized:
         matches the dist path where a node's own shard is not
         transmitted); q_slots: S received payloads (R, C) int8/fp8;
    scale_slots: S received (R, 1) f32 scales; weights: (S+1,) with
    w_self first.  Accumulation order matches the Pallas kernel.
    """
    w = jnp.asarray(weights, jnp.float32)
    acc = w[0] * own.astype(jnp.float32)
    for i, (q, sc) in enumerate(zip(q_slots, scale_slots)):
        acc = acc + w[i + 1] * (q.astype(jnp.float32)
                                * sc.astype(jnp.float32))
    return acc
