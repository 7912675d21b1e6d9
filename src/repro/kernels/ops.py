"""Public kernel entry points with explicit backend dispatch.

Dispatch is governed by a :class:`KernelConfig` value — there is no
mutable module flag read at trace time.  Factories that pin compiled
executables (``repro.optim.decentralized.make_method``,
``repro.sim.engine.compiled_scan_run``, ``repro.dist.steps``) resolve
their config ONCE at construction and carry it in their cache keys, so
flipping the process-wide default between two runs produces a fresh
trace with the new backend instead of silently reusing the stale one
(see DESIGN.md Sec. 9).

Backends:

* ``auto`` (default) — Pallas on TPU, pure-jnp references everywhere
  else (this container, the simulation engine, the dry-run lowering).
* ``pallas`` — force the Pallas kernels.  Off the TPU they need
  ``interpret=True`` (the CI ``kernels`` lane and the parity tests say
  so explicitly); there is no implicit interpret fallback.
* ``ref`` — force the references.

Shape support is centralised in :func:`pallas_shape_ok` — the single
guard every entry point consults.  All three kernels mask their ragged
edge tiles in-kernel, so ANY non-empty shape dispatches to Pallas (odd
vocab rows, non-128 widths, ragged sequence lengths included);
``flash_attention``/``sdpa`` additionally zero-pad head dims to the
lane width in their wrapper.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import (flash_attention_pallas,
                              paged_flash_attention_pallas)
from .fused_dsgd import fused_dsgd_pallas
from .gossip_mix import gossip_mix_pallas, gossip_mix_slots_pallas
from .quantized_gossip import (quantize_ef_pallas,
                               quantized_gossip_mix_slots_pallas)

_BACKENDS = ("auto", "pallas", "ref")


@dataclass(frozen=True)
class KernelConfig:
    """Hashable dispatch policy, threaded through every factory that
    pins a compiled executable (it must be part of their cache keys).

    ``backend``: ``auto`` | ``pallas`` | ``ref``.
    ``interpret``: force Pallas interpret mode even on TPU (tests)."""
    backend: str = "auto"
    interpret: bool = False

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, got "
                             f"{self.backend!r}")

    @property
    def use_pallas(self) -> bool:
        if self.backend == "auto":
            return jax.default_backend() == "tpu"
        return self.backend == "pallas"

    @property
    def run_interpret(self) -> bool:
        """Exactly ``interpret``: forced Pallas off the TPU without
        ``interpret=True`` fails to lower instead of quietly switching
        to interpret mode, so a compile for a described chip sees the
        native kernels the chip will compile."""
        return self.interpret


_DEFAULT_CONFIG = KernelConfig()


def default_kernel_config() -> KernelConfig:
    return _DEFAULT_CONFIG


def set_default_kernel_config(config: KernelConfig) -> KernelConfig:
    """Install a new process-wide default; returns the previous one.
    Only affects factories/calls made AFTER this — anything built
    earlier keeps the config it resolved at construction time."""
    global _DEFAULT_CONFIG
    if not isinstance(config, KernelConfig):
        raise TypeError(f"expected KernelConfig, got {type(config)}")
    prev = _DEFAULT_CONFIG
    _DEFAULT_CONFIG = config
    return prev


def resolve_config(config: KernelConfig | None) -> KernelConfig:
    """``None`` -> the current process-wide default, resolved EAGERLY
    (callers bake the returned value into closures and cache keys)."""
    return _DEFAULT_CONFIG if config is None else config


def pallas_shape_ok(kind: str, shape: tuple[int, ...]) -> bool:
    """Single source of truth for which operand shapes dispatch to the
    Pallas kernels (``tests/test_kernel_dispatch.py`` pins this table).

    * ``gossip_mix``: a stacked ``(S, ...)`` buffer or one slot buffer
      of any rank — ragged tiles are masked in-kernel, so every
      non-empty shape is supported.
    * ``fused_dsgd``: any non-empty shape (leaves are 2-D-normalised
      by :func:`fused_dsgd_step`; ragged tiles are masked in-kernel).
    * ``flash_attention``: ``(Tq, Tk, D)`` — any non-empty shape (the
      kernel masks ragged sequence tiles; head dims are zero-padded to
      the lane width by the wrapper).
    * ``paged_attention``: ``(Tq, S_logical, D)`` with
      ``S_logical = max_pages * page_size`` — any non-empty shape (the
      tail page is masked via ``k_valid_len``; pages are read with the
      pool's own head dims).
    * ``quantize`` / ``quantized_gossip_mix``: the (R, C) chunk-row
      payload layout — exactly 2-D (repro.compress pads every leaf into
      it before the call); ragged row tiles are masked in-kernel.
    """
    if any(d == 0 for d in shape):
        return False
    if kind in ("gossip_mix", "fused_dsgd"):
        return len(shape) >= 1
    if kind in ("flash_attention", "paged_attention"):
        return len(shape) == 3
    if kind in ("quantize", "quantized_gossip_mix"):
        return len(shape) == 2
    raise ValueError(f"unknown kernel kind {kind!r}")


def _as_2d(a: jnp.ndarray, *, lead_rows: bool = False):
    """Normalise an arbitrary-rank leaf to the (R, C) layout the fused
    kernels tile.  ``lead_rows=True`` keeps axis 0 as the row axis (so a
    per-leading-axis scale vector maps onto rows); otherwise the last
    axis becomes lanes and everything before it folds into rows."""
    if a.ndim == 2 and not lead_rows:
        return a, a.shape
    shape = a.shape
    if a.ndim == 0:
        return a.reshape(1, 1), shape
    if lead_rows:   # before the 1-D case: an (n,) leaf maps to (n, 1)
        return a.reshape(shape[0], -1), shape
    if a.ndim == 1:
        return a.reshape(1, -1), shape
    return a.reshape(-1, shape[-1]), shape


# ---------------------------------------------------------------------------
# gossip combine
# ---------------------------------------------------------------------------

def gossip_mix(bufs, weights, *, config: KernelConfig | None = None
               ) -> jnp.ndarray:
    """Fused weighted combine ``sum_s weights[s] * bufs[s]``.

    ``bufs`` is either a stacked ``(S, ...)`` array or a sequence of S
    equal-shape buffers.  The distributed gossip hot path passes the
    slot *list* (own buffer + each ``ppermute`` result): the variadic
    kernel reads every slot exactly once and writes the combined
    output — ``S + 1`` HBM streams, with no stacked ``(S, ...)`` copy
    materialised first.  Output has the slot shape and dtype.
    """
    cfg = resolve_config(config)
    if isinstance(bufs, (list, tuple)):
        slots = list(bufs)
        if not slots:
            raise ValueError("gossip_mix needs at least one buffer")
        w = jnp.stack([jnp.asarray(x, jnp.float32) for x in weights]) \
            if isinstance(weights, (list, tuple)) else weights
        if cfg.use_pallas and pallas_shape_ok("gossip_mix",
                                              slots[0].shape):
            two_d = [_as_2d(b) for b in slots]
            out = gossip_mix_slots_pallas(
                tuple(b for b, _ in two_d), w,
                interpret=cfg.run_interpret)
            return out.reshape(two_d[0][1])
        return ref.gossip_mix_ref(jnp.stack(slots), w)
    if cfg.use_pallas and bufs.ndim >= 2 \
            and pallas_shape_ok("gossip_mix", bufs.shape):
        s = bufs.shape[0]
        if bufs.ndim == 2:
            b3 = bufs.reshape(s, 1, -1)
        elif bufs.ndim == 3:
            b3 = bufs
        else:
            b3 = bufs.reshape(s, -1, bufs.shape[-1])
        out = gossip_mix_pallas(b3, weights, interpret=cfg.run_interpret)
        return out.reshape(bufs.shape[1:])
    return ref.gossip_mix_ref(bufs, weights)


# ---------------------------------------------------------------------------
# quantized gossip payloads (repro.compress)
# ---------------------------------------------------------------------------

QUANT_FORMATS = ("int8", "fp8")


def quantize_payload(x, err=None, *, fmt: str, key, row_offset=0,
                     config: KernelConfig | None = None):
    """One-pass payload quantization for compressed gossip: per-row
    amax scale + hash-based stochastic rounding + EF21 residual.

    x: (R, C) f32 in the chunk-row layout (C = codec chunk size);
    ``err`` is the carried error-feedback residual (added to ``x``
    before rounding) or None; ``key`` a uint32 scalar from
    :func:`repro.kernels.ref.sr_key`; ``row_offset`` the global index
    of row 0 (shard callers pass ``node * rows_per_node`` so payload
    bits match the node-stacked layout).  Returns ``(q, scale,
    residual)`` — see :func:`repro.kernels.ref.quantize_ef_ref`.
    """
    cfg = resolve_config(config)
    if fmt not in QUANT_FORMATS:
        raise ValueError(f"fmt must be one of {QUANT_FORMATS}, got {fmt!r}")
    if cfg.use_pallas and pallas_shape_ok("quantize", x.shape):
        return quantize_ef_pallas(x, err, key,
                                  jnp.asarray(row_offset, jnp.int32),
                                  fmt=fmt, interpret=cfg.run_interpret)
    return ref.quantize_ef_ref(x, err, key, row_offset, fmt=fmt)


def quantized_gossip_mix(own, q_slots, scale_slots, weights, *,
                         config: KernelConfig | None = None):
    """Fused dequantize-and-combine for one compressed gossip round:
    ``w[0]*own + sum_s w[s+1]*(q_s * scale_s)`` with the dequantized
    f32 payloads never materialised (the compressed twin of
    :func:`gossip_mix` at the same variadic-slots insertion point).

    own: (R, C) f32; q_slots: S received (R, C) int8/fp8 payloads;
    scale_slots: S received (R, 1) f32 scales; weights: (S+1,) with
    the self weight first.  Returns (R, C) f32.
    """
    cfg = resolve_config(config)
    q_slots, scale_slots = list(q_slots), list(scale_slots)
    w = jnp.stack([jnp.asarray(x, jnp.float32) for x in weights]) \
        if isinstance(weights, (list, tuple)) else weights
    if q_slots and cfg.use_pallas \
            and pallas_shape_ok("quantized_gossip_mix", own.shape):
        return quantized_gossip_mix_slots_pallas(
            own, tuple(q_slots), tuple(scale_slots), w,
            interpret=cfg.run_interpret)
    return ref.quantized_gossip_mix_ref(own, q_slots, scale_slots, w)


# ---------------------------------------------------------------------------
# fused DSGD(-momentum) update
# ---------------------------------------------------------------------------

def fused_dsgd_step(x, u, g, beta, eta, pre_scale=1.0, *,
                    config: KernelConfig | None = None):
    """``u' = beta*u + g;  x' = pre_scale * (x - eta*u')`` in one pass
    (3 reads + 2 writes instead of the 8 streams of the unfused
    momentum/axpy/scale chain).

    Accepts leaves of any rank.  ``pre_scale`` is a scalar, or a vector
    over the leaf's leading axis (the simulation engine folds the
    per-node gossip self-weight ``diag(W)`` through it — see
    ``repro.optim.decentralized.DSGD``)."""
    cfg = resolve_config(config)
    per_row = hasattr(pre_scale, "ndim") and pre_scale.ndim >= 1
    if cfg.use_pallas and pallas_shape_ok("fused_dsgd", x.shape):
        x2, shape = _as_2d(x, lead_rows=per_row)
        u2, _ = _as_2d(u, lead_rows=per_row)
        g2, _ = _as_2d(g, lead_rows=per_row)
        x_new, u_new = fused_dsgd_pallas(x2, u2, g2, beta, eta, pre_scale,
                                         interpret=cfg.run_interpret)
        return x_new.reshape(shape), u_new.reshape(shape)
    if per_row:
        pre_scale = pre_scale.reshape((-1,) + (1,) * (x.ndim - 1))
    return ref.fused_dsgd_ref(x, u, g, beta, eta, pre_scale)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softcap=None, scale=None,
                    config: KernelConfig | None = None):
    """(B, H, Tq, D) x (B, H, Tk, D)^2 -> (B, H, Tq, D)."""
    cfg = resolve_config(config)
    if cfg.use_pallas and pallas_shape_ok(
            "flash_attention", (q.shape[2], k.shape[2], q.shape[3])):
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      softcap=softcap, scale=scale,
                                      interpret=cfg.run_interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale)


# ---------------------------------------------------------------------------
# model-stack attention (grouped layout)
# ---------------------------------------------------------------------------

def sdpa(q, k, v, *, causal: bool = True, window=None, softcap=None,
         scale=None, q_pos0=None, k_valid_len=None, q_chunk: int = 1024,
         config: KernelConfig | None = None):
    """Grouped-query attention in the model stack's layout — the entry
    point ``repro.models.attention`` dispatches prefill/train/decode
    attention through.

    q: (B, Tq, H, hd);  k, v: (B, S, KV, hd[, hd_v]) with H % KV == 0
    (grouped caches stay at KV heads).  Queries are contiguous: query i
    sits at absolute position ``q_pos0 + i`` (default ``S - Tq``).
    ``q_pos0`` must be a scalar — it is shared across the batch (the
    custom VJP recomputes the backward through the reference math,
    which holds one position vector for the whole batch; the kernel's
    per-batch ``q_start`` operand stays internal until a per-request
    ragged-prefill path needs it AND carries its own VJP).
    ``k_valid_len`` is the (B,) valid-cache-prefix length.

    ``ref`` is :func:`repro.kernels.ref.grouped_sdpa_ref` — bit-exact
    with the streaming-softmax math the model layer historically ran
    inline, and the semantic oracle for the Pallas path.  The Pallas
    forward pairs with a custom VJP whose backward recomputes through
    the reference math (the kernel itself has no backward), so the
    train path can run the flash forward under ``jax.grad``.
    """
    cfg = resolve_config(config)
    B, Tq, H, hd = q.shape
    S = k.shape[1]
    if not (cfg.use_pallas
            and pallas_shape_ok("flash_attention", (Tq, S, hd))):
        return ref.grouped_sdpa_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, q_pos0=q_pos0, k_valid_len=k_valid_len,
            q_chunk=q_chunk)
    statics = (causal, window, softcap, scale, q_chunk, cfg.run_interpret)
    q_pos0 = S - Tq if q_pos0 is None else q_pos0
    if jnp.ndim(q_pos0) != 0:
        raise ValueError(f"q_pos0 must be a scalar (shared across the "
                         f"batch), got shape {jnp.shape(q_pos0)}")
    q_start = jnp.broadcast_to(jnp.asarray(q_pos0, jnp.int32), (B,))
    k_valid = jnp.broadcast_to(
        jnp.asarray(S if k_valid_len is None else k_valid_len, jnp.int32),
        (B,))
    return _sdpa_pallas(statics, q, k, v, q_start, k_valid)


def sdpa_decode(q, k, v, *, q_start, k_valid_len, causal: bool = True,
                window=None, softcap=None, scale=None,
                config: KernelConfig | None = None):
    """Dense-cache decode/verify attention with PER-REQUEST ragged query
    positions — the k-token speculative-verify entry point.

    q: (B, Tq, H, hd);  k, v: (B, S, KV, hd[, hd_v]) with H % KV == 0;
    q_start / k_valid_len: (B,) int32 — unlike :func:`sdpa`, ``q_start``
    is a per-request vector (after the first speculative round every
    slot sits at a different position).  Decode/serving only: there is
    deliberately no custom VJP (training never holds a ragged decode
    window), which is exactly what lets the flash kernel's per-batch
    ``q_start`` operand be used directly — :func:`sdpa` cannot, because
    its backward recomputes through the shared-scalar reference.

    ``ref`` is :func:`repro.kernels.ref.grouped_sdpa_decode_ref`, whose
    row-scanned structure makes a (Tq = k+1)-token verify bit-identical
    to k+1 single-token calls — the speculative lossless contract.
    """
    cfg = resolve_config(config)
    B, Tq, H, hd = q.shape
    S = k.shape[1]
    q_start = jnp.broadcast_to(jnp.asarray(q_start, jnp.int32), (B,))
    k_valid = jnp.broadcast_to(jnp.asarray(k_valid_len, jnp.int32), (B,))
    if cfg.use_pallas and pallas_shape_ok("flash_attention", (Tq, S, hd)):
        out = flash_attention_pallas(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            softcap=softcap, scale=scale, q_start=q_start,
            k_valid_len=k_valid, interpret=cfg.run_interpret)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    return ref.grouped_sdpa_decode_ref(
        q, k, v, q_start=q_start, k_valid_len=k_valid, causal=causal,
        window=window, softcap=softcap, scale=scale)


def paged_sdpa(q, k_pages, v_pages, block_table, *, q_start, k_valid_len,
               causal: bool = True, window=None, softcap=None, scale=None,
               config: KernelConfig | None = None):
    """Paged-cache attention in the model stack's layout — the entry
    point ``repro.models.attention`` dispatches paged decode through.

    q: (B, Tq, H, hd);  k_pages, v_pages: (P, ps, KV, hd[, hd_v]) with
    H % KV == 0;  block_table: (B, maxp) int32 (absolute positions
    ``[j*ps, (j+1)*ps)`` of request ``b`` live at physical page
    ``block_table[b, j]``);  q_start / k_valid_len: (B,) int32 — unlike
    :func:`sdpa`, ``q_start`` is per-request (ragged slots are the
    whole point of the paged layout).

    ``ref`` is :func:`repro.kernels.ref.paged_sdpa_ref` (gather pages
    to the dense view, then the grouped-attention math verbatim — BIT
    identical to the dense path over the same cache contents); the
    Pallas path is :func:`paged_flash_attention_pallas`, one grid step
    per (slot, chunk of pages).  Decode/serving only: there is
    deliberately no custom VJP — the train path never sees a paged
    cache (the dense layout stays the train/sim default), so a paged
    backward would be dead code with a live maintenance cost.
    """
    cfg = resolve_config(config)
    _, ps, _, _ = k_pages.shape
    maxp = block_table.shape[1]
    if cfg.use_pallas and pallas_shape_ok(
            "paged_attention", (q.shape[1], maxp * ps, q.shape[3])):
        out = paged_flash_attention_pallas(
            q.transpose(0, 2, 1, 3), k_pages, v_pages, block_table,
            jnp.asarray(q_start, jnp.int32),
            jnp.asarray(k_valid_len, jnp.int32), causal=causal,
            window=window, softcap=softcap, scale=scale,
            interpret=cfg.run_interpret)
        return out.transpose(0, 2, 1, 3).astype(q.dtype)
    return ref.paged_sdpa_ref(q, k_pages, v_pages, block_table,
                              q_start=q_start, k_valid_len=k_valid_len,
                              causal=causal, window=window,
                              softcap=softcap, scale=scale)


def _sdpa_pallas_fwd_call(statics, q, k, v, q_start, k_valid):
    causal, window, softcap, scale, _, interpret = statics
    out = flash_attention_pallas(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        softcap=softcap, scale=scale, q_start=q_start, k_valid_len=k_valid,
        interpret=interpret)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sdpa_pallas(statics, q, k, v, q_start, k_valid):
    return _sdpa_pallas_fwd_call(statics, q, k, v, q_start, k_valid)


def _sdpa_pallas_fwd(statics, q, k, v, q_start, k_valid):
    return (_sdpa_pallas_fwd_call(statics, q, k, v, q_start, k_valid),
            (q, k, v, q_start, k_valid))


def _sdpa_pallas_bwd(statics, res, g):
    causal, window, softcap, scale, q_chunk, _ = statics
    q, k, v, q_start, k_valid = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.grouped_sdpa_ref(
            q_, k_, v_, causal=causal, window=window, softcap=softcap,
            scale=scale, q_pos0=q_start[0], k_valid_len=k_valid,
            q_chunk=q_chunk), q, k, v)
    dq, dk, dv = vjp(g.astype(q.dtype))
    zero_i = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dq, dk, dv, zero_i(q_start), zero_i(k_valid)


_sdpa_pallas.defvjp(_sdpa_pallas_fwd, _sdpa_pallas_bwd)
