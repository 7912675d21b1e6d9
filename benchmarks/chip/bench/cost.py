"""Operations and bytes the algorithms need, from shapes alone.

A model's forward pass is counted by its architecture module
(``archs/<name>.py``, ``forward_flops``), kept with the benchmark so that
no change to the program can move the yardstick.  Training counts 3 x
forward: the backward pass is twice the forward, and what remat
recomputes is not counted.

The kernel byte counts follow the HBM stream model of
``benchmarks/kernels.py``: the fused DSGD-momentum update reads x, u, g
and writes x', u' (5 streams); the fused gossip combine reads its S
received slots and its own buffer and writes one output (S + 2).
"""
from __future__ import annotations

from .peaks import Peak


def causal_pairs(t: int) -> float:
    """(query, key) pairs of one causal sequence of length ``t``."""
    return t * (t + 1) / 2


def train_step_flops(arch, model: dict, *, sequences: int,
                     seq: int) -> float:
    """Forward + backward (3 x forward) of one step; no recompute."""
    return 3 * arch.forward_flops(model, tokens=sequences * seq,
                                  attended=sequences * causal_pairs(seq))


def flash_fwd_flops(*, batch: int, heads: int, head_dim: int,
                    q_len: int, k_len: int, causal: bool) -> float:
    """Flash attention forward: QK^T and PV over the attended pairs."""
    if causal:
        off = k_len - q_len
        pairs = sum(min(k_len, off + i + 1) for i in range(q_len))
    else:
        pairs = q_len * k_len
    return 4.0 * batch * heads * head_dim * pairs


def flash_fwd_bytes(*, batch: int, heads: int, kv_heads: int, head_dim: int,
                    q_len: int, k_len: int, itemsize: int) -> float:
    """q and out once, k and v once per KV head."""
    return itemsize * batch * head_dim * (2 * heads * q_len
                                          + 2 * kv_heads * k_len)


def dsgd_update_bytes(elements: int, *, x_itemsize: int, u_itemsize: int,
                      g_itemsize: int) -> float:
    """Fused DSGD-momentum update of one leaf: read x, u, g; write x', u'."""
    return elements * (2 * x_itemsize + 2 * u_itemsize + g_itemsize)


def gossip_mix_bytes(elements: int, *, slots: int, itemsize: int = 4) -> float:
    """Fused gossip combine of one leaf: S received + own in, one out."""
    return elements * itemsize * (slots + 2)


def paged_decode_bytes(valid_lens, *, heads: int, kv_heads: int,
                       head_dim: int, itemsize: int) -> float:
    """One layer's paged decode attention over a batch of slots: the K
    and V rows up to each slot's valid length, plus q and out."""
    kv = sum(valid_lens) * kv_heads * head_dim * 2 * itemsize
    return kv + len(valid_lens) * heads * head_dim * 2 * itemsize


def paged_decode_flops(valid_lens, *, heads: int, head_dim: int) -> float:
    return 4.0 * heads * head_dim * sum(valid_lens)


def roofline_seconds(peak: Peak, *, flops: float, bytes_: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak.bf16_flops, bytes_ / peak.hbm_bytes_per_s)
