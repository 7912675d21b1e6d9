"""Collective-permute gossip: execute a compiled ``ppermute_plan``
schedule on a device mesh.

A round of the plan is ``x'_i = w_self[i] x_i + sum_s w_recv[s][i] *
ppermute(x, perm_s)`` — each slot is one ``jax.lax.ppermute`` over the
gossip axis (a partial permutation: every node sends and receives at most
one message), so a degree-k round costs exactly k point-to-point
messages per node and no all-reduce at all.  This is the TPU-native form
of the paper's communication saving.

``ppermute`` needs static source/destination pairs, so round
indexability under ``jit`` is realised with ``lax.switch`` over the
(static, small — <= 2 log_{k+1} n + 2 by Theorem 1) list of per-round
bodies; the traced round counter only selects the branch.

The mixer runs under ``shard_map`` over the full mesh: leaves keep
whatever tensor-parallel sharding their PartitionSpec gives them, and the
permute moves shards along the gossip axis only — mixing is elementwise,
so it commutes with any sharding of the non-node dims.

On-chip, the per-round combine dispatches through
``repro.kernels.ops.gossip_mix`` (DESIGN.md Sec. 9): the Pallas path
feeds the S+1 slot buffers (own shard + each ppermute result) to one
fused kernel — (S+2) HBM streams per leaf instead of the ~3S of the
slot-by-slot accumulate, which stays as the shard-level reference (and
the bit-exact default off-TPU).

Only inexact (floating) leaves are gossip-averaged.  Integer / bool
leaves (step counters, masks riding in method state trees) pass through
unchanged: a weighted average is meaningless for them, and the
historical float32 round-trip silently corrupted values outside f32's
exact-integer range.

Compressed gossip (``compression=`` — repro.compress, DESIGN.md
Sec. 13): each float leaf's shard is packed to the codec's (rows,
chunk) layout and quantized ONCE per step, outside the round switch
(the payload depends on the step's stochastic-rounding key, not the
round), and the per-round ``ppermute``\\ s move the **payload** arrays —
int8 / fp8-e4m3 / packed-int4 values plus one f32 scale per chunk row,
or top-k (value, index) pairs — so the on-wire bytes shrink by the
codec's ratio.  The combine dequantizes received payloads against the
node's own EXACT buffer via ``ops.quantized_gossip_mix`` (fused Pallas
kernel at the same variadic-slots insertion point as the uncompressed
path) for the int8/fp8 codecs, or decode+accumulate for the rest.  The
EF21 residual rides next to the tree through the same shard_map.  The
stochastic-rounding hash is indexed by GLOBAL row (``me * rows``), so
on a node-only mesh the payload bits match the dense simulation
bit-for-bit; tensor-parallel meshes chunk per shard instead (same
semantics, different grouping).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compress import flat_to_rows, get_codec, rows_to_flat
from repro.compress import resolve as resolve_compression
from repro.core.ppermute_plan import RoundPlan, SchedulePlan
from repro.kernels import ops
from repro.kernels.ref import sr_key


def _round_body(rp: RoundPlan, axis: str, me, kcfg: ops.KernelConfig):
    """Per-shard mixing for one round over a list of f32 work buffers."""
    w_self = jnp.asarray(rp.self_weight, jnp.float32)[me]

    def body_ref(bufs):
        # Reference accumulate — one self-scale plus one scaled add per
        # slot; kept verbatim as the shard-level oracle.
        out = [w_self * b for b in bufs]
        for slot in rp.slots:
            w_recv = jnp.asarray(slot.recv_weight, jnp.float32)[me]
            for i, b in enumerate(bufs):
                recv = lax.ppermute(b, axis, perm=list(slot.perm))
                out[i] = out[i] + w_recv * recv
        return out

    def body_fused(bufs):
        # Fused combine: all S+1 slot buffers stream through one
        # ops.gossip_mix call per leaf.
        w = jnp.stack(
            [w_self] + [jnp.asarray(s.recv_weight, jnp.float32)[me]
                        for s in rp.slots])
        out = []
        for b in bufs:
            slots = [b] + [lax.ppermute(b, axis, perm=list(s.perm))
                           for s in rp.slots]
            out.append(ops.gossip_mix(slots, w, config=kcfg))
        return out

    return body_fused if kcfg.use_pallas else body_ref


def _round_body_compressed(rp: RoundPlan, axis: str, me,
                           kcfg: ops.KernelConfig, codec, ccfg):
    """Per-shard compressed mixing for one round: ppermute the payload
    arrays per slot and combine against the node's own exact buffer."""
    w_self = jnp.asarray(rp.self_weight, jnp.float32)[me]

    def body(owns, payloads):
        ws = [jnp.asarray(s.recv_weight, jnp.float32)[me]
              for s in rp.slots]
        out = []
        for own, pay in zip(owns, payloads):
            recvs = [jax.tree.map(
                lambda a, _s=s: lax.ppermute(a, axis, perm=list(_s.perm)),
                pay) for s in rp.slots]
            # Non-receivers of a partial permutation get all-zero
            # payloads from ppermute; they decode to zero and carry
            # recv weight 0, so the accumulate below is unaffected.
            if codec.fused_mix:
                out.append(ops.quantized_gossip_mix(
                    own, [rc["q"] for rc in recvs],
                    [rc["scale"] for rc in recvs],
                    [w_self] + ws, config=kcfg))
            else:
                acc = w_self * own
                for wr, rc in zip(ws, recvs):
                    acc = acc + wr * codec.decode(ccfg, rc)
                out.append(acc)
        return out

    return body


def make_gossip_mixer(mesh, plan: SchedulePlan, axis: str, specs, *,
                      flatten: bool = False,
                      kernel_config: ops.KernelConfig | None = None,
                      compression=None):
    """Build ``mixer(tree, r) -> tree`` applying round ``r % len(plan)``.

    ``specs`` is a PartitionSpec pytree matching ``tree`` (the node-stack
    dim of every leaf must be sharded over ``axis``).  With
    ``flatten=True`` all float leaves are raveled into a single f32
    buffer per shard so each slot issues ONE ppermute for the whole tree
    instead of one per leaf (fewer, larger messages — better for
    latency-bound cross-pod links).  Non-float leaves are never mixed
    (module docstring); ``kernel_config`` selects the combine backend
    and is resolved once here, at build time.

    With ``compression`` (a resolved ``CompressionConfig``; identity /
    None mean uncompressed) the mixer signature becomes
    ``mixer(tree, r, ef, t) -> (tree, ef')`` — ``ef`` the EF21 residual
    tree mirroring ``tree`` (or None when error feedback is off) and
    ``t`` the step counter feeding the stochastic-rounding key.

    ``mixer.per_shard`` takes the same arguments and is the unmapped
    per-shard body, for callers that already run inside a ``shard_map``
    over every axis of ``mesh`` (shard_maps do not nest)."""
    kcfg = ops.resolve_config(kernel_config)
    ccfg = resolve_compression(compression)
    if ccfg is not None and flatten:
        raise ValueError(
            "flatten_gossip + compression is unsupported: the whole-tree "
            "flat buffer would chunk across leaf boundaries, breaking "
            "payload-bit parity with the per-leaf simulation layout")
    n_rounds = len(plan.rounds)
    axis_size = mesh.shape[axis]
    if axis_size != plan.n:
        raise ValueError(
            f"plan built for n={plan.n} nodes but mesh axis {axis!r} has "
            f"{axis_size} shards")
    if n_rounds == 0:
        raise ValueError("empty schedule plan")
    if ccfg is not None:
        return _make_compressed_mixer(mesh, plan, axis, specs, kcfg, ccfg)

    def shard_body(r, tree):
        me = lax.axis_index(axis)
        leaves, treedef = jax.tree.flatten(tree)
        mixed = [jnp.issubdtype(x.dtype, jnp.inexact) for x in leaves]
        flt = [x for x, m in zip(leaves, mixed) if m]
        if not flt:   # nothing mixable: counters/masks pass through
            return tree
        dtypes = [x.dtype for x in flt]
        shapes = [x.shape for x in flt]
        if flatten:
            work = [jnp.concatenate(
                [x.astype(jnp.float32).reshape(-1) for x in flt])]
        else:
            work = [x.astype(jnp.float32) for x in flt]
        branches = [_round_body(rp, axis, me, kcfg) for rp in plan.rounds]
        work = lax.switch(r % n_rounds, branches, work)
        if flatten:
            offsets = np.cumsum([0] + [int(np.prod(s)) for s in shapes])
            work = [work[0][offsets[i]:offsets[i + 1]].reshape(shapes[i])
                    for i in range(len(flt))]
        out = iter(w.astype(d) for w, d in zip(work, dtypes))
        return jax.tree.unflatten(
            treedef, [next(out) if m else x
                      for x, m in zip(leaves, mixed)])

    mapped = jax.shard_map(shard_body, mesh=mesh, in_specs=(P(), specs),
                           out_specs=specs, check_vma=False)

    def mixer(tree, r):
        return mapped(jnp.asarray(r, jnp.int32), tree)

    mixer.per_shard = lambda tree, r: shard_body(r, tree)
    return mixer


def _make_compressed_mixer(mesh, plan: SchedulePlan, axis: str, specs,
                           kcfg: ops.KernelConfig, ccfg):
    """Compressed twin of the shard_map body above (module docstring)."""
    codec = get_codec(ccfg.codec)
    with_ef = ccfg.error_feedback
    n_rounds = len(plan.rounds)

    def shard_body(r, t, tree, *maybe_ef):
        ef = maybe_ef[0] if with_ef else None
        me = lax.axis_index(axis)
        leaves, treedef = jax.tree.flatten(tree)
        mixed = [jnp.issubdtype(x.dtype, jnp.inexact) for x in leaves]
        if not any(mixed):   # nothing mixable: counters/masks pass through
            return (tree, ef) if with_ef else tree
        ef_leaves = treedef.flatten_up_to(ef) if with_ef \
            else [None] * len(leaves)
        key = sr_key(ccfg.seed, t)

        # Quantize every float leaf ONCE — the payload depends on the
        # step key, not on which of the schedule's rounds fires.
        owns, payloads, resids = [], [], []
        for x, e, m in zip(leaves, ef_leaves, mixed):
            if not m:
                continue
            x2d = flat_to_rows(x.reshape(-1), ccfg.chunk)
            e2d = None if e is None \
                else flat_to_rows(e.reshape(-1), ccfg.chunk)
            pay, resid = codec.compress(ccfg, x2d, e2d, key,
                                        me * x2d.shape[0], kcfg)
            owns.append(x2d)
            payloads.append(pay)
            resids.append(resid)

        branches = [_round_body_compressed(rp, axis, me, kcfg, codec,
                                           ccfg) for rp in plan.rounds]
        work = lax.switch(r % n_rounds, branches, owns, payloads)

        out_leaves, ef_out, it = [], [], iter(zip(work, resids))
        for x, e, m in zip(leaves, ef_leaves, mixed):
            if not m:
                out_leaves.append(x)
                ef_out.append(e)
                continue
            w2d, resid = next(it)
            n_el = int(np.prod(x.shape))
            out_leaves.append(
                rows_to_flat(w2d, n_el).reshape(x.shape).astype(x.dtype))
            if with_ef:
                ef_out.append(rows_to_flat(resid, n_el)
                              .reshape(x.shape).astype(e.dtype))
        out = jax.tree.unflatten(treedef, out_leaves)
        if not with_ef:
            return out
        return out, jax.tree.unflatten(treedef, ef_out)

    in_specs = (P(), P(), specs) + ((specs,) if with_ef else ())
    out_specs = (specs, specs) if with_ef else specs
    mapped = jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)

    def mixer(tree, r, ef, t):
        r = jnp.asarray(r, jnp.int32)
        t = jnp.asarray(t, jnp.int32)
        if with_ef:
            return mapped(r, t, tree, ef)
        return mapped(r, t, tree), None

    def per_shard(tree, r, ef, t):
        if with_ef:
            return shard_body(r, t, tree, ef)
        return shard_body(r, t, tree), None

    mixer.per_shard = per_shard
    return mixer
