"""pjit'd step factories: decentralized train step + serving
prefill/decode, with shardings derived from ``repro.dist.sharding`` and
the gossip realised by ``repro.dist.gossip``.

The train step is the distributed twin of ``repro.sim.engine``: the
node-stacked parameter tree (leading axis = gossip nodes) lives sharded
over ``rules.node_axis``; per-node gradients come from a ``vmap`` over
that axis (GSPMD turns it into pure SPMD — no cross-node traffic); the
method's mixing is the compiled collective-permute schedule instead of
the dense ``W(r) @ X``.  Numerics match the simulation up to f32
reduction order — ``tests/test_dist.py`` is the oracle.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compress import CompressionConfig
from repro.compress import resolve as resolve_compression
from repro.core.graphs import TopologySchedule
from repro.core.ppermute_plan import SchedulePlan
from repro.kernels import ops
from repro.models import model as M
from repro.optim.decentralized import make_method
from repro.topology import Schedule, TopologySpec, as_schedule, spec_from_cli

from .gossip import make_gossip_mixer
from .sharding import (ShardingRules, batch_partition_specs,
                       cache_partition_specs, make_rules,
                       param_partition_specs)


def node_stack_specs(params, n: int):
    """ShapeDtypeStructs with the leading node axis prepended — the
    shape-only twin of broadcasting real params to (n, ...)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype),
        params)


def _shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _dp_entry(rules: ShardingRules, batch: int | None = None):
    """dp spec entry, dropped when the known batch size doesn't divide
    over it (pjit rejects uneven argument shardings)."""
    if not rules.dp:
        return None
    if batch is not None and not rules.divides(batch, rules.dp):
        return None
    return tuple(rules.dp)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainStepBundle:
    step_fn: Any                  # jitted (params_n, opt, batch, step)
    n_nodes: int
    n_rounds: int
    rules: ShardingRules
    schedule: TopologySchedule
    plan: SchedulePlan
    param_shardings: Any
    spec: TopologySpec | None = None   # canonical topology spec
    kernel_config: ops.KernelConfig | None = None
    overlap: bool = False         # gossip/backward overlap enabled?
    # resolved gossip-payload compression (None = uncompressed)
    compression: CompressionConfig | None = None
    # the Method this step was traced against — callers must init the
    # optimizer state from THIS object (its state tree depends on the
    # compression / kernel configs baked in at factory time)
    method: Any = None
    # jitted key -> (params_n, opt): a fresh init copied to every node,
    # built in place with the step's shardings (each device holds only
    # its own shard; nothing is staged on one device first)
    init_fn: Any = None


def make_train_step(cfg, mesh, *,
                    topology: str | TopologySpec | Schedule = "base",
                    k: int = 1,
                    method_name: str = "dsgdm", eta: float = 0.01,
                    param_dtype=jnp.bfloat16, remat: bool = True,
                    flatten_gossip: bool = False,
                    embed_lookup_replicated: bool = False,
                    batch_shapes=None, momentum: float = 0.9,
                    kernel_config: ops.KernelConfig | None = None,
                    overlap: bool = False,
                    compression=None
                    ) -> TrainStepBundle:
    """One DSGD-family step: per-node grads -> method update -> gossip
    round ``step % n_rounds`` over the mesh's node axis.

    ``topology`` is a registered name (with ``k``), an inline JSON spec
    string, a ``TopologySpec`` (its ``n`` must match the mesh's node
    count) or a prebuilt ``Schedule``; the compiled ppermute plan comes
    from the spec-memoized artifact cache.

    ``kernel_config`` picks the fused-kernel backend for the method
    update and the gossip combine.  ``None`` resolves the process-wide
    default HERE, at factory time — the bundle's jitted step is built
    against the resolved value (and records it), so later flips of the
    default cannot silently retarget an already-built step.

    ``overlap=True`` enables communication/computation overlap: instead
    of one whole-tree method-update + gossip barrier after the full
    backward, the parameter tree is split into its top-level groups
    (embed / stack / final_norm / lm_head / ...) and each group's update
    + gossip is emitted as its own independent chain.  Because every
    group's gossip then depends only on THAT group's gradients — and in
    reverse-mode the output-end grads (lm_head, final_norm, mtp) are
    produced before the layer stack's backward scan even starts — XLA's
    scheduler is free to run those groups' collective-permutes while the
    stack backward is still computing ("gossip layer l while layer l+1's
    backward runs", at the granularity the scan-stacked layers permit:
    the stack is one scan op, so intra-stack layers share one group).
    The mixing weights, per-leaf arithmetic, and reduction order are
    identical to the sequential path, so results are BIT-EXACT either
    way (pinned by tests/test_overlap.py); only the schedule differs.

    ``compression`` (a ``CompressionConfig``, a CLI string like
    ``"int8"``, or None) turns the gossip into quantized +
    error-feedback payload exchange (repro.compress): the ppermutes
    move int8/fp8/int4/topk payloads instead of f32 buffers, the
    EF residual + step counter ride in the optimizer state, and the
    bundle records the resolved config.  Identity resolves to None —
    the uncompressed step, same trace.  Incompatible with ``overlap``
    (the scalar step counter in the method state cannot be split along
    the per-group chains) and with ``flatten_gossip`` (chunking the
    whole-tree flat buffer would span leaf boundaries)."""
    kcfg = ops.resolve_config(kernel_config)
    ccfg = resolve_compression(compression)
    if ccfg is not None and overlap:
        raise ValueError(
            "overlap + compression is unsupported: the compressed "
            "method's scalar step counter cannot be split along the "
            "per-group overlap chains")
    rules = make_rules(mesh, arch_name=cfg.name, context="train")
    n = rules.n_nodes
    if isinstance(topology, Schedule):
        if topology.n != n:
            raise ValueError(f"schedule built for n={topology.n} but the "
                             f"mesh provides {n} gossip nodes")
        sched = topology
    else:
        sched = as_schedule(spec_from_cli(topology, n=n, k=k))
    plan = sched.as_ppermute_plan()
    method = make_method(method_name, momentum, kernel_config=kcfg,
                         compression=ccfg)

    p_sds = node_stack_specs(M.param_specs(cfg, param_dtype), n)
    pspecs = param_partition_specs(p_sds, rules, node_axis=True)
    psh = _shardings(mesh, pspecs)
    ospecs = param_partition_specs(jax.eval_shape(method.init, p_sds),
                                   rules, node_axis=True)
    osh = _shardings(mesh, ospecs)
    if batch_shapes is not None:
        bsh = _shardings(mesh, batch_partition_specs(batch_shapes, rules))
        refine_batch = None
    else:
        # Batch shapes unknown until the first call: pin only the node
        # axis (always exact) here, and refine the per-node batch dim
        # over dp at trace time, when batch_partition_specs can apply
        # its divisibility guard to the real shapes.
        bsh = NamedSharding(mesh, P(rules.node_axis))

        def refine_batch(batch):
            return jax.lax.with_sharding_constraint(
                batch, _shardings(mesh, batch_partition_specs(batch,
                                                              rules)))
    scalar = NamedSharding(mesh, P())

    # GSPMD cannot partition a Pallas kernel: on a mesh of more than one
    # device it must run inside a shard_map over every mesh axis.  When
    # the node axis is the only one that splits, each device holds whole
    # nodes, so the node-local work (gradients, method update, gossip)
    # runs per device under ONE such shard_map.  Meshes that also split
    # the weights (tensor parallelism) stay on GSPMD, where only the ref
    # and interpret-mode kernels lower.
    node_local = rules.node_axis is not None and all(
        mesh.shape[a] == 1 for a in mesh.axis_names if a != rules.node_axis)

    # Degenerate 1-node gossip has no communication to overlap with.
    overlap = overlap and rules.node_axis is not None
    if rules.node_axis is None:
        if ccfg is not None:
            def mix_round_c(tree, step, ef, t):
                return tree, ef
        else:
            def mix_round(tree, step):
                return tree
    elif ccfg is not None:
        mix_round_c = make_gossip_mixer(mesh, plan, rules.node_axis,
                                        pspecs, flatten=flatten_gossip,
                                        kernel_config=kcfg,
                                        compression=ccfg)
    elif overlap:
        # One independent mixer per top-level parameter group: separate
        # shard_map regions -> separate collective chains the scheduler
        # can interleave with compute (see the factory docstring).
        group_mixers = {
            key: make_gossip_mixer(mesh, plan, rules.node_axis,
                                   pspecs[key], flatten=flatten_gossip,
                                   kernel_config=kcfg)
            for key in p_sds}
    else:
        mix_round = make_gossip_mixer(mesh, plan, rules.node_axis, pspecs,
                                      flatten=flatten_gossip,
                                      kernel_config=kcfg)

    # Named scopes label the step's operations in the compiled HLO's
    # op names, and so in the profiler's trace: "forward" (its transpose
    # and remat recompute carry JAX's own markers), "update" and, inside
    # it, "gossip".  They cost nothing at run time.
    def loss_one(p, b):
        with jax.named_scope("forward"):
            return M.loss_fn(cfg, p, b, remat=remat, kernel_config=kcfg)[0]

    def gossip(mixer, *args):
        with jax.named_scope("gossip"):
            return mixer(*args)

    embed_repl = NamedSharding(mesh, P(rules.node_axis))

    def _update(params_n, opt, batch, step, local):
        # inside the node-local shard_map the mixers run their per-shard
        # bodies (shard_maps do not nest)
        pick = (lambda m: m.per_shard) if local else (lambda m: m)
        params_l = params_n
        if embed_lookup_replicated and not local:
            # Re-lay-out the (node-stacked) embedding table replicated
            # over the weight axes before the token lookup: one table
            # all-gather instead of a (B, T, D) partial-gather all-reduce
            # per step (§Perf C1).
            table = jax.lax.with_sharding_constraint(
                params_n["embed"]["table"], embed_repl)
            params_l = dict(params_n)
            params_l["embed"] = {"table": table}
        losses, grads = jax.vmap(jax.value_and_grad(loss_one))(
            params_l, batch)
        if overlap:
            # Per-group update + gossip.  Method state trees mirror the
            # params structure (init is zeros_like / tree.map over
            # params), so the state splits and re-merges along the same
            # top-level keys.  Every method's update and mixing are
            # per-leaf, hence grouping is bit-exact vs the whole-tree
            # call — the Python loop order is irrelevant to the XLA
            # schedule, which follows the per-group data dependencies.
            new_p, new_opt = {}, {sk: {} for sk in opt}
            for key in params_n:
                sub_state = {sk: sv[key] for sk, sv in opt.items()}
                mix_k = pick(group_mixers[key])
                with jax.named_scope("update"):
                    p_k, s_k = method.step(
                        params_n[key], grads[key], sub_state,
                        lambda t, _m=mix_k: gossip(_m, t, step), eta)
                new_p[key] = p_k
                for sk in s_k:
                    new_opt[sk][key] = s_k[sk]
            params_n, opt = new_p, new_opt
        elif ccfg is not None:
            # Compressed methods drive the 3-arg transport protocol:
            # the round is selected by the jitted step argument, the
            # stochastic-rounding key by the counter in the method
            # state (equal from step 0, and the counter survives
            # checkpoint restore inside the optimizer state).
            mix_c = pick(mix_round_c)
            with jax.named_scope("update"):
                params_n, opt = method.step(
                    params_n, grads, opt,
                    lambda tr, e, c: gossip(mix_c, tr, step, e, c), eta)
        else:
            mix = pick(mix_round)
            with jax.named_scope("update"):
                params_n, opt = method.step(
                    params_n, grads, opt, lambda t: gossip(mix, t, step),
                    eta)
        return params_n, opt, losses

    def _step(params_n, opt, batch, step):
        if node_local:
            local = jax.shard_map(
                lambda p, o, b, s: _update(p, o, b, s, True), mesh=mesh,
                in_specs=(pspecs, ospecs, batch_partition_specs(batch, rules),
                          P()),
                out_specs=(pspecs, ospecs, P(rules.node_axis)),
                check_vma=False)
            params_n, opt, losses = local(params_n, opt, batch, step)
        else:
            if refine_batch is not None:
                batch = refine_batch(batch)
            params_n, opt, losses = _update(params_n, opt, batch, step,
                                            False)
        return params_n, opt, losses.mean()

    step_fn = jax.jit(_step, in_shardings=(psh, osh, bsh, scalar),
                      out_shardings=(psh, osh, scalar))

    def _init(key):
        params_n = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape),
            M.init(cfg, key, param_dtype))
        return params_n, method.init(params_n)

    init_fn = jax.jit(_init, out_shardings=(psh, osh))
    return TrainStepBundle(step_fn=step_fn, n_nodes=n, n_rounds=len(sched),
                           rules=rules,
                           schedule=sched.as_topology_schedule(), plan=plan,
                           param_shardings=psh, spec=sched.spec,
                           kernel_config=kcfg, overlap=overlap,
                           compression=ccfg, method=method, init_fn=init_fn)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PrefillBundle:
    fn: Any                       # jitted (params, batch)
    rules: ShardingRules
    seq: int
    kernel_config: ops.KernelConfig | None = None


@dataclass(frozen=True)
class DecodeBundle:
    fn: Any                       # jitted (params, cache, tokens, index[, enc])
    rules: ShardingRules
    seq: int
    decode_mode: str = "dus"
    kernel_config: ops.KernelConfig | None = None


def make_prefill(cfg, mesh, *, batch: int, seq: int,
                 param_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
                 kernel_config: ops.KernelConfig | None = None
                 ) -> PrefillBundle:
    """Prompt -> (last-position logits, filled KV cache, enc_out|None).
    ``bundle.fn`` IS the jitted ``(params, batch)`` callable."""
    kcfg = ops.resolve_config(kernel_config)
    rules = make_rules(mesh, arch_name=cfg.name, context="serve")
    psh = _shardings(mesh,
                     param_partition_specs(M.param_specs(cfg, param_dtype),
                                           rules))
    bsh = NamedSharding(mesh, P(_dp_entry(rules, batch)))
    cache_sds = jax.eval_shape(
        lambda: M.init_cache(cfg, batch, seq, cache_dtype))
    # Pin the cache layout so prefill's output commits to the same
    # sharding make_decode_step pins on its input (a committed arg with a
    # different layout would be rejected by pjit, not resharded).
    csh = _shardings(mesh, cache_partition_specs(cache_sds, rules))

    fn = jax.jit(
        lambda params, b: M.prefill(cfg, params, b, seq, cache_dtype,
                                    kernel_config=kcfg),
        in_shardings=(psh, bsh), out_shardings=(bsh, csh, bsh))
    return PrefillBundle(fn=fn, rules=rules, seq=seq, kernel_config=kcfg)


def make_decode_step(cfg, mesh, *, batch: int, seq: int,
                     param_dtype=jnp.bfloat16, cache_dtype=jnp.bfloat16,
                     append_free: bool = False,
                     kernel_config: ops.KernelConfig | None = None
                     ) -> DecodeBundle:
    """One-token decode step against a sharded KV cache.  The cache
    policy is the explicit ``decode_mode`` argument of
    ``model.decode_step`` — baked into this bundle's trace, so two
    bundles with different modes coexist without poisoning each other's
    jit caches (the historical module-global flag was save/restored
    around the trace here, which worked only as long as nobody traced
    concurrently)."""
    kcfg = ops.resolve_config(kernel_config)
    mode = "append_free" if append_free else "dus"
    rules = make_rules(mesh, arch_name=cfg.name, context="serve")
    psh = _shardings(mesh,
                     param_partition_specs(M.param_specs(cfg, param_dtype),
                                           rules))
    cache_sds = jax.eval_shape(
        lambda: M.init_cache(cfg, batch, seq, cache_dtype))
    csh = _shardings(mesh, cache_partition_specs(cache_sds, rules))
    dsh = NamedSharding(mesh, P(_dp_entry(rules, batch)))
    scalar = NamedSharding(mesh, P())

    def run(params, caches, tokens, index, enc_out=None):
        return M.decode_step(cfg, params, caches, tokens, index,
                             enc_out=enc_out, decode_mode=mode,
                             kernel_config=kcfg)

    if cfg.encoder is not None:
        fn = jax.jit(lambda p, c, t, i, e: run(p, c, t, i, e),
                     in_shardings=(psh, csh, dsh, scalar, dsh),
                     out_shardings=(dsh, csh))
    else:
        fn = jax.jit(lambda p, c, t, i: run(p, c, t, i),
                     in_shardings=(psh, csh, dsh, scalar),
                     out_shardings=(dsh, csh))
    return DecodeBundle(fn=fn, rules=rules, seq=seq, decode_mode=mode,
                        kernel_config=kcfg)
