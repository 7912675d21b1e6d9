"""Driver of a training cell: ``make_train_step`` as ``launch/train.py``
builds it (default kernels, no overlap, no compression).

Set-up builds the one compiled step and its state from the seed, drives
it through the checked steps on batches that all differ, and hands the
same step and state to the window.  The window runs steps until
``seconds`` have passed, with up to ``AHEAD_S`` seconds of steps queued
on the device ahead of the one the host waits for, so that a host that
stands still for less than the queue leaves the chip busy.  The chip's
runtime may keep the queue shorter: it takes a step only once there is
memory for the step's new state, since the program donates none.  When
the time is up the window sends nothing more, waits for every step it
sent, and reads the clock after that wait: all of those steps count,
over all of that time.  A traced run then runs the mix's
``trace_seconds`` more under the profiler, so that the host-clock
numbers of both kinds of run come from an untraced window of the same
length.  After it, the program's state is freed and the reference runs
the checked steps again.
"""
from __future__ import annotations

import collections
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import common, cost, reference, traffic
from . import weights as W
from .program import dtype_of, program_config

AHEAD_S = 6.0       # seconds of steps queued ahead of the one waited for


def _sds(tree, shardings=None):
    if shardings is None:
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            tree)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


class TrainCell:
    """The compiled step of one training cell and its helpers."""

    def __init__(self, cell, devices):
        from repro.dist.steps import make_train_step, node_stack_specs
        from repro.launch.mesh import make_mesh
        from repro.models import model as M

        model, mix = self.model, self.mix = cell.model, cell.mix
        self.cfg = program_config(model, cell.arch)
        shape = tuple(mix["mesh"])
        self.mesh = make_mesh(shape, ("data", "model"),
                              devices=devices[:int(np.prod(shape))])
        self.devices = list(self.mesh.devices.flat)
        dt = dtype_of(model)
        bundle = make_train_step(
            self.cfg, self.mesh, topology=mix["topology"], k=mix["k"],
            method_name=mix["method"], eta=mix["eta"],
            momentum=mix["momentum"], param_dtype=dt, remat=mix["remat"])
        n = self.n = bundle.n_nodes
        if n != mix["nodes"]:
            raise ValueError(f"mesh gives {n} nodes, the mix asks for "
                             f"{mix['nodes']}")
        single = M.param_specs(self.cfg, dt)
        p_sds = node_stack_specs(single, n)
        o_sds = jax.eval_shape(bundle.method.init, p_sds)
        b, t = mix["rows_per_node"], mix["seq"]
        b_sds = {k: jax.ShapeDtypeStruct((n, b, t), jnp.int32)
                 for k in ("tokens", "labels")}
        self.step = bundle.step_fn.lower(
            p_sds, o_sds, b_sds, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        psh, osh, self.batch_sharding, self.scalar_sharding = \
            self.step.input_shardings[0]
        std = mix["weights"]

        def init_params(key_data):
            tree = W.make_tree(key_data, single, std)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)

        def init_opt(params):
            return bundle.method.init(params)

        def leaf_norms(tree):
            return jax.tree.map(
                lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                           axis=tuple(range(1, a.ndim)))),
                tree)

        def change_norms(a, b):
            return leaf_norms(jax.tree.map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                a, b))

        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        self.init_params = jax.jit(init_params, out_shardings=psh).lower(
            key_sds).compile()
        p_in = _sds(p_sds, psh)
        self.init_opt = jax.jit(init_opt, out_shardings=osh).lower(
            p_in).compile()
        self.u_norms = jax.jit(leaf_norms).lower(
            _sds(o_sds["u"], osh["u"])).compile()
        self.change_norms = jax.jit(change_norms).lower(p_in, p_in).compile()
        self.tokens_per_step = n * b * t
        self.leaves = [(int(np.prod(a.shape)), a.dtype.itemsize)
                       for a in jax.tree.leaves(single)]

    # -- feeding -----------------------------------------------------------

    def batch(self, step: int, seed: int):
        raw = traffic.node_batch(step, self.mix, self.model["vocab_size"], seed)
        return jax.device_put(raw, self.batch_sharding)

    def step_index(self, step: int):
        return jax.device_put(np.int32(step), self.scalar_sharding)

    # -- the checked steps -------------------------------------------------

    def first_steps(self, seed: int):
        """Drive the step from the seed through the checked steps; returns
        the state after them and the program's readings."""
        key = W.seed_key_data(seed)
        params = self.init_params(key)
        opt = self.init_opt(params)
        losses, grad_norms = [], None
        n = self.mix["checked_steps"]
        t0 = time.perf_counter()
        for s in range(n):
            params, opt, loss = self.step(params, opt, self.batch(s, seed),
                                          self.step_index(s))
            losses.append(loss)
            if s == 0:
                grad_norms = self.u_norms(opt["u"])
                if n > 1:
                    jax.block_until_ready((loss, grad_norms))
                    t0 = time.perf_counter()
        jax.block_until_ready(loss)
        # the length of a step after the first, which sets how many steps
        # the window queues
        self.step_s = (time.perf_counter() - t0) / max(n - 1, 1)
        start = self.init_params(key)
        changes = self.change_norms(params, start)
        del start
        readings = {"losses": [float(x) for x in losses],
                    "grad_norms": _per_node(grad_norms, self.n),
                    "change_norms": _per_node(changes, self.n)}
        return (params, opt), readings

    # -- the window --------------------------------------------------------

    def window(self, state, seed: int, seconds: float, first: int):
        """Run steps ``first``, ``first + 1``... for ``seconds``; returns
        (steps, t_open, t_close, final loss, state, longest wait).  Host
        spans name what the host does between steps."""
        params, opt = state
        ahead = max(1, math.ceil(AHEAD_S / self.step_s))
        queued = collections.deque()
        s = first
        t_open = time.perf_counter()
        last = None                     # when the last wait returned
        longest = (0.0, 0.0)            # (longest gap between steps, when)
        while True:
            with common.span("bench.feed"):
                batch, idx = self.batch(s, seed), self.step_index(s)
            with common.span("bench.dispatch"):
                params, opt, loss = self.step(params, opt, batch, idx)
            s += 1
            queued.append(loss)
            if len(queued) > ahead:
                with common.span("bench.wait"):
                    queued.popleft().block_until_ready()
                now = time.perf_counter()
                if last is not None:
                    longest = max(longest, (now - last, now - t_open))
                last = now
            if time.perf_counter() - t_open >= seconds:
                break
        with common.span("bench.wait"):
            jax.block_until_ready((params, opt, loss))
        t_close = time.perf_counter()
        return (s - first, t_open, t_close, float(loss), (params, opt),
                longest, ahead)


def _per_node(tree, n: int) -> list[dict]:
    named = {k: np.asarray(v) for k, v in W.named_leaves(tree).items()}
    return [{k: float(v[i]) for k, v in named.items()} for i in range(n)]


def compare(prog: dict, ref: dict) -> dict:
    """The numbers the check compares (see PERF.md): the worst step's
    relative loss gap, and the worst leaf's gap of norms for the first
    gradient and for the change after the checked steps.  Leaves whose
    reference gradient is under a thousandth of the node's median leaf
    are left out of the change."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_at = common.worst_leaf_gap(prog["grad_norms"],
                                              ref["grad_norms"])
    meds = [float(np.median(list(g.values()))) for g in ref["grad_norms"]]

    def moved(node, name):
        return ref["grad_norms"][node][name] >= 1e-3 * meds[node]

    upd_gap, upd_at = common.worst_leaf_gap(prog["change_norms"],
                                            ref["change_norms"], moved)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": upd_gap,
            "where": {"grad_norm_gap": grad_at, "update_norm_gap": upd_at}}


def run(cell, args, devices, peak, on_setup_done):
    """One run of a training cell; returns the driver's result dict."""
    tc = TrainCell(cell, devices)
    state, prog = tc.first_steps(args.seed)
    profile = common.Profile(bool(args.trace))
    counter = common.CompileCounter()
    setup_s = on_setup_done()
    first = cell.mix["checked_steps"]
    with counter.watching():
        steps, t_open, t_close, last_loss, state, longest, ahead = tc.window(
            state, args.seed, args.seconds, first)
        traced = 0
        if args.trace:
            profile.start()
            traced, _, _, last_loss, state, _, _ = tc.window(
                state, args.seed, cell.mix["trace_seconds"], first + steps)
            profile.stop()
    mem = common.peak_bytes(tc.devices)
    del state
    window_s = t_close - t_open
    tokens = steps * tc.tokens_per_step
    step_flops = cost.train_step_flops(
        cell.arch, cell.model, sequences=tc.n * cell.mix["rows_per_node"],
        seq=cell.mix["seq"])
    out = {
        "e2e": {"train_tokens_per_s": tokens / window_s,
                "setup_s": setup_s},
        "ctx": {"kind": "train", "steps": steps, "window_s": window_s,
                "traced_steps": traced,
                "tokens": tokens, "step_flops": step_flops,
                "chips": len(tc.devices), "peak": peak, "model": cell.model,
                "arch": cell.arch,
                "mix": cell.mix, "trace_path": profile.path,
                "param_leaves": tc.leaves,
                "devices": [d.id for d in tc.devices]},
        "notes": {"longest_step_gap_ms": 1e3 * longest[0],
                  "longest_at_s": longest[1], "steps": steps,
                  "ahead_steps": ahead},
        "profile": profile, "memory_peak_bytes": mem,
        "window_compiles": counter.count, "attempted": steps + traced,
        "failed": 0 if np.isfinite(last_loss) else 1,
    }
    ref = reference.Reference(cell.arch, cell.model, cell.mix).train_readings(
        args.seed, tc.devices, steps=cell.mix["checked_steps"])
    out["numbers"] = compare(prog, ref)
    out["readings"] = {"program": prog, "reference": ref}
    return out
