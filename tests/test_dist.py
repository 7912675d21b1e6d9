"""Distributed-runtime correctness: the collective-permute gossip and the
pjit'd train step reproduce the dense-matrix simulation bit-for-bit
(up to f32 reduction order).

These tests need >1 device, so they run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag must be set
before jax initialises; per the assignment it must NOT be set globally)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_gossip_mixer_equals_dense_matrix():
    out = _run("""
        from repro.core.graphs import build_topology
        from repro.core.ppermute_plan import compile_schedule
        from repro.dist.gossip import make_gossip_mixer
        mesh = make_mesh((8,), ("data",))
        n = 8
        for name, k in (("base", 1), ("base", 3), ("simple_base", 2),
                        ("one_peer_exp", None), ("ring", None)):
            sched = build_topology(name, n, k)
            plan = compile_schedule(sched)
            tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 6)),
                    "b": jax.random.normal(jax.random.PRNGKey(1), (n, 3))}
            specs = {"a": P("data", None, None), "b": P("data", None)}
            for flatten in (False, True):
                mixer = make_gossip_mixer(mesh, plan, "data", specs,
                                          flatten=flatten)
                cur = jax.device_put(
                    tree, jax.tree.map(
                        lambda s: jax.sharding.NamedSharding(mesh, s),
                        specs, is_leaf=lambda x: isinstance(x, P)))
                for r in range(len(sched)):
                    cur = jax.jit(mixer)(cur, jnp.int32(r))
                W = np.eye(n)
                for r in range(len(sched)):
                    W = sched.W(r) @ W
                for key in ("a", "b"):
                    want = np.tensordot(W, np.asarray(tree[key]),
                                        axes=([1], [0]))
                    np.testing.assert_allclose(np.asarray(cur[key]), want,
                                               atol=1e-5)
        print("GOSSIP_OK")
    """)
    assert "GOSSIP_OK" in out


def test_distributed_train_step_matches_simulation():
    out = _run("""
        from repro.configs import get_config
        from repro.core.graphs import build_topology
        from repro.dist.steps import make_train_step, node_stack_specs
        from repro.models import model as M
        from repro.optim.decentralized import make_method
        from repro.sim.engine import simulate_decentralized

        cfg = get_config("granite-8b").reduced()
        mesh = make_mesh((4, 2), ("data", "model"))
        n = 4
        key = jax.random.PRNGKey(0)
        params = M.init(cfg, key, jnp.float32)

        def mk_batch(step):
            kk = jax.random.fold_in(jax.random.PRNGKey(7), step)
            toks = jax.random.randint(kk, (n, 2, 16), 0, cfg.vocab_size)
            labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-100)
            return {"tokens": toks, "labels": labels}

        # --- distributed ---
        bundle = make_train_step(cfg, mesh, topology="base", k=1,
                                 method_name="dsgdm", eta=0.05,
                                 param_dtype=jnp.float32, remat=False)
        params_n = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0,
            params)
        method = make_method("dsgdm")
        opt = method.init(params_n)
        pn, op = params_n, opt
        for step in range(4):
            pn, op, loss = bundle.step_fn(pn, op, mk_batch(step),
                                          jnp.int32(step))

        # --- dense simulation (ground truth) ---
        sched = build_topology("base", n, 1)
        res_params = [None]
        import repro.sim.engine as E
        sim_pn = params_n
        sim_state = method.init(sim_pn)
        loss_one = lambda p, b: M.loss_fn(cfg, p, b)[0]
        grad_fn = jax.vmap(jax.grad(loss_one))
        for step in range(4):
            b = mk_batch(step)
            g = grad_fn(sim_pn, b)
            sim_pn, sim_state = method.step(sim_pn, g, sim_state,
                                            jnp.asarray(sched.W(step)), 0.05)

        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(pn),
                                  jax.tree.leaves(sim_pn)))
        print("MAXERR", err)
        assert err < 2e-4, err
        print("TRAIN_OK")
    """)
    assert "TRAIN_OK" in out


def test_gossip_mixer_pallas_forced_matches_dense_matrix():
    """The fused ops.gossip_mix combine (Pallas interpret) is a LIVE
    call site in the dist hot path — counted via the kernel wrapper,
    not grep — and stays within f32 tolerance of the dense matrix."""
    out = _run("""
        from repro.core.graphs import build_topology
        from repro.core.ppermute_plan import compile_schedule
        from repro.dist.gossip import make_gossip_mixer
        from repro.kernels import ops
        from repro.kernels.ops import KernelConfig

        CALLS = [0]
        real = ops.gossip_mix_slots_pallas
        def counted(*a, **k):
            CALLS[0] += 1
            return real(*a, **k)
        ops.gossip_mix_slots_pallas = counted

        mesh = make_mesh((8,), ("data",))
        n = 8
        cfg = KernelConfig(backend="pallas", interpret=True)
        for name, k in (("base", 3), ("one_peer_exp", None)):
            sched = build_topology(name, n, k)
            plan = compile_schedule(sched)
            tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 6)),
                    "b": jax.random.normal(jax.random.PRNGKey(1), (n, 3))}
            specs = {"a": P("data", None, None), "b": P("data", None)}
            mixer = make_gossip_mixer(mesh, plan, "data", specs,
                                      kernel_config=cfg)
            cur = jax.device_put(
                tree, jax.tree.map(
                    lambda s: jax.sharding.NamedSharding(mesh, s),
                    specs, is_leaf=lambda x: isinstance(x, P)))
            for r in range(len(sched)):
                cur = jax.jit(mixer)(cur, jnp.int32(r))
            W = np.eye(n)
            for r in range(len(sched)):
                W = sched.W(r) @ W
            for key in ("a", "b"):
                want = np.tensordot(W, np.asarray(tree[key]),
                                    axes=([1], [0]))
                np.testing.assert_allclose(np.asarray(cur[key]), want,
                                           atol=1e-5)
        assert CALLS[0] > 0, "fused kernel never dispatched"
        print("PALLAS_GOSSIP_OK", CALLS[0])
    """)
    assert "PALLAS_GOSSIP_OK" in out


def test_gossip_mixed_dtype_tree_passes_non_floats_through():
    """Integer/bool leaves (step counters, masks) must come back
    bit-identical from the mixer — both flatten modes and both
    backends; the historical f32 round-trip corrupted values outside
    f32's exact-integer range (2**25 + 1 is the canary)."""
    out = _run("""
        from repro.core.graphs import build_topology
        from repro.core.ppermute_plan import compile_schedule
        from repro.dist.gossip import make_gossip_mixer
        from repro.kernels.ops import KernelConfig
        mesh = make_mesh((8,), ("data",))
        n = 8
        big = 2**25 + 1            # not representable in float32
        sched = build_topology("base", n, 1)
        plan = compile_schedule(sched)
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 6)),
                "step": jnp.full((n, 2), big, jnp.int32),
                "flag": jnp.ones((n, 3), bool)}
        specs = {"w": P("data", None, None), "step": P("data", None),
                 "flag": P("data", None)}
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        for flatten in (False, True):
            for cfg in (KernelConfig(backend="ref"),
                        KernelConfig(backend="pallas", interpret=True)):
                mixer = make_gossip_mixer(mesh, plan, "data", specs,
                                          flatten=flatten,
                                          kernel_config=cfg)
                out = jax.jit(mixer)(jax.device_put(tree, shardings),
                                     jnp.int32(0))
                assert out["step"].dtype == jnp.int32
                assert bool((out["step"] == big).all()), (flatten, cfg)
                assert out["flag"].dtype == jnp.bool_
                assert bool(out["flag"].all())
                want = np.tensordot(sched.W(0), np.asarray(tree["w"]),
                                    axes=([1], [0]))
                np.testing.assert_allclose(np.asarray(out["w"]), want,
                                           atol=1e-5)
        print("MIXED_DTYPE_OK")
    """)
    assert "MIXED_DTYPE_OK" in out


def test_distributed_train_step_pallas_forced_matches_simulation():
    """Sim-vs-dist parity with the whole Pallas path forced on: the
    fused gossip combine AND the fused DSGD update run (interpret mode)
    inside the pjit'd step, and the result still matches the dense
    simulation within f32 reduction-order tolerance."""
    out = _run("""
        from repro.configs import get_config
        from repro.core.graphs import build_topology
        from repro.dist.steps import make_train_step
        from repro.kernels import ops
        from repro.kernels.ops import KernelConfig
        from repro.models import model as M
        from repro.optim.decentralized import make_method

        CALLS = {"dsgd": 0, "gossip": 0}
        real_d, real_g = ops.fused_dsgd_pallas, ops.gossip_mix_slots_pallas
        def cd(*a, **k):
            CALLS["dsgd"] += 1
            return real_d(*a, **k)
        def cg(*a, **k):
            CALLS["gossip"] += 1
            return real_g(*a, **k)
        ops.fused_dsgd_pallas = cd
        ops.gossip_mix_slots_pallas = cg

        cfg = get_config("granite-8b").reduced()
        mesh = make_mesh((4, 2), ("data", "model"))
        n = 4
        params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)

        def mk_batch(step):
            kk = jax.random.fold_in(jax.random.PRNGKey(7), step)
            toks = jax.random.randint(kk, (n, 2, 16), 0, cfg.vocab_size)
            labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-100)
            return {"tokens": toks, "labels": labels}

        kc = KernelConfig(backend="pallas", interpret=True)
        bundle = make_train_step(cfg, mesh, topology="base", k=1,
                                 method_name="dsgdm", eta=0.05,
                                 param_dtype=jnp.float32, remat=False,
                                 kernel_config=kc)
        assert bundle.kernel_config == kc
        params_n = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0,
            params)
        method = make_method("dsgdm", kernel_config=kc)
        pn, op = params_n, method.init(params_n)
        for step in range(3):
            pn, op, loss = bundle.step_fn(pn, op, mk_batch(step),
                                          jnp.int32(step))
        assert CALLS["dsgd"] > 0 and CALLS["gossip"] > 0, CALLS

        # dense simulation ground truth (default ref backend)
        sched = build_topology("base", n, 1)
        ref_m = make_method("dsgdm")
        sim_pn, sim_state = params_n, ref_m.init(params_n)
        loss_one = lambda p, b: M.loss_fn(cfg, p, b)[0]
        grad_fn = jax.vmap(jax.grad(loss_one))
        for step in range(3):
            b = mk_batch(step)
            g = grad_fn(sim_pn, b)
            sim_pn, sim_state = ref_m.step(sim_pn, g, sim_state,
                                           jnp.asarray(sched.W(step)), 0.05)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(pn),
                                  jax.tree.leaves(sim_pn)))
        print("MAXERR", err, CALLS)
        assert err < 2e-4, err
        print("PALLAS_TRAIN_OK")
    """)
    assert "PALLAS_TRAIN_OK" in out


def test_serve_steps_run_sharded():
    out = _run("""
        from repro.configs import get_config
        from repro.dist.steps import make_decode_step, make_prefill
        from repro.models import model as M
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("gemma3-1b").reduced()
        params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
        B, S = 4, 32
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (B, 16), 0, cfg.vocab_size)}
        pre = make_prefill(cfg, mesh, batch=B, seq=S,
                           param_dtype=jnp.float32,
                           cache_dtype=jnp.float32)
        logits, cache, enc = pre.fn(params, batch)
        assert logits.shape == (B, 1, cfg.vocab_size)
        dec = make_decode_step(cfg, mesh, batch=B, seq=S,
                               param_dtype=jnp.float32,
                               cache_dtype=jnp.float32)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        logits2, cache = dec.fn(params, cache, tok, jnp.int32(16))
        assert logits2.shape == (B, 1, cfg.vocab_size)
        assert bool(jnp.isfinite(logits2).all())
        print("SERVE_OK")
    """)
    assert "SERVE_OK" in out
