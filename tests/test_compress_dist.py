"""Compressed gossip over the real shard_map transport: the quantized
ppermute mixer must be wire- and bit-compatible with the dense
simulation path (repro.compress.mixing), the fused Pallas
dequantize-mix kernel must be a LIVE call site when forced, and the
end-to-end compressed train step must track the dense simulation.

Same subprocess pattern as tests/test_dist.py: >1 device needs
XLA_FLAGS=--xla_force_host_platform_device_count set before jax
initialises, so each test body runs in a fresh interpreter.
"""
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


def test_compressed_mixer_matches_dense_mix_all_codecs():
    """Every codec, every round of a time-varying schedule: the
    shard_map mixer (per-node shards, global row offsets, ppermute'd
    payload dicts) equals the full-array dense mix — the invariant that
    lets the sim engine stand in for the wire protocol."""
    out = _run("""
        from repro.compress import (CompressionConfig,
                                    compressed_dense_mix, init_ef)
        from repro.core.graphs import build_topology
        from repro.core.ppermute_plan import compile_schedule
        from repro.dist.gossip import make_gossip_mixer

        mesh = make_mesh((8,), ("data",))
        n = 8
        tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 6)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (n, 3)),
                "step": jnp.int32(5)}
        specs = {"a": P("data", None, None), "b": P("data", None),
                 "step": P()}
        shard = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        for name, k in (("base", 1), ("one_peer_exp", None)):
            sched = build_topology(name, n, k)
            plan = compile_schedule(sched)
            for codec in ("int8", "fp8", "int4", "topk"):
                for ef_on in (True, False):
                    ccfg = CompressionConfig(codec=codec, chunk=8,
                                             topk_frac=0.5,
                                             error_feedback=ef_on)
                    mixer = make_gossip_mixer(mesh, plan, "data", specs,
                                              compression=ccfg)
                    cur = jax.device_put(tree, shard)
                    ef = init_ef(cur, ccfg)
                    ref, ref_ef = tree, init_ef(tree, ccfg)
                    for r in range(len(sched)):
                        cur, ef = jax.jit(mixer)(cur, jnp.int32(r), ef,
                                                 jnp.int32(r))
                        W = jnp.asarray(sched.W(r), jnp.float32)
                        ref, ref_ef = compressed_dense_mix(
                            W, ref, ref_ef, ccfg, jnp.int32(r))
                        for key in ("a", "b"):
                            np.testing.assert_allclose(
                                np.asarray(cur[key]),
                                np.asarray(ref[key]), atol=1e-5,
                                err_msg=f"{name}/{codec}/ef={ef_on}/r{r}")
                            if ef_on:
                                np.testing.assert_allclose(
                                    np.asarray(ef[key]),
                                    np.asarray(ref_ef[key]), atol=1e-5)
                    assert int(cur["step"]) == 5
        print("MIX_PARITY_OK")
    """)
    assert "MIX_PARITY_OK" in out


def test_quantized_mix_pallas_forced_is_live_and_matches_ref():
    """Forcing the Pallas backend must route the compressed round
    through BOTH fused kernels (quantize+EF and dequantize-mix) —
    counted via the ops-module wrappers, not grep — and agree with the
    reference mixer to f32 tolerance."""
    out = _run("""
        from repro.compress import CompressionConfig, init_ef
        from repro.core.graphs import build_topology
        from repro.core.ppermute_plan import compile_schedule
        from repro.dist.gossip import make_gossip_mixer
        from repro.kernels import ops
        from repro.kernels.ops import KernelConfig

        QCALLS, MCALLS = [0], [0]
        real_q = ops.quantize_ef_pallas
        real_m = ops.quantized_gossip_mix_slots_pallas
        def counted_q(*a, **k):
            QCALLS[0] += 1
            return real_q(*a, **k)
        def counted_m(*a, **k):
            MCALLS[0] += 1
            return real_m(*a, **k)
        ops.quantize_ef_pallas = counted_q
        ops.quantized_gossip_mix_slots_pallas = counted_m

        mesh = make_mesh((8,), ("data",))
        n = 8
        sched = build_topology("base", n, 1)
        plan = compile_schedule(sched)
        tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 6))}
        specs = {"a": P("data", None, None)}
        shard = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        ccfg = CompressionConfig(codec="int8", chunk=8)
        outs = {}
        for label, kcfg in (("ref", KernelConfig(backend="ref")),
                            ("pallas", KernelConfig(backend="pallas",
                                                    interpret=True))):
            mixer = make_gossip_mixer(mesh, plan, "data", specs,
                                      kernel_config=kcfg,
                                      compression=ccfg)
            cur = jax.device_put(tree, shard)
            ef = init_ef(cur, ccfg)
            for r in range(len(sched)):
                cur, ef = jax.jit(mixer)(cur, jnp.int32(r), ef,
                                         jnp.int32(r))
            outs[label] = np.asarray(cur["a"])
        assert QCALLS[0] > 0, "fused quantize kernel never dispatched"
        assert MCALLS[0] > 0, "fused dequantize-mix kernel never dispatched"
        np.testing.assert_allclose(outs["pallas"], outs["ref"], atol=1e-5)
        print("FUSED_LIVE_OK")
    """)
    assert "FUSED_LIVE_OK" in out


def test_compressed_train_step_matches_simulation():
    """End-to-end: the pjit'd int8+EF train step tracks the dense
    simulation.  Tolerance is wider than the uncompressed 2e-4 —
    stochastic rounding amplifies ulp-level grad differences (vmap vs
    shard_map reduction order) into full quantization-step flips; EF
    keeps the gap bounded at ~1e-3 after 4 steps."""
    out = _run("""
        from repro.compress import CompressionConfig
        from repro.configs import get_config
        from repro.core.graphs import build_topology
        from repro.dist.steps import make_train_step
        from repro.models import model as M
        from repro.optim.decentralized import make_method

        cfg = get_config("granite-8b").reduced()
        # model axis must be size 1: tensor-parallel shards chunk the
        # payload per shard, which regroups the scale rows vs the sim
        mesh = make_mesh((8, 1), ("data", "model"))
        n = 8
        ccfg = CompressionConfig(codec="int8", chunk=256)
        params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)

        def mk_batch(step):
            kk = jax.random.fold_in(jax.random.PRNGKey(7), step)
            toks = jax.random.randint(kk, (n, 2, 16), 0, cfg.vocab_size)
            labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-100)
            return {"tokens": toks, "labels": labels}

        bundle = make_train_step(cfg, mesh, topology="base", k=1,
                                 method_name="dsgd", eta=0.05,
                                 param_dtype=jnp.float32, remat=False,
                                 compression=ccfg)
        assert bundle.compression == ccfg
        params_n = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0,
            params)
        opt = bundle.method.init(params_n)
        assert "ef" in opt and "ct" in opt
        pn, op = params_n, opt
        for step in range(4):
            pn, op, loss = bundle.step_fn(pn, op, mk_batch(step),
                                          jnp.int32(step))
        assert int(op["ct"]) == 4

        sched = build_topology("base", n, 1)
        method = make_method("dsgd", compression=ccfg)
        sim_pn = params_n
        sim_state = method.init(sim_pn)
        loss_one = lambda p, b: M.loss_fn(cfg, p, b)[0]
        grad_fn = jax.vmap(jax.grad(loss_one))
        for step in range(4):
            g = grad_fn(sim_pn, mk_batch(step))
            sim_pn, sim_state = method.step(
                sim_pn, g, sim_state, jnp.asarray(sched.W(step)), 0.05)

        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(pn),
                                  jax.tree.leaves(sim_pn)))
        print("MAXERR", err)
        assert err < 1e-3, err
        ef_err = max(float(jnp.max(jnp.abs(a - b)))
                     for a, b in zip(jax.tree.leaves(op["ef"]),
                                     jax.tree.leaves(sim_state["ef"])))
        print("EF_MAXERR", ef_err)
        assert ef_err < 1e-2, ef_err
        print("TRAIN_C_OK")
    """)
    assert "TRAIN_C_OK" in out


def test_identity_bundle_and_composition_guards():
    """identity compression canonicalizes to the uncompressed bundle
    (same memoized Method object -> bit-exact by construction), and the
    unsupported compositions fail loudly at factory time."""
    out = _run("""
        from repro.compress import CompressionConfig
        from repro.configs import get_config
        from repro.core.graphs import build_topology
        from repro.core.ppermute_plan import compile_schedule
        from repro.dist.gossip import make_gossip_mixer
        from repro.dist.steps import make_train_step
        from repro.optim.decentralized import make_method

        cfg = get_config("granite-8b").reduced()
        mesh = make_mesh((8, 1), ("data", "model"))
        bundle = make_train_step(cfg, mesh, topology="base", k=1,
                                 method_name="dsgdm", eta=0.05,
                                 param_dtype=jnp.float32, remat=False,
                                 compression="identity")
        assert bundle.compression is None
        assert bundle.method is make_method(
            "dsgdm", kernel_config=bundle.kernel_config)
        assert bundle.method.compression is None

        try:
            make_train_step(cfg, mesh, topology="base", k=1,
                            method_name="dsgd", overlap=True,
                            param_dtype=jnp.float32, remat=False,
                            compression="int8")
            raise SystemExit("overlap+compression did not raise")
        except ValueError as e:
            assert "overlap" in str(e)

        sched = build_topology("base", 8, 1)
        plan = compile_schedule(sched)
        try:
            make_gossip_mixer(mesh, plan, "data", {"a": P("data")},
                              flatten=True,
                              compression=CompressionConfig(codec="int8"))
            raise SystemExit("flatten+compression did not raise")
        except ValueError as e:
            assert "flatten" in str(e)
        print("GUARDS_OK")
    """)
    assert "GUARDS_OK" in out
