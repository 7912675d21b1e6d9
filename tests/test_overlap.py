"""Gossip/backward overlap (dist.steps make_train_step(overlap=True)):
the per-group update+gossip chains must produce BIT-IDENTICAL params and
method state to the sequential whole-tree path — overlap changes the
schedule, never the numbers.

Needs >1 device, so each case runs in a subprocess with the virtual-mesh
flag set before jax imports (same pattern as tests/test_dist.py).  The
device count honours REPRO_TEST_DEVICES so the multihost CI lane's
workflow_dispatch matrix ({2, 8, 32}) drives the same tests at other
mesh sizes.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))


def _run(body: str):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={_DEVICES}")
        DEVICES = {_DEVICES}
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh

        def make_mesh_and_n():
            model = 2 if DEVICES % 2 == 0 and DEVICES >= 4 else 1
            mesh = make_mesh((DEVICES // model, model),
                             ("data", "model"))
            return mesh, DEVICES // model
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def _bitexact_body(method: str, extra: str = "",
                   kernel_cfg: str = "None") -> str:
    return f"""
        from repro.configs import get_config
        from repro.dist.steps import make_train_step
        from repro.models import model as M
        from repro.optim.decentralized import make_method

        cfg = get_config("granite-8b").reduced()
        mesh, n = make_mesh_and_n()
        params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)

        def mk_batch(step):
            kk = jax.random.fold_in(jax.random.PRNGKey(7), step)
            toks = jax.random.randint(kk, (n, 2, 16), 0, cfg.vocab_size)
            labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-100)
            return {{"tokens": toks, "labels": labels}}

        kcfg = {kernel_cfg}
        params_n = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0,
            params)
        outs = []
        for overlap in (False, True):
            bundle = make_train_step(cfg, mesh, topology="base", k=1,
                                     method_name={method!r}, eta=0.05,
                                     param_dtype=jnp.float32, remat=False,
                                     overlap=overlap,
                                     kernel_config=kcfg {extra})
            # overlap is recorded on the bundle (degenerate 1-node gossip
            # downgrades it, which only happens when the mesh has no node
            # axis)
            assert bundle.overlap == (overlap and n > 1), bundle.overlap
            method = make_method({method!r}, kernel_config=kcfg)
            pn, op = params_n, method.init(params_n)
            for step in range(3):
                pn, op, loss = bundle.step_fn(pn, op, mk_batch(step),
                                              jnp.int32(step))
            outs.append((pn, op))
        (p0, s0), (p1, s1) = outs
        for a, b in ((p0, p1), (s0, s1)):
            la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
            assert len(la) == len(lb)
            for x, y in zip(la, lb):
                assert np.array_equal(np.asarray(x), np.asarray(y)), \\
                    (x.shape, x.dtype)
        print("BITEXACT_OK", {method!r})
    """


def test_overlap_bit_exact_dsgdm():
    out = _run(_bitexact_body("dsgdm"))
    assert "BITEXACT_OK" in out


def test_overlap_bit_exact_gradient_tracking():
    """Two mixes per step (x and the tracker y) both split per group."""
    out = _run(_bitexact_body("gt"))
    assert "BITEXACT_OK" in out


def test_overlap_bit_exact_pallas_forced():
    """The fused gossip-combine + fused DSGD kernels (interpret mode)
    take the per-group path too and stay bit-identical to the
    sequential fused step."""
    out = _run(_bitexact_body(
        "dsgdm",
        kernel_cfg="__import__('repro.kernels.ops', fromlist=['x'])"
                   ".KernelConfig(backend='pallas', interpret=True)"))
    assert "BITEXACT_OK" in out


def test_overlap_matches_dense_simulation():
    """Overlap-enabled distributed step vs the dense W(r) @ X simulation
    (the PR-4/5 oracle) — same tolerance as the sequential parity test
    in tests/test_dist.py."""
    out = _run("""
        from repro.configs import get_config
        from repro.core.graphs import build_topology
        from repro.dist.steps import make_train_step
        from repro.models import model as M
        from repro.optim.decentralized import make_method

        cfg = get_config("granite-8b").reduced()
        mesh, n = make_mesh_and_n()
        params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)

        def mk_batch(step):
            kk = jax.random.fold_in(jax.random.PRNGKey(7), step)
            toks = jax.random.randint(kk, (n, 2, 16), 0, cfg.vocab_size)
            labels = jnp.roll(toks, -1, axis=2).at[:, :, -1].set(-100)
            return {"tokens": toks, "labels": labels}

        bundle = make_train_step(cfg, mesh, topology="base", k=1,
                                 method_name="dsgdm", eta=0.05,
                                 param_dtype=jnp.float32, remat=False,
                                 overlap=True)
        params_n = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0.0,
            params)
        method = make_method("dsgdm")
        pn, op = params_n, method.init(params_n)
        for step in range(3):
            pn, op, loss = bundle.step_fn(pn, op, mk_batch(step),
                                          jnp.int32(step))

        sched = build_topology("base", n, 1)
        sim_pn, sim_state = params_n, method.init(params_n)
        loss_one = lambda p, b: M.loss_fn(cfg, p, b)[0]
        grad_fn = jax.vmap(jax.grad(loss_one))
        for step in range(3):
            b = mk_batch(step)
            g = grad_fn(sim_pn, b)
            sim_pn, sim_state = method.step(sim_pn, g, sim_state,
                                            jnp.asarray(sched.W(step)),
                                            0.05)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(pn),
                                  jax.tree.leaves(sim_pn)))
        print("MAXERR", err)
        assert err < 2e-4, err
        print("SIM_PARITY_OK")
    """)
    assert "SIM_PARITY_OK" in out
