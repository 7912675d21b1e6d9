"""The plain reference against the program's own float32 path at test
size: the same model, loss and Base-(k+1) rounds."""
import json

import jax
import jax.numpy as jnp
import numpy as np

from conftest import DATA

from bench import program, reference, spec
from bench import weights as W

STD = {"matrix": 0.05, "norm": 0.1, "bias": 0.05}
dense = spec.load_arch("dense_decoder")


def setup(name):
    from repro.models import model as M
    m = json.loads((DATA / name).read_text())
    m = dict(m, torch_dtype="float32")
    cfg = program.program_config(m, dense)
    key = W.seed_key_data(12345)
    specs = M.param_specs(cfg, jnp.float32)
    params = W.make_tree(key, specs, STD)
    return m, cfg, params


def test_loss_matches_the_program():
    from repro.kernels.ops import KernelConfig
    from repro.models import model as M
    for name in ("tiny-dense.json", "tiny-dense-bias.json"):
        m, cfg, params = setup(name)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, m["vocab_size"], (2, 24)).astype(np.int32)
        labels = np.roll(toks, -1, 1)
        labels[:, -1] = -100
        with jax.default_matmul_precision("highest"):
            want, _ = M.loss_fn(cfg, params, {"tokens": toks, "labels": labels},
                                kernel_config=KernelConfig(backend="ref"))
        named = W.named_leaves(params)
        s, c = reference.nll_sum(dense, named, toks, labels, m, "f32")
        np.testing.assert_allclose(float(s / c), float(want), rtol=2e-6)


def test_logits_match_the_program_prefill():
    from repro.kernels.ops import KernelConfig
    from repro.models import model as M
    m, cfg, params = setup("tiny-dense-bias.json")
    toks = np.random.default_rng(1).integers(
        0, m["vocab_size"], (2, 16)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = M.prefill(cfg, params, {"tokens": toks}, 16, jnp.float32,
                               kernel_config=KernelConfig(backend="ref"))
    outer, layers = reference.split_params(dense, W.named_leaves(params), m)
    x = dense.embed(toks, outer, m)
    for i, w in enumerate(layers):
        x = dense.layer(x, w, m, i, "f32")
    got = dense.head(x[:, -1:], outer, m, "f32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_base_graph_rounds_are_the_program_schedule():
    from repro.topology import as_schedule, spec_from_cli
    for n, k in ((4, 1), (8, 1), (9, 2), (1, 1)):
        mine = reference.base_graph_rounds(n, k)
        sched = as_schedule(spec_from_cli("base", n=n, k=k))
        ts = sched.as_topology_schedule()
        theirs = [np.asarray(ts.W(r)) for r in range(len(sched))]
        if n == 1:
            continue
        assert len(mine) == len(theirs)
        for a, b in zip(mine, theirs):
            np.testing.assert_allclose(a, b)
    # after every round the nodes hold the exact mean
    prod = np.linalg.multi_dot(reference.base_graph_rounds(8, 1))
    np.testing.assert_allclose(prod, np.full((8, 8), 1 / 8))


def test_fp8_control_rounds_its_operands():
    a = jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)
    exact = reference.einsum("ij,jk->ik", a, a, "f32")
    low = reference.einsum("ij,jk->ik", a, a, "fp8")
    err = float(jnp.max(jnp.abs(low - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-4 < err < 0.1
