"""Seeded weights: the program's tree and the reference draw the same
numbers under the same names."""
import json

import jax
import jax.numpy as jnp
import numpy as np

from conftest import DATA

from bench import program, reference, spec
from bench import weights as W

STD = {"matrix": 0.02, "norm": 0.1, "bias": 0.02}
dense = spec.load_arch("dense_decoder")


def model():
    return json.loads((DATA / "tiny-dense-bias.json").read_text())


def test_program_tree_names_are_the_reference_names():
    from repro.models import model as M
    m = model()
    specs = M.param_specs(program.program_config(m, dense), jnp.bfloat16)
    got = {n: tuple(a.shape) for n, a in W.named_leaves(specs).items()}
    assert got == reference.param_shapes(dense, m)


def test_stacked_leaves_equal_the_per_layer_draw():
    from repro.models import model as M
    m = model()
    key = W.seed_key_data(2**40 + 3)
    specs = M.param_specs(program.program_config(m, dense), jnp.bfloat16)
    tree = W.named_leaves(jax.jit(lambda k: W.make_tree(k, specs, STD))(key))
    for layer in range(m["num_hidden_layers"]):
        per = reference.draw_layer(dense, key, m, STD, layer,
                                   *reference.layer_tags(dense, m, layer))
        for n, a in per.items():
            np.testing.assert_array_equal(
                np.asarray(tree[W.STACK_PREFIX + "0/" + n][layer], np.float32),
                np.asarray(a))
    outer = reference.draw_outer(dense, key, m, STD)
    np.testing.assert_array_equal(
        np.asarray(tree["embed/table"], np.float32),
        np.asarray(outer["embed/table"]))


def test_seeds_differ_and_kinds_take_their_std():
    m = model()
    tags = reference.layer_tags(dense, m, 0)
    a = reference.draw_layer(dense, W.seed_key_data(1), m, STD, 0, *tags)
    b = reference.draw_layer(dense, W.seed_key_data(2), m, STD, 0, *tags)
    assert not np.array_equal(np.asarray(a["attn/wq/w"]),
                              np.asarray(b["attn/wq/w"]))
    zero = reference.draw_layer(dense, W.seed_key_data(1), m,
                                {"matrix": 0.02, "norm": 0.0, "bias": 0.0}, 0,
                                *tags)
    assert not np.asarray(zero["ln1/scale"]).any()
    assert not np.asarray(zero["attn/wq/b"]).any()
    assert 0.015 < float(jnp.std(a["mlp/up/w"])) < 0.025
