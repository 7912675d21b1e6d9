"""The train step's named scopes reach the compiled program: its HLO op
names carry ``forward``, ``update`` and ``gossip`` in every branch of
``make_train_step`` (plain, overlapped per group, compressed), and the
backward keeps JAX's transpose and remat markers, so the profiler's
trace can split a step's device time by layer."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.dist.steps import make_train_step, node_stack_specs
from repro.launch.mesh import make_mesh
from repro.models import model as M

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(**kw) -> list[str]:
    cfg = dataclasses.replace(
        get_config("granite-8b"), num_blocks=2, d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=128)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    b = make_train_step(cfg, mesh, topology="base", k=1,
                        param_dtype=jnp.bfloat16, remat=True, **kw)
    p = node_stack_specs(M.param_specs(cfg, jnp.bfloat16), 1)
    o = jax.eval_shape(b.method.init, p)
    batch = {k: jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    hlo = b.step_fn.lower(p, o, batch, jax.ShapeDtypeStruct(
        (), jnp.int32)).compile().as_text()
    return _OP_NAME.findall(hlo)


@pytest.mark.parametrize("kw", [{}, {"overlap": True},
                                {"compression": "int8"}],
                         ids=["plain", "overlap", "int8"])
def test_train_step_op_names_carry_scopes(kw):
    names = _op_names(**kw)
    fwd = [n for n in names if "/forward" in n or "(forward)" in n]
    assert any("transpose(" not in n for n in fwd), "no forward op"
    assert any("transpose(jvp(forward))" in n for n in names), "no backward"
    assert any("rematted_computation" in n for n in names), "no remat"
    assert any("/update/" in n and "/gossip/" not in n for n in names)
    assert any("/update/gossip/" in n for n in names), "no gossip op"
