"""A dense decoder whose attention RMS-normalizes each head's queries and
keys before RoPE (as Qwen3 and OLMo-2 do): an architecture the harness
takes as files alone.  The tests copy it into a checkout's ``archs/``,
where a configuration file finds it by its ``"reference"``.

Everything but the check, the layer's leaves and the layer is the dense
decoder's."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.reference import einsum, rmsnorm, rope

dense = spec.load_arch("dense_decoder")
outer_shapes, embed, head = dense.outer_shapes, dense.embed, dense.head
NORMS = ("attn/q_norm/scale", "attn/k_norm/scale")


def check(cfg, model: dict) -> None:
    if not cfg.qk_norm:
        raise ValueError(f"program config {cfg.name} has no q/k norm")
    dense.check(dataclasses.replace(cfg, qk_norm=False), model)


def layer_leaves(model: dict, i: int) -> dict:
    leaves = dense.layer_leaves(model, i)
    stacked, _, _ = leaves["ln1/scale"]
    prefix = stacked[:-len("ln1/scale")]
    for n in NORMS:
        leaves[n] = (prefix + n, i, (model["head_dim"],))
    return leaves


def layer(x, w, model: dict, i: int, precision: str):
    b, t, _ = x.shape
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    eps = model["rms_norm_eps"]
    pos = jnp.arange(t)

    def proj(a, name, heads):
        return einsum("btd,df->btf", a, w[name], precision).reshape(
            b, t, heads, hd)

    a = rmsnorm(x, w["ln1/scale"], eps)
    q = rope(rmsnorm(proj(a, "attn/wq/w", h), w["attn/q_norm/scale"], eps),
             pos, model["rope_theta"])
    k = rope(rmsnorm(proj(a, "attn/wk/w", kv), w["attn/k_norm/scale"], eps),
             pos, model["rope_theta"])
    v = proj(a, "attn/wv/w", kv)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = einsum("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(hd)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
               precision).reshape(b, t, h * hd)
    x = x + einsum("btf,fd->btd", o, w["attn/wo/w"], precision)
    m = rmsnorm(x, w["ln2/scale"], eps)
    g = jax.nn.silu(einsum("btd,df->btf", m, w["mlp/gate/w"], precision))
    u = einsum("btd,df->btf", m, w["mlp/up/w"], precision)
    return x + einsum("btf,fd->btd", g * u, w["mlp/down/w"], precision)


def forward_flops(model: dict, *, tokens: float, attended: float) -> float:
    """The dense decoder's count and the two norms' (square, mean,
    scale and gain: 4 an element) on every head's q and k."""
    norms = 4 * tokens * model["head_dim"] * (
        model["num_attention_heads"] + model["num_key_value_heads"])
    return (dense.forward_flops(model, tokens=tokens, attended=attended)
            + model["num_hidden_layers"] * norms)
