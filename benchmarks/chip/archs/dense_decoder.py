"""Plain reference of a dense decoder: what the harness assumes about a
configuration file whose ``"reference"`` is ``"dense_decoder"``.

Written from the published descriptions, in float32 at the ``highest``
matmul precision (``precision="fp8"`` is the control), with no kernel,
cache or batching.  Token embedding; per layer ``x += Attn(RMSNorm(x))``
and ``x += MLP(RMSNorm(x))``; a final RMSNorm and the output head (the
embedding's transpose where tied).  RMSNorm's gain is stored as an
offset from 1 (gain = 1 + scale), the same function as a gain vector.
Attention is causal, with rotary embeddings (rotate-half form) on q and
k, grouped K/V heads and optional q/k/v biases.  The MLP is
``down(silu(gate x) * up x)``.

An architecture module gives ``check``, ``outer_shapes``,
``layer_leaves``, ``embed``, ``layer``, ``head`` and ``forward_flops``;
``bench/reference.py`` and ``bench/cost.py`` hold what every
architecture shares.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import einsum, rmsnorm, rope


def check(cfg, model: dict) -> None:
    """Raise where the program's ``ArchConfig`` differs from the file in
    a size, bias, tying, RoPE base, activation or RMSNorm epsilon, or has
    any mechanism this reference does not compute."""
    from repro.models.layers import rmsnorm as program_rmsnorm

    layer = cfg.pattern[0]
    have = {
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff,
        "vocab_size": cfg.vocab_size,
        "attention_bias": cfg.qkv_bias,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_theta": layer.rope_theta,
        "hidden_act": cfg.mlp_act,
        # the program's RMSNorm takes no epsilon from the configuration
        "rms_norm_eps": inspect.signature(
            program_rmsnorm).parameters["eps"].default,
    }
    wrong = {k: (v, model[k]) for k, v in have.items() if v != model[k]}
    plain = (len(cfg.pattern) == 1 and not cfg.prologue and layer.kind == "attn"
             and layer.ffn == "dense" and layer.window is None
             and cfg.moe is None and cfg.mla is None and not cfg.qk_norm
             and cfg.attn_softcap is None and cfg.final_softcap is None
             and cfg.attn_scale is None and not cfg.embed_scale
             and not cfg.post_norm and cfg.encoder is None and not cfg.mtp)
    if wrong or not plain:
        raise ValueError(f"program config {cfg.name} does not match the file: "
                         f"{wrong or 'not a plain dense decoder'}")


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------

def outer_shapes(model: dict) -> dict:
    """``{leaf name: shape}`` of the leaves outside the layers."""
    d, v = model["hidden_size"], model["vocab_size"]
    shapes = {"embed/table": (v, d), "final_norm/scale": (d,)}
    if not model["tie_word_embeddings"]:
        shapes["lm_head/w"] = (d, v)
    return shapes


def layer_leaves(model: dict, i: int) -> dict:
    """Layer ``i``'s leaves: ``{name in the layer: (the program's leaf
    name, index along its stacked axis or None, shape in the layer)}``.
    Every layer is one slice of the one stacked block."""
    d, h, kv, hd = (model["hidden_size"], model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    f = model["intermediate_size"]
    shapes = {"ln1/scale": (d,), "attn/wq/w": (d, h * hd),
              "attn/wk/w": (d, kv * hd), "attn/wv/w": (d, kv * hd),
              "attn/wo/w": (h * hd, d), "ln2/scale": (d,),
              "mlp/gate/w": (d, f), "mlp/up/w": (d, f), "mlp/down/w": (f, d)}
    if model["attention_bias"]:
        shapes.update({"attn/wq/b": (h * hd,), "attn/wk/b": (kv * hd,),
                       "attn/wv/b": (kv * hd,)})
    return {n: (W.STACK_PREFIX + "0/" + n, i, s) for n, s in shapes.items()}


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def embed(tokens, outer: dict, model: dict):
    return outer["embed/table"][tokens]


def _proj(x, w, name, precision):
    y = einsum("btd,df->btf", x, w[name + "/w"], precision)
    if name + "/b" in w:
        y = y + w[name + "/b"]
    return y


def layer(x, w, model: dict, i: int, precision: str):
    """Layer ``i`` over x: (B, T, D), positions 0..T-1, causal."""
    b, t, _ = x.shape
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    eps = model["rms_norm_eps"]
    pos = jnp.arange(t)
    a = rmsnorm(x, w["ln1/scale"], eps)
    q = _proj(a, w, "attn/wq", precision).reshape(b, t, h, hd)
    k = _proj(a, w, "attn/wk", precision).reshape(b, t, kv, hd)
    v = _proj(a, w, "attn/wv", precision).reshape(b, t, kv, hd)
    q = rope(q, pos, model["rope_theta"])
    k = rope(k, pos, model["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = einsum("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(hd)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum("bhqk,bkhd->bqhd", p, v, precision).reshape(b, t, h * hd)
    x = x + einsum("btf,fd->btd", o, w["attn/wo/w"], precision)
    m = rmsnorm(x, w["ln2/scale"], eps)
    g = jax.nn.silu(einsum("btd,df->btf", m, w["mlp/gate/w"], precision))
    u = einsum("btd,df->btf", m, w["mlp/up/w"], precision)
    return x + einsum("btf,fd->btd", g * u, w["mlp/down/w"], precision)


def head(x, outer: dict, model: dict, precision: str):
    """Final RMSNorm and the output head: logits over the vocabulary."""
    x = rmsnorm(x, outer["final_norm/scale"], model["rms_norm_eps"])
    out = outer["lm_head/w"] if "lm_head/w" in outer \
        else outer["embed/table"].T
    return einsum("...d,dv->...v", x, out, precision)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------

def forward_flops(model: dict, *, tokens: float, attended: float) -> float:
    """One forward pass over ``tokens`` new tokens, where ``attended`` is
    the number of (query, key) pairs summed over those tokens.  Follows
    ``repro.analysis.flops.forward_flops`` for a dense decoder
    (attention, gated MLP and the output head all counted)."""
    d, h, kv, hd = (model["hidden_size"], model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    layers = model["num_hidden_layers"]
    attn = 2 * tokens * d * (h + 2 * kv) * hd        # q, k, v projections
    attn += 2 * 2 * attended * h * hd               # scores + weighted sum
    attn += 2 * tokens * h * hd * d                 # output projection
    ffn = 6 * tokens * d * model["intermediate_size"]
    head_ = 2 * tokens * d * model["vocab_size"]
    return layers * (attn + ffn) + head_
