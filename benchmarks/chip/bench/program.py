"""How the harness reaches the program under test: its configuration
object, built from a configuration file and checked key by key."""
from __future__ import annotations

import dataclasses
import inspect

import jax.numpy as jnp


def program_config(model: dict):
    """The program's ``ArchConfig`` for a configuration file: its
    ``program.arch`` entry with ``program.overrides`` applied to the
    configuration and ``program.layer`` to each layer of its pattern,
    refused where any key differs from the file's published-style keys."""
    from repro.configs import get_config
    from repro.models.layers import rmsnorm

    prog = model["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    cfg = dataclasses.replace(cfg, pattern=tuple(
        dataclasses.replace(s, **prog.get("layer", {})) for s in cfg.pattern))
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
        "rms_norm_eps": inspect.signature(rmsnorm).parameters["eps"].default,
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
    return cfg


def dtype_of(model: dict):
    return jnp.dtype(model["torch_dtype"])
