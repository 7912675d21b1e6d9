"""How the harness reaches the program under test: its configuration
object, built from a configuration file and checked key by key by the
file's architecture module."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


def program_config(model: dict, arch):
    """The program's ``ArchConfig`` for a configuration file: its
    ``program.arch`` entry with ``program.overrides`` applied to the
    configuration and ``program.layer`` to each layer of its pattern,
    refused by ``arch.check`` where it differs from the file or has a
    mechanism the architecture's reference does not compute."""
    from repro.configs import get_config

    prog = model["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]),
                              **prog.get("overrides", {}))
    cfg = dataclasses.replace(cfg, pattern=tuple(
        dataclasses.replace(s, **prog.get("layer", {})) for s in cfg.pattern))
    arch.check(cfg, model)
    return cfg


def dtype_of(model: dict):
    return jnp.dtype(model["torch_dtype"])
