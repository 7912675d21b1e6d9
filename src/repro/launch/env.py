"""Centralized XLA_FLAGS management for every launcher and example.

Historically each entry point hand-rolled

    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_..."

which (a) APPENDS a fresh copy of the flag on every call/import — a
second import of ``repro.launch.dryrun`` used to leave two
``--xla_force_host_platform_device_count`` entries in the environment —
and (b) silently does nothing when jax already initialised its backend
before the mutation (XLA reads the variable once, at first backend
construction).  Both failure modes route through here now:

  * :func:`set_host_device_count` REPLACES any previous occurrence of
    the flag instead of appending (idempotent: calling it twice with the
    same count leaves the environment byte-identical), and
  * it detects an already-initialised jax backend and warns (or raises
    with ``strict=True``) instead of mutating an environment variable
    that can no longer take effect.

:func:`enable_compile_cache` is the one place that places JAX's
persistent compilation cache.

Nothing in this module imports jax at import time — importing it is
always safe, even before the flag dance.
"""
from __future__ import annotations

import os
import sys
import warnings

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _jax_backend_initialized() -> bool:
    """True iff jax is imported AND has already built a backend (at which
    point XLA_FLAGS edits are dead letters)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:
        # Defensive: if the private probe breaks on a future jax,
        # assume initialised — the warning is the safe direction.
        return True


def host_device_count() -> int | None:
    """The currently-requested fake host device count, or None."""
    for part in os.environ.get("XLA_FLAGS", "").split():
        if part.startswith(HOST_DEVICE_FLAG + "="):
            try:
                return int(part.split("=", 1)[1])
            except ValueError:
                return None
    return None


def set_xla_flag(flag: str, value: str | int | None) -> None:
    """Set ``flag=value`` in XLA_FLAGS, replacing (not appending to) any
    existing occurrence of ``flag``.  ``value=None`` removes the flag."""
    parts = [p for p in os.environ.get("XLA_FLAGS", "").split()
             if not (p == flag or p.startswith(flag + "="))]
    if value is not None:
        parts.append(f"{flag}={value}")
    if parts:
        os.environ["XLA_FLAGS"] = " ".join(parts)
    else:
        os.environ.pop("XLA_FLAGS", None)


def set_host_device_count(n: int, *, strict: bool = False) -> bool:
    """Request ``n`` fake host devices (CPU testing / CI virtual mesh).

    Returns True when the environment was (or already is) set so the
    flag will take effect; False when jax's backend pre-dates the call
    (the flag cannot apply to this process any more).  ``strict=True``
    raises in that case instead — use it from entry points whose whole
    run depends on the device count.

    Idempotent: repeated calls replace the flag in place; the historical
    append-on-every-import grew XLA_FLAGS without bound.
    """
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    if _jax_backend_initialized():
        import jax
        have = jax.local_device_count()
        if have == n and host_device_count() == n:
            return True  # already effective — nothing to do
        msg = (f"set_host_device_count({n}) called after jax initialised "
               f"its backend ({have} devices); XLA_FLAGS edits no longer "
               "take effect in this process. Set the count before the "
               "first jax use (or run in a subprocess, as tests/ do).")
        if strict:
            raise RuntimeError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=2)
        set_xla_flag(HOST_DEVICE_FLAG, n)   # still fix the env for children
        return False
    set_xla_flag(HOST_DEVICE_FLAG, n)
    return True


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.
    Entry points call it at start-up, never on import.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout (git-ignored): a fixed path, because the
    path is part of what the cache is keyed on, so a temporary or
    per-run directory would never hit."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(checkout, ".jax_cache"))
