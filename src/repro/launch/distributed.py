"""Multi-process runtime bring-up: ``jax.distributed.initialize`` from
env or CLI, one place.

A P-process x D-device deployment (P hosts in production; P local
processes rehearsing on a laptop/CI runner via
``scripts/launch_multiprocess.sh``) is described by four values:

  coordinator address   REPRO_COORDINATOR_ADDRESS   --coordinator
  process count         REPRO_NUM_PROCESSES         --num-processes
  process id            REPRO_PROCESS_ID            --process-id
  local device count    REPRO_LOCAL_DEVICE_COUNT    --local-devices

CLI flags override env; env alone is enough (the launch script only
exports variables).  ``initialize()`` is idempotent — a second call with
the same config is a no-op, a different config raises — and single-
process configs (num_processes == 1, the default) skip the coordination
service entirely, so every existing single-process entry point can call
it unconditionally.

Backend reality, pinned by tests/test_distributed_runtime.py: on the CPU
backend the coordination service, process/device enumeration, and
*local*-device collectives all work, but cross-process computations are
not implemented (XLA raises "Multiprocess computations aren't
implemented on the CPU backend").  The P x D rehearsal therefore
validates bring-up, global device visibility, and per-process compute;
cross-process gossip executes on TPU/GPU backends, and its single-host
stand-in — the 8-virtual-device mesh of the ``multihost`` CI lane —
exercises the identical collective code paths in one process.
"""
from __future__ import annotations

import argparse
import os
from dataclasses import dataclass

from repro.launch import env as env_mod


@dataclass(frozen=True)
class DistributedConfig:
    coordinator_address: str | None = None
    num_processes: int = 1
    process_id: int = 0
    local_device_count: int | None = None

    def __post_init__(self):
        if self.num_processes < 1:
            raise ValueError(f"num_processes must be >= 1, got "
                             f"{self.num_processes}")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(f"process_id {self.process_id} not in "
                             f"[0, {self.num_processes})")
        if self.num_processes > 1 and not self.coordinator_address:
            raise ValueError("multi-process config needs a coordinator "
                             "address (REPRO_COORDINATOR_ADDRESS or "
                             "--coordinator)")


def config_from_env(environ=None) -> DistributedConfig:
    """Read the REPRO_* variables; absent ones keep single-process
    defaults."""
    e = os.environ if environ is None else environ

    def geti(key):
        v = e.get(key)
        return int(v) if v not in (None, "") else None

    ld = geti("REPRO_LOCAL_DEVICE_COUNT")
    return DistributedConfig(
        coordinator_address=e.get("REPRO_COORDINATOR_ADDRESS") or None,
        num_processes=geti("REPRO_NUM_PROCESSES") or 1,
        process_id=geti("REPRO_PROCESS_ID") or 0,
        local_device_count=ld)


def add_distributed_args(ap: argparse.ArgumentParser) -> None:
    """Attach the standard multi-process flags to a launcher parser."""
    g = ap.add_argument_group("multi-process runtime")
    g.add_argument("--coordinator", default=None,
                   help="coordinator address host:port "
                        "(env REPRO_COORDINATOR_ADDRESS)")
    g.add_argument("--num-processes", type=int, default=None,
                   help="total process count (env REPRO_NUM_PROCESSES)")
    g.add_argument("--process-id", type=int, default=None,
                   help="this process's id (env REPRO_PROCESS_ID)")
    g.add_argument("--local-devices", type=int, default=None,
                   help="fake host devices for THIS process "
                        "(env REPRO_LOCAL_DEVICE_COUNT)")


def config_from_args(args, environ=None) -> DistributedConfig:
    """CLI flags override env; unset flags fall through to env."""
    base = config_from_env(environ)
    return DistributedConfig(
        coordinator_address=(args.coordinator
                             if getattr(args, "coordinator", None)
                             is not None else base.coordinator_address),
        num_processes=(args.num_processes
                       if getattr(args, "num_processes", None) is not None
                       else base.num_processes),
        process_id=(args.process_id
                    if getattr(args, "process_id", None) is not None
                    else base.process_id),
        local_device_count=(args.local_devices
                            if getattr(args, "local_devices", None)
                            is not None else base.local_device_count))


_ACTIVE: DistributedConfig | None = None


def initialize(cfg: DistributedConfig | None = None) -> bool:
    """Bring this process into the runtime described by ``cfg`` (env when
    None).  Returns True iff the multi-process coordination service was
    started (False for plain single-process configs).  Idempotent per
    process: re-initialising with the same config is a no-op; a
    conflicting config raises RuntimeError.
    """
    global _ACTIVE
    cfg = config_from_env() if cfg is None else cfg
    if _ACTIVE is not None:
        if cfg == _ACTIVE:
            return _ACTIVE.num_processes > 1
        raise RuntimeError(f"distributed runtime already initialised with "
                           f"{_ACTIVE}, cannot re-initialise with {cfg}")
    if cfg.local_device_count:
        # Must land before the first jax backend use in this process.
        env_mod.set_host_device_count(cfg.local_device_count, strict=True)
    if cfg.num_processes > 1:
        import jax
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator_address,
            num_processes=cfg.num_processes,
            process_id=cfg.process_id)
    _ACTIVE = cfg
    return cfg.num_processes > 1


def runtime_info() -> dict:
    """Process/device topology as seen by this process (post-init)."""
    import jax
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }


# ---------------------------------------------------------------------------
# smoke entry point (what scripts/launch_multiprocess.sh runs per process)
# ---------------------------------------------------------------------------

def _smoke(expect_processes: int | None, global_collective: bool) -> None:
    import jax
    import jax.numpy as jnp

    info = runtime_info()
    if expect_processes is not None \
            and info["process_count"] != expect_processes:
        raise SystemExit(f"expected {expect_processes} processes, runtime "
                         f"reports {info['process_count']}")
    # Per-process compute over the LOCAL devices: works on every backend.
    ld = jax.local_devices()
    mesh = jax.sharding.Mesh(ld, ("local",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jnp.arange(len(ld) * 4, dtype=jnp.float32).reshape(len(ld), 4)
    x = jax.device_put(x, NamedSharding(mesh, P("local")))
    total = float(jax.jit(jnp.sum)(x))
    want = float(sum(range(len(ld) * 4)))
    assert total == want, (total, want)
    line = (f"SMOKE_OK proc={info['process_index']}/"
            f"{info['process_count']} local={info['local_device_count']} "
            f"global={info['global_device_count']} local_sum={total:.0f}")
    if global_collective and info["process_count"] > 1:
        # Cross-process computation: documented to fail on the CPU
        # backend (module docstring) — only attempt when asked.
        from repro.launch.mesh import make_mesh
        gmesh = make_mesh((jax.device_count(),), ("data",))
        y = jax.make_array_from_callback(
            (jax.device_count(),), NamedSharding(gmesh, P("data")),
            lambda idx: jnp.ones((1,), jnp.float32))
        s = jax.jit(jnp.sum, out_shardings=NamedSharding(gmesh, P()))(y)
        line += f" global_sum={float(s):.0f}"
    print(line, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="multi-process bring-up smoke (per-process worker)")
    add_distributed_args(ap)
    ap.add_argument("--smoke", action="store_true",
                    help="run the bring-up smoke and exit")
    ap.add_argument("--expect-processes", type=int, default=None,
                    help="fail unless the runtime reports exactly this "
                         "many processes")
    ap.add_argument("--global-collective", action="store_true",
                    help="also attempt a cross-process computation "
                         "(requires a non-CPU backend)")
    args = ap.parse_args()
    cfg = config_from_args(args)
    multi = initialize(cfg)
    if args.smoke or not multi:
        _smoke(args.expect_processes, args.global_collective)


if __name__ == "__main__":
    main()
