"""Multi-process bring-up, end to end: scripts/launch_multiprocess.sh
spawns P local processes x D virtual devices each, every process joins
the coordination service, sees the P*D global devices, and runs a
local-device computation.

Cross-process collectives are NOT exercised here — the CPU backend does
not implement multi-process computations (see the module docstring of
repro.launch.distributed); the 8-virtual-device single-process mesh in
tests/test_dist.py covers the collective code paths.  These tests pin
the bring-up layer itself.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "scripts", "launch_multiprocess.sh")


def _clean_env():
    env = dict(os.environ)
    for var in ("XLA_FLAGS", "REPRO_COORDINATOR_ADDRESS",
                "REPRO_NUM_PROCESSES", "REPRO_PROCESS_ID",
                "REPRO_LOCAL_DEVICE_COUNT"):
        env.pop(var, None)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    return env


def test_launch_script_two_procs_two_devices():
    """2 processes x 2 fake devices: both workers print SMOKE_OK with a
    4-device global view and the correct local shard sums."""
    r = subprocess.run(["bash", _SCRIPT, "-p", "2", "-d", "2"],
                       capture_output=True, text=True, env=_clean_env(),
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    oks = [ln for ln in r.stdout.splitlines() if "SMOKE_OK" in ln]
    assert len(oks) == 2, r.stdout
    procs = set()
    for ln in oks:
        fields = dict(f.split("=", 1) for f in ln.split()[1:])
        procs.add(fields["proc"])
        assert fields["local"] == "2"
        assert fields["global"] == "4"
        # sum(range(2*4)) = 28 on each process's local mesh
        assert fields["local_sum"] == "28"
    assert procs == {"0/2", "1/2"}


def test_launch_script_propagates_worker_failure():
    """A failing worker command must fail the whole launch."""
    r = subprocess.run(["bash", _SCRIPT, "-p", "2", "-d", "1", "--",
                        sys.executable, "-c", "import sys; sys.exit(3)"],
                       capture_output=True, text=True, env=_clean_env(),
                       timeout=600)
    assert r.returncode != 0


def test_single_process_initialize_honors_env_device_count():
    """initialize() with REPRO_LOCAL_DEVICE_COUNT set (single process,
    no coordinator) must yield that many local devices — the path every
    existing entry point takes when launched stand-alone."""
    devices = int(os.environ.get("REPRO_TEST_DEVICES", "8"))
    code = textwrap.dedent(f"""
        import os
        os.environ["REPRO_LOCAL_DEVICE_COUNT"] = "{devices}"
        from repro.launch.distributed import initialize, runtime_info
        assert initialize() is False          # single-process
        info = runtime_info()
        assert info["process_count"] == 1, info
        assert info["local_device_count"] == {devices}, info
        assert info["global_device_count"] == {devices}, info
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(({devices},), ("data",))
        x = jax.device_put(
            jnp.arange({devices}, dtype=jnp.float32),
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        assert float(jax.jit(jnp.sum)(x)) == sum(range({devices}))
        print("INIT_OK")
    """)
    env = _clean_env()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "INIT_OK" in r.stdout


def test_initialize_strict_when_jax_already_up():
    """Asking initialize() for a device count after jax has already
    built its backend must raise (strict), not silently run with the
    wrong mesh."""
    code = textwrap.dedent("""
        import jax
        jax.devices()                          # force backend init
        from repro.launch.distributed import (DistributedConfig,
                                              initialize)
        try:
            initialize(DistributedConfig(local_device_count=64))
        except RuntimeError as e:
            assert "no longer take effect" in str(e), e
            print("STRICT_OK")
        else:
            raise SystemExit("expected RuntimeError")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_clean_env(), timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "STRICT_OK" in r.stdout
