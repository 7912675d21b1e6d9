"""repro.launch.env + repro.launch.distributed config plumbing.

All in-process and single-device: these pin the XLA_FLAGS hygiene
(replace-not-append, idempotency, the post-init warning) and the
DistributedConfig env/CLI resolution — no subprocesses needed because
nothing here requires the flag to actually take effect.
"""
import argparse
import importlib
import os
import warnings

import pytest

from repro.launch import distributed
from repro.launch import env as env_mod
from repro.launch.distributed import (DistributedConfig, config_from_args,
                                      config_from_env)

FLAG = env_mod.HOST_DEVICE_FLAG


# ---------------------------------------------------------------------------
# set_xla_flag / host_device_count
# ---------------------------------------------------------------------------

def test_set_xla_flag_replaces_not_appends(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}=4 --xla_foo=1")
    env_mod.set_xla_flag(FLAG, 8)
    flags = os.environ["XLA_FLAGS"]
    assert flags.count(FLAG) == 1
    assert f"{FLAG}=8" in flags
    assert "--xla_foo=1" in flags          # unrelated flags survive


def test_set_xla_flag_none_removes_and_unsets(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}=4")
    env_mod.set_xla_flag(FLAG, None)
    assert "XLA_FLAGS" not in os.environ
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}=4 --xla_foo=1")
    env_mod.set_xla_flag(FLAG, None)
    assert os.environ["XLA_FLAGS"] == "--xla_foo=1"


def test_host_device_count_parses(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert env_mod.host_device_count() is None
    monkeypatch.setenv("XLA_FLAGS", f"--xla_foo=1 {FLAG}=32")
    assert env_mod.host_device_count() == 32


# ---------------------------------------------------------------------------
# set_host_device_count
# ---------------------------------------------------------------------------

def test_set_host_device_count_idempotent(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(env_mod, "_jax_backend_initialized", lambda: False)
    assert env_mod.set_host_device_count(8) is True
    once = os.environ["XLA_FLAGS"]
    assert env_mod.set_host_device_count(8) is True
    assert os.environ["XLA_FLAGS"] == once          # byte-identical
    assert once.count(FLAG) == 1
    # a different count replaces in place, never appends
    env_mod.set_host_device_count(4)
    assert os.environ["XLA_FLAGS"].count(FLAG) == 1
    assert env_mod.host_device_count() == 4


def test_set_host_device_count_rejects_nonpositive():
    with pytest.raises(ValueError):
        env_mod.set_host_device_count(0)


def test_post_init_warns_and_returns_false(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(env_mod, "_jax_backend_initialized", lambda: True)
    import jax
    have = jax.local_device_count()
    with pytest.warns(RuntimeWarning, match="no longer take effect"):
        assert env_mod.set_host_device_count(have + 1) is False
    # the env is still fixed up for child processes
    assert env_mod.host_device_count() == have + 1


def test_post_init_strict_raises(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(env_mod, "_jax_backend_initialized", lambda: True)
    import jax
    with pytest.raises(RuntimeError, match="no longer take effect"):
        env_mod.set_host_device_count(jax.local_device_count() + 1,
                                      strict=True)


def test_post_init_noop_when_already_effective(monkeypatch):
    """Asking for the count jax already runs with is not an error even
    after init — common when a launcher re-runs its own setup."""
    import jax
    have = jax.local_device_count()
    monkeypatch.setenv("XLA_FLAGS", f"{FLAG}={have}")
    monkeypatch.setattr(env_mod, "_jax_backend_initialized", lambda: True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # any warning -> failure
        assert env_mod.set_host_device_count(have) is True


def test_dryrun_import_is_idempotent(monkeypatch):
    """The historical bug: every import of repro.launch.dryrun appended
    another copy of the flag.  Re-importing now leaves exactly one."""
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        import repro.launch.dryrun as dryrun
        importlib.reload(dryrun)
        importlib.reload(dryrun)
    assert os.environ.get("XLA_FLAGS", "").count(FLAG) == 1


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_enable_compile_cache_placement(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX (no directory is set
    in code); unset, the cache goes to ``.jax_cache`` at the checkout
    root, the one path ``.gitignore`` lists."""
    import jax
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    sentinel = "/unset/by/test"
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        env_mod.enable_compile_cache()
        got = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env_dir is None:
        assert got == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        assert got == sentinel


# ---------------------------------------------------------------------------
# DistributedConfig resolution
# ---------------------------------------------------------------------------

def test_config_from_env_defaults():
    cfg = config_from_env(environ={})
    assert cfg == DistributedConfig()
    assert cfg.num_processes == 1 and cfg.process_id == 0
    assert cfg.coordinator_address is None
    assert cfg.local_device_count is None


def test_config_from_env_reads_repro_vars():
    cfg = config_from_env(environ={
        "REPRO_COORDINATOR_ADDRESS": "127.0.0.1:2222",
        "REPRO_NUM_PROCESSES": "4",
        "REPRO_PROCESS_ID": "3",
        "REPRO_LOCAL_DEVICE_COUNT": "2"})
    assert cfg.coordinator_address == "127.0.0.1:2222"
    assert cfg.num_processes == 4
    assert cfg.process_id == 3
    assert cfg.local_device_count == 2


def test_config_validation():
    with pytest.raises(ValueError, match="num_processes"):
        DistributedConfig(num_processes=0)
    with pytest.raises(ValueError, match="process_id"):
        DistributedConfig(coordinator_address="h:1", num_processes=2,
                          process_id=2)
    with pytest.raises(ValueError, match="coordinator"):
        DistributedConfig(num_processes=2, process_id=0)


def test_cli_overrides_env():
    ap = argparse.ArgumentParser()
    distributed.add_distributed_args(ap)
    args = ap.parse_args(["--process-id", "1", "--coordinator",
                          "cli:9999"])
    cfg = config_from_args(args, environ={
        "REPRO_COORDINATOR_ADDRESS": "env:1111",
        "REPRO_NUM_PROCESSES": "2",
        "REPRO_PROCESS_ID": "0"})
    assert cfg.coordinator_address == "cli:9999"    # CLI wins
    assert cfg.process_id == 1                      # CLI wins
    assert cfg.num_processes == 2                   # env fallthrough


def test_cli_defaults_fall_through_to_env():
    ap = argparse.ArgumentParser()
    distributed.add_distributed_args(ap)
    cfg = config_from_args(ap.parse_args([]), environ={})
    assert cfg == DistributedConfig()


# ---------------------------------------------------------------------------
# initialize() idempotency (single-process path only — in-process safe)
# ---------------------------------------------------------------------------

def test_initialize_idempotent_and_conflict(monkeypatch):
    monkeypatch.setattr(distributed, "_ACTIVE", None)
    cfg = DistributedConfig()
    assert distributed.initialize(cfg) is False     # single-process
    assert distributed._ACTIVE == cfg
    assert distributed.initialize(cfg) is False     # same cfg: no-op
    with pytest.raises(RuntimeError, match="already initialised"):
        distributed.initialize(DistributedConfig(
            coordinator_address="h:1", num_processes=2, process_id=0))


def test_initialize_reads_env_when_cfg_none(monkeypatch):
    monkeypatch.setattr(distributed, "_ACTIVE", None)
    for var in ("REPRO_COORDINATOR_ADDRESS", "REPRO_NUM_PROCESSES",
                "REPRO_PROCESS_ID", "REPRO_LOCAL_DEVICE_COUNT"):
        monkeypatch.delenv(var, raising=False)
    assert distributed.initialize() is False
    assert distributed._ACTIVE == DistributedConfig()


def test_runtime_info_keys():
    info = distributed.runtime_info()
    assert set(info) == {"process_index", "process_count",
                         "local_device_count", "global_device_count"}
    assert info["process_count"] >= 1
    assert info["global_device_count"] >= info["local_device_count"]
