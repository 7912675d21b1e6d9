"""Checkpoint format v2: async writes, crash consistency, and
mesh-shape-agnostic restore.

Two groups:

* in-process tests (no marker) — crash consistency and the async
  writer's lifecycle, all on the default single device;
* ``multidevice`` subprocess tests — save under one virtual-mesh shape,
  restore under another (8 -> 4 -> 1 -> 8 with the default
  REPRO_TEST_DEVICES=8), asserting BITWISE equality of the gathered
  values including bfloat16 and exact-integer canaries.

Each mesh shape needs its own process because the virtual-device flag
must be set before jax initialises; the checkpoint directory is the
only thing the processes share.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, load_pytree, save_pytree
from repro.checkpoint import io as ckpt_io

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))


# ---------------------------------------------------------------------------
# in-process: async lifecycle + crash consistency
# ---------------------------------------------------------------------------

def _small_tree():
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "step": jnp.int32(2**25 + 1)}


def test_async_save_future_resolves_and_loads(tmp_path):
    ckpt = AsyncCheckpointer(str(tmp_path))
    fut = ckpt.save(_small_tree(), name="a")
    path = fut.result(timeout=60)
    assert os.path.isdir(path)
    ckpt.close()
    out = load_pytree(_small_tree(), str(tmp_path), name="a")
    assert np.array_equal(np.asarray(out["w"]),
                          np.asarray(_small_tree()["w"]))
    assert int(out["step"]) == 2**25 + 1


def test_wait_drains_multiple_pending_saves(tmp_path):
    ckpt = AsyncCheckpointer(str(tmp_path))
    for i in range(4):
        tree = {"w": jnp.full((2, 2), float(i)), "i": jnp.int32(i)}
        ckpt.save(tree, name=f"s{i}")
    ckpt.wait()
    for i in range(4):
        out = load_pytree({"w": jnp.zeros((2, 2)), "i": jnp.int32(0)},
                          str(tmp_path), name=f"s{i}")
        assert float(out["w"][0, 0]) == float(i)
        assert int(out["i"]) == i
    ckpt.close()


def test_manifest_is_written_last(tmp_path, monkeypatch):
    """The marker manifest is the commit point: when it is written, the
    shard payload and the per-process manifest must already be on disk
    in the staging dir."""
    order = []
    real = ckpt_io._write_manifest

    def spying(tmp_dir, fname, manifest):
        if fname == "manifest.json":
            assert os.path.exists(os.path.join(tmp_dir, "shards-p0.npz"))
            assert os.path.exists(os.path.join(tmp_dir,
                                               "manifest-p0.json"))
        order.append(fname)
        real(tmp_dir, fname, manifest)

    monkeypatch.setattr(ckpt_io, "_write_manifest", spying)
    save_pytree(_small_tree(), str(tmp_path), name="c")
    assert order[-1] == "manifest.json"


def test_crash_before_commit_leaves_no_loadable_checkpoint(tmp_path,
                                                           monkeypatch):
    """Sever the write at the commit point: the future re-raises, no
    final directory appears, and the loader refuses the name."""
    def boom(tmp_dir, fname, manifest):
        raise OSError("simulated crash mid-write")

    monkeypatch.setattr(ckpt_io, "_write_manifest", boom)
    ckpt = AsyncCheckpointer(str(tmp_path))
    fut = ckpt.save(_small_tree(), name="crashed")
    with pytest.raises(OSError, match="simulated crash"):
        fut.result(timeout=60)
    with pytest.raises(OSError, match="simulated crash"):
        ckpt.wait()
    ckpt._pool.shutdown(wait=True)
    assert not os.path.exists(str(tmp_path / "crashed"))
    with pytest.raises(FileNotFoundError):
        load_pytree(_small_tree(), str(tmp_path), name="crashed")


def test_stray_staging_dir_is_not_loadable(tmp_path):
    """A leftover .tmp-* staging dir (hard kill before rename) must not
    masquerade as a checkpoint."""
    stray = tmp_path / ".tmp-ckpt-deadbeef"
    stray.mkdir()
    (stray / "shards-p0.npz").write_bytes(b"partial")
    with pytest.raises(FileNotFoundError):
        load_pytree(_small_tree(), str(tmp_path), name="ckpt")


def test_missing_shard_file_is_detected(tmp_path):
    """Coverage check: a manifest whose shard payload vanished must not
    reassemble silently."""
    save_pytree(_small_tree(), str(tmp_path), name="gap")
    os.remove(str(tmp_path / "gap" / "shards-p0.npz"))
    with pytest.raises((FileNotFoundError, ValueError)):
        load_pytree(_small_tree(), str(tmp_path), name="gap")


def test_resave_same_name_swaps_atomically(tmp_path):
    save_pytree({"w": jnp.zeros((2,))}, str(tmp_path), name="latest")
    save_pytree({"w": jnp.ones((2,))}, str(tmp_path), name="latest")
    out = load_pytree({"w": jnp.zeros((2,))}, str(tmp_path),
                      name="latest")
    assert float(out["w"][0]) == 1.0
    # no .old-* husk left behind
    assert not [d for d in os.listdir(tmp_path) if ".old-" in d]


def test_bf16_roundtrip_single_device(tmp_path):
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 7),
                          dtype=jnp.bfloat16)
    save_pytree({"x": x}, str(tmp_path), name="bf")
    out = load_pytree({"x": jnp.zeros((5, 7), jnp.bfloat16)},
                      str(tmp_path), name="bf")
    assert out["x"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(out["x"]).view(np.uint16),
                          np.asarray(x).view(np.uint16))


# ---------------------------------------------------------------------------
# multidevice: save under one mesh shape, restore under another
# ---------------------------------------------------------------------------

# Deterministic tree both sides regenerate independently: node-stacked
# f32 params (sharded over the node axis when one exists), a replicated
# bf16 leaf, momentum-like nested state, and an int canary outside
# f32's exact range.
_TREE_SRC = """
def make_tree(n_nodes):
    k = jax.random.PRNGKey(11)
    return {
        "params": {
            "embed": jax.random.normal(k, (n_nodes, 16, 8), jnp.float32),
            "head": jax.random.normal(jax.random.fold_in(k, 1),
                                      (n_nodes, 8, 16), jnp.float32)},
        "opt": {"m": {
            "embed": jax.random.normal(jax.random.fold_in(k, 2),
                                       (n_nodes, 16, 8), jnp.float32),
            "head": jnp.zeros((n_nodes, 8, 16), jnp.float32)}},
        "scales": jax.random.normal(jax.random.fold_in(k, 3), (32,),
                                    jnp.bfloat16),
        "step": jnp.int32(2**25 + 1)}

def put(tree, mesh):
    ax = mesh.axis_names[0]
    def sh(leaf):
        spec = P(ax, *([None] * (leaf.ndim - 1))) \\
            if leaf.ndim >= 1 and leaf.shape[0] % mesh.devices.size == 0 \\
            and leaf.ndim == 3 else P()
        return jax.sharding.NamedSharding(mesh, spec)
    return jax.tree.map(lambda x: jax.device_put(x, sh(x)), tree)

def check_bitwise(got, n_nodes):
    want = make_tree(n_nodes)
    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(gl) == len(wl)
    for g, w in zip(gl, wl):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (g.dtype,
                                                           w.dtype)
        if g.dtype == jnp.bfloat16:
            g, w = g.view(np.uint16), w.view(np.uint16)
        assert np.array_equal(g, w), g.dtype
"""

def _run_with_devices(devices: int, body: str):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        DEVICES = {devices}
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.checkpoint import load_pytree, save_pytree
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(_TREE_SRC) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


_SAVE_BODY = """
    mesh = make_mesh((DEVICES,), ("nodes",))
    tree = put(make_tree({n_nodes}), mesh)
    path = save_pytree(tree, {d!r}, name="ck")
    import json
    m = json.load(open(os.path.join(path, "manifest.json")))
    print("SAVED", m["format_version"],
          len(m["leaves"]["params/embed"]["shards"]))
"""

_LOAD_BODY = """
    mesh = make_mesh((DEVICES,), ("nodes",))
    template = put(make_tree({n_nodes}), mesh)
    got = load_pytree(template, {d!r}, name="ck")
    check_bitwise(got, {n_nodes})
    # restored layout follows the template's committed shardings
    ax_sharded = [l for l in jax.tree.leaves(got)
                  if l.ndim == 3 and
                  not l.sharding.is_fully_replicated]
    assert (len(ax_sharded) > 0) == (DEVICES > 1), DEVICES
    print("RESTORE_OK", DEVICES)
"""


@pytest.mark.multidevice
def test_save_wide_restore_narrow_and_single(tmp_path):
    """Save on the full virtual mesh; restore on half the devices and on
    one device — bitwise-equal gathered trees each time."""
    n_nodes = _DEVICES
    d = str(tmp_path)
    out = _run_with_devices(_DEVICES,
                            _SAVE_BODY.format(n_nodes=n_nodes, d=d))
    assert "SAVED 2" in out
    for devices in sorted({max(1, _DEVICES // 2), 1}):
        out = _run_with_devices(devices,
                                _LOAD_BODY.format(n_nodes=n_nodes, d=d))
        assert f"RESTORE_OK {devices}" in out


@pytest.mark.multidevice
def test_save_narrow_restore_wide(tmp_path):
    """The reverse direction: a single-device save restores onto the
    full virtual mesh with node-axis sharding applied."""
    n_nodes = _DEVICES
    d = str(tmp_path)
    out = _run_with_devices(1, _SAVE_BODY.format(n_nodes=n_nodes, d=d))
    assert "SAVED 2" in out
    out = _run_with_devices(_DEVICES,
                            _LOAD_BODY.format(n_nodes=n_nodes, d=d))
    assert f"RESTORE_OK {_DEVICES}" in out


@pytest.mark.multidevice
def test_explicit_shardings_override_template(tmp_path):
    """load_pytree(shardings=...) lays leaves out per the explicit
    pytree even when the template leaves are uncommitted host arrays."""
    d = str(tmp_path)
    out = _run_with_devices(_DEVICES, _SAVE_BODY.format(
        n_nodes=_DEVICES, d=d) + """
    template = make_tree(DEVICES)   # uncommitted, no layout info
    shardings = jax.tree.map(
        lambda l: jax.sharding.NamedSharding(
            mesh, P("nodes", *([None] * (l.ndim - 1)))
            if l.ndim == 3 else P()), template)
    got = load_pytree(template, """ + repr(d) + """, name="ck",
                      shardings=shardings)
    check_bitwise(got, DEVICES)
    emb = got["params"]["embed"]
    assert not emb.sharding.is_fully_replicated
    assert len(emb.sharding.device_set) == DEVICES
    print("EXPLICIT_OK")
    """)
    assert "EXPLICIT_OK" in out
