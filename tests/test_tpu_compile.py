"""Compile rehearsals of the main-path Pallas kernels for a TPU v5e.

Each case lowers one kernel at granite-8b widths for a *described*
v5e chip (no chip attached) and checks that the TPU compiler accepts it
and emits a Mosaic ``tpu_custom_call``.  Interpret-mode tests cannot see
tiling or memory-space refusals; these can, at no chip time.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, and under
several pytest workers an import-time call would make the workers
collect different tests.  Keep every such compile in this one file.
"""
from __future__ import annotations

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_dsgd import fused_dsgd_pallas
from repro.kernels.gossip_mix import gossip_mix_slots_pallas
from repro.kernels.ops import KernelConfig
from repro.kernels.quantized_gossip import quantize_ef_pallas

# granite-8b published widths (repro/configs/granite_8b.py)
D_MODEL, D_FF, H, KV, HD = 4096, 14336, 32, 8, 128
NATIVE = KernelConfig(backend="pallas")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the executable"
    return text


def test_flash_attention_batched_prefill(one_chip):
    """B > 1 is the case whose per-batch SMEM operands were refused."""
    B, T = 2, 2048
    q = _sds(one_chip, (B, H, T, HD), jnp.bfloat16)
    kv = _sds(one_chip, (B, KV, T, HD), jnp.bfloat16)
    _compile_text(lambda q, k, v: flash_attention_pallas(q, k, v), q, kv, kv)


def test_sdpa_decode_verify_window(one_chip):
    """The speculative verify window: ragged per-request positions."""
    B, Tq, S = 8, 5, 1024
    q = _sds(one_chip, (B, Tq, H, HD), jnp.bfloat16)
    kv = _sds(one_chip, (B, S, KV, HD), jnp.bfloat16)
    pos = _sds(one_chip, (B,), jnp.int32)
    _compile_text(
        lambda q, k, v, p: ops.sdpa_decode(q, k, v, q_start=p,
                                           k_valid_len=p + Tq,
                                           config=NATIVE),
        q, kv, kv, pos)


@pytest.mark.parametrize("tq", [1, 5])
def test_paged_sdpa(one_chip, tq):
    B, ps, maxp = 8, 16, 34
    pages = _sds(one_chip, (B * maxp + 1, ps, KV, HD), jnp.bfloat16)
    q = _sds(one_chip, (B, tq, H, HD), jnp.bfloat16)
    table = _sds(one_chip, (B, maxp), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)
    _compile_text(
        lambda q, k, v, t, p: ops.paged_sdpa(q, k, v, t, q_start=p,
                                             k_valid_len=p + tq,
                                             config=NATIVE),
        q, pages, pages, table, pos)


@pytest.mark.parametrize("tq", [1, 5])
def test_paged_sdpa_layer_scan(one_chip, tq):
    """The serving cell's decode shapes (qwen1.5-4b, 8 slots, 72 pages of
    16 a slot, 577 pages) inside a scan over the stacked layer pools, as
    the model runs it: the fresh rows are written into the layer's pool,
    then the layer attends.  One kernel a layer, and the pool is read in
    its own layout: no transpose or copy of it into (P, KV, ps, D)."""
    L, B, ps, maxp, P, heads, hd = 40, 8, 16, 72, 577, 20, 128
    pools = _sds(one_chip, (L, P, ps, heads, hd), jnp.bfloat16)
    q = _sds(one_chip, (B, tq, heads, hd), jnp.bfloat16)
    table = _sds(one_chip, (B, maxp), jnp.int32)
    pos = _sds(one_chip, (B,), jnp.int32)

    def decode(q, kp, vp, table, pos):
        at = pos[:, None] + jnp.arange(tq)
        page = jnp.take_along_axis(table, at // ps, axis=1)

        def layer(x, kv):
            k = kv[0].at[page, at % ps].set(x)
            v = kv[1].at[page, at % ps].set(x)
            y = ops.paged_sdpa(x, k, v, table, q_start=pos,
                               k_valid_len=pos + tq, config=NATIVE)
            return y, (k, v)

        return jax.lax.scan(layer, q, (kp, vp))

    text = _compile_text(decode, q, pools, pools, table, pos)
    assert "while(" in text, "the layer scan was unrolled"
    kernels = re.findall(r"%paged_flash_attention_pallas(?:\.\d+)? = [^\n]*"
                         r"custom-call\(", text)
    assert len(kernels) == 1, kernels
    assert f"bf16[{P},{heads},{ps},{hd}]" not in text


def test_fused_dsgd_mlp_leaf(one_chip):
    leaf = _sds(one_chip, (D_MODEL, D_FF), jnp.bfloat16)
    _compile_text(lambda x, u, g: fused_dsgd_pallas(x, u, g, 0.9, 0.01),
                  leaf, leaf, leaf)


def test_gossip_mix_three_slots(one_chip):
    leaf = _sds(one_chip, (D_MODEL, D_FF), jnp.float32)
    w = _sds(one_chip, (3,), jnp.float32)
    _compile_text(lambda a, b, c, w: gossip_mix_slots_pallas((a, b, c), w),
                  leaf, leaf, leaf, w)


def test_quantize_ef_int8(one_chip):
    chunk = 256   # CompressionConfig.chunk default
    rows = D_MODEL * D_FF // chunk
    x = _sds(one_chip, (rows, chunk), jnp.float32)
    key = _sds(one_chip, (), jnp.uint32)
    _compile_text(
        lambda x, e, k: quantize_ef_pallas(x, e, k, 0, fmt="int8"),
        x, x, key)
