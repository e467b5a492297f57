"""Compile-only checks of the serving kernels for a TPU v5e.

The TPU compiler compiles for a described ``v5e:2x2`` topology without a
chip attached, so these tests refuse what the chip's compiler would
refuse (block shapes off the (8, 128) tiling, dtypes Mosaic cannot load,
operand layouts that do not match XLA's) at the shapes the engine passes
for bert_base / gpt2_small (H = 12, dh = 64, arena length 128, buckets 64
and 128). Nothing runs, so nothing here says anything about results or
times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.memo_attention.ops import memo_attention
from repro.kernels.nn_search.ops import nn_search

B, H, DH, L, N = 8, 12, 64, 128, 1024     # bert_base / gpt2_small widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


@pytest.mark.parametrize("S", [64, 128])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("codec", ["f16", "int8"])
def test_memo_attention_compiles_for_v5e(one_chip, codec, causal, S):
    """The engine's kernel-mode call: q/k/v at the bucket length S, the
    device arena at its calibration length L (DeviceDB keeps float16 as
    its int16 bits), per-sequence lengths for varlen batches."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qkv = sds((B, S, H, DH), jnp.float32)
    idx = sds((B,), jnp.int32)
    if codec == "f16":
        def fn(q, k, v, db, hit_idx, hit, lengths):
            return memo_attention(q, k, v, db, hit_idx, hit,
                                  lengths=lengths, causal=causal,
                                  impl="pallas", interpret=False)
        _compile(fn, qkv, qkv, qkv, sds((N, H, L, L), jnp.int16),
                 idx, idx, idx)
    else:
        def fn(q, k, v, codes, scales, hit_idx, hit, lengths):
            return memo_attention(q, k, v, codes, hit_idx, hit,
                                  db_scales=scales, lengths=lengths,
                                  causal=causal, impl="pallas",
                                  interpret=False)
        _compile(fn, qkv, qkv, qkv, sds((N, H, L, L), jnp.int8),
                 sds((N, H, L), jnp.int16), idx, idx, idx)


def test_memo_attention_short_bucket_does_not_copy_the_arena(one_chip):
    """A bucket shorter than the arena pads q/k/v up to the arena length
    instead of slicing the (N, H, L, L) DB. The bound, in bytes, is the
    smallest whole-arena copy: the int16 arena sliced to the bucket."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S = 64
    qkv = sds((B, S, H, DH), jnp.float32)
    idx = sds((B,), jnp.int32)

    def fn(q, k, v, db, hit_idx, hit):
        return memo_attention(q, k, v, db, hit_idx, hit, causal=False,
                              impl="pallas", interpret=False)
    compiled = jax.jit(fn).lower(qkv, qkv, qkv,
                                 sds((N, H, L, L), jnp.int16),
                                 idx, idx).compile()
    sliced_arena = N * H * S * S * jnp.dtype(jnp.int16).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < sliced_arena


@pytest.mark.parametrize("queries", [8, 1000])
@pytest.mark.parametrize("norms", [False, True])
def test_nn_search_compiles_for_v5e(one_chip, norms, queries):
    """The device index's search: a (N, 128) float32 table, with and
    without the cached per-row norms, for one batch of queries and for
    more queries than one block."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, table, dn):
        return nn_search(q, table, db_norms=dn if norms else None,
                         interpret=False)
    _compile(fn, sds((queries, 128), jnp.float32),
             sds((4096, 128), jnp.float32), sds((4096,), jnp.float32))
