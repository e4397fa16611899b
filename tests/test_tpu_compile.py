"""Every Pallas kernel a local backend can select compiles for a TPU v5e.

The chip is described, not attached: the TPU compiler lowers each kernel
at the paper CNN's payload width (n=70 clients padded to 72, ~1.66M fp32
params padded to the 2048-column chunk) and must emit a Mosaic
``tpu_custom_call``.  Nothing runs, so these say nothing about results or
time; the interpret-mode suites pin the results.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fl.packing import QuantSpec
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas)
from repro.kernels.mixing import ops
from repro.kernels.mixing.fused import (aggregate_dequant_pallas,
                                        aggregate_pallas,
                                        mix_aggregate_dequant_pallas,
                                        mix_aggregate_pallas)
from repro.kernels.mixing.mixing import mix_pallas

N, N_PAD = 70, 72
CHUNK = 2048
P_PAD = -(-1_663_370 // CHUNK) * CHUNK      # the CNN's packed payload
BLOCK = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory on one described v5e chip, with the
    persistent compile cache off (its entries cannot be read back
    without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_aggregate_compiles_at_cnn_width(sds):
    fn = functools.partial(aggregate_pallas, chunk=CHUNK, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, sds((8, N_PAD)), sds((N_PAD, P_PAD)))


def test_mix_compiles_at_cnn_width(sds):
    fn = functools.partial(mix_pallas, chunk=CHUNK, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, sds((N_PAD, N_PAD)), sds((N_PAD, P_PAD)))


def test_mix_aggregate_compiles_at_cnn_width(sds):
    fn = functools.partial(mix_aggregate_pallas, chunk=CHUNK,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, sds((N_PAD, N_PAD)), sds((8, N_PAD)), sds((N_PAD, P_PAD)))


@pytest.mark.parametrize("storage", ["int8", "int4", "fp8"])
def test_aggregate_dequant_compiles_at_cnn_width(sds, storage):
    q = QuantSpec(storage=storage, block=BLOCK)
    fn = functools.partial(aggregate_dequant_pallas, storage=storage,
                           block=BLOCK, chunk=CHUNK, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, sds((8, N_PAD)),
        sds((N_PAD, q.stored_cols(P_PAD)), q.storage_dtype),
        sds((N_PAD, P_PAD // BLOCK)))


@pytest.mark.parametrize("storage", ["int8", "int4", "fp8"])
def test_mix_aggregate_dequant_compiles_at_cnn_width(sds, storage):
    q = QuantSpec(storage=storage, block=BLOCK)
    fn = functools.partial(mix_aggregate_dequant_pallas, storage=storage,
                           block=BLOCK, chunk=CHUNK, interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, sds((N_PAD, N_PAD)), sds((8, N_PAD)),
        sds((N_PAD, q.stored_cols(P_PAD)), q.storage_dtype),
        sds((N_PAD, P_PAD // BLOCK)))


def test_sparse_mix_aggregate_compiles_at_cnn_width(sds):
    """The ELL mix is an XLA gather; its aggregate leg is the kernel."""
    idx, w = sds((N, 9), jnp.int32), sds((N, 9))
    fn = functools.partial(ops.sparse_mix_aggregate, chunk=CHUNK,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, idx, w, sds((N,)), sds(()), sds((N, P_PAD)))


def test_flash_attention_compiles_hd128(sds):
    fn = functools.partial(flash_attention_pallas, causal=True, window=None,
                           true_seq_k=2048, bq=128, bk=128, interpret=False)
    q = sds((1, 8, 2048, 128), jnp.bfloat16)
    kv = sds((1, 2, 2048, 128), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)
