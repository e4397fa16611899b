"""Every Pallas kernel a local backend can select compiles for a TPU v5e.

The chip is described, not attached: the TPU compiler lowers each kernel
at the paper CNN's payload width (n=70 clients padded to 72, ~1.66M fp32
params padded to the 2048-column chunk) and must emit a Mosaic
``tpu_custom_call``.  The batcher's device gather compiles at the same
cell's training set.  Nothing runs, so these say nothing about results or
time; the interpret-mode suites pin the results.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.data.loader import _gather, _lane_rows
from repro.fl.packing import QuantSpec
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas)
from repro.kernels.mixing import ops
from repro.kernels.mixing.fused import (aggregate_dequant_pallas,
                                        aggregate_pallas,
                                        mix_aggregate_dequant_pallas,
                                        mix_aggregate_pallas)

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
    ell = (sds((N, 9), jnp.int32), sds((N, 9)))
    fn = functools.partial(ops.mix_aggregate_grouped, chunk=CHUNK,
                           interpret=False)
    assert "tpu_custom_call" in _compiled_text(
        fn, ell, sds((N,)), sds(()), (sds((N, P_PAD)),))


def test_flash_attention_compiles_hd128(sds):
    fn = functools.partial(flash_attention_pallas, causal=True, window=None,
                           true_seq_k=2048, bq=128, bk=128, interpret=False)
    q = sds((1, 8, 2048, 128), jnp.bfloat16)
    kv = sds((1, 2, 2048, 128), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv)


def _named(kernel, *args):
    """The HLO instruction of the one Mosaic call that ``kernel`` makes,
    compiled inside a caller of another name."""
    def caller(*a):
        return kernel(*a)

    calls = [re.search(r"%([\w.-]+) = ", line).group(1)
             for line in _compiled_text(caller, *args).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    return calls[0].rsplit(".", 1)[0]


@pytest.mark.parametrize("op", ["aggregate", "mix_aggregate",
                                "aggregate_q", "mix_aggregate_q",
                                "flash_attention"])
def test_each_kernel_is_named_after_its_op(sds, op):
    """The trace finds a kernel by its own name (``pallas_call(name=)``),
    whatever function calls it."""
    p = 2 * CHUNK
    q = QuantSpec(storage="int8", block=BLOCK)
    xq = sds((N_PAD, q.stored_cols(p)), q.storage_dtype)
    s = sds((N_PAD, p // BLOCK))
    w, A, X = sds((8, N_PAD)), sds((N_PAD, N_PAD)), sds((N_PAD, p))
    kernels = {
        "aggregate": (functools.partial(aggregate_pallas, chunk=CHUNK,
                                        interpret=False), w, X),
        "mix_aggregate": (functools.partial(
            mix_aggregate_pallas, chunk=CHUNK, interpret=False), A, w, X),
        "aggregate_q": (functools.partial(
            aggregate_dequant_pallas, storage="int8", block=BLOCK,
            chunk=CHUNK, interpret=False), w, xq, s),
        "mix_aggregate_q": (functools.partial(
            mix_aggregate_dequant_pallas, storage="int8", block=BLOCK,
            chunk=CHUNK, interpret=False), A, w, xq, s),
        "flash_attention": (functools.partial(
            flash_attention_pallas, causal=True, window=None,
            true_seq_k=256, bq=128, bk=128, interpret=False),
            sds((1, 2, 256, 128), jnp.bfloat16),
            sds((1, 1, 256, 128), jnp.bfloat16),
            sds((1, 1, 256, 128), jnp.bfloat16)),
    }
    kernel, *args = kernels[op]
    assert _named(kernel, *args) == op


def test_batch_gather_reads_training_rows_in_place(sds):
    """At the CNN cell's 60,000 MNIST-shaped samples, the lane-padded rows
    keep the row-major layout, so the gather copies no part of the
    training set but the rows it draws."""
    width = _lane_rows(np.zeros((1, 28, 28, 1), np.float32)).shape[1]
    rows = sds((60_000, width))
    compiled = _gather.lower(rows, sds((60_000,), jnp.int32),
                             sds((70, 5, 32), jnp.int32),
                             shape=(28, 28, 1)).compile()
    assert f"f32[60000,{width}]{{1,0:T(8,128)}}" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 60_000 * 784
