"""Compile the transfer path's device programs for a TPU v5e, without one.

The TPU compiler is installed next to JAX, and compiles for a chip that
is described rather than attached: every kernel the weight-update path
can dispatch is lowered and compiled here for one chip of a ``v5e:2x2``
topology at real transfer-unit sizes — a 1.88 GB unit (one TP-2 slice of
llama3-8b's layer-stacked FFN weights in bf16) and a 156 MB unit (the
paper's 9B shard in 64 units, ``configs/paper_workloads.py``). The
compiler refuses here what the chip would refuse: unsupported Mosaic
ops, VMEM overuse, and programs that do not fit the chip's HBM. Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until it
exits.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.checksum.kernel import checksum_words
from repro.kernels.checksum.ops import tensor_checksum
from repro.kernels.quant.fused import dequant_gather
from repro.kernels.quant.kernel import quantize_rows
from repro.kernels.quant.ref import quantize_ref
from repro.kernels.repack.kernel import GATHER_WINDOW, gather_bytes
from repro.transfer.codec import INT8_ROW_LEN

#: bytes of one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 1024**3

#: transfer-unit sizes in bytes of bf16 payload
UNIT_BYTES = {
    "llama3_8b_tp2_ffn": 32 // 2 * 4096 * 14336 * 2,  # 1,879,048,192
    "paper_9b_unit": 10 * 10**9 // 64,  # 156,250,000
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert used <= V5E_HBM_BYTES, f"{used / 1e9:.2f} GB exceeds one v5e chip"
    return compiled


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("unit", sorted(UNIT_BYTES))
def test_checksum_words_compiles(one_chip, unit):
    words = -(-UNIT_BYTES[unit] // 4)
    compiled = _compile(checksum_words, _spec((words,), jnp.uint32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "shape",
    [(16, 4096, 14336), (64128, 4096), (2048, 128256), (4096,)],
    ids=["ffn_slice", "embed_slice", "head_slice", "norm"],
)
def test_tensor_checksum_compiles_without_a_copy(one_chip, shape):
    """Verifying a landed llama3-8b TP-2 slice in HBM reads it in place:
    a copy of a 1.88 GB slice beside an 8 GB shard would not fit."""
    compiled = _compile(tensor_checksum, _spec(shape, jnp.bfloat16, one_chip))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("unit", sorted(UNIT_BYTES))
def test_quantize_rows_compiles(one_chip, unit):
    rows = -(-UNIT_BYTES[unit] // 2 // INT8_ROW_LEN)
    x = _spec((rows, INT8_ROW_LEN), jnp.float32, one_chip)
    compiled = _compile(quantize_rows, x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("unit", sorted(UNIT_BYTES))
def test_quantize_ref_compiles(one_chip, unit):
    """The int8 codec's encode: ``jax.jit(quantize_ref)`` on f32 rows."""
    rows = -(-UNIT_BYTES[unit] // 2 // INT8_ROW_LEN)
    _compile(quantize_ref, _spec((rows, INT8_ROW_LEN), jnp.float32, one_chip))


@pytest.mark.parametrize("unit", sorted(UNIT_BYTES))
def test_dequant_gather_compiles(one_chip, unit):
    """The fused reshard decode of one bf16 unit: whole quantized rows
    plus the sentinel row in, one output window's element map."""
    rows = -(-UNIT_BYTES[unit] // 2 // INT8_ROW_LEN) + 1
    _compile(
        lambda q, s, i: dequant_gather(
            q, s, i, row_len=INT8_ROW_LEN, out_dtype="bfloat16"
        ),
        _spec((rows * INT8_ROW_LEN,), jnp.int8, one_chip),
        _spec((rows,), jnp.float32, one_chip),
        _spec((GATHER_WINDOW,), jnp.int32, one_chip),
    )


@pytest.mark.parametrize("unit", sorted(UNIT_BYTES))
def test_gather_bytes_compiles(one_chip, unit):
    """The raw reshard repack of one unit: staging plus its zero byte in,
    one output window's byte map."""
    _compile(
        gather_bytes,
        _spec((UNIT_BYTES[unit] + 1,), jnp.uint8, one_chip),
        _spec((GATHER_WINDOW,), jnp.int32, one_chip),
    )

