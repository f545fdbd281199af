"""Compile the relocation codec for a described TPU v5e chip.

Interpret mode on CPU checks what the kernels compute; only the TPU
compiler can say whether Mosaic accepts them (tile-aligned slices,
in-kernel bit widths, SMEM and VMEM budgets).  These tests lower the
kernels and the composite exchange at the shapes ``chip_smoke.py``
drives — 1 KiB f32 rows, 1 KiB bf16 rows and 8-byte uint8 rows over
64 (src, dest) pairs × 4096 slots — for a chip that is described, not
attached.  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.transport import DeviceTransport
from repro.kernels import reloc_codec as rc

PAIRS, SLOTS, PLACES = 64, 4096, 8
ROWS = 32768                # rows of one place's chunk matrix


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the TPU compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent
    # cache but can never be read back here: keep it out
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    # a byte view of 32-bit data on a TPU pads its minor dimension many
    # times over: scratch beyond the program's own in/out bytes means
    # such a view crept into the codec
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes)
    return compiled.as_text()


@pytest.mark.parametrize("dtype,k", [(jnp.float32, 256),
                                     (jnp.bfloat16, 512),
                                     (jnp.uint8, 8)])
def test_encode_pack_compiles_for_v5e(one_chip, dtype, k):
    width = max(k * jnp.dtype(dtype).itemsize, 8)
    fn = rc._encode_pack_call(PAIRS, SLOTS, width, ROWS, k, dtype, False)
    text = _compile(fn, _spec(one_chip, (ROWS, k), dtype),
                    _spec(one_chip, (PAIRS * SLOTS,), jnp.int32),
                    _spec(one_chip, (PAIRS * SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [8, 1024])
def test_pack_rows_compiles_for_v5e(one_chip, width):
    arena = ROWS * width // 4          # words
    fn = rc._pack_rows_call(PAIRS, SLOTS, width, arena, False)
    text = _compile(fn, _spec(one_chip, (arena,), jnp.uint32),
                    _spec(one_chip, (PAIRS * SLOTS,), jnp.int32),
                    _spec(one_chip, (PAIRS * SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype,nbytes,width", [(jnp.float32, 1024, 1024),
                                                (jnp.bfloat16, 1024, 1024),
                                                (jnp.uint8, 8, 8)])
def test_decode_rows_compiles_for_v5e(one_chip, dtype, nbytes, width):
    fn = rc._decode_call(SLOTS, width // 4, nbytes, jnp.dtype(dtype), False)
    text = _compile(fn, _spec(one_chip, (SLOTS, width // 4), jnp.uint32))
    assert "tpu_custom_call" in text


def test_composite_exchange_compiles_for_v5e(one_chip):
    # the XLA composite path: capacity covers a sender's whole fan-out
    S = (PLACES - 1) * SLOTS
    S = 1 << (S - 1).bit_length()
    fn = DeviceTransport()._exchange_fn(PLACES, S, 1024)
    compiled = fn.lower(_spec(one_chip, (PLACES, S, 1024), jnp.uint8),
                        _spec(one_chip, (PLACES, PLACES), jnp.int32)
                        ).compile()
    assert compiled.memory_analysis() is not None
