"""AOT compiles of the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present. These tests hand it the
kernels at the widths chip_smoke.py runs (a 64 MiB fp32 tensor, the
256 KiB delta chunk) and require the Pallas custom call in the result.
They catch what interpret mode cannot: blocks not aligned to the TPU's
tiling and operations Mosaic has no lowering for.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. All such tests stay in this one file so one worker holds them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.delta import DEFAULT_CHUNK_BYTES
from repro.kernels import fingerprint as fpk
from repro.kernels.quantize import (LANE_COLS, dequantize_blocks,
                                    quantize_blocks)

ROWS = (64 << 20) // 4 // LANE_COLS          # 64 MiB of fp32
CHUNK_LANES = DEFAULT_CHUNK_BYTES // fpk.LANE_BYTES
N_CHUNKS = (64 << 20) // DEFAULT_CHUNK_BYTES


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
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(sh):
    """name -> (function, argument shapes) at chip_smoke.py's widths."""
    return {
        "quantize_blocks": (
            quantize_blocks, (_sds((ROWS, LANE_COLS), jnp.float32, sh),)),
        "dequantize_blocks": (
            dequantize_blocks, (_sds((ROWS, LANE_COLS), jnp.int8, sh),
                                _sds((ROWS,), jnp.float32, sh))),
        "fingerprint_chunks": (
            fpk.fingerprint_chunks,
            (_sds((N_CHUNKS, CHUNK_LANES), jnp.uint32, sh),
             _sds((N_CHUNKS, 1), jnp.uint32, sh))),
        "quantize_fingerprint_blocks": (
            lambda x: fpk.quantize_fingerprint_blocks(x, DEFAULT_CHUNK_BYTES),
            (_sds((ROWS, LANE_COLS), jnp.float32, sh),)),
    }


@pytest.mark.parametrize("name", ["quantize_blocks", "dequantize_blocks",
                                  "fingerprint_chunks",
                                  "quantize_fingerprint_blocks"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, args = _kernels(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [8, 504, ROWS + 8])
def test_ragged_quantize_compiles_for_v5e(rows, one_chip,
                                          no_persistent_cache):
    """Row counts the packed format allows (multiples of 8): a block that
    is the whole small array, and a last block that runs past the end."""
    x = _sds((rows, LANE_COLS), jnp.float32, one_chip)
    compiled = jax.jit(quantize_blocks).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
