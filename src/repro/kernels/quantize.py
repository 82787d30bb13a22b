"""Pallas TPU kernel: blockwise int8 quantization for checkpoint compression.

The paper's roofline is storage bandwidth: every checkpoint byte rides the
host→PFS link. Quantizing optimizer moments (bf16/f32 → int8 + per-row fp32
scales) halves/quarters flush volume at negligible compute cost — but the
quantize pass itself must not become a host bottleneck, hence a fused
absmax+scale+round kernel tiled for VMEM.

Layout: input is viewed as (rows, LANE_COLS) with one quantization group per
row; LANE_COLS is a multiple of 128 (VPU lane width). ROW_BLK=8 is the row
alignment of the packed wire format (``quant_codec.packed_rows``).

Tiling (what the TPU compiler accepts): each grid step moves a
(BLOCK_ROWS, LANE_COLS) block through VMEM and walks it in SLAB_ROWS-row
slabs, so every int8 store covers whole (32, 128) int8 tiles. The grid is
``cdiv(R, BLOCK_ROWS)``: the last block may run past the array, and Pallas
drops the rows it writes there (rows are independent, so the rows it reads
there never reach a kept output). Scales leave the kernel as an (R, 1)
column block, whose last dim equals the array's; the wrappers take the
public (R,) vector from it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLK = 8
LANE_COLS = 512     # 4 × 128 lanes per row-group
SLAB_ROWS = 32      # int8 sublane tile: one inner-loop iteration
BLOCK_ROWS = 512    # rows per grid step (1 MiB of f32 input)


def _quotient_guess(a, scale):
    """The backend's f32 division: within an ulp or two of the quotient."""
    return a / scale


def round_div(x, scale):
    """``round(x / scale)`` (half to even) of the correctly rounded f32
    quotient, as numpy computes it, on any backend. ``scale`` > 0, normal.

    A TPU's f32 division can miss the correctly rounded quotient by an ulp,
    which moves ``round()`` at half-integers (14 of 16 M elements of a
    normal tensor on a v5e). So the quotient ``t`` is only a guess here:
    the choice between ``k = floor(t)`` and ``k + 1`` is made against the
    half-integer ``h = k + 0.5`` from ``d = |x| - h * scale``, which is
    exact in f32 wherever the choice is close (``scale`` is split into two
    12-bit halves and ``h`` has at most 9 significant bits). The correctly
    rounded quotient equals ``h`` exactly when ``d`` lies in h's rounding
    interval, ``[-u_lo / 2, u / 2] * scale`` with ``u`` the ulp above ``h``
    and ``u_lo`` the one below (``u / 2`` at ``h = 0.5``); ties at ``h`` go
    to the even neighbour.
    """
    i32, f32 = jnp.int32, jnp.float32
    a = jnp.abs(x)
    k = jnp.floor(_quotient_guess(a, scale))
    h = k + 0.5
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(scale, i32) & i32(-4096), f32)
    d = (a - h * hi) - h * (scale - hi)
    expo = jax.lax.bitcast_convert_type(h, i32) & i32(0x7F800000)
    half_u = jax.lax.bitcast_convert_type(expo - i32(24 << 23), f32)
    half_u_lo = jnp.where(k == 0, half_u * 0.5, half_u)
    up = d > half_u * scale
    down = d < -(half_u_lo * scale)
    even = k + (k - 2.0 * jnp.floor(k * 0.5))
    q = jnp.where(up, k + 1.0, jnp.where(down, k, even))
    return jnp.where(x < 0, -q, q)


def quant_rows(x):
    """Shared per-row quantize math: (rows, C) -> (int8 q, f32 scales
    (rows, 1)).

    Row-independent, so any tiling of the row axis gives identical bits —
    the quantize kernel, the fused quantize+fingerprint kernel
    (kernels/fingerprint.py) and the jnp oracle all call this.
    """
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # multiply by the f32 reciprocal (not a / 127.0): XLA strength-reduces
    # constant divides to reciprocal multiplies, so spelling it out keeps
    # compiled and eager (oracle) paths bit-identical at round-half points
    scale = jnp.where(absmax > 0, absmax * jnp.float32(1.0 / 127.0), 1.0)
    q = jnp.clip(round_div(x, scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def slab_rows(block_rows: int) -> int:
    """Inner-loop slab: whole int8 tiles when the block holds them, else
    the whole (small, full-array) block in one step."""
    return SLAB_ROWS if block_rows % SLAB_ROWS == 0 else block_rows


def for_slabs(block_rows: int, body, init=None):
    """Run ``body(row0, carry)`` over the block's slabs (row0 aligned)."""
    sr = slab_rows(block_rows)

    def step(i, carry):
        return body(pl.multiple_of(i * sr, sr), carry)
    return jax.lax.fori_loop(0, block_rows // sr, step, init)


def _quant_kernel(x_ref, q_ref, s_ref):
    sr = slab_rows(x_ref.shape[0])

    def body(r0, carry):
        q, s = quant_rows(x_ref[pl.ds(r0, sr), :])
        q_ref[pl.ds(r0, sr), :] = q
        s_ref[pl.ds(r0, sr), :] = s
        return carry
    for_slabs(x_ref.shape[0], body)


def _dequant_kernel(q_ref, s_ref, o_ref):
    sr = slab_rows(q_ref.shape[0])

    def body(r0, carry):
        q = q_ref[pl.ds(r0, sr), :].astype(jnp.float32)
        s = s_ref[pl.ds(r0, sr), :]
        o_ref[pl.ds(r0, sr), :] = (q * s).astype(o_ref.dtype)
        return carry
    for_slabs(q_ref.shape[0], body)


def quantize_blocks(x, *, interpret: bool = False):
    """x: (R, LANE_COLS) — R % ROW_BLK == 0. Returns (int8 q, f32 scales)."""
    R, C = x.shape
    assert C == LANE_COLS and R % ROW_BLK == 0, (R, C)
    br = min(R, BLOCK_ROWS)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(R, br),),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return q, s.reshape(R)


def dequantize_blocks(q, scales, out_dtype=jnp.bfloat16, *,
                      interpret: bool = False):
    R, C = q.shape
    assert C == LANE_COLS and R % ROW_BLK == 0
    br = min(R, BLOCK_ROWS)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(R, br),),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
    )(q, scales.reshape(R, 1).astype(jnp.float32))
