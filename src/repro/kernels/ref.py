"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .quantize import round_div


def quantize_blocks_ref(x):
    """x: (R, C) -> (int8 (R, C), f32 scales (R,)); one group per row."""
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1)
    # reciprocal multiply, matching the kernel (see _quant_kernel)
    scale = jnp.where(absmax > 0, absmax * jnp.float32(1.0 / 127.0), 1.0)
    q = jnp.clip(round_div(x, scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_blocks_ref(q, scales, out_dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scales[:, None].astype(jnp.float32)
            ).astype(out_dtype)


def fingerprint_chunks_ref(lanes, lengths):
    """Oracle for kernels.fingerprint.fingerprint_chunks.

    lanes: (n_chunks, CL) uint32; lengths: (n_chunks, 1) uint32 byte
    lengths of each chunk's digest domain -> (n_chunks, 4) uint32. One
    dot_general instead of the kernel's per-chunk multiply-sum — exact
    mod-2^32 arithmetic makes the association order irrelevant.
    """
    from .fingerprint import _LEN, _weights_jnp
    d = jax.lax.dot_general(lanes.astype(jnp.uint32),
                            _weights_jnp(lanes.shape[1]),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.uint32)
    return d + (lengths.reshape(-1, 1).astype(jnp.uint32)
                * jnp.asarray(_LEN, jnp.uint32))


def quantize_fingerprint_blocks_ref(x, chunk_bytes):
    """Oracle for kernels.fingerprint.quantize_fingerprint_blocks:
    quantize (R, LANE_COLS) rows and digest the int8 q-stream on the
    ``chunk_bytes`` grid. Returns (q, scales, digests)."""
    from .fingerprint import _digest_lane_stream, lanes_u32
    q, s = quantize_blocks_ref(x)
    nbytes = q.shape[0] * q.shape[1]
    d = _digest_lane_stream(lanes_u32(q.reshape(-1)), nbytes, chunk_bytes)
    return q, s, d


def rglru_scan_ref(a, b):
    """First-order linear recurrence h_t = a_t * h_{t-1} + b_t, h_0 = 0.

    Uses jax.lax.associative_scan — the XLA path the kernel replaces.
    """
    def comb(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl
    _, h = jax.lax.associative_scan(comb, (a.astype(jnp.float32),
                                           b.astype(jnp.float32)), axis=1)
    return h
