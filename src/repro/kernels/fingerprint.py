"""Pallas chunk-fingerprint kernel + bit-identical host/oracle twins.

On-device dirty detection (DESIGN.md §14): the delta path's blake2b chunk
hash ran on the host, so every payload byte crossed the device→host link
just to discover it was clean. This module computes a per-chunk 128-bit
non-cryptographic digest (kind ``fp128``) *where the bytes already live*
— as a Pallas kernel on TPU, as one jitted XLA pass on other backends,
and as a vectorized numpy fallback for host-resident arrays — so the
delta diff can run before any D2H copy and only dirty chunks ever cross
the link.

Digest spec (``fp128`` / version 1) — chosen so one integer matmul
computes it and a TPU VPU can reproduce it (no 64-bit lanes on TPU):

  lanes     the chunk's bytes, zero-padded to a multiple of 4, viewed as
            little-endian uint32 words ``v_0 .. v_{L-1}``.
  weights   ``w_k[i] = fmix32((i+1) ^ SEED_k) | 1`` for four fixed seeds
            (murmur3's finalizer; forcing odd weights makes any
            single-lane difference unconditionally detectable, since an
            odd multiplier is invertible mod 2^32).
  digest    ``d_k = (sum_i v_i * w_k[i] + n * LEN_k)  mod 2^32`` where
            ``n`` is the chunk's byte length (folds ragged tails apart
            from zero-padded full chunks). Serialized as 32 hex chars
            (``%08x`` per accumulator) — same width as blake2b-128.

All three implementations are bit-identical by construction: uint32
multiply-accumulate is exact mod 2^32 in any association order, so a
numpy ``lanes @ W`` matmul, an XLA ``dot_general`` and the kernel's
per-chunk multiply-sum agree word for word (property-tested in
tests/test_fingerprint.py). The host path is ~1 memory pass (a
``(chunks, lanes) @ (lanes, 4)`` uint32 matmul) — ~3x cheaper than
the per-chunk blake2b loop it replaces on the same buffer, and ~5x
vs the PR-5 recorded hash pass (which also paid per-chunk Python
slicing).

The fused ``quantize_fingerprint_blocks`` kernel extends the int8
quantize kernel (kernels/quantize.py) so quant + digest of the quantized
stream is one pass over the shard in VMEM: the digest domain there is
the *packed* representation (int8 q rows then f32 scales), which is what
actually gets written — see core/delta.py for the packed-payload chunk
grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core import trace
from .quantize import (LANE_COLS, SLAB_ROWS, for_slabs, quant_rows,
                       quantize_blocks, slab_rows)

DIGEST_KIND = "fp128"
LANE_BYTES = 4

# four independent weight streams (xxhash/murmur-lineage odd constants)
_SEEDS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)
# per-accumulator length-fold multipliers (odd, so length always lands)
_LEN = (0x165667B1, 0xD3A2646D, 0x9E3779B9, 0x27D4EB2F)
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def lanes_per_chunk(chunk_bytes: int) -> int:
    return -(-chunk_bytes // LANE_BYTES)


def _fmix32_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> 16
    x = x * np.uint32(_M1)
    x ^= x >> 13
    x = x * np.uint32(_M2)
    x ^= x >> 16
    return x


def _fmix32_jnp(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> 16)
    return x


@functools.lru_cache(maxsize=64)
def _weights_host(n_lanes: int) -> np.ndarray:
    """(n_lanes, 4) uint32 weight matrix, cached per lane count.

    Weights depend only on the lane index, so ``_weights_host(a)`` is a
    prefix of ``_weights_host(b)`` for a < b — ragged tail chunks reuse
    the full-chunk matrix truncated to their lane count."""
    i = np.arange(1, n_lanes + 1, dtype=np.uint32)
    return np.stack(
        [_fmix32_np(i ^ np.uint32(s)) | np.uint32(1) for s in _SEEDS],
        axis=1)


def _weights_jnp(n_lanes: int):
    i = jnp.arange(1, n_lanes + 1, dtype=jnp.uint32)
    return jnp.stack(
        [_fmix32_jnp(i ^ jnp.uint32(s)) | jnp.uint32(1) for s in _SEEDS],
        axis=1)


# ------------------------------------------------------------------ host path
def fingerprint_chunks_host(payload: np.ndarray,
                            chunk_bytes: int) -> np.ndarray:
    """Digest every chunk of a host payload: (n_chunks, 4) uint32.

    One uint32 matmul over the full-chunk body (zero-copy view when the
    grid is lane-aligned), a short padded loop for the ragged tail —
    ~1 memory pass total, which is the point of replacing blake2b.
    """
    payload = np.ascontiguousarray(payload).reshape(-1).view(np.uint8)
    n = payload.nbytes
    nc = -(-n // chunk_bytes) if n else 0
    out = np.zeros((nc, 4), np.uint32)
    if nc == 0:
        return out
    cl = lanes_per_chunk(chunk_bytes)
    w = _weights_host(cl)
    body = n // chunk_bytes if chunk_bytes % LANE_BYTES == 0 else 0
    if body:
        lanes = payload[:body * chunk_bytes].view(np.uint32) \
            .reshape(body, cl)
        np.matmul(lanes, w, out=out[:body])
    for j in range(body, nc):
        pos = j * chunk_bytes
        m = min(chunk_bytes, n - pos)
        lanes_n = -(-m // LANE_BYTES)
        buf = np.zeros(lanes_n * LANE_BYTES, np.uint8)
        buf[:m] = payload[pos:pos + m]
        out[j] = buf.view(np.uint32) @ w[:lanes_n]
    lens = np.full(nc, chunk_bytes, np.uint32)
    lens[-1] = n - (nc - 1) * chunk_bytes
    out += lens[:, None] * np.asarray(_LEN, np.uint32)
    return out


def digest_hex(d) -> str:
    """One digest row -> 32 hex chars (blake2b-128 width)."""
    return "%08x%08x%08x%08x" % tuple(int(v) for v in d)


def digests_hex(d: np.ndarray) -> list[str]:
    return [digest_hex(row) for row in np.asarray(d)]


def digest_bytes(data) -> str:
    """fp128 of one standalone chunk (domain = exactly these bytes).

    Matches the per-chunk digest whenever the chunk's digest domain is
    its written byte span — used by the store scrubber to content-verify
    fp128 references that carry no CRC."""
    a = np.frombuffer(data, np.uint8) if not isinstance(data, np.ndarray) \
        else data.reshape(-1).view(np.uint8)
    if a.nbytes == 0:
        return digest_hex(np.zeros(4, np.uint32))
    return digest_hex(fingerprint_chunks_host(a, a.nbytes)[0])


# -------------------------------------------------------------- device lanes
def lanes_u32(flat):
    """1-D device array (itemsize 1/2/4) -> little-endian uint32 lanes.

    Built arithmetically from same-width bitcasts: XLA's
    ``bitcast_convert_type`` is only byte-order-defined at equal widths,
    so wider lanes are assembled as ``b0 | b1<<8 | ...`` — bit-identical
    to the host's ``view(np.uint32)`` on little-endian layouts. The bytes
    of a lane are taken with strided slices, not a reshape to a minor dim
    of 2 or 4, which a TPU pads to 128 lanes (64x the memory)."""
    isz = np.dtype(flat.dtype).itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if isz not in (1, 2):
        raise ValueError(f"unsupported itemsize {isz} for device fingerprint")
    per = LANE_BYTES // isz
    u = jax.lax.bitcast_convert_type(flat, (jnp.uint8, jnp.uint16)[isz - 1]) \
        .astype(jnp.uint32)
    if u.shape[0] % per:
        u = jnp.pad(u, (0, per - u.shape[0] % per))
    out = u[0::per]
    for j in range(1, per):
        out = out | (u[j::per] << (8 * isz * j))
    return out


def _digest_lane_stream(lanes, nbytes: int, chunk_bytes: int):
    """Trace-time core: flat lane vector -> (n_chunks, 4) uint32 digests.

    Requires ``chunk_bytes % 4 == 0`` so per-chunk lane domains tile the
    global lane stream (the delta planner falls back to the host path
    otherwise)."""
    assert chunk_bytes % LANE_BYTES == 0
    cl = chunk_bytes // LANE_BYTES
    nc = -(-nbytes // chunk_bytes)
    lanes = jnp.pad(lanes, (0, nc * cl - lanes.shape[0])).reshape(nc, cl)
    d = jax.lax.dot_general(lanes, _weights_jnp(cl),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.uint32)
    lens = jnp.full((nc,), chunk_bytes, jnp.uint32) \
        .at[-1].set(nbytes - (nc - 1) * chunk_bytes)
    return d + lens[:, None] * jnp.asarray(_LEN, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("chunk_bytes",))
def _fp_device_jit(flat, chunk_bytes: int):
    nbytes = flat.shape[0] * np.dtype(flat.dtype).itemsize
    return _digest_lane_stream(lanes_u32(flat), nbytes, chunk_bytes)


@functools.partial(jax.jit, static_argnames=("chunk_bytes",))
def _fp_prep_jit(flat, chunk_bytes: int):
    """Kernel prologue: lanes padded + reshaped to the chunk grid."""
    nbytes = flat.shape[0] * np.dtype(flat.dtype).itemsize
    cl = chunk_bytes // LANE_BYTES
    nc = -(-nbytes // chunk_bytes)
    lanes = lanes_u32(flat)
    lanes = jnp.pad(lanes, (0, nc * cl - lanes.shape[0])).reshape(nc, cl)
    lens = jnp.full((nc, 1), chunk_bytes, jnp.uint32) \
        .at[-1, 0].set(nbytes - (nc - 1) * chunk_bytes)
    return lanes, lens


def fingerprint_digests(flat, chunk_bytes: int) -> np.ndarray:
    """Device dispatch: digest a 1-D device array's byte image.

    TPU runs the Pallas kernel over the lane grid; other backends run the
    jitted oracle (one XLA uint32 matmul). Either way only the
    (n_chunks, 4) digest table — 16 bytes per 256 KiB chunk — comes back
    to the host."""
    if jax.default_backend() == "tpu":
        lanes, lens = _fp_prep_jit(flat, chunk_bytes)
        return np.asarray(fingerprint_chunks(lanes, lens))
    return np.asarray(_fp_device_jit(flat, chunk_bytes))


# ------------------------------------------------------------- Pallas kernels
# TPU tiling: every block's last two dims are (8, 128)-aligned or whole, no
# reduction runs on unsigned integers, and no scalar is bitcast. Weights and
# products are formed in uint32, bitcast to int32 as vectors and summed in
# int32: wrapping int32 addition has the same bits as uint32 addition mod
# 2^32. The length fold and the bitcast back to uint32 run outside the
# kernel, in ``_finish_digests``.
FP_ROWS = 8          # chunks per fingerprint_chunks grid step
FP_SLAB = 512        # lanes per inner iteration


def _weights(pos, seed: int):
    return _fmix32_jnp(pos ^ jnp.uint32(seed)) | jnp.uint32(1)


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _fold_rows(x):
    """(r, c) -> (8, c) by adding aligned 8-row groups (r % 8 == 0)."""
    if x.shape[0] % 8:
        return x
    out = x[0:8]
    for r in range(8, x.shape[0], 8):
        out = out + x[r:r + 8]
    return out


def _put_columns(cols):
    """Four (r, 1) int32 columns -> one (r, 4) block, no lane concat."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], 4), 1)
    out = jnp.zeros(lane.shape, jnp.int32)
    for k, c in enumerate(cols):
        out = jnp.where(lane == k, c, out)
    return out


def _finish_digests(acc, lengths):
    """Kernel int32 sums (n, 4) + byte lengths (n, 1) -> uint32 digests."""
    return (jax.lax.bitcast_convert_type(acc, jnp.uint32)
            + lengths.reshape(-1, 1).astype(jnp.uint32)
            * jnp.asarray(_LEN, jnp.uint32))


def _fp_kernel(lanes_ref, d_ref):
    rows, cl = lanes_ref.shape                     # rows chunks of cl lanes
    slab = FP_SLAB if cl % FP_SLAB == 0 else cl

    def body(j, accs):
        c0 = pl.multiple_of(j * slab, slab)
        v = lanes_ref[:, pl.ds(c0, slab)]
        pos = jax.lax.broadcasted_iota(jnp.uint32, (1, slab), 1) \
            + (c0 + 1).astype(jnp.uint32)
        return tuple(a + _i32(v * _weights(pos, s))
                     for a, s in zip(accs, _SEEDS))

    zero = jnp.zeros((rows, slab), jnp.int32)
    accs = jax.lax.fori_loop(0, cl // slab, body, (zero,) * 4)
    d_ref[...] = _put_columns([jnp.sum(a, axis=1, keepdims=True)
                               for a in accs])


def fingerprint_chunks(lanes, lengths, *, interpret: bool = False):
    """lanes: (n_chunks, CL) uint32; lengths: (n_chunks, 1) uint32 byte
    length of each chunk's digest domain. Returns (n_chunks, 4) uint32.
    FP_ROWS chunks per grid step: eight 256 KiB chunks are a 2 MiB block
    whose last dim is the whole lane axis; weights are regenerated from
    iota one FP_SLAB-lane slab at a time."""
    nc, cl = lanes.shape
    rows = min(nc, FP_ROWS)
    acc = pl.pallas_call(
        _fp_kernel,
        grid=(pl.cdiv(nc, rows),),
        in_specs=[pl.BlockSpec((rows, cl), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, 4), jnp.int32),
        interpret=interpret,
    )(lanes)
    return _finish_digests(acc, lengths)


def _quant_fp_kernel(x_ref, q_ref, s_ref, d_ref):
    rows = x_ref.shape[0]                          # one digest chunk
    sr = slab_rows(rows)
    lanes_per_row = LANE_COLS // LANE_BYTES
    col = jax.lax.broadcasted_iota(jnp.uint32, (sr, LANE_COLS), 1)
    # byte c of a row is byte c % 4 of lane c // 4 (little-endian), so
    # lane * w == sum over its bytes of byte * (w << 8 * (c % 4)) mod 2^32
    shift = (col & jnp.uint32(3)) * jnp.uint32(8)

    def body(r0, accs):
        q, s = quant_rows(x_ref[pl.ds(r0, sr), :])
        q_ref[pl.ds(r0, sr), :] = q
        s_ref[pl.ds(r0, sr), :] = s
        byte = (q.astype(jnp.int32) & 0xFF).astype(jnp.uint32)
        row = jax.lax.broadcasted_iota(jnp.uint32, (sr, LANE_COLS), 0) \
            + r0.astype(jnp.uint32)
        pos = row * jnp.uint32(lanes_per_row) + (col >> 2) + jnp.uint32(1)
        return tuple(a + _fold_rows(_i32(byte * (_weights(pos, sd) << shift)))
                     for a, sd in zip(accs, _SEEDS))

    zero = jnp.zeros((8 if sr % 8 == 0 else sr, LANE_COLS), jnp.int32)
    accs = for_slabs(rows, body, (zero,) * 4)
    d_ref[...] = _put_columns([
        jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0, keepdims=True)
        for a in accs])


def quantize_fingerprint_blocks(x, chunk_bytes: int, *,
                                interpret: bool = False):
    """Fused quantize + fingerprint: one VMEM pass per digest chunk.

    x: (R, LANE_COLS) f32 rows to quantize, where ``chunk_bytes`` int8
    bytes = ``chunk_bytes // LANE_COLS`` quantized rows and R covers
    whole chunks (``R*LANE_COLS % chunk_bytes == 0``). Returns
    ``(q int8 (R, LANE_COLS), scales f32 (R,), digests uint32 (nc, 4))``
    where digest j covers q-stream bytes [j*chunk_bytes, (j+1)*chunk_bytes)
    — the quantized payload never leaves VMEM unfingerprinted, so clean
    chunks are known before any D2H copy. The digest leaves the kernel as
    an (nc, 1, 4) block per chunk, whose last two dims are whole."""
    R, C = x.shape
    assert C == LANE_COLS, (R, C)
    assert chunk_bytes % C == 0, (chunk_bytes, C)
    rows = chunk_bytes // C
    assert R % rows == 0, (R, rows)
    nc = R // rows
    q, s, acc = pl.pallas_call(
        _quant_fp_kernel,
        grid=(nc,),
        in_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, C), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((None, 1, 4), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nc, 1, 4), jnp.int32)],
        interpret=interpret,
    )(x)
    lens = jnp.full((nc, 1), chunk_bytes, jnp.uint32)
    return q, s.reshape(R), _finish_digests(acc.reshape(nc, 4), lens)


# ------------------------------------------- fused quant+digest (device path)
@functools.partial(jax.jit, static_argnames=("chunk_bytes",))
def _quant_fp_ref_jit(padded, chunk_bytes: int):
    """XLA-fused oracle: quantize + digest the packed qs-stream
    (q int8 rows then f32 scales — the packed payload minus its header)
    in one compiled pass. Bit-identical to the Pallas kernels."""
    q, s = quant_rows(padded)
    s = s.reshape(-1)
    rows = q.shape[0]
    qlanes = lanes_u32(q.reshape(-1))
    slanes = jax.lax.bitcast_convert_type(s, jnp.uint32)
    lanes = jnp.concatenate([qlanes, slanes])
    nbytes = rows * LANE_COLS + rows * 4
    return q, s, _digest_lane_stream(lanes, nbytes, chunk_bytes)


def fused_kernel_fits(n_rows: int, chunk_bytes: int) -> bool:
    """Whether the fused kernel takes at least one chunk of an (n_rows,
    LANE_COLS) tensor on the chip: a chunk must be whole int8 row tiles
    (``chunk_bytes`` a multiple of SLAB_ROWS rows) and the q region must
    hold at least one whole chunk."""
    return (chunk_bytes % (LANE_COLS * SLAB_ROWS) == 0
            and n_rows * LANE_COLS >= chunk_bytes)


def quant_fingerprint(padded, chunk_bytes: int):
    """Quantize ``padded`` (R, LANE_COLS) f32 on device and digest the
    packed qs-stream on the ``chunk_bytes`` grid. Returns device
    ``(q, s)`` plus the host digest table (n_chunks, 4) uint32.

    TPU: the fused Pallas kernel covers every chunk made purely of q
    bytes (quant + digest in one VMEM pass); the ragged tail (q remainder
    + the scales region) is digested from jit-assembled lanes. Other
    backends run the whole thing as one jitted XLA program. On TPU, a
    shape the fused kernel cannot take (``fused_kernel_fits``) runs the
    oracle too; the tracer counts each way under
    ``quant_fingerprint.kernel`` / ``quant_fingerprint.oracle``."""
    R = padded.shape[0]
    if jax.default_backend() != "tpu":
        q, s, d = _quant_fp_ref_jit(padded, chunk_bytes)
        return q, s, np.asarray(d)
    if not fused_kernel_fits(R, chunk_bytes):
        trace.count("quant_fingerprint.oracle")
        q, s, d = _quant_fp_ref_jit(padded, chunk_bytes)
        return q, s, np.asarray(d)
    trace.count("quant_fingerprint.kernel")
    body = R * LANE_COLS // chunk_bytes
    body_rows = body * (chunk_bytes // LANE_COLS)
    qb, sb, db = quantize_fingerprint_blocks(padded[:body_rows], chunk_bytes)
    if body_rows < R:
        qt, st = quantize_blocks(padded[body_rows:])
        q = jnp.concatenate([qb, qt])
        s = jnp.concatenate([sb, st])
    else:
        q, s = qb, sb
    dt = _quant_tail_digests_jit(q, s, chunk_bytes, body)
    return q, s, np.concatenate([np.asarray(db), np.asarray(dt)])


@functools.partial(jax.jit, static_argnames=("chunk_bytes", "body"))
def _quant_tail_digests_jit(q, s, chunk_bytes: int, body: int):
    """Digests of the qs-stream's chunks from chunk ``body`` on: the q
    bytes past the fused kernel's whole chunks, then the scales."""
    start = body * chunk_bytes
    lanes = jnp.concatenate([lanes_u32(q.reshape(-1)[start:]),
                             jax.lax.bitcast_convert_type(s, jnp.uint32)])
    nbytes = q.size - start + s.size * 4
    return _digest_lane_stream(lanes, nbytes, chunk_bytes)
