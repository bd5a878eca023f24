"""Pallas TPU kernels for the comm codecs' bit-packing: block-scaled
int8/int4 quantize (pack) and dequantize (unpack).

This is the wire transform of a compressed sync (repro.comm): before the
agent-axis all-reduce each agent's flat parameter stream is cut into
``block``-wide tiles, every tile gets one f16 scale (max-abs / qmax, the
value that actually ships, so encode and decode agree bit-for-bit), and the
payload is rounded to ``bits``-wide signed codes — two codes per byte for
int4.

Layout: an (R, N) stream is viewed (for free, row-major) as (R, N // block,
block), so one quantization block is one row of lanes and the grid walks
``blocks_per_step`` blocks at a time on the sublane axis.  Every block
shape then meets Mosaic's tiling rule — the last dim is the whole block,
the second-to-last a multiple of 8 or the whole axis — for any even block
size.  Scales travel as an (R, N // block, 1) f32 column beside the tile
and are cast to f16 outside the kernel: the value is rounded to f16 inside
the kernel with f32 bit operations (``_round_f16``), because Mosaic cannot
lower an f16 cast, so the cast outside is exact.

Zero-blocks: a tile whose max-abs underflows f16 gets scale 0 on the wire
and decodes to exact zeros — the decode-side ``where`` keeps the division
well-defined without inventing a floor the wire couldn't represent.

The int4 nibble pack maps each 32-bit word of four codes to the 16-bit
half-word of their four nibbles, lane for lane, so the packed stream keeps
the codes' order without moving data across lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F16_MAX = 65504.0
# f32 bytes of one (rows, blocks_per_step, block) input tile
TILE_BYTES = 512 * 1024
# lanes per row of the int4 pack/unpack layout, and rows per grid step
LANES = 128
PACK_ROWS = 512


def _round_f16(x):
    """Round non-negative f32 values no larger than F16_MAX to the nearest
    f16 value (ties to even), returned as f32: the f16 cast, done with f32
    bit operations because Mosaic cannot lower an f16 cast.  Normal range:
    drop the 13 mantissa bits f16 lacks.  Subnormal range (below 2^-14):
    round to a multiple of f16's spacing 2^-24."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = (bits + 0x0FFF + ((bits >> 13) & 1)) & ~0x1FFF
    normal = jax.lax.bitcast_convert_type(bits, jnp.float32)
    sub = jnp.round(x * 2.0 ** 24) * 2.0 ** -24
    return jnp.where(x < 2.0 ** -14, sub, normal)


def _wire_scale(amax, qmax):
    """The f16 scale that ships (as its exact f32 value), and the f32 value
    both ends divide by.  Clamped to f16's finite range: an overflowing
    block clips hard (error feedback absorbs it) instead of shipping inf
    and decoding 0 * inf = NaN."""
    s_wire = _round_f16(jnp.minimum(amax / qmax, F16_MAX))
    return s_wire, jnp.where(s_wire > 0, s_wire, 1.0)


def blocks_per_step(rows: int, block: int, n_blocks: int) -> int:
    """Quantization blocks per grid step: about ``TILE_BYTES`` of f32 per
    input tile, a multiple of 8, or every block when fewer remain."""
    bps = max(8, TILE_BYTES // (4 * rows * block) // 8 * 8)
    return n_blocks if n_blocks <= bps else bps


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax):
    # x_ref: (R, bps, block) source tile; q_ref: (R, bps, block) int8
    # codes; s_ref: (R, bps, 1) f32 wire scales
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s_wire, s_dec = _wire_scale(amax, qmax)
    q = jnp.clip(jnp.round(x / s_dec), -qmax, qmax)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = s_wire


def _dequant_kernel(q_ref, s_ref, o_ref):
    s = s_ref[...]
    o_ref[...] = q_ref[...].astype(jnp.float32) * jnp.where(s > 0, s, 1.0)


def _nibbles(w):
    """int32 word of four int8 codes -> its four low nibbles as 16 bits."""
    return ((w & 0xF) | ((w >> 4) & 0xF0) | ((w >> 8) & 0xF00)
            | ((w >> 12) & 0xF000))


def _codes(h):
    """16 bits of four nibbles -> int32 word of the four sign-extended
    int8 codes (the inverse of ``_nibbles``)."""
    out = jnp.zeros_like(h)
    for k in range(4):
        c = (((h >> (4 * k)) & 0xF) ^ 8) - 8
        out = out | ((c & 0xFF) << (8 * k))
    return out


def _pack4_kernel(w_ref, p_ref):
    # w_ref: (rows, 128) int32 words of four int8 codes; p_ref: (rows, 128)
    # int16 half-words of their four nibbles
    p_ref[...] = _nibbles(w_ref[...]).astype(jnp.int16)


def _unpack4_kernel(p_ref, w_ref):
    # p_ref: (rows, 128) int16 half-words of nibbles; w_ref: (rows, 128)
    # int32 words of the four codes
    w_ref[...] = _codes(p_ref[...].astype(jnp.int32) & 0xFFFF)


def _block_spec(rows, bps, lanes):
    return pl.BlockSpec((rows, bps, lanes), lambda i: (0, i, 0))


@functools.partial(jax.jit, static_argnames=("qmax", "block", "interpret"))
def quant_flat(x: jax.Array, *, qmax: int, block: int = 128,
               interpret: bool = False):
    """x: (R, N) with N a multiple of ``block``.  Returns (codes int8 (R, N),
    f16 scales (R, N // block))."""
    R, N = x.shape
    nb = N // block
    bps = blocks_per_step(R, block, nb)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(pl.cdiv(nb, bps),),
        in_specs=[_block_spec(R, bps, block)],
        out_specs=[_block_spec(R, bps, block), _block_spec(R, bps, 1)],
        out_shape=[jax.ShapeDtypeStruct((R, nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((R, nb, 1), jnp.float32)],
        interpret=interpret,
    )(x.reshape(R, nb, block))
    return q.reshape(R, N), s.reshape(R, nb).astype(jnp.float16)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequant_flat(q: jax.Array, scales: jax.Array, *, block: int = 128,
                 interpret: bool = False) -> jax.Array:
    """codes (R, N) + scales (R, N // block) -> f32 (R, N)."""
    R, N = q.shape
    nb = N // block
    bps = blocks_per_step(R, block, nb)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(nb, bps),),
        in_specs=[_block_spec(R, bps, block), _block_spec(R, bps, 1)],
        out_specs=_block_spec(R, bps, block),
        out_shape=jax.ShapeDtypeStruct((R, nb, block), jnp.float32),
        interpret=interpret,
    )(q.reshape(R, nb, block), scales.astype(jnp.float32).reshape(R, nb, 1))
    return out.reshape(R, N)


def _lanes_call(kernel, src, in_dtype, out_dtype, interpret):
    """Run a lane-wise kernel over a flat byte stream viewed as rows of 128
    ``in_dtype`` lanes (zero-padded to whole rows); returns the output
    bytes, flat and padded."""
    size = jnp.dtype(in_dtype).itemsize
    rows = -(-src.size // (size * LANES))
    step = rows if rows <= PACK_ROWS else PACK_ROWS
    flat = jnp.pad(src.reshape(-1), (0, rows * LANES * size - src.size))
    lanes = jax.lax.bitcast_convert_type(flat.reshape(rows, LANES, size),
                                         in_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, step),),
        in_specs=[pl.BlockSpec((step, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((step, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(lanes)
    return jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack4_flat(q: jax.Array, *, interpret: bool = False) -> jax.Array:
    """int8 codes (R, N) in [-7, 7], N even -> packed uint8 nibbles
    (R, N // 2), consecutive pairs low-nibble-first."""
    R, N = q.shape
    codes = jax.lax.bitcast_convert_type(q, jnp.uint8)
    out = _lanes_call(_pack4_kernel, codes, jnp.int32, jnp.int16, interpret)
    return out[:R * N // 2].reshape(R, N // 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack4_flat(p: jax.Array, *, interpret: bool = False) -> jax.Array:
    """packed uint8 nibbles (R, M) -> int8 codes (R, 2 M)."""
    R, M = p.shape
    out = _lanes_call(_unpack4_kernel, p, jnp.int16, jnp.int32, interpret)
    return jax.lax.bitcast_convert_type(out[:2 * R * M],
                                       jnp.int8).reshape(R, 2 * M)
