"""Pure-jnp oracle for the qpack kernels.  Every op mirrors the kernel's
arithmetic exactly (same f16 scale round-trip, same rounding, same nibble
order) so kernel-vs-ref parity is bit-identical, not merely close."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quant_blocks_ref(x: jax.Array, *, qmax: int, block: int):
    """x: (R, N), N a multiple of ``block``.  Returns (codes int8 (R, N),
    scales (R, N // block)).  The scale that ships is the f16 cast of
    max-abs / qmax, clamped to f16's finite range; BOTH ends divide by that
    f16 value (1.0 for underflowed all-but-zero blocks), so encode and
    decode agree exactly."""
    R, N = x.shape
    tiles = x.astype(jnp.float32).reshape(R, N // block, block)
    amax = jnp.max(jnp.abs(tiles), axis=-1, keepdims=True)
    # clamp to the wire dtype's finite range: overflowing blocks clip hard
    # (EF absorbs it) instead of shipping inf and decoding 0*inf = NaN
    fmax = float(jnp.finfo(jnp.float16).max)
    # the barrier pins the f16 rounding: with excess precision allowed (its
    # default), XLA:TPU folds an f32 -> f16 -> f32 round trip to the
    # unrounded f32, and the decode below would use a scale the wire
    # cannot carry
    s_wire = jax.lax.optimization_barrier(
        jnp.minimum(amax / qmax, fmax).astype(jnp.float16))
    s_dec = jnp.where(s_wire > 0, s_wire.astype(jnp.float32), 1.0)
    q = jnp.clip(jnp.round(tiles / s_dec), -qmax, qmax).astype(jnp.int8)
    return q.reshape(R, N), s_wire[..., 0]


def dequant_blocks_ref(q: jax.Array, scales: jax.Array, *,
                       block: int) -> jax.Array:
    R, N = q.shape
    s = scales.astype(jnp.float32)
    s = jnp.where(s > 0, s, 1.0)[..., None]
    tiles = q.astype(jnp.float32).reshape(R, N // block, block)
    return (tiles * s).reshape(R, N)


def pack4_ref(q: jax.Array) -> jax.Array:
    """int8 codes (R, N) in [-7, 7] -> uint8 (R, N // 2), low nibble first."""
    pairs = (q.astype(jnp.uint8) & 0xF).reshape(q.shape[0], -1, 2)
    return pairs[:, :, 0] | (pairs[:, :, 1] << 4)


def unpack4_ref(p: jax.Array) -> jax.Array:
    lo = (p & 0xF).astype(jnp.int8)
    hi = ((p >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(p.shape[0], -1)
