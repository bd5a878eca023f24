"""Fused Pallas kernel for the coded-sync hot path.

``coded_sync`` composed from the qpack pieces runs EF-add → quantize →
dequantize → weighted reduce → downlink re-quantize as separate dispatches,
each materializing its full (B, N) intermediate in HBM.  The fused kernel
here does the whole chain per VMEM tile: for every ``block``-wide column of
the agent-stacked stream it adds the carried uplink residual, builds the
per-agent wire image (block max-abs → f16 scale → rounded codes, EXACTLY
the qpack arithmetic — ``_wire_scale`` is imported, not re-derived), reduces
the decoded images over the agent axis with the §3.1 weights, adds the
server's downlink residual, re-encodes the average for the broadcast, and
emits the synced block plus both new residuals — the per-agent wire image
never exists in HBM at all.

Layout follows ``kernels/qpack``: the (B, N) stream is viewed as (B,
N // block, block) and each grid step takes ``blocks_per_step`` blocks.

Bit parity with the composed pipeline is exact, not approximate: the codes
are integral f32 in [-qmax, qmax] (an int8 cast round-trips them
losslessly, so skipping the cast changes nothing), and the reduce is the
same materialized w·x then sum in agent order as
``collectives.weighted_mean``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qpack.kernel import _wire_scale, blocks_per_step


def _qsync_kernel(*refs, grid, qmax, has_ef, has_ef_down, barrier):
    # refs: w (B, 1, 1) f32, x (B, bps, block), [e (B, bps, block)],
    #       [ed (1, bps, block)] -> synced (1, bps, block),
    #       [new_e (B, bps, block)], [new_ed (1, bps, block)]
    it = iter(refs)
    w_ref, x_ref = next(it), next(it)
    e_ref = next(it) if has_ef else None
    ed_ref = next(it) if has_ef_down else None
    o_ref = next(it)
    ne_ref = next(it) if has_ef else None
    ned_ref = next(it) if has_ef_down else None

    x = x_ref[...].astype(jnp.float32)
    y = x + e_ref[...] if has_ef else x
    # uplink wire image: per-agent block quantize -> dequantize (qpack math)
    amax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    _, s_dec = _wire_scale(amax, qmax)
    dq = jnp.clip(jnp.round(y / s_dec), -qmax, qmax) * s_dec
    # eq. (2): weighted reduce over the agent axis — products materialized
    # before the sum AND reduced in the (P, A) grid shape, because XLA's
    # multi-axis reduce groups differently from a flat axis-0 sum; only the
    # grid-shaped reduce is bit-identical to collectives.weighted_mean
    prod = (w_ref[...] * dq).reshape(grid + dq.shape[1:])
    if barrier:
        # interpret mode only: keep the product from fusing into the
        # reduction, which changes XLA:CPU's accumulation grouping — the
        # standalone reduce is the one that matches weighted_mean bit-for-bit
        prod = jax.lax.optimization_barrier(prod)
    m = jnp.sum(prod, axis=tuple(range(len(grid))))[None]
    # downlink: server residual + re-encode of the average
    yd = m + ed_ref[...] if has_ef_down else m
    amax_d = jnp.max(jnp.abs(yd), axis=-1, keepdims=True)
    _, sd_dec = _wire_scale(amax_d, qmax)
    dqd = jnp.clip(jnp.round(yd / sd_dec), -qmax, qmax) * sd_dec
    o_ref[...] = dqd
    if has_ef:
        ne_ref[...] = y - dq
    if has_ef_down:
        ned_ref[...] = yd - dqd


@functools.partial(jax.jit, static_argnames=("qmax", "block", "interpret"))
def qsync_flat(weights, stacked, ef=None, ef_down=None, *, qmax: int,
               block: int = 128, interpret: bool = False):
    """weights shaped like the agent grid ((P, A) or (B,)) with B total
    entries, stacked (B, N) f32 with N a multiple of ``block``; optional
    per-agent uplink residual ``ef`` (B, N) and shared downlink residual
    ``ef_down`` (N,).  The reduce runs over the weights' own grid shape
    (bit parity with ``collectives.weighted_mean``).  Returns
    ``(synced (N,), new_ef | None, new_ef_down | None)`` — residual
    outputs mirror the inputs."""
    grid = weights.shape
    B, N = stacked.shape
    nb = N // block
    bps = blocks_per_step(B, block, nb)
    has_ef = ef is not None
    has_ef_down = ef_down is not None
    agents = pl.BlockSpec((B, bps, block), lambda i: (0, i, 0))
    shared = pl.BlockSpec((1, bps, block), lambda i: (0, i, 0))
    inputs = [weights.astype(jnp.float32).reshape(B, 1, 1),
              stacked.reshape(B, nb, block)]
    in_specs = [pl.BlockSpec((B, 1, 1), lambda i: (0, 0, 0)), agents]
    out_shape = [jax.ShapeDtypeStruct((1, nb, block), jnp.float32)]
    out_specs = [shared]
    if has_ef:
        inputs.append(ef.reshape(B, nb, block))
        in_specs.append(agents)
        out_shape.append(jax.ShapeDtypeStruct((B, nb, block), jnp.float32))
        out_specs.append(agents)
    if has_ef_down:
        inputs.append(ef_down.reshape(1, nb, block))
        in_specs.append(shared)
        out_shape.append(jax.ShapeDtypeStruct((1, nb, block), jnp.float32))
        out_specs.append(shared)
    outs = pl.pallas_call(
        functools.partial(_qsync_kernel, grid=grid, qmax=qmax,
                          has_ef=has_ef, has_ef_down=has_ef_down,
                          barrier=interpret),
        grid=(pl.cdiv(nb, bps),), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret)(*inputs)
    it = iter(outs)
    synced = next(it).reshape(N)
    new_e = next(it).reshape(B, N) if has_ef else None
    new_ed = next(it).reshape(N) if has_ef_down else None
    return synced, new_e, new_ed
