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

Two entries share the one kernel body; only the tiling around it differs.

``qsync_flat`` takes a (B, N) stream and views it as (B, N // block,
block), as ``kernels/qpack`` does: each grid step takes
``blocks_per_step`` blocks, one block per row of lanes.

``qsync_flat_rows`` takes a leaf in its own layout, viewed as (B, R, C)
with the last dimension C a multiple of ``block`` and ``block`` a multiple
of the 128 lanes: block k of the leaf's row-major flat stream is row
k // (C/block), lane group k % (C/block) of that view.  A grid over rows
and lane groups, in (B, rows, block) tiles, therefore hands the body
exactly the blocks the flat entry would.  On a TPU the view costs nothing
when R's minor factor is a multiple of 8 (the (8, 128) tiling of the
leaf's last two dims is kept), where the flat entry's (N // block, block)
view rewrites the whole leaf into tiles of 1,024 consecutive elements,
before the call and again after it.

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
    # refs: w (B, 1, 1) f32, x (B, rows, block), [e (B, rows, block)],
    #       [ed (1, rows, block)] -> synced (1, rows, block),
    #       [new_e (B, rows, block)], [new_ed (1, rows, block)]
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


def _call(weights, x, ef, ef_down, *, tile, grid_steps, index, qmax,
          interpret, name):
    """One fused pallas_call over (B, ...) views ``x``/``ef`` and a (1, ...)
    view ``ef_down``, each tiled ``tile`` (agents first) by ``index``; the
    tile's last dim is one quantization block."""
    B = x.shape[0]
    has_ef = ef is not None
    has_ef_down = ef_down is not None
    agents = pl.BlockSpec((B,) + tile, index)
    shared = pl.BlockSpec((1,) + tile, index)
    inputs = [weights.astype(jnp.float32).reshape(B, 1, 1), x]
    in_specs = [pl.BlockSpec((B, 1, 1), lambda *_: (0, 0, 0)), agents]
    out_shape = [jax.ShapeDtypeStruct((1,) + x.shape[1:], jnp.float32)]
    out_specs = [shared]
    if has_ef:
        inputs.append(ef)
        in_specs.append(agents)
        out_shape.append(jax.ShapeDtypeStruct(x.shape, jnp.float32))
        out_specs.append(agents)
    if has_ef_down:
        inputs.append(ef_down)
        in_specs.append(shared)
        out_shape.append(jax.ShapeDtypeStruct((1,) + x.shape[1:],
                                              jnp.float32))
        out_specs.append(shared)
    outs = pl.pallas_call(
        functools.partial(_qsync_kernel, grid=weights.shape, qmax=qmax,
                          has_ef=has_ef, has_ef_down=has_ef_down,
                          barrier=interpret),
        grid=grid_steps, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name=name)(*inputs)
    it = iter(outs)
    return (next(it), next(it) if has_ef else None,
            next(it) if has_ef_down else None)


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
    B, N = stacked.shape
    nb = N // block
    bps = blocks_per_step(B, block, nb)
    synced, new_e, new_ed = _call(
        weights, stacked.reshape(B, nb, block),
        ef.reshape(B, nb, block) if ef is not None else None,
        ef_down.reshape(1, nb, block) if ef_down is not None else None,
        tile=(bps, block), grid_steps=(pl.cdiv(nb, bps),),
        index=lambda i: (0, i, 0), qmax=qmax, interpret=interpret,
        name="qsync_flat")
    return (synced.reshape(N),
            new_e.reshape(B, N) if new_e is not None else None,
            new_ed.reshape(N) if new_ed is not None else None)


@functools.partial(jax.jit, static_argnames=("qmax", "block", "interpret"))
def qsync_flat_rows(weights, stacked, ef=None, ef_down=None, *, qmax: int,
                    block: int = 128, interpret: bool = False):
    """``qsync_flat`` on a leaf's own layout: stacked (B, R, C) f32 with C
    a multiple of ``block`` (and ``block`` of 128 lanes), optional uplink
    residual ``ef`` (B, R, C) and downlink residual ``ef_down`` (R, C).
    Returns ``(synced (R, C), new_ef | None, new_ef_down | None)``, equal
    bit for bit to ``qsync_flat`` on the row-major flattening (see the
    module docstring)."""
    B, R, C = stacked.shape
    rows = blocks_per_step(B, block, R)
    synced, new_e, new_ed = _call(
        weights, stacked, ef,
        ef_down[None] if ef_down is not None else None,
        tile=(rows, block), grid_steps=(pl.cdiv(R, rows), C // block),
        index=lambda i, j: (0, i, j), qmax=qmax, interpret=interpret,
        name="qsync_flat_rows")
    return synced[0], new_e, new_ed[0] if new_ed is not None else None
