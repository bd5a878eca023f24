"""Pallas TPU kernel for the FedGAN sync: fused weighted average of B
agent parameter shards.

This is the intermediary's eq. (2) compute: out = sum_i p_i * W_i over the
agent axis, fused with the dtype cast of a compressed sync.  On the wire the
average is an all-reduce; this kernel is the on-chip reduction used when the
agent-stacked shard is resident (e.g. per-host staging of the sync, or the
B-way average inside one pod's shard before the cross-pod collective of the
hierarchical mode).

Tiling: parameters are flattened to (B, N); the grid walks N in
``block``-wide tiles that sit in VMEM (8 agents x 512 f32 lanes = 16 KiB per
tile — deliberately small so the averaging stream overlaps the HBM loads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fedavg_kernel(w_ref, x_ref, o_ref, *, acc_dtype):
    # w_ref: (B, 1) f32 weights; x_ref: (B, block); o_ref: (1, block)
    x = x_ref[...].astype(acc_dtype)
    w = w_ref[...].astype(acc_dtype)
    o_ref[...] = jnp.sum(w * x, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fedavg_flat(weights: jax.Array, stacked: jax.Array, *,
                block: int = 512, interpret: bool = False) -> jax.Array:
    """stacked: (B, N) agent-stacked flat params; weights: (B,) summing to 1.
    Returns (N,) weighted average in stacked.dtype."""
    B, N = stacked.shape
    pad = (-N) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    n_blocks = stacked.shape[1] // block

    out = pl.pallas_call(
        functools.partial(_fedavg_kernel, acc_dtype=jnp.float32),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((B, 1), lambda i: (0, 0)),
            pl.BlockSpec((B, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, stacked.shape[1]), stacked.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32)[:, None], stacked)
    return out[0, :N]
