"""Pure-jnp oracle for the fused qsync kernels.

Built from the same qpack ref pieces the composed ``coded_sync`` pipeline
uses (``quant_blocks_ref`` / ``dequant_blocks_ref``) plus the
``collectives.weighted_mean`` contraction written out inline, so
fused-vs-composed parity is bit-identical by construction — this oracle IS
the composed pipeline, minus the per-leaf Python loop.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.qpack.ref import dequant_blocks_ref, quant_blocks_ref


def qsync_flat_ref(weights, stacked, ef=None, ef_down=None, *, qmax: int,
                   block: int):
    """Same contract as ``kernel.qsync_flat``: weights shaped like the
    agent grid ((P, A) or (B,)), stacked (B, N) with N a block multiple,
    optional residuals; returns ``(synced (N,), new_ef | None,
    new_ef_down | None)``.  The reduce runs in the weights' own grid shape
    — XLA's multi-axis reduce groups differently from a flat axis-0 sum,
    and only the grid-shaped reduce matches ``collectives.weighted_mean``
    bit for bit."""
    grid = weights.shape
    w = weights.astype(jnp.float32).reshape(-1, 1)
    y = stacked + ef if ef is not None else stacked
    q, s = quant_blocks_ref(y, qmax=qmax, block=block)
    dq = dequant_blocks_ref(q, s, block=block)        # uplink wire image
    prod = (w * dq).reshape(grid + (-1,))             # eq. (2) reduce
    m = jnp.sum(prod, axis=tuple(range(len(grid))))[None, :]
    yd = m + ef_down.reshape(1, -1) if ef_down is not None else m
    qd, sd = quant_blocks_ref(yd, qmax=qmax, block=block)
    dqd = dequant_blocks_ref(qd, sd, block=block)     # downlink wire image
    return (dqd[0],
            y - dq if ef is not None else None,
            yd[0] - dqd[0] if ef_down is not None else None)
