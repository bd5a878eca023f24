"""Public entry points for the fused coded sync.

``qsync_flat`` runs one agent-stacked flat stream through the fused
EF-add → quantize → dequantize → weighted-reduce → re-encode pass, padding
to the block multiple like ``qpack.ops`` and trimming the tiling lanes on
the way out.  Like the other kernel packages, ``use_kernel=None`` picks the
Pallas kernel on a real TPU backend and the vectorized ref oracle
elsewhere, so the jitted round on CPU never pays interpret-mode overhead.

``qsync_leaves`` is the flatten-once leaf bucketer (the ``ClientStore``
gather/scatter trick applied to ``coded_sync``): every f32 leaf of a
(P, A)-stacked subtree is flattened to (B, n_i), padded PER LEAF to the
block multiple, and concatenated into one (B, N_flat) buffer — so syncing a
whole subtree is a constant number of dispatches instead of O(leaves).
Padding each leaf before concatenating (rather than once at the end)
preserves every leaf's block boundaries, which is what keeps the bucketed
sync bit-identical to the per-leaf composed pipeline: the quantizer sees
exactly the same tiles either way, and the zero pad lanes neither move a
block's max-abs nor survive the trim.

The Pallas kernel path does not bucket: it runs one fused kernel per leaf.
On a TPU the concatenation copies every parameter and residual once more,
and XLA takes minutes to compile it: compiled for a TPU v5e, the bucketed
int8+EF sync of mamba2-2.7b at its published widths (two layers) took
259 s and 3.9 GiB of temporaries, per-leaf kernels a few seconds and under
1 GiB.

Per leaf, the kernel has two entries (``kernel.qsync_flat_rows`` and
``kernel.qsync_flat``), chosen by the leaf's shape alone
(``rows_view``).  A leaf whose per-agent shape has at least two dims, the
second-minor a multiple of 8 and the last a multiple of ``block`` (itself
a multiple of 128 lanes) is handed over as a (B, R, C) view of its own
layout, which on a TPU is free, and its outputs come back the same way.
Every other leaf goes through the (B, n) flat stream.  Both entries run
the same blocks through the same body, so the choice never changes a bit.
The flat view is not free on a TPU: traced on a v5e, the int8+EF round of
mamba2-2.7b (two layers, two agents) spent 22.6 ms a round, twice the
kernel's own time, relaying out every leaf and residual into the flat
view's tiles and back.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.qsync import kernel, ref


def _use_kernel_default() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _check(bits: int, block: int):
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    if block < 2 or block % 2:
        raise ValueError(f"block must be even and >= 2, got {block}")


def qsync_flat(weights, stacked, ef=None, ef_down=None, *, bits: int = 8,
               block: int = 128, use_kernel: bool | None = None):
    """Fused coded sync of one flat stream: weights shaped like the agent
    grid ((P, A) or (B,)), stacked (B, n) f32 (any n), optional uplink
    residual ef (B, n) and downlink residual ef_down (n,).  Returns
    ``(synced (n,), new_ef | None, new_ef_down | None)`` — bit-identical
    to the composed roundtrip→weighted_mean→roundtrip pipeline (the
    reduce runs in the weights' grid shape, see ``ref.qsync_flat_ref``)."""
    _check(bits, block)
    kern = _use_kernel_default() if use_kernel is None else use_kernel
    qmax = 2 ** (bits - 1) - 1
    B, n = stacked.shape
    pad = (-n) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
        if ef is not None:
            ef = jnp.pad(ef, ((0, 0), (0, pad)))
        if ef_down is not None:
            ef_down = jnp.pad(ef_down, (0, pad))
    if kern:
        synced, ne, ned = kernel.qsync_flat(weights, stacked, ef, ef_down,
                                            qmax=qmax, block=block,
                                            interpret=_interpret())
    else:
        synced, ne, ned = ref.qsync_flat_ref(weights, stacked, ef, ef_down,
                                             qmax=qmax, block=block)
    return (synced[:n],
            ne[:, :n] if ne is not None else None,
            ned[:n] if ned is not None else None)


def fusable_leaf(x) -> bool:
    """Whether a leaf can ride the fused path: (P, A)-stacked float32 (the
    kernel reduces in f32 — a bf16 leaf would reduce wider than the
    composed pipeline, breaking bit parity, so it falls back)."""
    return (hasattr(x, "dtype") and x.dtype == jnp.float32
            and getattr(x, "ndim", 0) >= 2)


def _bucket(leaves, B: int, block: int):
    """[(B, ...)] -> one (B, N_flat) buffer + per-leaf (offset, n) spans.
    Each leaf is padded to its own block multiple before concatenation so
    block boundaries match the per-leaf pipeline exactly."""
    cols, spans, off = [], [], 0
    for x in leaves:
        flat = x.reshape(B, -1)
        n = flat.shape[1]
        pad = (-n) % block
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        cols.append(flat)
        spans.append((off, n))
        off += n + pad
    return (cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1),
            spans)


def qsync_leaves(leaves, weights, ef_leaves=None, ef_down_leaves=None, *,
                 bits: int = 8, block: int = 128,
                 use_kernel: bool | None = None):
    """Fused sync of a group of (P, A, ...) f32 leaves: O(1) bucketed
    dispatches on the ref path, one kernel per leaf on the kernel path
    (see the module docstring).  ``ef_leaves`` match the leaves''
    shapes; ``ef_down_leaves`` their per-agent shapes (leaf.shape[2:]).
    Returns ``(synced, new_ef, new_ef_down)`` leaf lists — synced leaves
    broadcast back over the agent grid like ``coded_sync``."""
    _check(bits, block)
    B = int(weights.size)
    kern = _use_kernel_default() if use_kernel is None else use_kernel
    if kern:
        return _qsync_per_leaf(leaves, weights, ef_leaves, ef_down_leaves,
                               bits=bits, block=block)
    stacked, spans = _bucket(leaves, B, block)
    ef = None
    if ef_leaves is not None:
        ef, _ = _bucket(ef_leaves, B, block)
    ef_down = None
    if ef_down_leaves is not None:
        ef_down, _ = _bucket([e[None] for e in ef_down_leaves], 1, block)
        ef_down = ef_down[0]
    synced, ne, ned = qsync_flat(weights, stacked, ef, ef_down, bits=bits,
                                 block=block, use_kernel=False)
    outs, new_e, new_ed = [], [], []
    for x, (off, n) in zip(leaves, spans):
        seg = synced[off:off + n]
        outs.append(jnp.broadcast_to(seg.reshape(x.shape[2:]), x.shape))
        new_e.append(ne[:, off:off + n].reshape(x.shape)
                     if ne is not None else None)
        new_ed.append(ned[off:off + n].reshape(x.shape[2:])
                      if ned is not None else None)
    return outs, new_e, new_ed


def rows_view(shape, block: int):
    """The (R, C) view of a per-agent leaf ``shape`` that the layout-native
    kernel entry takes, or None when the leaf must go through the flat
    stream: C the last dim, a multiple of ``block`` (itself a multiple of
    the 128 lanes), R the product of the others, of which the last is a
    multiple of 8 so that folding them into R keeps the chip's (8, 128)
    tiling — the view is then a bitcast."""
    if (block % 128 or len(shape) < 2 or shape[-1] % block
            or shape[-2] % 8):
        return None
    return math.prod(shape[:-1]), shape[-1]


def _qsync_per_leaf(leaves, weights, ef_leaves, ef_down_leaves, *, bits,
                    block):
    """The kernel path of ``qsync_leaves``: one fused kernel per leaf, on
    the leaf's own layout where ``rows_view`` allows it."""
    B = int(weights.size)
    qmax = 2 ** (bits - 1) - 1
    outs, new_e, new_ed = [], [], []
    for i, x in enumerate(leaves):
        e = ef_leaves[i] if ef_leaves is not None else None
        ed = ef_down_leaves[i] if ef_down_leaves is not None else None
        view = rows_view(x.shape[2:], block)
        if view is None:
            synced, ne, ned = qsync_flat(
                weights, x.reshape(B, -1),
                e.reshape(B, -1) if e is not None else None,
                ed.reshape(-1) if ed is not None else None,
                bits=bits, block=block, use_kernel=True)
        else:
            synced, ne, ned = kernel.qsync_flat_rows(
                weights, x.reshape((B,) + view),
                e.reshape((B,) + view) if e is not None else None,
                ed.reshape(view) if ed is not None else None,
                qmax=qmax, block=block, interpret=_interpret())
        outs.append(jnp.broadcast_to(synced.reshape(x.shape[2:]), x.shape))
        new_e.append(ne.reshape(x.shape) if ne is not None else None)
        new_ed.append(ned.reshape(x.shape[2:]) if ned is not None else None)
    return outs, new_e, new_ed
