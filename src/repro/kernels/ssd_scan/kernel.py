"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the GPU version
pipelines chunk outer products through shared memory; here each grid step
processes one (batch, head-block, chunk) tile entirely in VMEM, and the
inter-chunk recurrent state — one (hd, ds) matrix per head of the block —
lives in VMEM scratch carried across the innermost "arbitrary" grid
dimension (the chunk axis).

Layout: heads lead, so every block's last two dims are a (chunk, head_dim)
or (1, chunk) tile: x and the output are (Bsz, nh, T, hd); dt and the
inclusive within-chunk cumsum L of the log decay A * dt are (Bsz, nh, 1, T)
rows.  Mosaic has no cumsum, so L is computed by XLA before the kernel,
with the same ``jnp.cumsum`` as the reference.  The kernel needs dt and L
as columns too; it moves a row to a column exactly by masking with the
identity and summing over lanes.

Per chunk (Q = chunk length):
  intra:  y = M @ u              M[q,p] = exp(L_q - L_p) * (C_q . B_p)  (q>=p)
  inter:  y += exp(L) * (C @ S_prev^T)
  state:  S = exp(L_last) * S_prev + sum_p exp(L_last - L_p) u_p B_p^T
with u = dt * x, all in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, l_ref, b_ref, c_ref, o_ref, s_ref, *, nh_blk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    Bm = b_ref[0].astype(jnp.float32)           # (Q, ds)
    Cm = c_ref[0].astype(jnp.float32)           # (Q, ds)
    Q = Bm.shape[0]
    qpos = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ppos = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = qpos >= ppos
    eye = qpos == ppos
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)

    def column(row):                            # (1, Q) -> (Q, 1), exact
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    # per-head-block compute; nh_blk is small so the python loop unrolls
    for h in range(nh_blk):
        x = x_ref[0, h].astype(jnp.float32)     # (Q, hd)
        L_row = l_ref[0, h]                     # (1, Q) log decay cumsum
        L = column(L_row)
        Llast = L[Q - 1:, :]                    # (1, 1)
        u = column(dt_ref[0, h]) * x            # (Q, hd)
        # clamp masked (p > q) entries: valid log-decays are <= 0
        decay = jnp.exp(jnp.minimum(L - L_row, 0.0))
        M = jnp.where(tri, scores * decay, 0.0)
        y = jax.lax.dot_general(M, u, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # inter-chunk: y += exp(L) * (C @ S_prev^T)
        S_prev = s_ref[h]                       # (hd, ds)
        cs = jax.lax.dot_general(Cm, S_prev, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        o_ref[0, h] = (y + jnp.exp(L) * cs).astype(o_ref.dtype)
        # state update: S_loc = sum_p exp(L_last - L_p) u_p B_p^T
        S_loc = jax.lax.dot_general(u * jnp.exp(Llast - L), Bm,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s_ref[h] = jnp.exp(Llast) * S_prev + S_loc


@functools.partial(jax.jit, static_argnames=("chunk", "head_block",
                                             "interpret"))
def ssd_bthd(x, dt, A, B, C, *, chunk: int = 128, head_block: int = 4,
             interpret: bool = False):
    """x: (Bsz, T, nh, hd); dt: (Bsz, T, nh) f32; A: (nh,) f32;
    B, C: (Bsz, T, ds).  Returns (Bsz, T, nh, hd) in x.dtype."""
    Bsz, T, nh, hd = x.shape
    ds = B.shape[-1]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"T={T} % chunk={Q} != 0")
    nhb = min(head_block, nh)
    if nh % nhb:
        nhb = 1
    NC = T // Q
    dtf = dt.astype(jnp.float32)
    L = jnp.cumsum((A[None, None, :] * dtf).reshape(Bsz, NC, Q, nh), axis=2)
    rows = lambda v: jnp.moveaxis(v.reshape(Bsz, T, nh), 1, 2)[:, :, None]
    row = pl.BlockSpec((1, nhb, 1, Q), lambda b, h, c: (b, h, 0, c))

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, nh_blk=nhb),
        grid=(Bsz, nh // nhb, NC),
        in_specs=[
            pl.BlockSpec((1, nhb, Q, hd), lambda b, h, c: (b, h, c, 0)),
            row, row,
            pl.BlockSpec((1, Q, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, ds), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, nhb, Q, hd), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, nh, T, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((nhb, hd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.swapaxes(x, 1, 2), rows(dtf), rows(L), B, C)
    return jnp.swapaxes(out, 1, 2)
