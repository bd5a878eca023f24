"""Pallas TPU flash attention (online softmax), GQA + causal + sliding window.

TPU adaptation notes (vs the CUDA FlashAttention formulation):
  * tiles are BlockSpec-mapped VMEM windows; the MXU wants the contraction
    dims to be multiples of 128 — block_q/block_k default to 128;
  * the kv loop is the innermost ("arbitrary") grid dimension, with the
    running (max, denom, acc) held in VMEM scratch across grid steps — the
    revisiting-output pattern — instead of a warp-level register pipeline;
  * causal + sliding-window block skipping happens at two levels: fully
    masked kv blocks are skipped via pl.when (no MXU work issued), partially
    masked blocks apply an element mask.

Layout: q (B, nh, T, hd), k/v (B, nkv, S, hd); GQA maps query head h to kv
head h // (nh // nkv) in the index_map, so no kv replication is materialised.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, block_q, block_k, causal, window, seq_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip decision (static shapes, dynamic predicate)
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window > 0:
        # kv block entirely below the window of every query row in the block
        run &= k_start + block_k - 1 > q_start - window

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new
        acc_ref[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q: (B, nh, T, hd); k/v: (B, nkv, S, hd); returns (B, nh, T, hd)."""
    B, nh, T, hd = q.shape
    _, nkv, S, _ = k.shape
    group = nh // nkv
    scale = 1.0 / math.sqrt(hd)

    bq = min(block_q, T)
    bk = min(block_k, S)
    pad_q = (-T) % bq
    pad_k = (-S) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Tq, Sk = q.shape[2], k.shape[2]

    grid = (B, nh, Tq // bq, Sk // bk)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=bq, block_k=bk,
                          causal=causal, window=window, seq_k=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh, Tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T]
