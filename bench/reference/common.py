"""Arithmetic shared by the references: matrix products at a stated
precision, RMSNorm, rotary embedding, attention and the SwiGLU MLP.

``Prec("f32")`` multiplies in float32 at ``HIGHEST`` precision (a TPU
otherwise runs a float32 product in bfloat16 passes).  ``Prec("fp8")`` is
the control: every operand and every result of every product is rounded
to float8 e4m3 with one scale per tensor (its max-abs at 448), the step
below the bfloat16 the configurations state for activations; products
still accumulate in float32, and the rounding passes gradients straight
through.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
NEG_INF = -2.0 ** 30       # the program's finite mask value


@jax.custom_vjp
def fp8_round(x):
    s = jnp.max(jnp.abs(x)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    # the barrier keeps XLA from folding the round trip away
    q = jax.lax.optimization_barrier((x / s).astype(jnp.float8_e4m3fn))
    return q.astype(jnp.float32) * s


fp8_round.defvjp(lambda x: (fp8_round(x), None), lambda _, g: (g,))


class Prec:
    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def operand(self, x):
        x = x.astype(jnp.float32)
        return fp8_round(x) if self.mode == "fp8" else x

    def einsum(self, spec, *ops):
        out = jnp.einsum(spec, *[self.operand(o) for o in ops],
                         precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        return self.operand(out)

    def mm(self, a, w):
        return self.einsum("...i,ij->...j", a, w)


def rmsnorm(x, scale, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """Rotary embedding of (B, T, H, D) at positions 0..T-1: the first and
    second halves of each head are the two coordinates of each pair."""
    T, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs       # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, prec: Prec, *, heads: int, kv_heads: int, head_dim: int,
              theta: float, causal: bool):
    """Grouped-query attention with rotary embedding; p holds wq, wk, wv,
    wo as {"w": (in, out)}."""
    B, T, _ = x.shape
    q = prec.mm(x, p["wq"]["w"]).reshape(B, T, heads, head_dim)
    k = prec.mm(x, p["wk"]["w"]).reshape(B, T, kv_heads, head_dim)
    v = prec.mm(x, p["wv"]["w"]).reshape(B, T, kv_heads, head_dim)
    q, k = rope(q, theta), rope(k, theta)
    g = heads // kv_heads
    q = q.reshape(B, T, kv_heads, g, head_dim)
    s = prec.einsum("btkgd,bskd->bkgts", q, k) / math.sqrt(head_dim)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    y = prec.einsum("bkgts,bskd->btkgd", a, v).reshape(B, T, heads * head_dim)
    return prec.mm(y, p["wo"]["w"])


def swiglu(p, x, prec: Prec):
    h = jax.nn.silu(prec.mm(x, p["w_gate"]["w"])) * prec.mm(x, p["w_up"]["w"])
    return prec.mm(h, p["w_down"]["w"])


def decoder_block(p, h, prec: Prec, *, heads, kv_heads, head_dim, theta,
                  causal):
    """Pre-norm residual block: attention, then SwiGLU."""
    h = h + attention(p["attn"], rmsnorm(h, p["ln1"]["scale"]), prec,
                      heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                      theta=theta, causal=causal)
    return h + swiglu(p["mlp"], rmsnorm(h, p["ln2"]["scale"]), prec)


def layer(tree, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def num_layers(tree) -> int:
    return jax.tree_util.tree_leaves(tree)[0].shape[0]
