"""Plain Mamba2 language model (arXiv:2405.21060), as the configuration
file describes it (its ``departures`` list where it leaves the published
model).

Each layer: h += out_proj(RMSNorm(y) * silu(z)), where from u = RMSNorm(h)
the projections give z, x, B, C and dt; x, B and C pass a causal depthwise
convolution and silu; dt = softplus(dt + dt_bias); and y is the state
space recurrence

    S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T,   y_t = S_t C_t + D x_t,

computed in its quadratic (attention-like) form over the whole sequence,
y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} A dt_r) dt_s x_s, with the
decay sums taken as masked cumulative sums rather than as differences of
one long cumulative sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import Prec, layer, num_layers, rmsnorm


def causal_conv(x, w):
    """x (B, T, C), w (k, C): y_t = sum_j w_j x_{t-k+1+j}, zeros before 0."""
    k, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j] for j in range(k))


def segsum(la):
    """la (B, T, H) -> (B, H, T, T): [t, s] = sum_{s<r<=t} la_r for s <= t,
    -inf above the diagonal."""
    T = la.shape[1]
    x = jnp.broadcast_to(jnp.moveaxis(la, 1, -1)[..., :, None],
                         la.shape[:1] + la.shape[2:] + (T, T))   # [t, s] = la_t
    strict = jnp.tril(jnp.ones((T, T), bool), -1)
    out = jnp.cumsum(jnp.where(strict, x, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(x, dt, A, Bm, Cm, prec: Prec):
    """x (B, T, H, P), dt (B, T, H), A (H,), Bm and Cm (B, T, N)."""
    decay = jnp.exp(segsum(dt * A))                            # (B, H, T, S)
    scores = prec.einsum("btn,bsn->bts", Cm, Bm)
    return prec.einsum("bhts,bts,bsh,bshp->bthp", decay, scores, dt, x)


def mixer(p, u, prec: Prec, cfg: dict):
    d_inner = cfg["ssm_expand"] * cfg["d_model"]
    heads = cfg["ssm_heads"] or d_inner // 64
    B, T, _ = u.shape
    z = prec.mm(u, p["z_proj"]["w"])
    x = jax.nn.silu(causal_conv(prec.mm(u, p["x_proj"]["w"]), p["conv"]["x"]))
    Bm = jax.nn.silu(causal_conv(prec.mm(u, p["b_proj"]["w"]), p["conv"]["b"]))
    Cm = jax.nn.silu(causal_conv(prec.mm(u, p["c_proj"]["w"]), p["conv"]["c"]))
    dt = jax.nn.softplus(prec.mm(u, p["dt_proj"]["w"]) + p["ssd"]["dt_bias"])
    A = -jnp.exp(p["ssd"]["A_log"])
    x = x.reshape(B, T, heads, d_inner // heads)
    y = ssd(x, dt, A, Bm, Cm, prec) + x * p["ssd"]["D"][:, None]
    y = rmsnorm(y.reshape(B, T, d_inner), p["norm"]["scale"]) * jax.nn.silu(z)
    return prec.mm(y, p["out_proj"]["w"])


def generator(p, tokens, cfg: dict, prec: Prec):
    """tokens (B, T) -> (final-normed hidden (B, T, d), logits (B, T, V))."""
    h = p["embed"]["table"][tokens]
    for i in range(num_layers(p["blocks"])):
        b = layer(p["blocks"], i)
        h = h + mixer(b["mixer"], rmsnorm(h, b["ln"]["scale"]), prec, cfg)
    h = rmsnorm(h, p["final_norm"]["scale"])
    return h, prec.mm(h, p["lm_head"]["w"])
