"""FedAvg: every agent's parameters of both players replaced by the
agents' weighted mean, on a plain wire or, where the traffic names a
``codec``, on a block-quantized one with error feedback.

A traffic file's ``sync`` names this file by its ``strategy``;
``program(sync)`` builds the program's strategy and ``reference(sync)``
the plain reference's, a function ``(per-agent parameter trees, carried
state, agent weights) -> (per-agent parameter trees, carried state)``.

With a codec the mean runs over each agent's block-quantized image of
(parameters + its error residual), and the result is quantized again
against the server's residual; both residuals are carried to the next
round.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

tmap = jax.tree_util.tree_map


def program(sync: dict):
    from repro.comm import IntQuant
    from repro.core.strategies import FedAvgSync
    codec = sync.get("codec")
    if codec is None:
        return FedAvgSync()
    return FedAvgSync(codec=IntQuant(bits=codec["bits"], block=codec["block"]),
                      error_feedback=sync["error_feedback"])


def quantize(y, qmax: int, block: int):
    """Block-quantized image of a flat (N,) stream, N a block multiple:
    per block the f16 scale of max-abs / qmax (1 where it is 0), and codes
    rounded half to even and clipped to +-qmax."""
    t = y.reshape(-1, block)
    f16max = float(np.finfo(np.float16).max)
    s = jax.lax.optimization_barrier(
        jnp.minimum(jnp.max(jnp.abs(t), -1, keepdims=True) / qmax, f16max)
        .astype(jnp.float16)).astype(jnp.float32)
    s = jnp.where(s > 0, s, 1.0)
    return (jnp.clip(jnp.round(t / s), -qmax, qmax) * s).reshape(-1)


def coded_mean(xs, es, ed, w, qmax: int, block: int):
    """One leaf's coded sync: xs and es are each agent's (N,) value and
    residual, ed the server's residual.  Returns (synced, new es, new ed)."""
    n = xs[0].size
    pad = (-n) % block
    flat = lambda a: jnp.pad(a.reshape(-1), (0, pad))
    ys = [flat(x) + flat(e) for x, e in zip(xs, es)]
    qs = [quantize(y, qmax, block) for y in ys]
    m = sum(wi * q for wi, q in zip(w, qs))
    yd = m + flat(ed)
    qd = quantize(yd, qmax, block)
    back = lambda a: a[:n].reshape(xs[0].shape)
    return back(qd), [back(y - q) for y, q in zip(ys, qs)], back(yd - qd)


def _plain(params, carried, w):
    mean = tmap(lambda *xs: sum(wi * x for wi, x in zip(w, xs)), *params)
    return [mean] * len(params), carried


def _coded(coded, params, carried, w):
    A = len(params)
    leaves = [jax.tree_util.tree_flatten(p)[0] for p in params]
    treedef = jax.tree_util.tree_structure(params[0])
    if carried is None:
        zero = [jnp.zeros_like(x) for x in leaves[0]]
        carried = ([zero] * A, zero)
    es, ed = carried
    out, new_es, new_ed = [], [[] for _ in range(A)], []
    for j in range(len(leaves[0])):
        s, e2, d2 = coded([l[j] for l in leaves], [e[j] for e in es],
                          ed[j], w)
        out.append(s)
        for a in range(A):
            new_es[a].append(e2[a])
        new_ed.append(d2)
    synced = jax.tree_util.tree_unflatten(treedef, out)
    return [synced] * A, (new_es, new_ed)


def reference(sync: dict):
    codec = sync.get("codec")
    if codec is None:
        return _plain
    if not sync.get("error_feedback"):
        raise ValueError("the reference codes the wire with error feedback "
                         "only")
    coded = jax.jit(functools.partial(
        coded_mean, qmax=2 ** (codec["bits"] - 1) - 1, block=codec["block"]))
    return functools.partial(_coded, coded)
