"""Plain federated adversarial training: the reference that decides a
training cell's ``correct``.

One agent's step, as FedGAN runs it (Algorithm 1 of arXiv:2006.07228 with
the language-model pair of the program):

* G is the backbone; its final-normed hidden states are the fake
  features and the embedding rows of the real tokens the real ones.
* D is a bidirectional transformer encoder over features: an input
  projection, pre-norm blocks with rotary attention and SwiGLU, a final
  RMSNorm, the mean over positions and a linear head to one logit.
* D's loss is mean softplus(-D(real)) + mean softplus(D(fake)), with
  neither feature set carrying gradient to G.  G's loss is the next-token
  cross entropy over its logits plus 0.1 mean softplus(-D(fake)).
* Both players take an Adam step (b1 0.5, b2 0.999, eps 1e-8) at the
  traffic's learning rate.

A round is K such steps on every agent, each on a batch drawn uniformly
from the agent's shard, then the sync that the traffic's ``sync`` names:
``bench/sync/<strategy>.py``'s ``reference``.

The batch draw follows the program's data pipeline from the round key:
the driver splits the key it is handed and uses the second half; that key
splits into K step keys, each splits into (batch, step) keys, and the
batch key into (index, extra) keys; the row indices are
``randint(index key, (P, A, batch), 0, shard size)``.
"""
from __future__ import annotations

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module
from reference.common import (Prec, decoder_block, layer, num_layers,
                              rmsnorm)

ADAM = (0.5, 0.999, 1e-8)
ADV_WEIGHT = 0.1
tmap = jax.tree_util.tree_map


def discriminator(p, feats, cfg: dict, prec: Prec):
    d, heads = cfg["disc_d_model"], cfg["disc_heads"]
    h = prec.mm(feats, p["proj_in"]["w"])
    for i in range(num_layers(p["blocks"])):
        h = decoder_block(layer(p["blocks"], i), h, prec, heads=heads,
                          kv_heads=heads, head_dim=d // heads,
                          theta=cfg["rope_theta"], causal=False)
    pooled = jnp.mean(rmsnorm(h, p["norm"]["scale"]), axis=1)
    return (prec.mm(pooled, p["head"]["w"]) + p["head"]["b"])[:, 0]


def lm_loss(logits, tokens):
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))


def grads(params, tokens, cfg: dict, gen_module, prec: Prec):
    """One agent's gradients and losses: (g_disc, g_gen, (d_loss, g_loss,
    its language-model part, its adversarial part))."""
    G = lambda gp: gen_module.generator(gp, tokens, cfg, prec)
    D = lambda dp, f: discriminator(dp, f, cfg, prec)
    real = jax.lax.stop_gradient(params["gen"]["embed"]["table"][tokens])
    fake = jax.lax.stop_gradient(G(params["gen"])[0])

    def d_loss(dp):
        return (jnp.mean(jax.nn.softplus(-D(dp, real)))
                + jnp.mean(jax.nn.softplus(D(dp, fake))))

    def g_loss(gp):
        h, logits = G(gp)
        adv = jnp.mean(jax.nn.softplus(-D(params["disc"], h)))
        lm = lm_loss(logits, tokens)
        return lm + ADV_WEIGHT * adv, (lm, adv)

    ld, gd = jax.value_and_grad(d_loss)(params["disc"])
    (lg, (lm, adv)), gg = jax.value_and_grad(g_loss, has_aux=True)(
        params["gen"])
    return gd, gg, jnp.stack([ld, lg, lm, adv])


def adam(p, g, state, lr):
    b1, b2, eps = ADAM
    count = state["count"] + 1
    mu = tmap(lambda m, x: b1 * m + (1 - b1) * x, state["mu"], g)
    nu = tmap(lambda v, x: b2 * v + (1 - b2) * x * x, state["nu"], g)
    c = count.astype(jnp.float32)
    new = tmap(lambda x, m, v: x - lr * (m / (1 - b1 ** c))
               / (jnp.sqrt(v / (1 - b2 ** c)) + eps), p, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}


def adam_init(p):
    z = lambda t: tmap(jnp.zeros_like, t)
    return {"count": jnp.zeros((), jnp.int32), "mu": z(p), "nu": z(p)}


@functools.cache
def _jitted_grads(reference: str, prec: str, cfg_json: str):
    """One jitted step per (model, precision, configuration), shared by
    every Reference that runs it."""
    gen = importlib.import_module("reference." + reference)
    return jax.jit(functools.partial(grads, cfg=json.loads(cfg_json),
                                     gen_module=gen, prec=Prec(prec)))


class Reference:
    """The reference run of one cell's checked rounds.  ``prec="fp8"`` is
    the control; ``fault`` plants one of the faults a run must catch:
    ``"unchanged"`` (steps leave the state as it was), ``"half_batch"``
    (each step sees half of its batch) or ``"no_sync"`` (the sync between
    agents is left out)."""

    def __init__(self, config: dict, traffic: dict, prec: str = "f32",
                 fault: str = ""):
        self.cfg = config["config"]
        self.traffic = traffic
        self.prec = Prec(prec)
        self.fault = fault
        self._grads = _jitted_grads(config["reference"], prec,
                                    json.dumps(self.cfg, sort_keys=True))
        self._adam = jax.jit(adam)
        self._sync = load_module("sync", traffic["sync"]["strategy"]
                                 ).reference(traffic["sync"])

    def batch_rows(self, round_key, n: int):
        """(K, agents, batch) row indices of one round."""
        t = self.traffic
        rb = jax.random.split(round_key)[1]
        rows = []
        for k in jax.random.split(rb, t["K"]):
            k_idx = jax.random.split(jax.random.split(k)[0])[0]
            idx = jax.random.randint(k_idx, (1, t["agents"], t["batch"]), 0,
                                     jnp.full((1, t["agents"], 1), n,
                                              jnp.int32))
            rows.append(np.asarray(idx[0]))
        return np.stack(rows)

    def run(self, params0, shards, round_keys, rounds: int):
        """Run ``rounds`` rounds from ``params0`` (one agent's tree) on the
        (agents, N, T) ``shards``; ``round_keys(r)`` is round r's key.
        Returns the readings the program's are compared with: each
        round's mean losses (``harness.compare.LOSSES``) over agents and
        steps, each leaf's per-agent norm of Adam's first moment after the
        first round, and of the parameters' change after the last."""
        with jax.default_matmul_precision("highest"):
            return self._run(params0, shards, round_keys, rounds)

    def _run(self, params0, shards, round_keys, rounds):
        t = self.traffic
        A, lr = t["agents"], jnp.float32(t["lr"])
        params = [params0] * A
        opt = [{"gen": adam_init(params0["gen"]),
                "disc": adam_init(params0["disc"])} for _ in range(A)]
        ef, losses, mu = None, [], None
        for r in range(rounds):
            rows = self.batch_rows(round_keys(r), shards.shape[1])
            loss_sum = np.zeros(4)
            for k in range(t["K"]):
                for a in range(A):
                    idx = rows[k, a]
                    if self.fault == "half_batch":
                        idx = idx[:len(idx) // 2]
                    gd, gg, ls = self._grads(params[a], shards[a][idx])
                    if self.fault != "unchanged":
                        gen, og = self._adam(params[a]["gen"], gg,
                                             opt[a]["gen"], lr)
                        disc, od = self._adam(params[a]["disc"], gd,
                                              opt[a]["disc"], lr)
                        params[a] = {"gen": gen, "disc": disc}
                        opt[a] = {"gen": og, "disc": od}
                    loss_sum += np.asarray(ls)
            losses.append(loss_sum / (A * t["K"]))
            if r == 0:
                mu = [_named(_norms({"gen": o["gen"]["mu"],
                                     "disc": o["disc"]["mu"]})) for o in opt]
            if self.fault != "no_sync":
                params, ef = self._sync(params, ef, [1.0 / A] * A)
        change = [_named(_change_norms(p, params0)) for p in params]
        return {"losses": np.asarray(losses),
                "mu": _by_agent(mu), "change": _by_agent(change)}


_norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
_norms = jax.jit(lambda tree: tmap(_norm, tree))
_change_norms = jax.jit(lambda p, p0: tmap(lambda a, b: _norm(a - b), p, p0))


def _named(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_name(p): float(x) for p, x in flat}


def _by_agent(per_agent: list) -> dict:
    return {k: np.asarray([d[k] for d in per_agent]) for k in per_agent[0]}


def _name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)
