"""The one traffic generator: every run's inputs and weights come from
``--seed`` through the streams below, so one seed always gives the same
work, and every traffic file is read by the same code.

A training job's traffic is each agent's shard of token sequences and the
key of every round.  The shards are non-IID: each agent draws most tokens
from its own slice of the vocabulary and the rest from the whole of it.
The sampler is a copy of ``repro.data.synthetic.sample_agent_tokens``,
kept here so that no change to the program can move the traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# independent streams drawn from one seed
STREAMS = {"weights": 0, "data": 1, "rounds": 2}


def key_of(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64: the low 32 bits seed
    the key and the high 32 bits are folded in, so seeds above 2**32 do
    not collide with their low halves."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def stream(seed: int, what: str):
    return jax.random.fold_in(key_of(seed), STREAMS[what])


def sample_agent_tokens(rng, n: int, seq_len: int, vocab: int, *, agent: int,
                        num_agents: int, shared: float = 0.3):
    """Non-IID token sequences: each agent draws from a distinct slice of
    the vocabulary, and a ``shared`` fraction of positions from all of it.
    (A copy of the program's sampler; ``shared`` was a constant 0.3.)"""
    k1, k2 = jax.random.split(jax.random.fold_in(rng, agent))
    shard = max(vocab // num_agents, 2)
    base = jax.random.randint(k1, (n, seq_len), 0, shard)
    offset = min(agent * shard, max(vocab - shard, 0))
    whole = jax.random.randint(k2, (n, seq_len), 0, vocab)
    use_shared = jax.random.bernoulli(k2, shared, (n, seq_len))
    return jnp.where(use_shared, whole, base + offset).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("agents", "n", "seq_len",
                                             "vocab", "shared"))
def _shards(rng, *, agents, n, seq_len, vocab, shared):
    return jnp.stack([sample_agent_tokens(rng, n, seq_len, vocab, agent=i,
                                          num_agents=agents, shared=shared)
                      for i in range(agents)])


def agent_shards(seed: int, traffic: dict, vocab: int):
    """(agents, sequences_per_agent, seq_len) int32 tokens, made on the
    device in one jitted call."""
    return _shards(stream(seed, "data"), agents=traffic["agents"],
                   n=traffic["sequences_per_agent"],
                   seq_len=traffic["seq_len"], vocab=vocab,
                   shared=float(traffic["shared_fraction"]))


_fold = jax.jit(jax.random.fold_in)


class RoundKeys:
    """``keys(r)`` is the key handed to round ``r``'s driver call."""

    def __init__(self, seed: int):
        self.base = stream(seed, "rounds")

    def __call__(self, r: int):
        return _fold(self.base, r)


def tokens_per_round(traffic: dict) -> int:
    return (traffic["agents"] * traffic["K"] * traffic["batch"]
            * traffic["seq_len"])
