"""The system under test: a cell's federated round, built through the
program's own entry points and driven one round per call.

``RunSpec`` builds the ``FedGAN`` and its device-resident data; the round
is ``RoundDriver.run`` with one round per call and the state carried from
call to call, which ends in ``block_until_ready``.  The initial state is
``FedGAN.init_state``'s, with the parameters replaced by the benchmark's
seeded weights (one jitted call on the device).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from harness import traffic as traffic_gen
from harness.spec import load_module
from harness.weights import leaf_name, maker

tmap = jax.tree_util.tree_map


def arch_config(config: dict, *, smoke: bool = False):
    """The ``ArchConfig`` a configuration file describes: the registry's
    entry with every field of the file's ``config`` applied."""
    from repro.configs import get_config
    fields = dict(config["config"])
    for k in ("dtype", "param_dtype"):
        fields[k] = getattr(jnp, fields[k])
    cfg = get_config(config["arch"]).scaled(**fields)
    return cfg.smoke() if smoke else cfg


def config_dict(cfg) -> dict:
    """An ``ArchConfig`` as the plain numbers a configuration file holds."""
    d = dataclasses.asdict(cfg)
    for k in ("dtype", "param_dtype"):
        d[k] = jnp.dtype(d[k]).name
    return d


def strategy(traffic: dict):
    """The program's sync strategy that the traffic's ``sync`` names:
    ``bench/sync/<strategy>.py``'s ``program``."""
    sync = traffic["sync"]
    return load_module("sync", sync["strategy"]).program(sync)


def _norm(x):
    """Per-agent norm of a (P, A, ...) leaf: (P, A)."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                            axis=tuple(range(2, x.ndim))))


_moment_norms = jax.jit(lambda st: tmap(_norm, {"gen": st["opt_g"]["mu"],
                                                "disc": st["opt_d"]["mu"]}))
_change_norms = jax.jit(lambda params, p0: tmap(
    lambda x, y: _norm(x - y[None, None]), params, p0))


@jax.jit
def agent_spread(params):
    """Largest |agent i - agent 0| over every parameter: 0 after a sync."""
    return jnp.max(jnp.stack([jnp.max(jnp.abs(x - x[:1, :1]))
                              for x in jax.tree_util.tree_leaves(params)]))


def by_leaf(tree) -> dict:
    """{leaf name: per-agent values} from a tree of (P, A) arrays."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {leaf_name(p): np.asarray(x).reshape(-1) for p, x in flat}


def build_round(cfg, traffic: dict, agent_data: list):
    """(FedGAN, RoundDriver) of a cell: the ``RunSpec`` of its traffic with
    device-resident data and the CLI's ``log_every=1``, and a driver that
    runs one round per call."""
    from repro.launch.steps import make_lm_gan_task
    from repro.launch.train import RunSpec
    from repro.optim import Adam, constant, equal_timescale
    from repro.run.driver import RoundDriver
    t = traffic
    spec = RunSpec(
        task=make_lm_gan_task(cfg), agent_data=agent_data,
        agent_grid=(1, t["agents"]), K=t["K"], steps=t["K"],
        batch_size=t["batch"], scales=equal_timescale(constant(t["lr"])),
        opt_g=Adam(), opt_d=Adam(), strategy=strategy(t), seed=0,
        log_every=1, data_mode="device")
    fed, _ = spec.build()
    return fed, RoundDriver(fed, spec.build_data(), 1,
                            log_every=spec.log_every, verbose=False)


class Program:
    """One cell's round on this process's devices, from ``seed``."""

    def __init__(self, cfg, config: dict, traffic: dict, seed: int):
        t = traffic
        self.shards = traffic_gen.agent_shards(seed, t, cfg.vocab_size)
        self.fed, self.driver = build_round(
            cfg, t, [{"tokens": self.shards[i]} for i in range(t["agents"])])
        fed = self.fed
        shapes = jax.eval_shape(fed.task.init, jax.random.key(0))
        self.make_params = maker(shapes, config["init"])
        grid = (1, t["agents"])

        @jax.jit
        def make_state(key):
            state = fed.init_state(key)
            params = self.make_params(key)
            state["params"] = tmap(lambda x: jnp.broadcast_to(
                x, grid + x.shape), params)
            return state

        self.wkey = traffic_gen.stream(seed, "weights")
        self.state = make_state(self.wkey)
        self.keys = traffic_gen.RoundKeys(seed)

    def run_round(self, key):
        """One round through the driver from ``key``; returns its
        RunResult and keeps its state for the next call."""
        res = self.driver.run(key, state=self.state)
        self.state = res.state
        return res

    def params0(self):
        """The initial weights of one agent, made again from the seed."""
        return self.make_params(self.wkey)

    def moment_norms(self) -> dict:
        return by_leaf(_moment_norms(self.state))

    def change_norms(self) -> dict:
        return by_leaf(_change_norms(self.state["params"], self.params0()))
