"""FedGAN — Algorithm 1 of the paper, as a composable JAX module.

Representation: *agent-stacked* state.  Every parameter/optimizer leaf gets a
leading (P, A) axis — P pods x A agents-per-pod, B = P*A agents total.  On
the production mesh that axis is sharded over ("pod", "data"), so

  * local steps  = vmap over (P, A)  ->  embarrassingly parallel, ZERO
    cross-agent communication (tensor-parallel collectives over "model"
    happen inside each agent's step);
  * the K-step sync = dataset-size-weighted average over (P, A)  ->  ONE
    all-reduce over ("pod", "data") — exactly the intermediary of eq. (2),
    realised TPU-idiomatically.

The same code runs unsharded on CPU for the paper's experiments (P=1, A=B).

Aggregation is pluggable: a :class:`repro.core.strategies.SyncStrategy`
owns when / what / how agents sync (and its own §3.2 wire-byte
accounting).  The paper's algorithm is ``FedAvgSync()`` (the default); the
per-step baseline is ``PerStepGradAvg()``; see ``repro.core.strategies``
for generator-only sharing, participation subsampling, hierarchical and
adaptive-K schedules.  The old closed-world ``mode: str`` field remains as
a deprecated shim that resolves to the equivalent strategy.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import strategies as sync_strategies
from repro.dist import collectives
from repro.optim import Adam, Optimizer, TimeScales, equal_timescale, constant

Params = Any
tmap = jax.tree_util.tree_map


@dataclasses.dataclass(frozen=True)
class GANTask:
    """Adapter between FedGAN and a concrete (G, D) model pair.

    init(rng) -> {"gen": ..., "disc": ...}
    disc_loss(params, batch, rng) -> scalar minimised in params["disc"]
    gen_loss(params, batch, rng) -> scalar minimised in params["gen"]
    Losses must stop-gradient the other player's contribution themselves
    (simultaneous updates, eq. (1)).
    """

    init: Callable[[jax.Array], Params]
    disc_loss: Callable[[Params, Any, jax.Array], jax.Array]
    gen_loss: Callable[[Params, Any, jax.Array], jax.Array]
    # Optional fused gradient path: (params, batch, rng) ->
    # (grad_disc, grad_gen, metrics).  Used to share the generator forward
    # pass between the two objectives (the separate-loss default runs G
    # forward twice).
    fused_grads: Callable[[Params, Any, jax.Array], Any] | None = None


@dataclasses.dataclass(frozen=True)
class FedGANConfig:
    agent_grid: tuple[int, int] = (1, 5)  # (P pods, A agents/pod); B = P*A
    sync_interval: int = 20               # K
    strategy: Any = None                  # SyncStrategy; None -> FedAvgSync
    dp: Any = None                        # repro.privacy.DPSGD; None -> no DP
    # -- deprecated closed-world fields, kept as a shim ---------------------
    mode: str = ""                        # fedgan|distributed|local_only|hierarchical
    intra_interval: int = 0               # K1 for the hierarchical shim
    sync_dtype: Any = None                # e.g. jnp.bfloat16 — compressed sync
    average_opt_state: bool = False       # optionally FedAvg the Adam moments too

    @property
    def num_agents(self) -> int:
        return self.agent_grid[0] * self.agent_grid[1]

    def resolve_strategy(self) -> sync_strategies.SyncStrategy:
        """The strategy this config denotes.  Explicit ``strategy`` wins;
        a legacy ``mode`` string resolves through the deprecation shim.
        Mixing the two is an error — the legacy knobs would be silently
        ignored otherwise."""
        if self.strategy is not None:
            legacy = {k: v for k, v in
                      (("mode", self.mode),
                       ("intra_interval", self.intra_interval),
                       ("sync_dtype", self.sync_dtype),
                       ("average_opt_state", self.average_opt_state)) if v}
            if legacy:
                raise ValueError(
                    f"strategy={self.strategy!r} conflicts with the "
                    f"deprecated config field(s) {sorted(legacy)}; move "
                    "them onto the strategy (e.g. "
                    "FedAvgSync(sync_dtype=...))")
            return self.strategy
        if self.mode:
            warnings.warn(
                f"FedGANConfig(mode={self.mode!r}) is deprecated; pass "
                "strategy= a repro.core.strategies.SyncStrategy instead "
                f"(e.g. strategies.strategy_from_mode({self.mode!r}))",
                DeprecationWarning, stacklevel=2)
            return sync_strategies.strategy_from_mode(
                self.mode, intra_interval=self.intra_interval,
                sync_dtype=self.sync_dtype,
                average_opt_state=self.average_opt_state)
        return sync_strategies.FedAvgSync(
            sync_dtype=self.sync_dtype,
            average_opt_state=self.average_opt_state)

    def validate(self):
        strat = self.resolve_strategy()  # raises on unknown mode strings
        strat.validate(self)
        if self.dp is not None:
            self.dp.validate()


def uniform_weights(cfg: FedGANConfig) -> jax.Array:
    P, A = cfg.agent_grid
    return jnp.full((P, A), 1.0 / (P * A), jnp.float32)


def dataset_weights(sizes) -> jax.Array:
    """p_i = |R_i| / sum_j |R_j|  (paper §3.1)."""
    s = jnp.asarray(sizes, jnp.float32)
    return s / jnp.sum(s)


@dataclasses.dataclass(frozen=True)
class FedGAN:
    task: GANTask
    cfg: FedGANConfig
    opt_g: Optimizer = Adam()
    opt_d: Optimizer = Adam()
    scales: TimeScales = dataclasses.field(
        default_factory=lambda: equal_timescale(constant(1e-3)))
    weights: Any = None  # (P, A) p_i; None -> uniform

    # ------------------------------------------------------------------
    def _w(self):
        w = uniform_weights(self.cfg) if self.weights is None else jnp.asarray(self.weights)
        return w / jnp.sum(w)

    def init_state(self, rng, *, agent_grid=None) -> dict:
        """All agents start from the same (w_hat, theta_hat) — Algorithm 1.
        Strategies may carry extra entries across rounds (e.g. the
        error-feedback residuals of a compressed sync) — those are merged
        here so every state-construction path gets them.

        ``agent_grid`` overrides the config grid for the broadcast — the
        virtual-client runtime uses a ``(1, 1)`` slot-view init to build
        the one per-client template row every not-yet-materialized client
        shares (Algorithm 1 starts the whole fleet from the same point)."""
        P, A = agent_grid or self.cfg.agent_grid
        params = self.task.init(rng)
        opt_g = self.opt_g.init(params["gen"])
        opt_d = self.opt_d.init(params["disc"])
        stacked = tmap(lambda x: jnp.broadcast_to(x, (P, A) + x.shape),
                       {"params": params, "opt_g": opt_g, "opt_d": opt_d})
        state = {**stacked, "step": jnp.zeros((), jnp.int32)}
        state.update(self.cfg.resolve_strategy().init_round_state(self, state))
        return state

    # ------------------------------------------------------------------
    # averaging primitives (legacy helpers; strategies call collectives
    # directly with their own knobs)
    # ------------------------------------------------------------------
    def _avg_full(self, tree):
        """Weighted average over (P, A) then broadcast back — eq. (2)+(3).
        Lowers to ONE all-reduce over ("pod","data") on the mesh."""
        return collectives.average_agents(tree, self._w(),
                                          sync_dtype=self.cfg.sync_dtype)

    def _avg_intra_pod(self, tree):
        """Average within each pod only (hierarchical tier 1)."""
        return collectives.average_intra_pod(tree, self._w())

    def _sync(self, state):
        new = dict(state)
        new["params"] = self._avg_full(state["params"])
        if self.cfg.average_opt_state:
            new["opt_g"] = self._avg_full(state["opt_g"])
            new["opt_d"] = self._avg_full(state["opt_d"])
        return new

    # ------------------------------------------------------------------
    # one simultaneous local step on every agent
    # ------------------------------------------------------------------
    def _local_grads(self, params, batch, rng):
        if self.task.fused_grads is not None:
            return self.task.fused_grads(params, batch, rng)
        rd, rg = jax.random.split(rng)
        ld, gd = jax.value_and_grad(
            lambda d: self.task.disc_loss({**params, "disc": d}, batch, rd))(params["disc"])
        lg, gg = jax.value_and_grad(
            lambda g: self.task.gen_loss({**params, "gen": g}, batch, rg))(params["gen"])
        return gd, gg, {"d_loss": ld, "g_loss": lg}

    def _step(self, state, step_input):
        """One parallel step across all agents.  step_input = (batch, rngs)
        with leading (P, A) axes.

        ``rngs`` is a (P, A) typed PRNG key array (the canonical path —
        keys are split off the round key, so no two agents/steps can
        collide).  A (P, A) uint32 array is also accepted as a compat shim
        for the seed-threading callers: each seed is folded into a fixed
        base key, which has birthday-collision risk across the fleet and
        survives only for bit-parity with pre-`repro.run` trajectories."""
        batch, rngs = step_input
        strat = self.cfg.resolve_strategy()
        n = state["step"]
        lr_a = self.scales.a(n.astype(jnp.float32))
        lr_b = self.scales.b(n.astype(jnp.float32))

        if self.cfg.dp is not None:
            # per-agent DP-SGD: per-example clip + Gaussian noise replace
            # the plain minibatch gradient (repro.privacy.dpsgd)
            from repro.privacy.dpsgd import dp_grads
            grads_of = lambda params, b, rng: dp_grads(
                self._local_grads, params, b, rng, self.cfg.dp)
        else:
            grads_of = self._local_grads

        if jnp.issubdtype(rngs.dtype, jax.dtypes.prng_key):
            def agent_grads(params, b, rng):
                return grads_of(params, b, rng)
        else:  # legacy uint32 seeds
            def agent_grads(params, b, seed):
                rng = jax.random.fold_in(jax.random.key(0), seed)
                return grads_of(params, b, rng)

        # named scopes tag the ops' HLO metadata (op_name) only, so a
        # profiler trace can split the round into sample / local step /
        # update / sync; they change no computation
        with jax.named_scope("fedgan.local_step"):
            gd, gg, metrics = jax.vmap(jax.vmap(agent_grads))(
                state["params"], batch, rngs)

        # per-step aggregation hook (PerStepGradAvg averages grads here —
        # the paper's distributed-GAN baseline communication pattern)
        with jax.named_scope("fedgan.sync"):
            gd, gg = strat.grad_hook(self, gd, gg, state)

        def upd_d(d, g, s):
            return self.opt_d.update(d, g, s, lr_a)

        def upd_g(p, g, s):
            return self.opt_g.update(p, g, s, lr_b)

        with jax.named_scope("fedgan.update"):
            new_disc, new_opt_d = jax.vmap(jax.vmap(upd_d))(
                state["params"]["disc"], gd, state["opt_d"])
            new_gen, new_opt_g = jax.vmap(jax.vmap(upd_g))(
                state["params"]["gen"], gg, state["opt_g"])

        new_state = {
            **state,  # strategy-carried entries (e.g. EF residuals) ride along
            "params": {"gen": new_gen, "disc": new_disc},
            "opt_g": new_opt_g, "opt_d": new_opt_d,
            "step": n + 1,
        }
        return new_state, tmap(jnp.mean, metrics)

    # ------------------------------------------------------------------
    # one K-step round (the jitted unit; this is what the dry-run lowers)
    # ------------------------------------------------------------------
    def _run_round(self, state, xs, body):
        """Shared K-step scan + strategy sync.  ``xs`` leaves carry a
        leading K dim; ``body(state, x)`` is one parallel step."""
        strat = self.cfg.resolve_strategy()
        K = self.cfg.sync_interval
        K1 = strat.intra_interval

        if K1:
            segs = K // K1

            def seg_body(st, seg_in):
                st, m = jax.lax.scan(body, st, seg_in)
                with jax.named_scope("fedgan.sync"):
                    return strat.segment_sync(self, st), m

            seg_in = tmap(lambda x: x.reshape((segs, K1) + x.shape[1:]), xs)
            state, metrics = jax.lax.scan(seg_body, state, seg_in)
            metrics = tmap(lambda x: x.reshape((K,) + x.shape[2:]), metrics)
        else:
            state, metrics = jax.lax.scan(body, state, xs)
        with jax.named_scope("fedgan.sync"):
            return strat.round_sync(self, state), metrics

    def round(self, state, batches, seeds):
        """batches: pytree with leading (K, P, A, ...); seeds: (K, P, A) —
        uint32 seeds (legacy) or a typed PRNG key array.  Runs K local
        steps then syncs per the configured strategy."""
        self.cfg.validate()
        return self._run_round(state, (batches, seeds), self._step)

    def _step_from_data(self, data, state, key):
        """One step whose minibatch is sampled *inside* the trace: draw a
        (P, A, batch, ...) batch from ``data`` and per-agent step keys."""
        P, A = self.cfg.agent_grid
        k_batch, k_step = jax.random.split(key)
        with jax.named_scope("fedgan.sample"):
            batch = data.sample_step(k_batch)
        rngs = jax.random.split(k_step, P * A).reshape(P, A)
        return self._step(state, (batch, rngs))

    def round_from_data(self, state, data, key):
        """Sampling-aware round: the K minibatches are drawn *inside* the
        jitted round from ``data`` (anything with ``sample_step(key) ->
        (P, A, batch, ...) pytree``, e.g. a device-resident
        ``repro.data.DeviceFederatedData``) instead of being materialized
        on host as a (K, P, A, batch, ...) tensor.  Eliminates the K× per
        round host->device transfer and the per-agent assembly loop; RNG
        is a properly threaded split key (no seed folding)."""
        self.cfg.validate()
        keys = jax.random.split(key, self.cfg.sync_interval)
        body = lambda st, k: self._step_from_data(data, st, k)
        return self._run_round(state, keys, body)

    # ------------------------------------------------------------------
    def agent_params(self, state, p: int = 0, a: int = 0):
        return tmap(lambda x: x[p, a], state["params"])

    def agent_opt_state(self, state, p: int = 0, a: int = 0):
        return {k: tmap(lambda x: x[p, a], state[k])
                for k in ("opt_g", "opt_d")}

    def averaged_params(self, state):
        """The intermediary's (w_n, theta_n) — weighted average, no broadcast."""
        w = self._w()
        return tmap(lambda x: jnp.einsum("pa,pa...->...", w.astype(x.dtype), x),
                    state["params"])

    def comm_bytes_per_round(self, state) -> dict:
        """§3.2 accounting.  The analytic comparison (FedGAN moves 2·2M per
        agent per ROUND, the distributed baseline 2·2M per STEP) plus the
        configured strategy's own wire-byte accounting."""
        strat = self.cfg.resolve_strategy()
        params = self.agent_params(state)
        M_bytes = collectives.tree_bytes(params)
        K = self.cfg.sync_interval
        per_round = {"fedgan": 2 * M_bytes, "distributed": 2 * M_bytes * K}
        codec = getattr(strat, "codec", None)
        return {"param_bytes_M": M_bytes, "per_agent_per_round": per_round,
                "ratio": K, "strategy": strat.name,
                "codec": codec.name if codec is not None else None,
                "strategy_bytes_per_round": strat.bytes_per_round(
                    self.cfg, params, opt=self.agent_opt_state(state))}
