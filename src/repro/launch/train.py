"""FedGAN training launcher.

Two entry modes:
  --experiment <paper_exp>   run one of the paper's experiments on synthetic
                             stand-in data (CPU-friendly; §4 of the paper)
  --arch <id>                federated adversarial training of an assigned
                             backbone at reduced scale (smoke-size by
                             default; full scale only makes sense on TPU)

Aggregation is selected with --strategy (see repro.core.strategies), e.g.:

  PYTHONPATH=src python -m repro.launch.train --experiment toy_2d --K 20
  PYTHONPATH=src python -m repro.launch.train --experiment toy_2d \
      --strategy hierarchical --intra-interval 5
  PYTHONPATH=src python -m repro.launch.train --experiment swiss_roll \
      --strategy partial_sharing --sync-dtype bf16
  PYTHONPATH=src python -m repro.launch.train --experiment mixed_gaussian \
      --codec int8          # quantized sync wire + error feedback
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-2.7b --steps 40

The legacy --mode flag still works (it resolves through the deprecation
shim, including the hierarchical/--intra-interval plumbing that used to be
unreachable from the CLI).
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (ACGAN, CONDITIONAL, FedGAN, FedGANConfig, GANTask,
                        make_gan_task, strategies)
from repro.data import (DeviceFederatedData, FederatedRounds,
                        StreamingFederatedData, synthetic)
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import Adam, constant, equal_timescale

tmap = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# Paper experiment tasks (all through the make_gan_task factory)
# ---------------------------------------------------------------------------


def toy2d_task():
    from repro.models.gan_nets import Toy2DDiscriminator, Toy2DGenerator
    G, D = Toy2DGenerator(theta0=0.5), Toy2DDiscriminator(psi0=0.5)
    return make_gan_task(G, D), (G, D)


def mlp_gan_task(data_dim=2, latent=2, hidden=128):
    from repro.models.gan_nets import MLPDiscriminator, MLPGenerator
    G = MLPGenerator(latent_dim=latent, out_dim=data_dim, hidden=hidden)
    D = MLPDiscriminator(in_dim=data_dim, hidden=hidden)
    return make_gan_task(G, D), (G, D)


def acgan_task(hw=16, channels=3, num_classes=10, latent=62):
    from repro.models.gan_nets import ACGANDiscriminator, ACGANGenerator
    G = ACGANGenerator(latent_dim=latent, num_classes=num_classes, image_hw=hw,
                       channels=channels)
    D = ACGANDiscriminator(num_classes=num_classes, image_hw=hw, channels=channels)
    return make_gan_task(G, D, ACGAN), (G, D)


def cgan1d_task(seq_len=24, label_dim=5):
    from repro.models.gan_nets import CGAN1DDiscriminator, CGAN1DGenerator
    G = CGAN1DGenerator(seq_len=seq_len, label_dim=label_dim)
    D = CGAN1DDiscriminator(seq_len=seq_len, label_dim=label_dim)
    return make_gan_task(G, D, CONDITIONAL), (G, D)


# ---------------------------------------------------------------------------
# RunSpec: one value object instead of the kwargs soup
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """Everything one simulated federated GAN run needs (agents stacked on
    one host).  ``build()`` gives the (FedGAN, FederatedRounds) pair;
    ``run()`` executes the round loop.

    Since the ``repro.run`` runtime landed, ``run()`` is a thin shim over
    :class:`repro.run.RoundDriver`: the default ``data_mode="stream"``
    keeps trajectories bit-identical to the pre-runtime blocking loop
    (held by ``tests/test_run_driver.py``), while ``data_mode="device"``
    switches to the device-resident sampling pipeline (different RNG
    stream, much less per-round host work).  Prefer driving the runtime
    directly for new code; this object remains the one-stop experiment
    config."""

    task: GANTask
    agent_data: list
    agent_grid: tuple[int, int] = (1, 5)
    K: int = 20
    steps: int = 100
    batch_size: int = 64
    scales: Any = None              # None -> equal_timescale(constant(1e-3))
    opt_g: Any = dataclasses.field(default_factory=Adam)
    opt_d: Any = dataclasses.field(default_factory=Adam)
    strategy: Any = None            # SyncStrategy; None -> FedAvgSync
    dp: Any = None                  # repro.privacy.DPSGD; None -> no DP
    sample_extra: Any = None
    weights: Any = None
    seed: int = 0
    log_every: int = 1
    ckpt_dir: str = ""
    data_mode: str = "stream"       # "stream" (legacy-parity) | "device"
    rounds_per_chunk: int = 1       # device mode: rounds per scan dispatch
    eval_every: int = 0             # rounds between eval-hook points
    eval_hooks: Any = ()
    # -- virtual-client scheduler (repro.run.virtual) -----------------------
    a_total: int = 0                # fleet size; 0 = dense (all on device)
    participation_seed: int = 0     # ParticipationSchedule seed
    straggler_policy: str = "block"  # "block" | "defer"

    @property
    def n_rounds(self) -> int:
        return max(self.steps // self.K, 1)

    @property
    def virtual(self) -> bool:
        """True when this spec runs the virtual-client scheduler: the fleet
        (``agent_data``, len ``a_total``) is larger than the device slot
        grid ``agent_grid`` and cohorts are paged per round."""
        return self.a_total > 0

    def build(self):
        fed = FedGAN(self.task,
                     FedGANConfig(agent_grid=self.agent_grid,
                                  sync_interval=self.K,
                                  strategy=self.strategy, dp=self.dp),
                     opt_g=self.opt_g, opt_d=self.opt_d,
                     scales=self.scales or equal_timescale(constant(1e-3)),
                     weights=self.weights)
        rounds = FederatedRounds(self.agent_data, self.agent_grid,
                                 self.batch_size, self.K,
                                 sample_extra=self.sample_extra)
        return fed, rounds

    def build_data(self):
        """The FederatedData pipeline ``data_mode`` denotes."""
        if self.data_mode == "device":
            return DeviceFederatedData.from_agent_data(
                self.agent_data, self.agent_grid, self.batch_size,
                sample_extra=self.sample_extra)
        if self.data_mode == "stream":
            return StreamingFederatedData.from_agent_data(
                self.agent_data, self.agent_grid, self.batch_size, self.K,
                sample_extra=self.sample_extra)
        raise ValueError(f"unknown data_mode {self.data_mode!r} "
                         "(expected 'stream' or 'device')")

    def build_fleet(self):
        """Virtual mode: the (FedGAN, FleetRounds) pair — the model on the
        ``agent_grid`` slot grid, the data over all ``a_total`` clients."""
        from repro.data.federated import FleetRounds
        if len(self.agent_data) != self.a_total:
            raise ValueError(f"a_total={self.a_total} but agent_data holds "
                             f"{len(self.agent_data)} client datasets")
        fed = FedGAN(self.task,
                     FedGANConfig(agent_grid=self.agent_grid,
                                  sync_interval=self.K,
                                  strategy=self.strategy, dp=self.dp),
                     opt_g=self.opt_g, opt_d=self.opt_d,
                     scales=self.scales or equal_timescale(constant(1e-3)),
                     weights=self.weights)
        fleet = FleetRounds(self.agent_data, self.agent_grid,
                            self.batch_size, self.K,
                            sample_extra=self.sample_extra)
        return fed, fleet

    def run_result(self):
        """Execute through the ``repro.run`` runtime; returns the full
        :class:`repro.run.RunResult` (state, history, evals, timings)."""
        if self.virtual:
            from repro.core.participation import ParticipationSchedule
            from repro.run.virtual import (StragglerPolicy,
                                           VirtualClientDriver)
            fed, fleet = self.build_fleet()
            driver = VirtualClientDriver(
                fed, fleet, self.n_rounds,
                schedule=ParticipationSchedule(seed=self.participation_seed),
                straggler=StragglerPolicy(mode=self.straggler_policy),
                log_every=self.log_every, verbose=bool(self.log_every),
                eval_every=self.eval_every, eval_hooks=self.eval_hooks,
                ckpt_dir=self.ckpt_dir,
                ckpt_every=max(self.n_rounds // 4, 1) if self.ckpt_dir else 0)
            return driver.run(jax.random.key(self.seed + 1))
        driver, state = self.round_driver()
        return driver.run(jax.random.key(self.seed + 1), state=state)

    def round_driver(self):
        """The dense run's :class:`repro.run.RoundDriver` and initial state,
        as ``run_result`` builds them; ``driver.run(jax.random.key(seed +
        1), state=state)`` is that run."""
        from repro.run.driver import RoundDriver
        fed, _ = self.build()
        state = fed.init_state(jax.random.key(self.seed))
        driver = RoundDriver(
            fed, self.build_data(), self.n_rounds,
            log_every=self.log_every,
            eval_every=self.eval_every, eval_hooks=self.eval_hooks,
            ckpt_dir=self.ckpt_dir,
            ckpt_every=max(self.n_rounds // 4, 1) if self.ckpt_dir else 0,
            rounds_per_chunk=self.rounds_per_chunk)
        return driver, state

    def run(self):
        """Legacy entry point: returns (fed, state, history)."""
        return self.run_result().legacy_tuple()


def train_fedgan(task, *, agent_data, agent_grid, K, steps, batch_size,
                 scales, opt_d, opt_g, strategy=None, mode="",
                 sample_extra=None, seed=0, log_every=1, ckpt_dir="",
                 weights=None):
    """Compat wrapper over RunSpec (prefer RunSpec(...).run() directly)."""
    if strategy is None and mode:
        strategy = strategies.strategy_from_mode(mode)
    return RunSpec(task=task, agent_data=agent_data, agent_grid=agent_grid,
                   K=K, steps=steps, batch_size=batch_size, scales=scales,
                   opt_g=opt_g, opt_d=opt_d, strategy=strategy,
                   sample_extra=sample_extra, weights=weights, seed=seed,
                   log_every=log_every, ckpt_dir=ckpt_dir).run()


def _pooled_real(agent_data, seed: int = 0):
    """Cross-agent pooled real samples, shuffled so any prefix is an
    unbiased draw from the GLOBAL distribution (what the paper's metrics
    compare against — never one agent's slice)."""
    xs = np.concatenate([np.asarray(d["x"]) for d in agent_data])
    return xs[np.random.RandomState(seed).permutation(len(xs))]


def experiment_spec(name: str, *, K: int | None = None,
                    steps: int | None = None, seed: int = 0, strategy=None,
                    dp=None, ckpt_dir: str = "",
                    batch_size: int | None = None,
                    agents: int | None = None, log_every: int | None = None,
                    eval_every: int = 0, data_mode: str = "stream",
                    rounds_per_chunk: int = 1, a_total: int = 0,
                    a_active: int = 0, participation_seed: int = 0,
                    straggler_policy: str = "block",
                    samples_per_agent: int | None = None):
    """Build (RunSpec, EvalSuite) for one of the paper's experiments on the
    synthetic stand-in data.  ``batch_size``/``agents``/``log_every``
    override the experiment-config defaults (the CLI knobs); the EvalSuite
    feeds the ``repro.run`` eval harness and the K-sweep runner.

    ``a_total`` switches to the virtual-client scheduler: the experiment's
    non-iid partition is dealt over ``a_total`` registered clients (mode
    assignments wrap, per-client shards shrink to ``samples_per_agent``,
    default 512, so a 1024-client fleet fits host memory) of which the
    ``ParticipationSchedule(participation_seed)``-sampled cohort of
    ``a_active`` runs per round on the device slots."""
    from repro.configs.paper_gans import ALL_EXPERIMENTS, optimizer_for, scales_for
    from repro.run.evals import EvalSuite, eval_hook
    exp = ALL_EXPERIMENTS[name]
    K = K or exp.default_K
    steps = steps or exp.iterations
    if a_total:
        if agents:
            raise ValueError("--agents conflicts with --a-total (the fleet "
                             "size IS the client count); use --a-active for "
                             "the per-round cohort size")
        B = a_total
        a_active = a_active or exp.num_agents
        if not 1 <= a_active <= a_total:
            raise ValueError(f"a_active={a_active} must be in [1, "
                             f"a_total={a_total}]")
    else:
        B = agents or exp.num_agents
    if samples_per_agent is None:
        # thousand-client fleets live host-side; shrink per-client shards so
        # the whole fleet's data fits (dense runs keep the paper-size shards)
        samples_per_agent = 512 if a_total else 0
    n_of = lambda default: samples_per_agent or default
    batch_size = batch_size or exp.batch_size
    rng = jax.random.key(seed)

    if name == "toy_2d":
        task, (G, _) = toy2d_task()
        agent_data = [{"x": synthetic.sample_2d_segment(
            jax.random.fold_in(rng, i), n_of(4096), i, B)} for i in range(B)]
        extra = lambda r, s: {"z": jax.random.uniform(r, s, minval=-1, maxval=1)}
        suite = EvalSuite(
            real=_pooled_real(agent_data, seed),
            sample_fake=lambda gp, r, n: G.apply(
                gp, jax.random.uniform(r, (n,), minval=-1, maxval=1)))
    elif name == "mixed_gaussian":
        task, (G, _) = mlp_gan_task()
        # 8 modes on the circle; with an --agents override beyond 4 the
        # mode assignment wraps (agents share modes, still non-iid pairs)
        agent_data = [{"x": synthetic.sample_mixed_gaussian(
            jax.random.fold_in(rng, i), n_of(8192),
            mode_subset=[(2 * i) % 8, (2 * i + 1) % 8])}
            for i in range(B)]
        extra = lambda r, s: {"z": jax.random.normal(r, s + (2,))}
        suite = EvalSuite(
            real=_pooled_real(agent_data, seed),
            sample_fake=lambda gp, r, n: G.apply(
                gp, jax.random.normal(r, (n, 2))),
            modes=np.asarray(synthetic.mixed_gaussian_modes()))
    elif name == "swiss_roll":
        task, (G, _) = mlp_gan_task()
        agent_data = [{"x": synthetic.sample_swiss_roll(
            jax.random.fold_in(rng, i), n_of(8192),
            t_range=(0.25 + 0.75 * i / B, 0.25 + 0.75 * (i + 1) / B))}
            for i in range(B)]
        extra = lambda r, s: {"z": jax.random.normal(r, s + (2,))}
        suite = EvalSuite(
            real=_pooled_real(agent_data, seed),
            sample_fake=lambda gp, r, n: G.apply(
                gp, jax.random.normal(r, (n, 2))))
    elif name in ("image_acgan", "celeba_acgan"):
        ncls = 16 if name == "celeba_acgan" else 10
        task, (G, _) = acgan_task(hw=16, num_classes=ncls)
        per = max(ncls // B, 1)
        def mk(i):
            # class slice wraps under an --agents override larger than the
            # class count (keeps randint bounds valid: lo < hi <= ncls)
            lo = (i * per) % ncls
            lab = jax.random.randint(jax.random.fold_in(rng, 100 + i),
                                     (n_of(2048),), lo, min(lo + per, ncls))
            img = synthetic.sample_class_images(
                jax.random.fold_in(rng, 200 + i), n_of(2048), lab, hw=16,
                num_classes=ncls)
            return {"x": img, "y": lab}
        agent_data = [mk(i) for i in range(B)]
        extra = lambda r, s: {"z": jax.random.normal(r, s + (62,))}

        def sample_images(gp, r, n, ncls=ncls):
            kz, kl = jax.random.split(r)
            lab = jax.random.randint(kl, (n,), 0, ncls)
            return G.apply(gp, jax.random.normal(kz, (n, 62)), lab)

        suite = EvalSuite(real=_pooled_real(agent_data, seed),
                          sample_fake=sample_images)
    elif name == "timeseries_cgan":
        task, (G, _) = cgan1d_task()
        def mk(i):
            cz = jnp.full((n_of(4096),), i % 5, jnp.int32)  # 5 climate zones
            x = synthetic.sample_household_load(jax.random.fold_in(rng, i),
                                                n_of(4096), climate_zone=cz)
            return {"x": x, "y": jax.nn.one_hot(cz, 5)}
        agent_data = [mk(i) for i in range(B)]
        extra = lambda r, s: {"z": jax.random.normal(r, s + (24,))}

        def sample_profiles(gp, r, n):
            kz, kl = jax.random.split(r)
            y = jax.nn.one_hot(jax.random.randint(kl, (n,), 0, 5), 5)
            return G.apply(gp, jax.random.normal(kz, (n, 24)), y)

        suite = EvalSuite(real=_pooled_real(agent_data, seed),
                          sample_fake=sample_profiles, kind="timeseries")
    else:
        raise KeyError(name)

    opt_d, opt_g = optimizer_for(exp)
    grid = (1, a_active) if a_total else (1, B)
    spec = RunSpec(
        task=task, agent_data=agent_data, agent_grid=grid, K=K, steps=steps,
        batch_size=batch_size, scales=scales_for(exp), opt_d=opt_d,
        opt_g=opt_g, strategy=strategy, dp=dp, sample_extra=extra, seed=seed,
        log_every=max((steps // K) // 10, 1) if log_every is None else log_every,
        ckpt_dir=ckpt_dir, data_mode=data_mode,
        rounds_per_chunk=rounds_per_chunk, eval_every=eval_every,
        eval_hooks=(eval_hook(suite, seed=seed),) if eval_every else (),
        a_total=a_total, participation_seed=participation_seed,
        straggler_policy=straggler_policy)
    return spec, suite


def run_experiment(name: str, *, K: int | None, steps: int | None, seed: int,
                   strategy=None, dp=None, ckpt_dir: str = "",
                   batch_size=None, agents=None, log_every=None,
                   eval_every: int = 0, data_mode: str = "stream",
                   a_total: int = 0, a_active: int = 0,
                   participation_seed: int = 0,
                   straggler_policy: str = "block",
                   samples_per_agent: int | None = None):
    spec, _ = experiment_spec(
        name, K=K, steps=steps, seed=seed, strategy=strategy, dp=dp,
        ckpt_dir=ckpt_dir, batch_size=batch_size, agents=agents,
        log_every=log_every, eval_every=eval_every, data_mode=data_mode,
        a_total=a_total, a_active=a_active,
        participation_seed=participation_seed,
        straggler_policy=straggler_policy,
        samples_per_agent=samples_per_agent)
    return spec.run()


def arch_smoke_spec(arch: str, *, steps: int, K: int, seed: int,
                    strategy=None, dp=None, ckpt_dir: str = "",
                    batch_size: int | None = None, agents: int | None = None,
                    log_every: int | None = None, data_mode: str = "stream",
                    rounds_per_chunk: int = 1, cfg=None,
                    seq_len: int = 32) -> RunSpec:
    """RunSpec for federated adversarial training of an assigned backbone
    (see :func:`run_arch_smoke`).  ``cfg`` is the ``ArchConfig`` to run;
    the default is the CPU-size ``get_config(arch).smoke()``, and e.g.
    ``get_config(arch).scaled(num_layers=2)`` runs the published widths at
    reduced depth.  Each agent holds 256 token sequences of length
    ``seq_len``."""
    from repro.configs import get_config
    from repro.launch.steps import make_lm_gan_task
    cfg = cfg or get_config(arch).smoke()
    task = make_lm_gan_task(cfg)
    B = agents or 4
    n = 256
    rng = jax.random.key(seed)
    agent_data = []
    for i in range(B):
        d = {"tokens": synthetic.sample_agent_tokens(
            rng, n, seq_len, cfg.vocab_size, agent=i,
            num_agents=B)}
        if cfg.family == "audio":
            d["frames"] = 0.1 * jax.random.normal(
                jax.random.fold_in(rng, 50 + i),
                (n, cfg.encoder_seq, cfg.d_model))
        agent_data.append(d)
    return RunSpec(
        task=task, agent_data=agent_data, agent_grid=(1, B), K=K, steps=steps,
        batch_size=batch_size or 8, scales=equal_timescale(constant(1e-3)),
        opt_d=Adam(), opt_g=Adam(), strategy=strategy, dp=dp, seed=seed,
        log_every=1 if log_every is None else log_every, ckpt_dir=ckpt_dir,
        data_mode=data_mode, rounds_per_chunk=rounds_per_chunk)


def run_arch_smoke(arch: str, *, steps: int, K: int, seed: int, strategy=None,
                   dp=None, ckpt_dir: str = "", batch_size=None, agents=None,
                   log_every=None, data_mode: str = "stream"):
    """Federated adversarial training of a reduced assigned backbone.

    With ``ckpt_dir`` the run checkpoints its FedGAN state, which a
    ``repro.serve`` engine in another process can hot-reload live — the
    two-terminal walkthrough in docs/serving.md."""
    return arch_smoke_spec(
        arch, steps=steps, K=K, seed=seed, strategy=strategy, dp=dp,
        ckpt_dir=ckpt_dir, batch_size=batch_size, agents=agents,
        log_every=log_every, data_mode=data_mode).run()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

_SYNC_DTYPES = {"": None, "f32": jnp.float32, "bf16": jnp.bfloat16,
                "bfloat16": jnp.bfloat16, "f16": jnp.float16}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experiment", default="")
    ap.add_argument("--arch", default="")
    ap.add_argument("--K", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--strategy", default="",
                    choices=[""] + sorted(strategies.STRATEGIES))
    ap.add_argument("--mode", default="",
                    help="DEPRECATED: legacy mode string (use --strategy)")
    ap.add_argument("--intra-interval", type=int, default=0,
                    help="hierarchical: steps between intra-pod averages")
    ap.add_argument("--sync-dtype", default="", choices=sorted(_SYNC_DTYPES),
                    help="wire dtype for compressed sync (e.g. bf16)")
    ap.add_argument("--codec", default="",
                    help="wire codec spec for compressed sync (repro.comm): "
                         "int8 | int4 | topk | chains like topk+int8")
    ap.add_argument("--codec-bits", type=int, default=0, choices=[0, 4, 8],
                    help="quantizer bits; retunes (or appends) the codec's "
                         "quantizer stage")
    ap.add_argument("--topk", type=float, default=0.0,
                    help="top-k sparsification fraction; retunes (or "
                         "prepends) the codec's sparsifier stage")
    ap.add_argument("--average-opt-state", action="store_true",
                    help="FedAvg the optimizer moments along with the params")
    ap.add_argument("--participation", type=float, default=0.0,
                    help="subsampled: per-round participating fraction")
    ap.add_argument("--warmup-rounds", type=int, default=0,
                    help="adaptive_k: rounds that sync every round")
    ap.add_argument("--sync-every", type=int, default=0,
                    help="adaptive_k: post-warmup rounds between syncs")
    ap.add_argument("--dp-clip", type=float, default=0.0,
                    help="DP-SGD per-example clip norm C (enables DP; "
                         "defaults to 1.0 when only --dp-noise is given)")
    ap.add_argument("--dp-noise", type=float, default=0.0,
                    help="DP-SGD noise multiplier sigma (0 = clip-only)")
    ap.add_argument("--dp-delta", type=float, default=1e-5,
                    help="delta at which the accountant reports epsilon")
    ap.add_argument("--secure-agg", action="store_true",
                    help="pairwise-mask secure summing at the sync "
                         "(bit-identical result; refuses --codec/--sync-dtype)")
    ap.add_argument("--robust", default="",
                    choices=["", "trimmed_mean", "median"],
                    help="Byzantine-robust aggregation (shorthand for "
                         "--strategy trimmed_mean|median)")
    ap.add_argument("--trim", type=int, default=0,
                    help="trimmed_mean: agents trimmed per tail (default 1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--batch-size", type=int, default=0,
                    help="per-agent minibatch size (0 = experiment default)")
    ap.add_argument("--agents", type=int, default=0,
                    help="number of agents B (0 = experiment default)")
    ap.add_argument("--log-every", type=int, default=-1,
                    help="rounds between metric logs; 0 silences, "
                         "-1 = experiment default")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="rounds between repro.evals scorings of the "
                         "averaged generator (experiments only; 0 = off)")
    ap.add_argument("--data-mode", default="stream",
                    choices=["stream", "device"],
                    help="round data pipeline: host-streaming (legacy-"
                         "parity) or device-resident in-round sampling")
    ap.add_argument("--a-total", type=int, default=0,
                    help="virtual-client fleet size A_total (0 = dense run; "
                         "conflicts with --agents)")
    ap.add_argument("--a-active", type=int, default=0,
                    help="per-round cohort size A_active — the device slot "
                         "count (0 = experiment's num_agents)")
    ap.add_argument("--participation-seed", type=int, default=0,
                    help="seed of the per-round cohort draw "
                         "(repro.core.participation)")
    ap.add_argument("--straggler-policy", default="block",
                    choices=["block", "defer"],
                    help="block: wait for every cohort member; defer: merge "
                         "late deltas into a later round with staleness "
                         "decay")
    ap.add_argument("--samples-per-agent", type=int, default=0,
                    help="per-client dataset size override (0 = experiment "
                         "default, or 512 under --a-total)")
    return ap


def strategy_from_args(args) -> strategies.SyncStrategy | None:
    """CLI flags -> SyncStrategy (None keeps the library default).  A knob
    that the chosen strategy does not declare is an error, not a silent
    no-op (mirroring FedGANConfig.resolve_strategy's conflict check)."""
    from repro.comm import codec_from_flags
    sync_dtype = _SYNC_DTYPES[args.sync_dtype]
    codec = codec_from_flags(args.codec, bits=args.codec_bits,
                             topk=args.topk)
    if codec is not None and args.sync_dtype:
        raise ValueError(
            "--codec and --sync-dtype are both wire compressions; pick one "
            "(chain codecs via --codec a+b instead)")
    robust = getattr(args, "robust", "")
    if robust:
        # --robust is shorthand for --strategy trimmed_mean|median
        if args.strategy and args.strategy != robust:
            raise ValueError(f"--robust {robust} conflicts with "
                             f"--strategy {args.strategy}; pick one")
        args.strategy = robust
    secure = getattr(args, "secure_agg", False)
    if args.strategy or ((codec is not None or secure) and not args.mode):
        # a bare --codec/--secure-agg implies the FedAvgSync base strategy,
        # through the same knob validation (no silent drops of e.g.
        # --participation)
        cls = (strategies.STRATEGIES[args.strategy] if args.strategy
               else strategies.FedAvgSync)
        fields = {f.name for f in dataclasses.fields(cls)}
        requested = {}
        if args.sync_dtype:
            requested["sync_dtype"] = sync_dtype
        if codec is not None:
            requested["codec"] = codec
        if secure:
            from repro.privacy import SecureAgg
            requested["secure_agg"] = SecureAgg(seed=args.seed)
        if args.average_opt_state:
            requested["average_opt_state"] = True
        if args.intra_interval:
            requested["intra_interval"] = args.intra_interval
        if args.participation:
            requested["fraction"] = args.participation
        if args.warmup_rounds:
            requested["warmup_rounds"] = args.warmup_rounds
        if args.sync_every:
            requested["sync_every"] = args.sync_every
        if getattr(args, "trim", 0):
            requested["trim"] = args.trim
        stray = sorted(set(requested) - fields)
        if stray:
            name = args.strategy or "fedgan (implied by --codec/--secure-agg)"
            raise ValueError(
                f"--strategy {name} does not accept {stray} "
                f"(its knobs: {sorted(fields)})")
        return cls(**requested)
    if args.mode:
        if codec is not None:
            raise ValueError("--codec requires --strategy (the legacy "
                             "--mode strings predate the codec axis)")
        if secure:
            raise ValueError("--secure-agg requires --strategy (the legacy "
                             "--mode strings predate the privacy axis)")
        return strategies.strategy_from_mode(
            args.mode, intra_interval=args.intra_interval,
            sync_dtype=sync_dtype, average_opt_state=args.average_opt_state)
    return None


def dp_from_args(args):
    """CLI flags -> repro.privacy.DPSGD (None when no DP flag is set).
    ``--dp-noise`` alone enables DP at the default clip of 1.0."""
    if not (getattr(args, "dp_clip", 0.0) or getattr(args, "dp_noise", 0.0)):
        return None
    from repro.privacy import DPSGD
    return DPSGD(clip=args.dp_clip or 1.0, noise_multiplier=args.dp_noise,
                 delta=getattr(args, "dp_delta", 1e-5))


def main():
    ap = build_parser()
    args = ap.parse_args()
    strategy = strategy_from_args(args)
    dp = dp_from_args(args)
    overrides = dict(batch_size=args.batch_size or None,
                     agents=args.agents or None,
                     log_every=None if args.log_every < 0 else args.log_every,
                     data_mode=args.data_mode)

    if args.experiment:
        enable_compile_cache()
        run_experiment(args.experiment, K=args.K or None, steps=args.steps or None,
                       seed=args.seed, strategy=strategy, dp=dp,
                       ckpt_dir=args.ckpt_dir,
                       eval_every=args.eval_every,
                       a_total=args.a_total, a_active=args.a_active,
                       participation_seed=args.participation_seed,
                       straggler_policy=args.straggler_policy,
                       samples_per_agent=args.samples_per_agent or None,
                       **overrides)
    elif args.arch:
        if args.a_total:
            ap.error("--a-total needs --experiment (the backbone smoke "
                     "runs are dense by construction)")
        if args.eval_every:
            ap.error("--eval-every needs --experiment (no eval suite exists "
                     "for backbone smoke runs)")
        enable_compile_cache()
        run_arch_smoke(args.arch, steps=args.steps or 20, K=args.K or 5,
                       seed=args.seed, strategy=strategy, dp=dp,
                       ckpt_dir=args.ckpt_dir, **overrides)
    else:
        ap.error("need --experiment or --arch")


if __name__ == "__main__":
    main()
