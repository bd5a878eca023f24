"""Launcher plumbing: the CLI must reach every strategy knob.

Regression for the pre-strategy gap where ``--mode hierarchical`` always
raised ValueError because ``intra_interval`` (and ``sync_dtype`` /
``average_opt_state``) were not exposed by ``repro.launch.train``.
"""
import jax.numpy as jnp
import pytest

from repro.core.strategies import (AdaptiveK, FedAvgSync, Hierarchical,
                                   PartialSharing, SubsampledFedAvg)
from repro.launch.train import (RunSpec, build_parser, experiment_spec,
                                run_experiment, strategy_from_args,
                                toy2d_task)


def _args(*argv):
    return build_parser().parse_args(list(argv))


def test_mode_hierarchical_cli_plumbing():
    """--mode hierarchical + --intra-interval must resolve (the old
    launcher dropped intra_interval on the floor)."""
    args = _args("--experiment", "toy_2d", "--mode", "hierarchical",
                 "--intra-interval", "2")
    strat = strategy_from_args(args)
    assert isinstance(strat, Hierarchical) and strat.intra_interval == 2


def test_legacy_sync_knobs_reach_strategy():
    args = _args("--experiment", "toy_2d", "--mode", "fedgan",
                 "--sync-dtype", "bf16", "--average-opt-state")
    strat = strategy_from_args(args)
    assert isinstance(strat, FedAvgSync)
    assert strat.sync_dtype == jnp.bfloat16 and strat.average_opt_state


def test_strategy_flag_selects_and_parameterises():
    cases = [
        (("--strategy", "partial_sharing"), PartialSharing, {}),
        (("--strategy", "subsampled", "--participation", "0.25"),
         SubsampledFedAvg, {"fraction": 0.25}),
        (("--strategy", "adaptive_k", "--warmup-rounds", "3",
          "--sync-every", "4"), AdaptiveK,
         {"warmup_rounds": 3, "sync_every": 4}),
        (("--strategy", "hierarchical", "--intra-interval", "5"),
         Hierarchical, {"intra_interval": 5}),
    ]
    for argv, cls, want in cases:
        strat = strategy_from_args(_args("--experiment", "toy_2d", *argv))
        assert isinstance(strat, cls)
        for k, v in want.items():
            assert getattr(strat, k) == v, (argv, k)


def test_no_flags_keeps_library_default():
    assert strategy_from_args(_args("--experiment", "toy_2d")) is None


def test_stray_knob_for_strategy_is_an_error():
    """A knob the chosen strategy doesn't declare must fail loudly."""
    with pytest.raises(ValueError, match="does not accept"):
        strategy_from_args(_args("--experiment", "toy_2d",
                                 "--strategy", "fedgan",
                                 "--intra-interval", "5"))
    with pytest.raises(ValueError, match="does not accept"):
        strategy_from_args(_args("--experiment", "toy_2d",
                                 "--strategy", "subsampled",
                                 "--warmup-rounds", "3"))


def test_run_experiment_hierarchical_end_to_end():
    """The crash repro: a hierarchical toy_2d run must train, not raise."""
    fed, state, hist = run_experiment(
        "toy_2d", K=2, steps=4, seed=0,
        strategy=Hierarchical(intra_interval=1))
    assert len(hist) == 2
    assert fed.cfg.resolve_strategy().name == "hierarchical"


def test_run_overrides_reach_the_spec():
    """--batch-size / --agents / --log-every were previously fixed by the
    experiment config with no CLI override; they must plumb through to the
    RunSpec (and reshape the agent fleet/data accordingly)."""
    spec, suite = experiment_spec("toy_2d", K=4, steps=8, batch_size=16,
                                  agents=3, log_every=7, eval_every=2)
    assert spec.batch_size == 16
    assert spec.agent_grid == (1, 3) and len(spec.agent_data) == 3
    assert spec.log_every == 7
    assert spec.eval_every == 2 and len(spec.eval_hooks) == 1
    # defaults stay when not overridden
    spec2, _ = experiment_spec("toy_2d", K=4, steps=8)
    from repro.configs.paper_gans import ALL_EXPERIMENTS
    exp = ALL_EXPERIMENTS["toy_2d"]
    assert spec2.batch_size == exp.batch_size
    assert spec2.agent_grid == (1, exp.num_agents)
    assert spec2.eval_every == 0 and spec2.eval_hooks == ()


def test_cli_exposes_run_overrides():
    args = _args("--experiment", "toy_2d", "--batch-size", "32",
                 "--agents", "3", "--log-every", "0", "--eval-every", "5",
                 "--data-mode", "device")
    assert args.batch_size == 32 and args.agents == 3
    assert args.log_every == 0 and args.eval_every == 5
    assert args.data_mode == "device"
    # defaults: sentinel values that mean "keep the experiment config"
    d = _args("--experiment", "toy_2d")
    assert d.batch_size == 0 and d.agents == 0 and d.log_every == -1
    assert d.eval_every == 0 and d.data_mode == "stream"
    with pytest.raises(SystemExit):
        _args("--experiment", "toy_2d", "--data-mode", "bogus")


def test_agent_override_wraps_class_assignments():
    """--agents beyond the experiment's natural fleet must wrap mode/class
    assignments, not clamp out of range (jnp indexing silently clamps, so
    agent 4 of mixed_gaussian used to get mode 7 twice instead of 0+1)."""
    import numpy as np

    from repro.data import synthetic
    spec, _ = experiment_spec("mixed_gaussian", K=2, steps=4, agents=5)
    modes = np.asarray(synthetic.mixed_gaussian_modes())
    x4 = np.asarray(spec.agent_data[4]["x"])
    nearest = np.linalg.norm(x4[:, None] - modes[None], axis=-1).argmin(1)
    assert set(np.unique(nearest)) == {0, 1}  # wrapped, not clamped to 7
    # image_acgan: randint bounds must stay valid when B > num classes
    spec, _ = experiment_spec("image_acgan", K=2, steps=4, agents=12,
                              batch_size=8)
    labs = np.concatenate([np.asarray(d["y"]) for d in spec.agent_data])
    assert labs.min() >= 0 and labs.max() < 10
    # timeseries: climate zone stays in [0, 5)
    spec, _ = experiment_spec("timeseries_cgan", K=2, steps=4, agents=7,
                              batch_size=8)
    for d in spec.agent_data:
        y = np.asarray(d["y"])
        assert y.sum(axis=-1).min() == 1.0  # one-hot stays valid


def test_run_experiment_with_overrides_and_evals():
    fed, state, hist = run_experiment(
        "toy_2d", K=2, steps=8, seed=0, batch_size=8, agents=2,
        log_every=0, eval_every=2, data_mode="device")
    assert fed.cfg.agent_grid == (1, 2)
    assert len(hist) == 4


def test_eval_every_with_arch_is_rejected():
    """No eval suite exists for backbone smoke runs — the CLI must say so
    instead of silently dropping the flag."""
    import sys
    from unittest import mock

    import repro.launch.train as train_mod
    argv = ["train", "--arch", "gemma3-4b", "--eval-every", "2"]
    with mock.patch.object(sys, "argv", argv):
        with pytest.raises(SystemExit):
            train_mod.main()


def test_runspec_builder_round_trip():
    import jax
    task, _ = toy2d_task()
    from repro.data import synthetic
    B = 3
    rng = jax.random.key(0)
    data = [{"x": synthetic.sample_2d_segment(jax.random.fold_in(rng, i),
                                              256, i, B)} for i in range(B)]
    spec = RunSpec(task=task, agent_data=data, agent_grid=(1, B), K=2,
                   steps=4, batch_size=16, strategy=PartialSharing(),
                   sample_extra=lambda r, s: {
                       "z": jax.random.uniform(r, s, minval=-1, maxval=1)},
                   log_every=0)
    fed, rounds = spec.build()
    assert fed.cfg.sync_interval == 2
    assert fed.cfg.resolve_strategy() == PartialSharing()
    _, state, hist = spec.run()
    assert len(hist) == 2 and "d_loss" in hist[0]


def test_dryrun_exposes_analyze_flag():
    """--analyze must reach run_pair (the per-cell trace audit hook).

    Runs in a subprocess: importing repro.launch.dryrun in-process would
    append the 512-device XLA flag to this process's environment, which
    every later subprocess test would inherit."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = (
        "import inspect\n"
        "from repro.launch.dryrun import main, run_pair\n"
        "assert 'analyze' in inspect.signature(run_pair).parameters\n"
        "import sys; sys.argv = ['dryrun', '--help']\n"
        "try:\n"
        "    main()\n"
        "except SystemExit as e:\n"
        "    assert e.code in (0, None)\n"
    )
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH=src),
                         timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "--analyze" in res.stdout


@pytest.mark.parametrize("env_dir", ["", "/srv/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The CLI's compile cache: ``JAX_COMPILATION_CACHE_DIR`` wins and is
    left to JAX; unset, a fixed ``.jax_cache`` at the checkout root.  The
    config update is recorded, not applied, so the cache stays off here."""
    import os

    import jax

    from repro.launch import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *kv: updates.append(kv))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = env_dir or os.path.join(root, ".jax_cache")
    assert path == want
    assert updates == ([] if env_dir else [("jax_compilation_cache_dir",
                                            want)])
