"""repro.run runtime tests: legacy-parity of the RunSpec shim, the
device-resident data pipeline, chunked driver invariance, RNG hygiene, and
the eval/checkpoint hooks.

The parity tests replicate the PRE-refactor ``RunSpec.run()`` loop inline
(host-assembled batches, non-donated jit, per-round blocking metric
floats) and hold the new driver bit-exact against it — the contract that
makes ``RunSpec.run()`` a safe shim rather than a behavior change.
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedGAN, FedGANConfig, PartialSharing
from repro.data import (DeviceFederatedData, FederatedRounds,
                        StreamingFederatedData, round_key_schedule, synthetic)
from repro.launch.train import experiment_spec, toy2d_task
from repro.run.driver import TOP_SPANS, RoundDriver, _chunk_sizes
from repro.run.evals import EvalSuite, eval_hook

tmap = jax.tree_util.tree_map


def _legacy_loop(spec):
    """The pre-refactor RunSpec.run() body, verbatim (minus prints/ckpt)."""
    fed, rounds = spec.build()
    state = fed.init_state(jax.random.key(spec.seed))
    round_fn = jax.jit(fed.round)
    rng = jax.random.key(spec.seed + 1)
    history = []
    for _ in range(max(spec.steps // spec.K, 1)):
        rng, rb = jax.random.split(rng)
        batches, seeds = rounds.round_batches(rb)
        state, metrics = round_fn(state, batches, seeds)
        history.append(tmap(lambda x: float(jnp.mean(x)), metrics))
    return fed, state, history


# ---------------------------------------------------------------------------
# parity: the shim must be bit-exact vs the old loop
# ---------------------------------------------------------------------------


def test_runspec_shim_parity_quickstart_settings():
    """Quickstart settings (toy_2d, K=20, 5 agents): identical history and
    final state, bit for bit."""
    spec, _ = experiment_spec("toy_2d", K=20, steps=100, seed=0, log_every=0)
    fed_old, state_old, hist_old = _legacy_loop(spec)
    fed_new, state_new, hist_new = spec.run()
    assert hist_old == hist_new
    for a, b in zip(jax.tree_util.tree_leaves(state_old),
                    jax.tree_util.tree_leaves(state_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_runspec_shim_parity_with_strategy_and_conditional_batches():
    """Parity must survive a non-default strategy and multi-field batches
    (labels + latents), not just the toy config."""
    spec, _ = experiment_spec("timeseries_cgan", K=4, steps=8, seed=3,
                              strategy=PartialSharing(), log_every=0,
                              batch_size=16)
    _, state_old, hist_old = _legacy_loop(spec)
    _, state_new, hist_new = spec.run()
    assert hist_old == hist_new
    for a, b in zip(jax.tree_util.tree_leaves(state_old),
                    jax.tree_util.tree_leaves(state_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_prefetch_preserves_batch_stream():
    """StreamingFederatedData must yield exactly the batches the blocking
    loop would assemble, in order, for any prefetch depth."""
    agent_data = [{"x": jnp.arange(40.0) + 100 * i} for i in range(4)]
    fr = FederatedRounds(agent_data, (2, 2), batch_size=8, sync_interval=3)
    rng = jax.random.key(9)
    want = [fr.round_batches(rb) for rb in round_key_schedule(rng, 5)]
    for prefetch in (1, 2, 4, 8):
        got = list(StreamingFederatedData(fr, prefetch=prefetch)
                   .iter_rounds(rng, 5))
        assert len(got) == 5
        for (gb, gs), (wb, ws) in zip(got, want):
            np.testing.assert_array_equal(np.asarray(gb["x"]), np.asarray(wb["x"]))
            np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


# ---------------------------------------------------------------------------
# device-resident data
# ---------------------------------------------------------------------------


def test_device_data_shapes_and_agent_separation():
    agent_data = [{"x": jnp.arange(40.0) + 100 * i} for i in range(4)]
    data = DeviceFederatedData.from_agent_data(
        agent_data, (2, 2), batch_size=8,
        sample_extra=lambda r, s: {"z": jax.random.normal(r, s + (2,))})
    batch = data.sample_step(jax.random.key(0))
    assert batch["x"].shape == (2, 2, 8)
    assert batch["z"].shape == (2, 2, 8, 2)
    for p in range(2):
        for a in range(2):
            i = p * 2 + a
            vals = np.asarray(batch["x"][p, a])
            assert ((vals >= 100 * i) & (vals < 100 * i + 40)).all()


def test_device_data_unequal_shards_never_sample_padding():
    """Shards are padded to the fleet max by wrapping; sampling must stay
    within each agent's true size."""
    agent_data = [{"x": jnp.arange(5.0)}, {"x": 1000 + jnp.arange(64.0)}]
    data = DeviceFederatedData.from_agent_data(agent_data, (1, 2), 16)
    assert np.asarray(data.sizes).tolist() == [[5, 64]]
    draws = [data.sample_step(jax.random.key(s))["x"] for s in range(20)]
    a0 = np.concatenate([np.asarray(d[0, 0]) for d in draws])
    a1 = np.concatenate([np.asarray(d[0, 1]) for d in draws])
    assert set(np.unique(a0)) <= set(range(5))
    assert a1.min() >= 1000 and a1.max() < 1064


def test_device_data_is_a_pytree():
    agent_data = [{"x": jnp.arange(8.0)} for _ in range(2)]
    data = DeviceFederatedData.from_agent_data(agent_data, (1, 2), 4)
    leaves, treedef = jax.tree_util.tree_flatten(data)
    assert len(leaves) == 2  # stacked data + sizes
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.batch_size == 4 and back.agent_grid == (1, 2)

    @jax.jit
    def through_jit(d, k):
        return d.sample_step(k)

    b = through_jit(data, jax.random.key(0))
    assert b["x"].shape == (1, 2, 4)


def test_round_from_data_runs_and_is_deterministic():
    task, _ = toy2d_task()
    B = 3
    rng = jax.random.key(0)
    agent_data = [{"x": synthetic.sample_2d_segment(
        jax.random.fold_in(rng, i), 128, i, B)} for i in range(B)]
    data = DeviceFederatedData.from_agent_data(
        agent_data, (1, B), 16,
        sample_extra=lambda r, s: {"z": jax.random.uniform(r, s, minval=-1,
                                                           maxval=1)})
    fed = FedGAN(task, FedGANConfig(agent_grid=(1, B), sync_interval=4))
    state = fed.init_state(jax.random.key(1))
    fn = jax.jit(fed.round_from_data)
    s1, m1 = fn(state, data, jax.random.key(2))
    s2, m2 = fn(state, data, jax.random.key(2))
    assert m1["d_loss"].shape == (4,)
    for a, b in zip(jax.tree_util.tree_leaves(s1), jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s3, _ = fn(state, data, jax.random.key(3))
    th2, th3 = s2["params"]["gen"]["theta"], s3["params"]["gen"]["theta"]
    assert not np.allclose(np.asarray(th2), np.asarray(th3))


def test_step_accepts_typed_keys_and_agents_decorrelate():
    """RNG hygiene: with a threaded key, agents holding IDENTICAL data and
    params must still draw different per-agent randomness (the z draws in
    sample_extra are per-agent), and the legacy uint32 seeds path keeps
    working."""
    task, _ = toy2d_task()
    B = 4
    x = jnp.linspace(-1, 1, 64)
    data = DeviceFederatedData.from_agent_data(
        [{"x": x} for _ in range(B)], (1, B), 16,
        sample_extra=lambda r, s: {"z": jax.random.uniform(r, s, minval=-1,
                                                           maxval=1)})
    fed = FedGAN(task, FedGANConfig(agent_grid=(1, B), sync_interval=2,
                                    strategy=None))
    # local_only so agent states do not get re-averaged
    from repro.core import LocalOnly
    fed = dataclasses.replace(fed, cfg=FedGANConfig(
        agent_grid=(1, B), sync_interval=2, strategy=LocalOnly()))
    state = fed.init_state(jax.random.key(0))
    out, _ = jax.jit(fed.round_from_data)(state, data, jax.random.key(5))
    thetas = np.asarray(out["params"]["gen"]["theta"][0])
    assert len(np.unique(thetas)) == B  # distinct despite identical data

    # seeds compat path: FedGAN.round with uint32 seeds still runs
    batches = {"x": jnp.zeros((2, 1, B, 16)) ,
               "z": jnp.zeros((2, 1, B, 16))}
    seeds = jnp.arange(2 * B, dtype=jnp.uint32).reshape(2, 1, B)
    st, m = jax.jit(fed.round)(state, batches, seeds)
    assert m["d_loss"].shape == (2,)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def test_chunk_sizes_respect_boundaries():
    assert _chunk_sizes(10, 4) == [4, 4, 2]
    assert _chunk_sizes(10, 4, 3) == [3, 3, 3, 1]  # never cross a %3 boundary
    assert _chunk_sizes(6, 100, 2, 3) == [2, 1, 1, 2]
    assert _chunk_sizes(5, 1) == [1] * 5
    for n, per, cads in ((17, 5, (4,)), (9, 3, (2, 5)), (8, 8, ())):
        sizes = _chunk_sizes(n, per, *cads)
        assert sum(sizes) == n and all(1 <= c <= per for c in sizes)
        r = 0
        for c in sizes:
            # a chunk starting at r must end at or before r's next cadence
            # boundary, for every active cadence
            for cad in cads:
                assert c <= cad - r % cad, (n, per, cads, sizes, r, c)
            r += c


def test_driver_chunking_is_bit_invariant():
    spec, _ = experiment_spec("toy_2d", K=5, steps=60, seed=0, log_every=0,
                              data_mode="device")
    runs = {}
    for c in (1, 4, 12):
        s = dataclasses.replace(spec, rounds_per_chunk=c)
        _, state, hist = s.run()
        runs[c] = (state, hist)
    for c in (4, 12):
        assert runs[1][1] == runs[c][1]
        for a, b in zip(jax.tree_util.tree_leaves(runs[1][0]),
                        jax.tree_util.tree_leaves(runs[c][0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_runtime_converges_toy2d():
    """The new pipeline trains: toy_2d to the paper fixed point (1, 0)."""
    spec, _ = experiment_spec("toy_2d", K=20, steps=3000, seed=0,
                              log_every=0, data_mode="device",
                              rounds_per_chunk=15)
    fed, state, hist = spec.run()
    avg = fed.averaged_params(state)
    assert abs(float(avg["gen"]["theta"]) - 1.0) < 0.1
    assert abs(float(avg["disc"]["psi"])) < 0.1
    assert len(hist) == 150 and np.isfinite(hist[-1]["g_loss"])


def test_driver_eval_hooks_and_checkpoints():
    spec, suite = experiment_spec("toy_2d", K=5, steps=40, seed=0,
                                  log_every=0, data_mode="device")
    fed, _ = spec.build()
    with tempfile.TemporaryDirectory() as d:
        driver = RoundDriver(
            fed, spec.build_data(), 8, log_every=0, verbose=False,
            eval_every=4, eval_hooks=(eval_hook(suite, n=256),),
            ckpt_every=4, ckpt_dir=d, rounds_per_chunk=3)
        res = driver.run(jax.random.key(1))
        assert [e["round"] for e in res.evals] == [3, 7]
        assert all("fd" in e and np.isfinite(e["fd"]) for e in res.evals)
        from repro.checkpoint import list_checkpoints
        assert list_checkpoints(d) == [20, 40]  # (r+1)*K at r=3,7
    assert res.timings["steps_per_s"] > 0
    assert res.timings["data_kind"] == "device"
    assert len(res.history) == 8
    assert all(isinstance(v, float) for m in res.history for v in m.values())


def test_driver_rejects_eval_every_without_hooks():
    spec, _ = experiment_spec("toy_2d", K=5, steps=10, log_every=0)
    fed, rounds = spec.build()
    with pytest.raises(ValueError, match="eval_hooks"):
        RoundDriver(fed, rounds, 2, eval_every=1)


def test_build_data_rejects_unknown_mode():
    spec, _ = experiment_spec("toy_2d", K=5, steps=10)
    with pytest.raises(ValueError, match="data_mode"):
        dataclasses.replace(spec, data_mode="nonsense").build_data()


# ---------------------------------------------------------------------------
# scopes and spans: what a profiler trace of the round can read
# ---------------------------------------------------------------------------

SCOPES = ("fedgan.sample", "fedgan.local_step", "fedgan.update",
          "fedgan.sync")


@pytest.mark.parametrize("wire", ["fedavg", "int8_ef"])
def test_round_hlo_carries_every_scope(wire):
    """The compiled device-data round names each of its four phases in
    its ops' metadata, on the plain and on the coded wire with error
    feedback; no scope name holds a kernel name a trace reader matches."""
    from repro.comm import IntQuant
    from repro.core import FedAvgSync
    strategy = (FedAvgSync(codec=IntQuant(bits=8), error_feedback=True)
                if wire == "int8_ef" else None)
    spec, _ = experiment_spec("mixed_gaussian", K=2, steps=4, agents=2,
                              log_every=0, data_mode="device",
                              samples_per_agent=256, strategy=strategy)
    driver, state = spec.round_driver()
    hlo = driver.compile_chunk(state, jax.random.key(1)).as_text()
    for scope in SCOPES:
        assert f"{scope}/" in hlo or f"{scope})" in hlo, scope
        assert "qsync_flat" not in scope
    assert ("ef_down" in state) == (wire == "int8_ef")


def _toy_driver(n_rounds, **kw):
    spec, _ = experiment_spec("toy_2d", K=5, steps=5 * n_rounds, seed=0,
                              log_every=0, data_mode="device")
    fed, _ = spec.build()
    return RoundDriver(fed, spec.build_data(), n_rounds, verbose=False, **kw)


def test_driver_spans_open_in_order(monkeypatch):
    """A device-path run with a metrics fetch every round opens the
    driver's spans in the order prepare, dispatch, boundary, wait,
    collect; ``host_s`` holds the seconds of each."""
    opened = []
    real = jax.profiler.TraceAnnotation

    def record(name, **kw):
        opened.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", record)
    res = _toy_driver(3, log_every=1, rounds_per_chunk=1).run(
        jax.random.key(1))
    first = sorted(set(opened), key=opened.index)
    assert first == ["driver.prepare", "driver.dispatch", "driver.boundary",
                     "driver.wait", "driver.collect"]
    # one dispatch and one boundary per round, a wait in each boundary and
    # one at the end
    assert opened.count("driver.dispatch") == 3
    assert opened.count("driver.boundary") == 3
    assert opened.count("driver.wait") == 4
    assert opened[-2:] == ["driver.collect", "driver.wait"]
    assert set(res.timings["host_s"]) == set(first)
    assert all(v >= 0.0 for v in res.timings["host_s"].values())


@pytest.mark.parametrize("data_mode", ["device", "stream"])
def test_round_gap_leaves_out_waiting(data_mode):
    """``round_gap_s`` is the host's work per round: the top-level spans'
    seconds less the waits for the device inside them."""
    spec, _ = experiment_spec("toy_2d", K=5, steps=20, seed=0, log_every=0,
                              data_mode=data_mode)
    fed, _ = spec.build()
    n = 4
    res = RoundDriver(fed, spec.build_data(), n, log_every=1,
                      verbose=False).run(jax.random.key(1))
    host = res.timings["host_s"]
    assert host["driver.wait"] > 0.0
    work = sum(host[k] for k in TOP_SPANS) - host["driver.wait"]
    assert res.timings["round_gap_s"] * n == pytest.approx(work, rel=1e-9)
    assert 0.0 < res.timings["round_gap_s"] * n < sum(
        host[k] for k in TOP_SPANS)
    assert len(res.history) == n
    assert all(isinstance(v, float) for m in res.history for v in m.values())


def test_driver_spans_reach_the_profiler(tmp_path):
    """A real profiler trace of a run holds the driver's spans in its host
    plane, on the clock its device operations use."""
    from jax.profiler import ProfileData
    driver = _toy_driver(2, log_every=1)
    driver.run(jax.random.key(1))           # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        driver.run(jax.random.key(2))
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    pd = ProfileData.from_file(path)
    host = pd.find_plane_with_name("/host:CPU")
    names = [e.name for line in host.lines for e in line.events]
    for span in TOP_SPANS + ("driver.wait",):
        assert span in names, span
    assert names.count("driver.dispatch") == 2


# ---------------------------------------------------------------------------
# sweep runner
# ---------------------------------------------------------------------------


def test_sweep_runner_end_to_end(tmp_path):
    import json

    from repro.run.experiments import parse_sweep, run_sweep, summary_table
    assert parse_sweep("K=1,5,20") == [1, 5, 20]
    assert parse_sweep("10,20") == [10, 20]
    with pytest.raises(ValueError):
        parse_sweep("K=zero")

    cells = run_sweep("toy_2d", [2, 4], strategy_names=("fedgan", "distributed"),
                      steps=16, seed=0, out_dir=str(tmp_path), eval_n=256,
                      verbose=False)
    assert len(cells) == 4
    assert {(c.K, c.strategy) for c in cells} == {
        (2, "fedgan"), (2, "distributed"), (4, "fedgan"), (4, "distributed")}
    for c in cells:
        assert np.isfinite(c.final["fd"])
        assert len(c.history) == 16 // c.K
    rows = [json.loads(l) for l in
            (tmp_path / "sweep_toy_2d.jsonl").read_text().splitlines()]
    finals = [r for r in rows if r.get("final")]
    assert len(finals) == 4 and all("fd" in r for r in finals)
    per_round = [r for r in rows if "round" in r and not r.get("eval")]
    assert len(per_round) == sum(len(c.history) for c in cells)
    table = summary_table(cells)
    assert "fedgan:fd" in table and "distributed:fd" in table
