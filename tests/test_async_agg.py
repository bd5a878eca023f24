"""Async buffered aggregation (repro.run.async_agg) + the virtual-clock
simulator (repro.run.simclock).

Two load-bearing contracts:

* **degenerate parity** — with no latency model, no timeout and a
  full-cohort buffer goal the async driver IS the synchronous per-round
  path: bit-identical params, optimizer state, EF residuals and metrics
  against the dense ``RoundDriver``;
* **replay determinism** — a seeded straggler simulation replays
  bit-exactly: byte-identical event journals and identical final
  parameters across runs (the CI determinism gate diffs the files raw).

Plus the buffered-mode semantics (flush at goal, staleness weighting,
expiry, timeout/retry/backoff), the loud strategy refusals, and
property-based invariants for the staleness-weight algebra.
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import codec_from_flags
from repro.core import strategies
from repro.core.participation import ParticipationSchedule
from repro.core.strategies import (AdaptiveK, FedAvgSync, PartialSharing,
                                   SubsampledFedAvg, TrimmedMeanSync,
                                   check_async_mergeable)
from repro.data import FleetRounds
from repro.optim import Adam
from repro.run.async_agg import AsyncAggDriver, modeled_sync_makespan
from repro.run.simclock import (EventJournal, LatencyModel, SimClock,
                                demo_driver, params_digest)
from repro.run.virtual import (StragglerPolicy, staleness_scale,
                               staleness_weights)
from test_virtual_clients import (assert_trees_equal, client_shards,
                                  dense_result, make_fed, virtual_result)

tmap = jax.tree_util.tree_map


def async_driver(strategy, agent_data, grid=(1, 4), K=3, n_rounds=5,
                 opt=None, **kw):
    fed = make_fed(strategy, grid, K, opt)
    fleet = FleetRounds(agent_data, grid, batch_size=8, sync_interval=K)
    return AsyncAggDriver(fed, fleet, n_rounds, log_every=0, **kw)


def in_flight_trace(journal):
    """Reconstruct the in-flight count after each event from the journal."""
    n, trace = 0, []
    for r in journal.records:
        if r["ev"] == "dispatch":
            n += 1
        elif r["ev"] in ("arrival", "expired", "timeout"):
            n -= 1
        trace.append(n)
    return trace


# ---------------------------------------------------------------------------
# degenerate parity: async(B=cohort, zero latency) == synchronous rounds
# ---------------------------------------------------------------------------

DEGENERATE_STRATEGIES = [
    ("fedavg", None),
    ("partial_sharing", PartialSharing()),
    ("codec_ef", FedAvgSync(codec=codec_from_flags("int8"))),
]


@pytest.mark.parametrize("name,strategy", DEGENERATE_STRATEGIES,
                         ids=[p[0] for p in DEGENERATE_STRATEGIES])
def test_degenerate_parity_bit_identical(name, strategy):
    """No latency, no timeout, full-cohort goal -> the dense per-round
    trajectory, bit for bit: params, opt moments, EF residuals, metrics."""
    data = client_shards(4)
    dense = dense_result(strategy, data, opt=Adam())
    drv = async_driver(strategy, data, opt=Adam())
    res = drv.run(jax.random.key(7))
    assert set(dense.state) == set(res.state)
    assert_trees_equal(dense.state, res.state)
    assert dense.history == res.history
    assert res.timings["mode"] == "sync_equivalent"


def test_degenerate_journal_shape_and_digest():
    data = client_shards(4)
    drv = async_driver(None, data, n_rounds=5)
    res = drv.run(jax.random.key(7))
    counts = drv.journal.counts()
    assert counts["flush"] == 5
    assert counts["dispatch"] == counts["arrival"] == 5 * 4
    end = drv.journal.select("end")[-1]
    assert end["params_digest"] == params_digest(res.state["params"])


def test_degenerate_matches_virtual_driver_exactly():
    data = client_shards(6)
    sched = ParticipationSchedule(seed=9)
    _, virt = virtual_result(None, data, n_rounds=4, schedule=sched)
    drv = async_driver(None, data, n_rounds=4, schedule=sched)
    res = drv.run(jax.random.key(7))
    assert_trees_equal(virt.state, res.state)
    assert virt.history == res.history


# ---------------------------------------------------------------------------
# replay determinism: same seed -> byte-identical journal + params
# ---------------------------------------------------------------------------


def _demo_run(seed=7, **kw):
    drv = demo_driver(seed=seed, n_rounds=4, **kw)
    res = drv.run(jax.random.key(seed))
    return drv, res


def test_buffered_replay_bit_exact():
    d1, r1 = _demo_run()
    d2, r2 = _demo_run()
    assert d1.journal.canonical_bytes() == d2.journal.canonical_bytes()
    assert_trees_equal(r1.state["params"], r2.state["params"])
    assert r1.timings["makespan"] == r2.timings["makespan"]


def test_buffered_other_seed_differs():
    d1, _ = _demo_run(seed=7)
    d2, _ = _demo_run(seed=8)
    assert d1.journal.canonical_bytes() != d2.journal.canonical_bytes()


def test_journal_end_digest_matches_final_params():
    drv, res = _demo_run()
    assert drv.journal.select("end")[-1]["params_digest"] == \
        params_digest(res.state["params"])


def test_cli_main_writes_identical_journals(tmp_path, capsys):
    from repro.run import simclock
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    assert simclock.main(["--seed", "5", "--rounds", "3", "--out", a]) == 0
    assert simclock.main(["--seed", "5", "--rounds", "3", "--out", b]) == 0
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()
    out = capsys.readouterr().out
    assert "params_digest=" in out and "makespan=" in out


# ---------------------------------------------------------------------------
# buffered semantics: goal, staleness weights, expiry, concurrency
# ---------------------------------------------------------------------------


def test_flush_fires_exactly_at_goal():
    drv, res = _demo_run(buffer_goal=2)
    flushes = drv.journal.select("flush")
    assert len(flushes) == 4 == res.timings["flushes"]
    assert all(f["merged"] == 2 for f in flushes)
    assert res.timings["merged_deltas"] == 8


def test_buffer_goal_one_merges_singletons():
    drv, _ = _demo_run(buffer_goal=1)
    assert all(f["merged"] == 1 and f["weights"] == [1.0]
               for f in drv.journal.select("flush"))


def test_in_flight_never_exceeds_cohort():
    drv, _ = _demo_run(cohort=4)
    assert max(in_flight_trace(drv.journal)) <= 4


def test_flush_weights_are_the_staleness_closed_form():
    """Every journalled flush weight vector is exactly
    ``normalize(decay**staleness)`` — decay 0.5 keeps the arithmetic in
    powers of two, so 'exactly' means bitwise."""
    drv, _ = _demo_run()
    policy = drv.straggler
    saw_stale = False
    for f in drv.journal.select("flush"):
        expect = staleness_weights(f["staleness"], policy)
        np.testing.assert_array_equal(np.float32(f["weights"]), expect)
        assert all(0 <= s <= policy.max_staleness for s in f["staleness"])
        saw_stale |= any(s > 0 for s in f["staleness"])
    assert saw_stale, "workload never produced a stale delta — vacuous"


def test_expired_deltas_are_dropped_and_counted():
    data = client_shards(8)
    drv = async_driver(
        None, data, n_rounds=6, buffer_goal=1,
        schedule=ParticipationSchedule(seed=7),
        straggler=StragglerPolicy(mode="defer", decay=0.5, max_staleness=1),
        latency=LatencyModel(base=1.0, jitter=0.5, straggler_frac=0.4,
                             straggler_factor=16.0))
    res = drv.run(jax.random.key(7))
    expired = drv.journal.select("expired")
    assert res.timings["expired_deltas"] == len(expired) > 0
    assert all(e["staleness"] > 1 for e in expired)
    # and everything that DID merge was within the staleness bound
    assert all(s <= 1 for f in drv.journal.select("flush")
               for s in f["staleness"])


def test_constant_latency_makespan_closed_form():
    """base-only latency, full-cohort goal: the loop degenerates to lock
    step — flush k lands at exactly (k+1) * base virtual seconds."""
    data = client_shards(4)
    drv = async_driver(None, data, n_rounds=3,
                       latency=LatencyModel(base=2.0))
    res = drv.run(jax.random.key(7))
    assert res.timings["mode"] == "buffered"
    assert res.timings["makespan"] == 3 * 2.0
    assert [f["t"] for f in drv.journal.select("flush")] == [2.0, 4.0, 6.0]
    assert all(np.isfinite(m["d_loss"]) for m in res.history)


def test_partial_sharing_buffered_leaves_disc_local():
    data = client_shards(4)
    drv = async_driver(PartialSharing(), data, n_rounds=3,
                       latency=LatencyModel(base=1.0))
    res = drv.run(jax.random.key(7))
    # the server only ever owns the shared subtree
    assert set(res.state["params"]) == {"gen"}
    rows = {cid: drv.store.row(cid) for cid in drv.store.client_ids()}
    discs = [np.asarray(r["params"]["disc"]["w"]) for r in rows.values()]
    assert len(discs) >= 2
    assert any(not np.array_equal(discs[0], d) for d in discs[1:])


def test_dataset_weighting_scales_flush_weights():
    data = client_shards(4, size=16) + client_shards(4, size=48, seed=1)
    fed = make_fed(None, (1, 4), 3)
    fleet = FleetRounds(data, (1, 4), batch_size=8, sync_interval=3)
    drv = AsyncAggDriver(fed, fleet, 3, log_every=0, weighting="dataset",
                         latency=LatencyModel(base=1.0), buffer_goal=2)
    drv.run(jax.random.key(7))
    for f in drv.journal.select("flush"):
        sizes = np.array([16.0 if c < 4 else 48.0 for c in f["clients"]])
        expect = staleness_weights(f["staleness"], drv.straggler, sizes)
        np.testing.assert_allclose(np.float32(f["weights"]), expect,
                                   rtol=1e-6)


def test_buffered_compiles_one_local_trace():
    drv, res = _demo_run()
    assert drv.n_traces == 1
    assert res.timings["data_kind"] == "async"
    assert res.timings["store_rows"] <= 8


# ---------------------------------------------------------------------------
# timeout / retry / backoff
# ---------------------------------------------------------------------------


def test_timeouts_retry_with_backed_off_budget():
    drv, _ = _demo_run()   # timeout=6, backoff=2, planted stragglers
    timeouts = drv.journal.select("timeout")
    assert timeouts, "workload planted stragglers but nothing timed out"
    dispatches = {r["seq"]: r for r in drv.journal.select("dispatch")}
    for ev in timeouts:
        d = dispatches[ev["seq"]]
        budget = drv.timeout * drv.backoff ** ev["attempt"]
        assert d["latency"] > budget
        np.testing.assert_allclose(ev["t"] - d["t"], budget)
    retries = drv.journal.select("retry")
    assert retries and all(r["attempt"] >= 1 for r in retries)


def test_retry_draws_fresh_latency():
    lm = LatencyModel(base=1.0, jitter=1.0)
    sched = ParticipationSchedule(seed=3)
    a = lm.draw(sched, dispatch_seq=5, client=2, n_total=8, attempt=0)
    b = lm.draw(sched, dispatch_seq=5, client=2, n_total=8, attempt=1)
    assert a != b
    assert a == lm.draw(sched, 5, 2, 8, attempt=0)   # pure function


def test_gave_up_is_loud_but_run_completes():
    """Some dispatches exhaust their retries; the run still reaches the
    flush target because replacements keep the pipeline full."""
    data = client_shards(8)
    drv = async_driver(
        None, data, grid=(1, 4), n_rounds=4, buffer_goal=2,
        schedule=ParticipationSchedule(seed=5),
        latency=LatencyModel(base=1.0, straggler_frac=0.5,
                             straggler_factor=50.0),
        timeout=2.0, max_retries=1, backoff=1.0)
    res = drv.run(jax.random.key(5))
    assert res.timings["flushes"] == 4
    assert res.timings["gave_up"] > 0
    assert drv.journal.counts()["gave_up"] == res.timings["gave_up"]


def test_starvation_raises_loudly():
    """timeout below every achievable latency + no retries: the driver
    must refuse with a diagnosis, not spin forever."""
    data = client_shards(6)
    drv = async_driver(None, data, n_rounds=2,
                       latency=LatencyModel(base=5.0),
                       timeout=1.0, max_retries=0)
    with pytest.raises(ValueError, match="starved"):
        drv.run(jax.random.key(7))


def test_modeled_sync_makespan_is_the_blocking_cost():
    sched = ParticipationSchedule(seed=7)
    lm = LatencyModel(base=1.0, jitter=0.5, straggler_frac=0.25,
                      straggler_factor=8.0)
    got = modeled_sync_makespan(sched, lm, n_rounds=3, n_total=8, m=4)
    expect = sum(max(lm.draw(sched, r, int(c), 8)
                     for c in sched.cohort(r, 8, 4)) for r in range(3))
    assert got == expect > 3.0   # at least base per round, stragglers more


# ---------------------------------------------------------------------------
# refusals: what the buffered merge cannot replay, it must refuse loudly
# ---------------------------------------------------------------------------

REFUSED = [
    ("subsampled", SubsampledFedAvg(fraction=0.5,
                                    schedule=ParticipationSchedule(seed=3)),
     "subsampled"),
    ("robust", TrimmedMeanSync(trim=1), "order statistic"),
    ("secure_agg", FedAvgSync(secure_agg="pairwise"), "uncancelled"),
    ("codec", FedAvgSync(codec=codec_from_flags("int8")), "stale payloads"),
    ("sync_dtype", FedAvgSync(sync_dtype=jnp.bfloat16), "wire cast"),
    ("avg_opt", FedAvgSync(average_opt_state=True), "moments stay local"),
    ("adaptive_k", AdaptiveK(), "per-round driver"),
]


@pytest.mark.parametrize("name,strategy,msg", REFUSED,
                         ids=[r[0] for r in REFUSED])
def test_check_async_mergeable_refuses(name, strategy, msg):
    with pytest.raises(ValueError, match=msg):
        check_async_mergeable(strategy)


def test_plain_strategies_are_async_mergeable():
    check_async_mergeable(FedAvgSync())
    check_async_mergeable(PartialSharing())


def test_buffered_construction_refuses_codec_but_degenerate_allows():
    data = client_shards(4)
    strat = FedAvgSync(codec=codec_from_flags("int8"))
    async_driver(strat, data)   # degenerate: fused sync path, codecs fine
    with pytest.raises(ValueError, match="codec"):
        async_driver(strat, data, latency=LatencyModel(base=1.0))


@pytest.mark.parametrize("kw,msg", [
    (dict(buffer_goal=0), "buffer_goal"),
    (dict(buffer_goal=5), "buffer_goal"),
    (dict(timeout=0.0), "timeout"),
    (dict(latency=LatencyModel(base=1.0), backoff=0.5), "backoff"),
    (dict(latency=LatencyModel(base=1.0), max_retries=-1), "max_retries"),
    (dict(weighting="nope"), "weighting"),
    (dict(latency=LatencyModel(base=-1.0)), "base/jitter"),
], ids=["goal_zero", "goal_over_cohort", "timeout_zero", "backoff_lt_one",
        "neg_retries", "bad_weighting", "neg_latency"])
def test_constructor_validation(kw, msg):
    with pytest.raises(ValueError, match=msg):
        async_driver(None, client_shards(4), **kw)


# ---------------------------------------------------------------------------
# staleness-weight algebra: property-based invariants
# ---------------------------------------------------------------------------

_POLICY = StragglerPolicy(mode="defer", decay=0.5, max_staleness=3)


@settings(max_examples=25, deadline=None)
@given(stal=st.lists(st.integers(0, 6), min_size=1, max_size=8))
def test_weights_normalize_to_one(stal):
    w = staleness_weights(stal, _POLICY)
    if any(s <= _POLICY.max_staleness for s in stal):
        np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
    else:
        assert w.sum() == 0.0


@settings(max_examples=25, deadline=None)
@given(s=st.integers(0, 10), decay=st.floats(0.05, 1.0))
def test_scale_monotone_nonincreasing(s, decay):
    pol = StragglerPolicy(mode="defer", decay=decay, max_staleness=5)
    assert staleness_scale(s, pol) >= staleness_scale(s + 1, pol)


@settings(max_examples=25, deadline=None)
@given(s=st.integers(4, 20))
def test_past_max_staleness_is_exactly_zero(s):
    assert staleness_scale(s, _POLICY) == 0.0
    w = staleness_weights([0, 1, s], _POLICY)
    assert w[2] == 0.0 and w.sum() > 0


@settings(max_examples=20, deadline=None)
@given(perm=st.permutations(list(range(6))))
def test_weights_commute_with_permutation(perm):
    """Merge-order invariance: permuting the buffer permutes the weights
    elementwise — decay 1/2 keeps every sum exact in binary, so this is
    bitwise, which is exactly what the canonical-sort flush relies on."""
    stal = [0, 1, 1, 2, 3, 0]
    base = staleness_weights(stal, _POLICY)
    permuted = staleness_weights([stal[i] for i in perm], _POLICY)
    np.testing.assert_array_equal(permuted, base[np.asarray(perm)])


def test_negative_staleness_refused():
    with pytest.raises(ValueError, match=">= 0"):
        staleness_scale(-1, _POLICY)


# ---------------------------------------------------------------------------
# simulator primitives
# ---------------------------------------------------------------------------


def test_simclock_orders_ties_by_push_sequence():
    clk = SimClock()
    clk.push(2.0, "b")
    clk.push(1.0, "a1", payload=1)
    clk.push(1.0, "a2", payload=2)
    assert clk.pop() == (1.0, "a1", 1)
    assert clk.pop() == (1.0, "a2", 2)
    assert clk.now == 1.0
    with pytest.raises(ValueError, match="before"):
        clk.push(0.5, "late")
    assert clk.pop()[1] == "b" and clk.now == 2.0


def test_journal_canonical_bytes_round_trip():
    j = EventJournal()
    j.append("flush", np.float64(1.5), merged=np.int64(3), w=[0.5, 0.5])
    j.append("end", 2.0)
    lines = j.canonical_bytes().decode().splitlines()
    assert lines[0] == '{"ev":"flush","i":0,"merged":3,"t":1.5,"w":[0.5,0.5]}'
    assert j.counts() == {"flush": 1, "end": 1}
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "j.jsonl")
        j.write(p)
        with open(p, "rb") as f:
            assert f.read() == j.canonical_bytes()


def test_arrival_uniforms_seeded_and_disjoint():
    sched = ParticipationSchedule(seed=11)
    u = sched.arrival_uniforms(3, 16)
    np.testing.assert_array_equal(u, sched.arrival_uniforms(3, 16))
    assert u.shape == (16,) and (u >= 0).all() and (u < 1).all()
    assert not np.array_equal(u, sched.arrival_uniforms(3, 16, salt=1))
    assert not np.array_equal(u, sched.arrival_uniforms(4, 16))


def test_params_digest_detects_any_leaf_change():
    tree = {"gen": {"theta": np.arange(3.0)}, "disc": {"w": np.ones(3)}}
    d0 = params_digest(tree)
    assert d0 == params_digest(tmap(np.copy, tree))
    bumped = {"gen": {"theta": np.arange(3.0)},
              "disc": {"w": np.ones(3) + 1e-9}}
    assert d0 != params_digest(bumped)
