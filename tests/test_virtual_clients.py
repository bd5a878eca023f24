"""Virtual-client runtime (repro.run.virtual): simulation parity,
participation-schedule properties, fault injection, and paging soundness.

The load-bearing contract is *simulation parity*: with ``A_total ==
A_active`` and the identity schedule, the virtual path must be
bit-identical to the dense ``RoundDriver`` stream path — params, opt
state, error-feedback residuals and metrics — across the strategy matrix.
Everything the scheduler adds (host store, diff-based paging, straggler
merges) must be invisible when it has nothing to do.
"""
import dataclasses
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import codec_from_flags
from repro.core import FedGAN, FedGANConfig, GANTask, strategies
from repro.core.participation import ParticipationSchedule
from repro.core.strategies import (FedAvgSync, PartialSharing,
                                   SubsampledFedAvg)
from repro.data import FederatedRounds, FleetRounds, StreamingFederatedData
from repro.optim import SGD, Adam, constant, equal_timescale
from repro.run.driver import RoundDriver
from repro.run.virtual import (ClientStore, StragglerPolicy,
                               VirtualClientDriver, load_fleet_checkpoint,
                               plan_swap, state_axes)

tmap = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# fixtures: a tiny quadratic GAN + non-iid per-client shards
# ---------------------------------------------------------------------------


def small_task():
    def init(rng):
        kg, kd = jax.random.split(rng)
        return {"gen": {"theta": 0.1 * jax.random.normal(kg, (3,))},
                "disc": {"w": 0.1 * jax.random.normal(kd, (3,))}}

    def disc_loss(params, batch, rng):
        xm = jnp.mean(batch["x"], axis=0)
        g = jax.lax.stop_gradient(params["gen"]["theta"])
        return (-jnp.dot(params["disc"]["w"], xm - g)
                + 0.5 * jnp.sum(params["disc"]["w"] ** 2))

    def gen_loss(params, batch, rng):
        w = jax.lax.stop_gradient(params["disc"]["w"])
        return jnp.dot(w, params["gen"]["theta"])

    return GANTask(init=init, disc_loss=disc_loss, gen_loss=gen_loss)


def client_shards(n_clients, size=32, d=3, seed=0):
    """Non-iid per-client data: shard i is offset by i, so any mixup
    between clients or slots shows up in the trajectories."""
    key = jax.random.key(seed)
    return [{"x": jax.random.normal(jax.random.fold_in(key, i), (size, d)) + i}
            for i in range(n_clients)]


def make_fed(strategy=None, grid=(1, 4), K=3, opt=None, **cfg_kw):
    opt = opt or SGD()
    return FedGAN(small_task(),
                  FedGANConfig(agent_grid=grid, sync_interval=K,
                               strategy=strategy, **cfg_kw),
                  opt_g=opt, opt_d=opt,
                  scales=equal_timescale(constant(0.05)))


def dense_result(strategy, agent_data, grid=(1, 4), K=3, n_rounds=5,
                 seed=7, opt=None, weights=None):
    fed = dataclasses.replace(make_fed(strategy, grid, K, opt),
                              weights=weights)
    data = StreamingFederatedData(
        FederatedRounds(agent_data, grid, batch_size=8, sync_interval=K))
    # both drivers derive (data_rng, init_rng) = split(rng): same root key
    # -> same init AND same batch schedule, so parity is apples-to-apples
    return RoundDriver(fed, data, n_rounds, log_every=0).run(
        jax.random.key(seed))


def virtual_driver(strategy, agent_data, grid=(1, 4), K=3, n_rounds=5,
                   opt=None, **kw):
    fed = make_fed(strategy, grid, K, opt)
    fleet = FleetRounds(agent_data, grid, batch_size=8, sync_interval=K)
    return VirtualClientDriver(fed, fleet, n_rounds, log_every=0, **kw)


def virtual_result(strategy, agent_data, grid=(1, 4), K=3, n_rounds=5,
                   seed=7, opt=None, **kw):
    driver = virtual_driver(strategy, agent_data, grid, K, n_rounds, opt, **kw)
    return driver, driver.run(jax.random.key(seed))


def assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# simulation parity: A_total == A_active + identity schedule == dense path
# ---------------------------------------------------------------------------

# strategy x codec grid: every aggregation family that can ride the fused
# virtual path, each with and without a quantizing codec (+ error
# feedback, the default) — codec state threading is where slot-paging
# bugs would hide, so the grid is the parity surface, not a sample
_PARITY_GRID = [
    ("fedavg", lambda codec: FedAvgSync(codec=codec) if codec else None),
    ("partial_sharing", lambda codec: PartialSharing(codec=codec)),
    ("adaptive_k", lambda codec: strategies.AdaptiveK(warmup_rounds=2,
                                                      sync_every=2,
                                                      codec=codec)),
]
PARITY_STRATEGIES = [
    (name if codec_name == "none" else f"{name}_{codec_name}",
     make(codec_from_flags(codec_name) if codec_name != "none" else None))
    for name, make in _PARITY_GRID
    for codec_name in ("none", "int8")
] + [
    ("subsampled", SubsampledFedAvg(fraction=0.5,
                                    schedule=ParticipationSchedule(seed=3))),
]


@pytest.mark.parametrize("name,strategy", PARITY_STRATEGIES,
                         ids=[p[0] for p in PARITY_STRATEGIES])
def test_parity_bit_identical(name, strategy):
    """Full-fleet virtual run == dense stream run, bit for bit: every
    state entry (params, opt moments, EF residuals) and every metric."""
    data = client_shards(4)
    dense = dense_result(strategy, data, opt=Adam())
    _, virt = virtual_result(strategy, data, opt=Adam())
    assert set(dense.state) == set(virt.state)
    assert_trees_equal(dense.state, virt.state)
    assert dense.history == virt.history


def test_parity_covers_ef_residuals():
    """The codec parity case must actually exercise error feedback — a
    zero residual would make that row of the matrix vacuous."""
    data = client_shards(4)
    _, virt = virtual_result(FedAvgSync(codec=codec_from_flags("int8")), data)
    assert "ef" in virt.state and "ef_down" in virt.state
    assert any(float(jnp.abs(x).max()) > 0
               for x in jax.tree_util.tree_leaves(virt.state["ef"]))


def test_identity_schedule_compiles_once_and_swaps_nothing():
    data = client_shards(4)
    driver, virt = virtual_result(None, data, n_rounds=6)
    assert driver.n_traces == 1
    assert virt.timings["swapped_rows"] == 0
    assert virt.timings["a_total"] == virt.timings["a_active"] == 4
    assert virt.timings["data_kind"] == "virtual"


def test_sampled_fleet_runs_and_pages():
    """12 clients on 4 slots: still one trace, rows actually swap, the
    store materializes only participants, history stays finite."""
    data = client_shards(12)
    driver, virt = virtual_result(None, data, n_rounds=8, seed=9,
                                  schedule=ParticipationSchedule(seed=9))
    assert driver.n_traces == 1
    assert virt.timings["swapped_rows"] > 0
    assert 4 <= virt.timings["store_rows"] <= 12
    assert all(np.isfinite(m["d_loss"]) for m in virt.history)
    # every store row belongs to a client that actually participated
    seen = set()
    for r in range(8):
        seen.update(int(c) for c in driver.cohort(r))
    assert set(driver.store.client_ids()) <= seen


# ---------------------------------------------------------------------------
# participation schedule properties
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 50), r=st.integers(0, 200),
       n=st.integers(2, 40))
def test_cohort_reproducible_sorted_unique(seed, r, n):
    m = max(1, n // 3)
    sched = ParticipationSchedule(seed=seed)
    a = sched.cohort(r, n, m)
    b = sched.cohort(r, n, m)
    np.testing.assert_array_equal(a, b)          # pure function of (seed, r)
    assert len(a) == m == len(set(a.tolist()))   # exact size, no repeats
    assert list(a) == sorted(a.tolist())         # slot-stable ordering
    assert 0 <= a.min() and a.max() < n


def test_cohort_identity_when_full():
    sched = ParticipationSchedule(seed=4)
    for r in range(5):
        np.testing.assert_array_equal(sched.cohort(r, 6, 6), np.arange(6))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 30), r=st.integers(0, 100),
       n=st.integers(2, 30))
def test_mask_agrees_with_cohort(seed, r, n):
    """The traced (P, A) mask and the host cohort derive from one score
    stream: the mask's set bits are exactly the cohort ids."""
    m = max(1, n // 2)
    sched = ParticipationSchedule(seed=seed)
    mask = np.asarray(sched.mask(r, (1, n), m)).reshape(-1)
    np.testing.assert_array_equal(np.nonzero(mask)[0], sched.cohort(r, n, m))


def test_cohort_coverage_over_rounds():
    """Uniform sampling visits the whole fleet: every one of 12 clients
    appears within 60 rounds of 3-client cohorts."""
    sched = ParticipationSchedule(seed=1)
    seen = set()
    for r in range(60):
        seen.update(sched.cohort(r, 12, 3).tolist())
    assert seen == set(range(12))


def test_weighted_sampling_prefers_heavy_clients():
    heavy = 5
    w = tuple(10.0 if i == heavy else 1.0 for i in range(8))
    sched = ParticipationSchedule(seed=2, weights=w)
    counts = np.zeros(8)
    for r in range(400):
        counts[sched.cohort(r, 8, 2)] += 1
    light = np.delete(counts, heavy).mean()
    assert counts[heavy] > 3 * light   # ~10x propensity, loose 3x bound


def test_schedule_validate_rejects_bad_weights():
    with pytest.raises(ValueError, match="strictly positive"):
        ParticipationSchedule(weights=(1.0, -2.0)).validate()
    with pytest.raises(ValueError, match="weights for"):
        ParticipationSchedule(weights=(1.0, 2.0)).validate(3)
    with pytest.raises(ValueError, match="cohort size"):
        ParticipationSchedule().cohort(0, 4, 5)


# ---------------------------------------------------------------------------
# fault injection: stragglers never corrupt the average
# ---------------------------------------------------------------------------


def faults_at(round_idx, spec):
    """A faults hook planting ``spec`` (dict client->kind) at one round."""
    return lambda r, cohort: spec if r == round_idx else {}


def test_drop_reverts_client_and_renormalizes():
    """A dropped client's state is untouched (slot and store row equal its
    pre-round values) and the round average excludes it."""
    data = client_shards(4)
    driver, virt = virtual_result(
        None, data, n_rounds=2, faults=faults_at(1, {2: "drop"}))
    assert virt.timings["dropped"] == 1
    # after the final flush, client 2's params row must equal what it held
    # *before* round 1 — i.e. the round-0 broadcast average, which clients
    # 0/1/3 then trained past.  Recompute that broadcast from a 1-round run
    # (with a faults hook so round 0 takes the same split local+merge path)
    _, one = virtual_result(None, data, n_rounds=1, faults=faults_at(9, {}))
    want = tmap(lambda x: np.asarray(x[0, 0]), one.state["params"])
    assert_trees_equal(driver.store.row(2)["params"], want)
    # the survivors moved on: their rows differ from the pre-round value
    assert not np.allclose(
        np.asarray(driver.store.row(0)["params"]["gen"]["theta"]),
        np.asarray(want["gen"]["theta"]))


def test_block_mode_treats_late_as_on_time():
    """Under the default blocking policy a planted-late client is merely
    slow — the trajectory is bit-identical to a fault-free run (same
    split local+merge path; plain-path parity is the identity tests)."""
    data = client_shards(4)
    _, clean = virtual_result(None, data, n_rounds=3, faults=faults_at(9, {}))
    _, late = virtual_result(None, data, n_rounds=3,
                             faults=faults_at(1, {1: "late"}))
    assert_trees_equal(clean.state["params"], late.state["params"])
    assert late.timings["late"] == 0 and late.timings["merged_deltas"] == 0


def _delta_of_client(data, grid, K, seed, client):
    """What `client` locally trains in round 0 minus its init — computed
    independently of the driver from public APIs (LocalOnly twin + the
    driver's documented key derivation)."""
    from repro.data.federated import round_key_schedule
    fed = make_fed(None, grid, K)
    cfg = dataclasses.replace(fed.cfg, strategy=strategies.LocalOnly())
    fed_local = dataclasses.replace(fed, cfg=cfg)
    fleet = FleetRounds(data, grid, batch_size=8, sync_interval=K)
    data_rng, init_rng = jax.random.split(jax.random.key(seed))
    state = fed_local.init_state(init_rng)
    key0 = round_key_schedule(data_rng, 1)[0]
    b, s = fleet.round_batches(key0, list(range(len(data))))
    init_params = tmap(np.asarray, jax.device_get(state["params"]))
    state, _ = jax.jit(fed_local.round)(state, b, s)
    post = tmap(np.asarray, jax.device_get(state["params"]))
    P, A = grid
    p, a = client // A, client % A
    return tmap(lambda x, y: x[p, a] - y[p, a], post, init_params)


@pytest.mark.parametrize("delay,gamma", [(1, 0.5), (2, 0.25)])
def test_late_delta_merges_with_staleness_decay(delay, gamma):
    """The closed form: a delta submitted at round 0 arriving ``delay``
    rounds later folds in as ``gamma**delay * w_share * delta``.  Held by
    differencing a decay-``gamma`` run against a decay-0 run (identical
    until the merge, so the final params differ by exactly that term)."""
    data = client_shards(4)
    late_client = 1
    n_rounds = 1 + delay
    kw = dict(n_rounds=n_rounds,
              faults=faults_at(0, {late_client: f"late:{delay}"}))
    _, base = virtual_result(
        None, data, straggler=StragglerPolicy(mode="defer", decay=0.0,
                                              max_staleness=2), **kw)
    _, dec = virtual_result(
        None, data, straggler=StragglerPolicy(mode="defer", decay=gamma,
                                              max_staleness=2), **kw)
    assert dec.timings["late"] == 1 and dec.timings["merged_deltas"] == 1
    delta = _delta_of_client(data, (1, 4), 3, 7, late_client)
    scale = (gamma ** delay) * (1.0 / 4.0)   # uniform w_share = 1/slots
    for key in ("gen", "disc"):
        got = (np.asarray(dec.state["params"][key]["theta" if key == "gen" else "w"])
               - np.asarray(base.state["params"][key]["theta" if key == "gen" else "w"]))
        want = scale * np.asarray(
            delta[key]["theta" if key == "gen" else "w"])
        # broadcast to every on-time slot
        np.testing.assert_allclose(got, np.broadcast_to(want, got.shape),
                                   rtol=0, atol=1e-6)


def test_expired_delta_is_discarded():
    """A delta older than max_staleness at arrival never merges: the run
    equals one whose delta never arrives at all."""
    data = client_shards(4)
    kw = dict(n_rounds=5,
              straggler=StragglerPolicy(mode="defer", decay=0.9,
                                        max_staleness=2))
    _, expired = virtual_result(
        None, data, faults=faults_at(0, {2: "late:4"}), **kw)
    _, never = virtual_result(
        None, data, faults=faults_at(0, {2: "late:99"}), **kw)
    assert expired.timings["expired_deltas"] == 1
    assert expired.timings["merged_deltas"] == 0
    assert_trees_equal(expired.state, never.state)


def test_all_faulted_round_refused():
    data = client_shards(2)
    driver = virtual_driver(None, data, grid=(1, 2), n_rounds=2,
                            faults=faults_at(0, {0: "drop", 1: "drop"}))
    with pytest.raises(ValueError, match="every cohort member faulted"):
        driver.run(jax.random.key(0))


def test_fault_for_absent_client_refused():
    data = client_shards(4)
    driver = virtual_driver(None, data, n_rounds=2,
                            faults=faults_at(0, {9: "drop"}))
    with pytest.raises(ValueError, match="not in this round's cohort"):
        driver.run(jax.random.key(0))


def test_unknown_fault_kind_refused():
    data = client_shards(4)
    driver = virtual_driver(None, data, n_rounds=1,
                            faults=faults_at(0, {0: "tardy"}))
    with pytest.raises(ValueError, match="unknown fault"):
        driver.run(jax.random.key(0))


def test_merge_path_refuses_unmergeable_strategies():
    """The host merge is plain weighted FedAvg; anything else must refuse
    loudly at construction, not average wrongly."""
    data = client_shards(4)
    for strat in (FedAvgSync(codec=codec_from_flags("int8")),
                  FedAvgSync(sync_dtype=jnp.bfloat16),
                  FedAvgSync(average_opt_state=True),
                  strategies.STRATEGIES["trimmed_mean"]()):
        with pytest.raises(ValueError, match="straggler-tolerant merge"):
            virtual_driver(strat, data, n_rounds=2, faults=faults_at(0, {}))
    # defer mode alone (no faults hook) needs the same guarantee
    with pytest.raises(ValueError, match="straggler-tolerant merge"):
        virtual_driver(FedAvgSync(average_opt_state=True), data, n_rounds=2,
                       straggler=StragglerPolicy(mode="defer"))


def test_faults_with_checkpointing_refused():
    data = client_shards(4)
    with pytest.raises(ValueError, match="fault-injection"):
        virtual_driver(None, data, n_rounds=4, faults=faults_at(0, {}),
                       ckpt_every=2, ckpt_dir="/tmp/x")


# ---------------------------------------------------------------------------
# paging soundness
# ---------------------------------------------------------------------------


def test_plan_swap_is_sticky_and_minimal():
    slots, evicted, entering = plan_swap([3, 7, 1], [1, 5, 7])
    assert evicted == [0] and entering == [5]
    assert slots == [5, 7, 1]          # 7 and 1 never move
    slots, evicted, entering = plan_swap([2, 4], [4, 2])
    assert evicted == [] and entering == [] and slots == [2, 4]


def test_swap_roundtrip_bit_exact():
    """fetch -> store -> gather -> apply is the identity on slot state."""
    data = client_shards(4)
    fed = make_fed()
    driver = virtual_driver(None, data, n_rounds=1)
    state = fed.init_state(jax.random.key(5))
    driver.store = ClientStore.from_fed(fed, jax.random.key(5), 4)
    axes = state_axes(fed, state)
    rows = driver._fetch_slots(state, [0, 1, 2, 3], axes)
    driver.store.scatter([0, 1, 2, 3], rows)
    staged = driver.store.gather([0, 1, 2, 3])
    state2 = driver._apply_swap(state, [0, 1, 2, 3], staged, axes)
    assert_trees_equal(state, state2)


def test_store_copy_on_write_and_template_immutable():
    data = client_shards(16)
    driver, _ = virtual_result(None, data, n_rounds=4, seed=3,
                               schedule=ParticipationSchedule(seed=3))
    template = driver.store.template
    # untouched clients still read the shared template, not a private row
    untouched = set(range(16)) - set(driver.store.client_ids())
    assert untouched
    for c in untouched:
        assert driver.store.row(c) is template
    assert driver.store.materialized < 16


def test_hlo_compiled_for_slots_never_fleet():
    """The round executable's shapes are (P, A_active) — A_total must not
    appear as any HLO dimension.  37 is prime and distinctive."""
    a_total, grid = 37, (1, 4)
    data = client_shards(a_total, size=16)
    fed = make_fed(None, grid)
    fleet = FleetRounds(data, grid, batch_size=8, sync_interval=3)
    state = fed.init_state(jax.random.key(0))
    b, s = fleet.round_batches(jax.random.key(1), [0, 1, 2, 3])
    hlo = jax.jit(fed.round).lower(state, b, s).as_text()
    # StableHLO spells shapes tensor<1x4x3xf32>; a 37 dim would appear as
    # "37x" (not preceded by another digit)
    assert re.search(r"(?<!\d)37x", hlo) is None
    assert re.search(r"(?<!\d)1x4x", hlo) is not None   # the slot axes ARE there


def test_ef_residuals_page_with_their_client():
    """With a codec strategy on a sampled fleet the per-client uplink
    residual rides the store rows; the shared downlink residual does not."""
    data = client_shards(8)
    driver, virt = virtual_result(FedAvgSync(codec=codec_from_flags("int8")),
                                  data, n_rounds=6, seed=2,
                                  schedule=ParticipationSchedule(seed=2))
    assert virt.timings["swapped_rows"] > 0
    cid = driver.store.client_ids()[0]
    row = driver.store.row(cid)
    assert "ef" in row and "ef_down" not in row
    assert "params" in row and "opt_g" in row and "opt_d" in row


def test_undeclared_round_state_refused():
    fed = make_fed()
    state = fed.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="without declaring"):
        state_axes(fed, {**state, "mystery": 0})


def test_store_rejects_out_of_fleet_ids():
    store = ClientStore({"x": np.zeros(2)}, n_total=4)
    with pytest.raises(ValueError, match="outside fleet"):
        store.put(4, {"x": np.ones(2)})


# ---------------------------------------------------------------------------
# checkpoint / resume: exact cohort replay
# ---------------------------------------------------------------------------


def test_resume_replays_run_bit_exactly():
    """Checkpoint mid-run, reload, resume: final device state AND every
    host fleet row match the uninterrupted run bit for bit — including the
    participation sequence (no RNG state beyond (seed, round))."""
    data = client_shards(10)
    kw = dict(n_rounds=6, seed=11, schedule=ParticipationSchedule(seed=5))
    _, full = virtual_result(None, data, **kw)
    full_driver, _ = virtual_result(None, data, **kw)   # fresh store to read
    with tempfile.TemporaryDirectory() as d:
        driver = virtual_driver(None, data, n_rounds=6,
                                schedule=ParticipationSchedule(seed=5),
                                ckpt_every=3, ckpt_dir=d)
        driver.run(jax.random.key(11))
        state, store, slot_clients, next_round, meta = \
            load_fleet_checkpoint(d, step=3 * 3)   # round 2's boundary
        assert next_round == 3
        assert meta["participation_seed"] == 5
        resumed = virtual_driver(None, data, n_rounds=6,
                                 schedule=ParticipationSchedule(seed=5))
        out = resumed.run(jax.random.key(11), state=state, store=store,
                          slot_clients=slot_clients, start_round=3)
    assert_trees_equal(full.state, out.state)
    assert full_driver.store.client_ids() == resumed.store.client_ids()
    for c in resumed.store.client_ids():
        assert_trees_equal(full_driver.store.row(c), resumed.store.row(c))


def test_fleet_checkpoint_stays_host_side():
    data = client_shards(6)
    with tempfile.TemporaryDirectory() as d:
        driver = virtual_driver(None, data, grid=(1, 3), n_rounds=2,
                                ckpt_every=1, ckpt_dir=d)
        driver.run(jax.random.key(0))
        _, store, _, _, _ = load_fleet_checkpoint(d)
        for leaf in jax.tree_util.tree_leaves(store.template):
            assert isinstance(leaf, np.ndarray)
        for c in store.client_ids():
            for leaf in jax.tree_util.tree_leaves(store.row(c)):
                assert isinstance(leaf, np.ndarray)


def test_load_fleet_checkpoint_refuses_dense_checkpoints():
    from repro.checkpoint import save_checkpoint
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, {"params": np.zeros(3)}, step=1)
        with pytest.raises(ValueError, match="not a virtual-client"):
            load_fleet_checkpoint(d)


def test_resume_requires_store():
    data = client_shards(4)
    driver = virtual_driver(None, data, n_rounds=2)
    state = make_fed().init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="pass store="):
        driver.run(jax.random.key(0), state=state, start_round=1)


# ---------------------------------------------------------------------------
# construction refusals + weighting
# ---------------------------------------------------------------------------


def test_secure_agg_refused_on_sampled_cohorts():
    """Pairwise-mask cancellation needs the whole fleet on the wire; a
    sampled cohort must refuse loudly, full participation still works."""
    from repro.privacy import SecureAgg
    strat = FedAvgSync(secure_agg=SecureAgg(seed=0))
    with pytest.raises(ValueError, match="uncancelled"):
        virtual_driver(strat, client_shards(8), n_rounds=2)
    virtual_driver(strat, client_shards(4), n_rounds=2)   # A_total==A_active


def test_driver_construction_refusals():
    data = client_shards(4)
    with pytest.raises(ValueError, match="weighting"):
        virtual_driver(None, data, weighting="fastest")
    with pytest.raises(ValueError, match="slot_grid"):
        VirtualClientDriver(make_fed(None, (1, 2)),
                            FleetRounds(data, (1, 4), 8, 3), 2)
    with pytest.raises(ValueError, match="eval_hooks is empty"):
        virtual_driver(None, data, eval_every=2)
    with pytest.raises(ValueError, match="FedGAN.weights"):
        driver = virtual_driver(None, data)
        driver.fed = dataclasses.replace(driver.fed,
                                         weights=jnp.ones((1, 4)) / 4)
        driver.__post_init__()
    driver = virtual_driver(None, data, n_rounds=3)
    with pytest.raises(ValueError, match="start_round"):
        driver.run(jax.random.key(0), start_round=3)
    with pytest.raises(ValueError, match="cannot fill"):
        FleetRounds(client_shards(2), (1, 4), 8, 3)


def test_dataset_weighting_matches_dense_weighted_run():
    """weighting='dataset' on the identity cohort == dense FedGAN with the
    §3.1 |R_i|/Σ|R_j| weights, bit for bit."""
    sizes = [16, 32, 48, 32]
    data = [{"x": jax.random.normal(jax.random.fold_in(jax.random.key(0), i),
                                    (n, 3)) + i} for i, n in enumerate(sizes)]
    shares = jnp.asarray(sizes, jnp.float32).reshape(1, 4)
    shares = shares / shares.sum()
    dense = dense_result(None, data, weights=shares)
    driver, virt = virtual_result(None, data, weighting="dataset")
    np.testing.assert_allclose(driver._weights_row([0, 1, 2, 3]),
                               np.asarray(shares).reshape(-1))
    assert_trees_equal(dense.state["params"], virt.state["params"])


# ---------------------------------------------------------------------------
# launcher integration
# ---------------------------------------------------------------------------


def test_experiment_spec_virtual_wiring():
    from repro.launch.train import experiment_spec
    spec, _ = experiment_spec("mixed_gaussian", a_total=16, a_active=4,
                              steps=10, K=5, log_every=0)
    assert spec.virtual and spec.a_total == 16
    assert spec.agent_grid == (1, 4)
    assert len(spec.agent_data) == 16
    assert spec.agent_data[0]["x"].shape[0] == 512   # fleet-size shards
    fed, fleet = spec.build_fleet()
    assert fleet.num_clients == 16 and fleet.cohort_size == 4
    with pytest.raises(ValueError, match="conflicts with"):
        experiment_spec("mixed_gaussian", a_total=16, agents=4)
    with pytest.raises(ValueError, match="must be in"):
        experiment_spec("mixed_gaussian", a_total=4, a_active=8)


def test_cli_virtual_smoke():
    from repro.launch.train import build_parser, run_experiment
    args = build_parser().parse_args(
        ["--experiment", "mixed_gaussian", "--a-total", "8", "--a-active",
         "2", "--participation-seed", "3", "--straggler-policy", "defer"])
    assert (args.a_total, args.a_active) == (8, 2)
    assert args.participation_seed == 3 and args.straggler_policy == "defer"
    fed, state, history = run_experiment(
        "mixed_gaussian", K=2, steps=4, seed=0, a_total=8, a_active=2,
        samples_per_agent=32, batch_size=8, log_every=0)
    assert len(history) == 2
    assert all(np.isfinite(h["d_loss"]) for h in history)
