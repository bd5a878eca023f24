"""repro.comm — codec round-trip properties (qpack kernel ↔ ref parity,
quantization error bounds, honest wire accounting), error-feedback
accumulation closed form, strategy/CLI integration, and the int8+EF
mixed-Gaussian convergence claim."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import (IntQuant, Sequential, TopK, codec_from_flags,
                        get_codec)
from repro.core import FedGAN, FedGANConfig, GANTask, losses
from repro.core.strategies import (FedAvgSync, LocalOnly, PartialSharing,
                                   SubsampledFedAvg)
from repro.dist import collectives
from repro.kernels.qpack import ops, ref
from repro.optim import Adam, SGD, constant, equal_timescale

tmap = jax.tree_util.tree_map


# ---------------------------------------------------------------------------
# qpack: Pallas pack/unpack vs ref oracle, bit-identical
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 700), rows=st.integers(1, 5),
       bits=st.integers(0, 1), block=st.integers(0, 2), seed=st.integers(0, 99))
def test_qpack_kernel_matches_ref(n, rows, bits, block, seed):
    """Kernel (interpret) and ref must agree exactly — codes, scales and
    dequantized values — across shapes, bit widths and block sizes."""
    bits = (8, 4)[bits % 2]
    block = (64, 128, 512)[block % 3]
    x = 3.0 * jax.random.normal(jax.random.key(seed), (rows, n))
    qk, sk = ops.quantize_blocks(x, bits=bits, block=block, use_kernel=True)
    qr, sr = ops.quantize_blocks(x, bits=bits, block=block, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    dk = ops.dequantize_blocks(qk, sk, n=n, bits=bits, block=block,
                               use_kernel=True)
    dr = ops.dequantize_blocks(qr, sr, n=n, bits=bits, block=block,
                               use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 900), bits=st.integers(0, 1), seed=st.integers(0, 99))
def test_quantize_roundtrip_error_bounded(n, bits, seed):
    """Per-block reconstruction error <= scale/2 (round-to-nearest) and the
    padded lanes never leak into the output."""
    bits = (8, 4)[bits % 2]
    block = 128
    x = jax.random.normal(jax.random.key(seed), (2, n))
    q, s = ops.quantize_blocks(x, bits=bits, block=block)
    out = ops.dequantize_blocks(q, s, n=n, bits=bits, block=block)
    assert out.shape == x.shape
    err = np.abs(np.asarray(out) - np.asarray(x))
    per_block_scale = np.repeat(np.asarray(s, np.float32), block,
                                axis=-1)[:, :n]
    assert (err <= 0.5 * per_block_scale + 1e-7).all()


def test_int4_pack_is_two_codes_per_byte():
    q = jnp.arange(-7, 8, dtype=jnp.int8).reshape(1, 15)
    q = jnp.pad(q, ((0, 0), (0, 1)))  # even length
    packed = ref.pack4_ref(q)
    assert packed.shape == (1, 8) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(ref.unpack4_ref(packed)),
                                  np.asarray(q))


def test_overflow_block_clips_instead_of_nan():
    """A block whose max-abs overflows the f16 scale must clip hard (EF
    absorbs the error) — never ship inf and decode 0*inf = NaN."""
    for bits in (8, 4):
        codec = IntQuant(bits=bits)
        x = jnp.full((256,), 9e6, jnp.float32)
        out = np.asarray(codec.roundtrip(x))
        assert np.isfinite(out).all(), bits
        qmax = 2 ** (bits - 1) - 1
        np.testing.assert_allclose(out, 65504.0 * qmax, rtol=1e-3)


def test_zero_block_roundtrips_to_zero():
    """A tile whose max-abs underflows f16 must decode to exact zeros, not
    NaN/inf from a zero-division."""
    x = jnp.concatenate([jnp.zeros((1, 128)),
                         1e-9 * jnp.ones((1, 128)),
                         jnp.ones((1, 128))], axis=1)
    q, s = ops.quantize_blocks(x, bits=8, block=128)
    out = np.asarray(ops.dequantize_blocks(q, s, n=384, bits=8, block=128))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[0, :256], 0.0)
    assert abs(out[0, 300] - 1.0) < 1e-2


# ---------------------------------------------------------------------------
# codec layer: wire accounting is honest, top-k keeps the right entries
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 2000), frac=st.floats(0.01, 1.0),
       seed=st.integers(0, 99))
def test_topk_keeps_largest_and_bills_indices(n, frac, seed):
    codec = TopK(fraction=frac)
    x = jax.random.normal(jax.random.key(seed), (n,))
    like = jax.ShapeDtypeStruct((n,), jnp.float32)
    k = codec._k(n)
    out = np.asarray(codec.roundtrip(x))
    xs = np.asarray(x)
    kept = np.flatnonzero(out)
    assert len(kept) <= k
    # every surviving entry is exact, and no dropped |x| beats a kept one
    np.testing.assert_array_equal(out[kept], xs[kept])
    if k < n:
        thresh = np.sort(np.abs(xs))[-k]
        dropped = np.setdiff1d(np.arange(n), kept)
        assert (np.abs(xs[dropped]) <= thresh + 1e-7).all()
    # indices billed at 4 bytes, values at the leaf dtype
    assert codec.wire_bytes(like) == k * 4 + k * 4


@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 4000), bits=st.integers(0, 1))
def test_wire_bytes_match_materialized_arrays(n, bits):
    """wire_bytes must equal the trimmed payload + every meta array — the
    accounting can never drift from what encode actually produces."""
    bits = (8, 4)[bits % 2]
    codec = IntQuant(bits=bits)
    x = jax.random.normal(jax.random.key(0), (n,))
    like = jax.ShapeDtypeStruct((n,), jnp.float32)
    payload, meta = codec.encode(x)
    trimmed = (n * bits + 7) // 8  # padding lanes are never shipped
    meta_b = sum(int(m.size) * m.dtype.itemsize
                 for m in jax.tree_util.tree_leaves(meta))
    assert codec.wire_bytes(like) == trimmed + meta_b
    # padded payload only ever exceeds the billed bytes by < one block
    assert 0 <= payload.size * payload.dtype.itemsize - trimmed \
        < codec.block * bits // 8


def test_roundtrip_override_matches_encode_decode():
    """IntQuant.roundtrip skips the int4 nibble pack/unpack (a bit-exact
    identity) — the values must match the real wire path exactly."""
    for bits in (8, 4):
        codec = IntQuant(bits=bits, block=64)
        x = jax.random.normal(jax.random.key(5), (2, 3, 333))
        like = jax.ShapeDtypeStruct((333,), jnp.float32)
        payload, meta = codec.encode(x, 2)
        via_wire = codec.decode(payload, meta, like, 2)
        np.testing.assert_array_equal(np.asarray(codec.roundtrip(x, 2)),
                                      np.asarray(via_wire))


def test_sequential_chains_and_bills_every_stage():
    n = 1000
    like = jax.ShapeDtypeStruct((n,), jnp.float32)
    chain = Sequential((TopK(fraction=0.1), IntQuant(bits=8)))
    chain.validate()
    k = TopK(fraction=0.1)._k(n)
    want = (k * 4                                    # indices
            + IntQuant(bits=8).wire_bytes(jax.ShapeDtypeStruct((k,),
                                                               jnp.float32)))
    assert chain.wire_bytes(like) == want
    x = jax.random.normal(jax.random.key(1), (2, 2, n))
    out = np.asarray(chain.roundtrip(x, batch_ndims=2))
    assert out.shape == x.shape
    assert (np.count_nonzero(out, axis=-1) <= k).all()
    # quantizers are terminal: int8 codes cannot be re-encoded downstream
    with pytest.raises(ValueError, match="last stage"):
        Sequential((IntQuant(bits=8), TopK())).validate()


def test_registry_and_flag_resolution():
    assert get_codec("int8") == IntQuant(bits=8)
    assert get_codec("topk+int8", fraction=0.25, bits=8) == \
        Sequential((TopK(fraction=0.25), IntQuant(bits=8)))
    with pytest.raises(ValueError, match="unknown codec"):
        get_codec("bogus")
    assert codec_from_flags() is None
    assert codec_from_flags("int4") == IntQuant(bits=4)
    assert codec_from_flags("", bits=4) == IntQuant(bits=4)
    assert codec_from_flags("", topk=0.05) == TopK(fraction=0.05)
    # --topk beside a quantizer spec builds the sparsify-then-quantize chain
    assert codec_from_flags("int8", topk=0.25) == \
        Sequential((TopK(fraction=0.25), IntQuant(bits=8)))
    with pytest.raises(ValueError):
        IntQuant(bits=3).validate()
    with pytest.raises(ValueError):
        IntQuant(block=7).validate()
    with pytest.raises(ValueError):
        TopK(fraction=0.0).validate()


# ---------------------------------------------------------------------------
# error feedback: closed-form accumulation
# ---------------------------------------------------------------------------


def test_error_feedback_telescopes():
    """EF invariant: with y_t = x + e_{t-1}, q_t = Q(y_t), e_t = y_t - q_t,
    the transmitted sum telescopes to sum(q_1..t) = t*x - e_t exactly, and
    the residual stays bounded by one quantization step (no blow-up)."""
    codec = IntQuant(bits=4, block=16)
    x = jax.random.normal(jax.random.key(3), (64,))
    e = jnp.zeros_like(x)
    total = jnp.zeros_like(x)
    qmax = 2 ** (codec.bits - 1) - 1
    for t in range(1, 9):
        y = x + e
        q = codec.roundtrip(y)
        total = total + q
        e = y - q
        np.testing.assert_allclose(np.asarray(total), t * np.asarray(x)
                                   - np.asarray(e), rtol=0, atol=1e-5)
        # residual bound: half a step of the *current* block scales
        _, meta = codec.encode(y)
        step = np.repeat(np.asarray(meta["scale"], np.float32),
                         codec.block)[:64]
        assert (np.abs(np.asarray(e)) <= 0.5 * step + 1e-7).all()
    # time-average of what the intermediary saw converges to x
    np.testing.assert_allclose(np.asarray(total) / 8, np.asarray(x),
                               atol=float(step.max()))


# ---------------------------------------------------------------------------
# strategy integration
# ---------------------------------------------------------------------------


def quad_task():
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


def _fed(strategy, K=4, grid=(1, 4)):
    return FedGAN(quad_task(),
                  FedGANConfig(agent_grid=grid, sync_interval=K,
                               strategy=strategy),
                  opt_g=SGD(), opt_d=SGD(),
                  scales=equal_timescale(constant(0.05)))


def _run_rounds(fed, n_rounds=2, K=4):
    P, A = fed.cfg.agent_grid
    state = fed.init_state(jax.random.key(0))
    round_fn = jax.jit(fed.round)
    for r in range(n_rounds):
        rng = jax.random.key(1 + r)
        x = (jax.random.normal(rng, (K, P, A, 8, 3))
             + jnp.arange(P * A, dtype=jnp.float32).reshape(P, A)[None, :, :,
                                                                  None, None])
        seeds = jax.random.randint(jax.random.fold_in(rng, 7), (K, P, A), 0,
                                   2 ** 31 - 1).astype(jnp.uint32)
        state, metrics = round_fn(state, {"x": x}, seeds)
    return state, metrics


def test_coded_sync_state_carries_residuals():
    state, metrics = _run_rounds(_fed(FedAvgSync(codec=IntQuant(bits=8))))
    assert "ef" in state and "ef_down" in state
    assert state["ef"]["gen"]["theta"].shape == (1, 4, 3)     # per-agent
    assert state["ef_down"]["gen"]["theta"].shape == (3,)     # shared
    assert float(jnp.max(jnp.abs(state["ef"]["gen"]["theta"]))) > 0
    assert np.isfinite(np.asarray(metrics["d_loss"])).all()
    # all agents hold the same (coded) average after sync
    th = state["params"]["gen"]["theta"]
    np.testing.assert_array_equal(np.asarray(th[0, 0]), np.asarray(th[0, -1]))
    # without error feedback (or without a codec) the state stays lean
    state, _ = _run_rounds(_fed(FedAvgSync(codec=IntQuant(bits=8),
                                           error_feedback=False)))
    assert "ef" not in state and "ef_down" not in state
    state, _ = _run_rounds(_fed(FedAvgSync()))
    assert "ef" not in state and "ef_down" not in state


def test_coded_sync_matches_manual_ef_average():
    """One round of the coded path == the hand-rolled EF + decode→average→
    encode pipeline applied to the uncoded (local-only) trajectory."""
    K, grid = 4, (1, 4)
    codec = IntQuant(bits=8, block=16)
    coded, _ = _run_rounds(_fed(FedAvgSync(codec=codec), K=K, grid=grid),
                           n_rounds=1, K=K)
    local, _ = _run_rounds(_fed(LocalOnly(), K=K, grid=grid),
                           n_rounds=1, K=K)
    w = np.full((1, 4), 0.25, np.float32)
    for sub in ("gen", "disc"):
        for key, pre in local["params"][sub].items():
            pre = jnp.asarray(pre)
            q = codec.roundtrip(pre, batch_ndims=2)       # ef was zero
            m = jnp.einsum("pa,pa...->...", jnp.asarray(w), q)
            qd = codec.roundtrip(m)                       # ef_down was zero
            np.testing.assert_allclose(
                np.asarray(coded["params"][sub][key][0, 0]), np.asarray(qd),
                rtol=0, atol=1e-7)
            np.testing.assert_allclose(
                np.asarray(coded["ef"][sub][key]), np.asarray(pre - q),
                rtol=0, atol=1e-7)
            np.testing.assert_allclose(
                np.asarray(coded["ef_down"][sub][key]), np.asarray(m - qd),
                rtol=0, atol=1e-7)


def test_subsampled_coded_keeps_nonparticipant_residuals():
    K, grid = 2, (1, 4)
    strat = SubsampledFedAvg(fraction=0.5, codec=IntQuant(bits=8))
    fed = _fed(strat, K=K, grid=grid)
    state, _ = _run_rounds(fed, n_rounds=1, K=K)
    mask = np.asarray(strat.participation_mask(fed,
                                               {"step": jnp.int32(K)}))
    ef = np.asarray(state["ef"]["gen"]["theta"])
    # non-participants never encoded -> their residuals are still zero
    assert (ef[~mask] == 0).all()
    assert (np.abs(ef[mask]).max(axis=-1) > 0).all()


def test_partial_sharing_coded_bytes_and_residual_scope():
    state, _ = _run_rounds(_fed(PartialSharing(codec=IntQuant(bits=8))))
    assert set(state["ef"]) == {"gen"}  # D never hits the wire
    fed = _fed(FedAvgSync())
    params = fed.agent_params(fed.init_state(jax.random.key(0)))
    full = FedAvgSync().bytes_per_round(fed.cfg, params)
    gen_only = PartialSharing(codec=IntQuant(bits=8)).bytes_per_round(
        fed.cfg, params)
    assert gen_only < FedAvgSync(codec=IntQuant(bits=8)).bytes_per_round(
        fed.cfg, params) < full


def test_codec_bytes_reduction_on_real_params():
    """On the paper's mixed-Gaussian MLP GAN the billed wire cut is >= 3.5x
    (int8, scales included) and >= 4x (int4 / topk+int8) vs f32 FedAvg."""
    from repro.launch.train import mlp_gan_task
    task, _ = mlp_gan_task()
    params = jax.eval_shape(task.init, jax.random.key(0))
    cfg = FedGANConfig(agent_grid=(1, 4), sync_interval=20)
    full = FedAvgSync().bytes_per_round(cfg, params)
    i8 = FedAvgSync(codec=IntQuant(bits=8)).bytes_per_round(cfg, params)
    i4 = FedAvgSync(codec=IntQuant(bits=4)).bytes_per_round(cfg, params)
    tk8 = FedAvgSync(codec=Sequential((TopK(fraction=0.125),
                                       IntQuant(bits=8)))
                     ).bytes_per_round(cfg, params)
    assert full / i8 >= 3.5
    assert full / i4 >= 4.0
    assert full / tk8 >= 4.0


def test_config_validation_rejects_codec_misuse():
    cfg = FedGANConfig(agent_grid=(1, 4), sync_interval=4)
    with pytest.raises(ValueError, match="wire compressions"):
        FedAvgSync(codec=IntQuant(bits=8),
                   sync_dtype=jnp.bfloat16).validate(cfg)
    with pytest.raises(ValueError, match="wire compressions"):
        collectives.sync_bytes({"x": jnp.ones(4)},
                               sync_dtype=jnp.bfloat16,
                               codec=IntQuant(bits=8))
    # invalid codec knobs surface through strategy validation too
    with pytest.raises(ValueError, match="bits"):
        FedGANConfig(agent_grid=(1, 4), sync_interval=4,
                     strategy=FedAvgSync(codec=IntQuant(bits=3))).validate()


def test_cli_codec_flags():
    from repro.launch.train import build_parser, strategy_from_args

    def args(*argv):
        return build_parser().parse_args(["--experiment", "toy_2d",
                                          *argv])

    strat = strategy_from_args(args("--codec", "int8"))
    assert isinstance(strat, FedAvgSync) and strat.codec == IntQuant(bits=8)
    strat = strategy_from_args(args("--strategy", "partial_sharing",
                                    "--codec", "int4"))
    assert isinstance(strat, PartialSharing)
    assert strat.codec == IntQuant(bits=4)
    strat = strategy_from_args(args("--codec", "int8", "--topk", "0.25"))
    assert strat.codec == Sequential((TopK(fraction=0.25),
                                      IntQuant(bits=8)))
    # strategies that never sync (or sync per step) have no codec knob
    with pytest.raises(ValueError, match="does not accept"):
        strategy_from_args(args("--strategy", "local_only",
                                "--codec", "int8"))
    with pytest.raises(ValueError, match="does not accept"):
        strategy_from_args(args("--strategy", "distributed",
                                "--codec", "int8"))
    # double compression and legacy-mode mixes fail loudly
    with pytest.raises(ValueError, match="pick one"):
        strategy_from_args(args("--codec", "int8", "--sync-dtype", "bf16"))
    with pytest.raises(ValueError, match="requires --strategy"):
        strategy_from_args(args("--mode", "fedgan", "--codec", "int8"))
    # bare --codec implies fedgan, still through the stray-knob validation
    with pytest.raises(ValueError, match="does not accept"):
        strategy_from_args(args("--codec", "int8", "--participation", "0.5"))
    # a malformed chain spec is a clean error, not a traceback
    assert codec_from_flags("int8+") == IntQuant(bits=8)
    with pytest.raises(ValueError, match="empty codec spec"):
        codec_from_flags("+")


def test_checkpoint_roundtrip_carries_residuals(tmp_path):
    """EF residuals are training state: they must survive a save/load."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    fed = _fed(FedAvgSync(codec=IntQuant(bits=8)))
    state, _ = _run_rounds(fed, n_rounds=1)
    save_checkpoint(str(tmp_path), state, step=1)
    loaded, _ = restore_checkpoint(str(tmp_path))
    la = jax.tree_util.tree_leaves(loaded)
    sa = jax.tree_util.tree_leaves(state)
    assert len(la) == len(sa)
    for a, b in zip(sa, la):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mesh_round_specs_cover_residuals():
    """build_train_round must give the strategy-carried EF entries mesh
    shardings (jit would reject a state/sharding pytree mismatch): the
    agent-stacked uplink residuals shard like the params, the shared
    downlink residual is replicated."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import build_train_round
    from repro.models.config import ShapeConfig
    mesh = make_test_mesh((1, 1), ("data", "model"))
    built = build_train_round(get_config("gemma3-4b").smoke(),
                              ShapeConfig("t", 1, 8, "train"), mesh, K=2,
                              strategy=FedAvgSync(codec=IntQuant(bits=8)))
    specs = built.meta["state_specs"]
    state_sds = built.input_sds[0]
    assert set(specs) == set(state_sds) >= {"ef", "ef_down"}
    assert jax.tree_util.tree_structure(
        tmap(lambda _: 0, specs["ef"], is_leaf=lambda x: isinstance(x, P))
    ) == jax.tree_util.tree_structure(tmap(lambda _: 0, state_sds["ef"]))
    down = jax.tree_util.tree_leaves(
        specs["ef_down"], is_leaf=lambda x: isinstance(x, P))
    assert down and all(s == P() for s in down)


# ---------------------------------------------------------------------------
# convergence: int8+EF holds mode coverage at matched steps
# ---------------------------------------------------------------------------


def _mixed_gaussian_coverage(strategy, steps=1500, B=4, K=5):
    from repro.data import synthetic
    from repro.evals import mode_stats
    from repro.models.gan_nets import MLPDiscriminator, MLPGenerator
    G = MLPGenerator(latent_dim=2, out_dim=2, hidden=64, depth=2)
    D = MLPDiscriminator(in_dim=2, hidden=64, depth=2)

    def init(rng):
        kg, kd = jax.random.split(rng)
        return {"gen": G.init(kg), "disc": D.init(kd)}

    def disc_loss(params, batch, rng):
        fake = jax.lax.stop_gradient(G.apply(params["gen"], batch["z"]))
        return losses.ns_d_loss(D.apply(params["disc"], batch["x"]),
                                D.apply(params["disc"], fake))

    def gen_loss(params, batch, rng):
        return losses.ns_g_loss(
            D.apply(params["disc"], G.apply(params["gen"], batch["z"])))

    task = GANTask(init=init, disc_loss=disc_loss, gen_loss=gen_loss)
    fed = FedGAN(task, FedGANConfig(agent_grid=(1, B), sync_interval=K,
                                    strategy=strategy),
                 opt_g=Adam(), opt_d=Adam(),
                 scales=equal_timescale(constant(1e-3)))
    state = fed.init_state(jax.random.key(0))
    round_fn = jax.jit(fed.round)
    rng = jax.random.key(1)
    n = 128
    for r in range(steps // K):
        rng, r1, r2, r3 = jax.random.split(rng, 4)
        x = jnp.stack([synthetic.sample_mixed_gaussian(
            jax.random.fold_in(r1, r * B + i), K * n,
            mode_subset=[2 * i, 2 * i + 1]).reshape(K, n, 2)
            for i in range(B)], axis=1).reshape(K, 1, B, n, 2)
        z = jax.random.normal(r2, (K, 1, B, n, 2))
        seeds = jax.random.randint(r3, (K, 1, B), 0,
                                   2 ** 31 - 1).astype(jnp.uint32)
        state, _ = round_fn(state, {"x": x, "z": z}, seeds)
    gp = fed.averaged_params(state)["gen"]
    samples = G.apply(gp, jax.random.normal(jax.random.key(9), (2000, 2)))
    assert not np.isnan(np.asarray(samples)).any()
    covered, _, _ = mode_stats(samples, synthetic.mixed_gaussian_modes(),
                               radius=0.5)
    return int(covered)


def test_int8_ef_holds_mode_coverage_at_matched_steps():
    """The acceptance claim: int8+error-feedback must keep the pooled mode
    coverage within 1 mode of the uncompressed run at equal (K, steps),
    while the billed wire shrinks 3.9x (see
    test_codec_bytes_reduction_on_real_params)."""
    base = _mixed_gaussian_coverage(None)
    coded = _mixed_gaussian_coverage(FedAvgSync(codec=IntQuant(bits=8)))
    assert coded >= base - 1, (base, coded)
    assert coded >= 5, coded
