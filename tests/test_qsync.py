"""kernels.qsync — the fused sync hot path: kernel ↔ ref bit parity,
fused-vs-composed ``coded_sync`` bit-identity (synced tree, EF residuals,
wire images), O(1)-dispatch bucketing, and the strategy-level
``fused_sync`` knob."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.comm import IntQuant, TopK
from repro.core import FedGAN, FedGANConfig, GANTask
from repro.core.strategies import FedAvgSync, TrimmedMeanSync
from repro.dist import collectives
from repro.kernels.qsync import ops
from repro.optim import SGD, constant, equal_timescale

tmap = jax.tree_util.tree_map


def _composed(leaves, weights, codec, e_leaves, ed_leaves):
    """The per-leaf composed pipeline, written out — the oracle the fused
    path must match bit for bit."""
    outs, new_e, new_ed = [], [], []
    for x, e, ed in zip(leaves, e_leaves, ed_leaves):
        y = x + e if e is not None else x
        q = codec.roundtrip(y, batch_ndims=2)
        m = collectives.weighted_mean(q, weights)
        yd = m + ed if ed is not None else m
        qd = codec.roundtrip(yd)
        outs.append(jnp.broadcast_to(qd, x.shape))
        new_e.append(y - q if e is not None else None)
        new_ed.append(yd - qd if ed is not None else None)
    return outs, new_e, new_ed


def _tree(seed, grid, shapes):
    ks = jax.random.split(jax.random.key(seed), len(shapes))
    return [3.0 * jax.random.normal(k, grid + s, jnp.float32)
            for k, s in zip(ks, shapes)]


# ---------------------------------------------------------------------------
# qsync_flat: Pallas kernel (interpret) vs pure-jnp ref, bit-identical
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(n=st.integers(1, 700), b=st.integers(1, 3), bits=st.integers(0, 1),
       ef=st.integers(0, 1), seed=st.integers(0, 99))
def test_qsync_kernel_matches_ref(n, b, bits, ef, seed):
    """kernel.qsync_flat (interpret) and ref.qsync_flat_ref must agree
    exactly — synced stream and both residuals — across shapes, bit widths
    and EF on/off, including non-block-aligned n."""
    bits = (8, 4)[bits % 2]
    B = 2 * b
    ks = jax.random.split(jax.random.key(seed), 4)
    w = jax.random.uniform(ks[0], (2, b)) + 0.1
    w = w / jnp.sum(w)
    x = 3.0 * jax.random.normal(ks[1], (B, n))
    e = 0.05 * jax.random.normal(ks[2], (B, n)) if ef else None
    ed = 0.05 * jax.random.normal(ks[3], (n,)) if ef else None
    outs = {}
    for uk in (False, True):
        outs[uk] = ops.qsync_flat(w, x, e, ed, bits=bits, use_kernel=uk)
    for a, r in zip(outs[True], outs[False]):
        if a is None:
            assert r is None
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


# ---------------------------------------------------------------------------
# qsync_leaves' kernel path: the layout-native entry == the flat entry
# ---------------------------------------------------------------------------

ROWS_CASES = [((256, 384), True), ((2, 16, 256), True), ((8, 128), True),
              ((1048, 128), True),       # more rows than one tile holds
              ((2560, 80), False), ((4, 5120), False), ((80,), False)]


def _kernel_names(fn, *args):
    """The names of the pallas_calls that ``fn(*args)`` traces, in order."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [eqn.params["name"] for eqn in _eqns(jaxpr)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("use_ef", [True, False])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("grid", [(1, 2), (2, 2)])
@pytest.mark.parametrize("shape,native", ROWS_CASES)
def test_rows_entry_matches_flat_kernel(shape, native, grid, bits, use_ef):
    """The kernel path hands a leaf whose per-agent shape ends in a
    block-multiple C over an 8-multiple second-minor dim to the
    layout-native entry, every other leaf to the flat one; either way the
    synced leaf and both residuals equal, bit for bit, the flat kernel run
    on the same leaf's (B, n) stream."""
    assert (ops.rows_view(shape, 128) is not None) == native
    B = grid[0] * grid[1]
    x, e = _tree(5, grid, [shape, shape])
    e = 0.05 * e if use_ef else None
    ed = 0.05 * _tree(6, (), [shape])[0] if use_ef else None
    w = jax.random.uniform(jax.random.key(7), grid) + 0.1
    w = w / jnp.sum(w)

    def leaves_path(w, x, e, ed):
        return ops.qsync_leaves([x], w, None if e is None else [e],
                                None if ed is None else [ed], bits=bits,
                                use_kernel=True)

    names = _kernel_names(leaves_path, w, x, e, ed)
    assert names == ["qsync_flat_rows" if native else "qsync_flat"]
    (got,), (got_e,), (got_ed,) = leaves_path(w, x, e, ed)
    synced, ne, ned = ops.qsync_flat(
        w, x.reshape(B, -1), None if e is None else e.reshape(B, -1),
        None if ed is None else ed.reshape(-1), bits=bits, use_kernel=True)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jnp.broadcast_to(synced.reshape(shape), x.shape)))
    if use_ef:
        np.testing.assert_array_equal(np.asarray(got_e),
                                      np.asarray(ne.reshape(x.shape)))
        np.testing.assert_array_equal(np.asarray(got_ed),
                                      np.asarray(ned.reshape(shape)))
    else:
        assert got_e is None and got_ed is None and ne is None


# ---------------------------------------------------------------------------
# fused coded_sync == composed coded_sync, bit for bit
# ---------------------------------------------------------------------------

SHAPES = [(5, 7), (130,), (), (128,), (3, 1, 2)]


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("use_ef", [True, False])
def test_fused_matches_composed(bits, weighted, use_ef):
    """The bucketed fused path reproduces the composed per-leaf pipeline
    exactly: synced values (the downlink wire image), uplink residuals and
    downlink residuals — which together pin both wire images, since
    uplink_wire = (x + ef) - new_ef and downlink_wire = synced."""
    grid = (2, 2)
    leaves = _tree(0, grid, SHAPES)
    if weighted:
        w = jax.random.uniform(jax.random.key(9), grid) + 0.1
        w = w / jnp.sum(w)
    else:
        w = jnp.full(grid, 0.25)
    e_leaves = ([0.05 * l for l in _tree(1, grid, SHAPES)] if use_ef
                else [None] * len(SHAPES))
    ed_leaves = ([jnp.mean(l, axis=(0, 1)) * 0.05
                  for l in _tree(2, grid, SHAPES)] if use_ef
                 else [None] * len(SHAPES))
    codec = IntQuant(bits=bits, use_kernel=False)
    c_out, c_ne, c_ned = _composed(leaves, w, codec, e_leaves, ed_leaves)
    for uk in (False, True):  # vectorized ref AND interpret-mode kernel
        f_out, f_ne, f_ned = ops.qsync_leaves(
            leaves, w,
            e_leaves if use_ef else None,
            ed_leaves if use_ef else None, bits=bits, use_kernel=uk)
        for cs, fs in ((c_out, f_out), (c_ne, f_ne), (c_ned, f_ned)):
            for a, b in zip(cs, fs):
                if a is None:
                    assert b is None
                    continue
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_coded_sync_fused_flag_matrix():
    """coded_sync(fused=None|False|True) all land on the same bits; auto
    fuses when the codec has a spec, True raises when it cannot."""
    grid = (2, 2)
    tree = {"a": _tree(0, grid, [(5, 7)])[0], "b": _tree(3, grid, [(33,)])[0],
            "count": jnp.asarray(3, jnp.int32)}
    ef = tmap(lambda x: x * 0.01, tree)
    ed = tmap(lambda x: (x[0, 0] * 0.01 if x.ndim > 0 else x), tree)
    w = jnp.full(grid, 0.25)
    codec = IntQuant(use_kernel=False)
    ref_out = collectives.coded_sync(tree, w, codec, ef=ef, ef_down=ed,
                                     fused=False)
    for fused in (None, True):
        got = collectives.coded_sync(tree, w, codec, ef=ef, ef_down=ed,
                                     fused=fused)
        for a, b in zip(jax.tree_util.tree_leaves(ref_out),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # integer leaves pass through untouched on the fused path too
    assert int(ref_out[0]["count"]) == 3
    with pytest.raises(ValueError, match="fused_sync_spec"):
        collectives.coded_sync(tree, w, TopK(), fused=True)
    with pytest.raises(ValueError, match="custom reduce"):
        collectives.coded_sync(tree, w, codec, fused=True,
                               reduce=collectives.make_robust_reduce("median"))
    # a custom reduce silently disables auto-fusion (robust stats need the
    # per-agent wire images) — same values as the explicit composed call
    red = collectives.make_robust_reduce("median")
    a = collectives.coded_sync(tree, w, codec, reduce=red)
    b = collectives.coded_sync(tree, w, codec, reduce=red, fused=False)
    for x, y in zip(jax.tree_util.tree_leaves(a[0]),
                    jax.tree_util.tree_leaves(b[0])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_non_f32_leaf_falls_back_to_composed():
    """bf16 leaves can't ride the fused kernel (it reduces in f32, which
    would widen the composed numerics) — they take the per-leaf pipeline
    and the result still matches fused=False exactly."""
    grid = (2, 2)
    tree = {"a": _tree(0, grid, [(40,)])[0],
            "h": _tree(1, grid, [(24,)])[0].astype(jnp.bfloat16)}
    w = jnp.full(grid, 0.25)
    codec = IntQuant(use_kernel=False)
    auto = collectives.coded_sync(tree, w, codec)
    composed = collectives.coded_sync(tree, w, codec, fused=False)
    for a, b in zip(jax.tree_util.tree_leaves(auto[0]),
                    jax.tree_util.tree_leaves(composed[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# O(1) dispatch: the bucketed sync quantizes twice, however many leaves
# ---------------------------------------------------------------------------


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(x, jax.extend.core.Jaxpr)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _count_prim(jaxpr, name: str) -> int:
    return sum(eqn.primitive.name == name for eqn in _eqns(jaxpr))


def test_bucketed_sync_is_constant_dispatch():
    """The composed pipeline rounds 2x per leaf (uplink + downlink); the
    bucketed fused path rounds exactly twice TOTAL, independent of leaf
    count — the jaxpr-level witness of O(1) kernel launches per sync."""
    grid = (2, 2)
    w = jnp.full(grid, 0.25)
    codec = IntQuant(use_kernel=False)
    for n_leaves in (2, 5):
        tree = {f"l{i}": x
                for i, x in enumerate(_tree(0, grid, [(9,)] * n_leaves))}
        fused_jaxpr = jax.make_jaxpr(
            lambda t: collectives.coded_sync(t, w, codec)[0])(tree)
        composed_jaxpr = jax.make_jaxpr(
            lambda t: collectives.coded_sync(t, w, codec, fused=False)[0])(
                tree)
        assert _count_prim(fused_jaxpr.jaxpr, "round") == 2
        assert _count_prim(composed_jaxpr.jaxpr, "round") == 2 * n_leaves


# ---------------------------------------------------------------------------
# strategy integration: fused_sync knob
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


def _run_rounds(strategy, n_rounds=2, K=4, grid=(1, 4)):
    fed = FedGAN(quad_task(),
                 FedGANConfig(agent_grid=grid, sync_interval=K,
                              strategy=strategy),
                 opt_g=SGD(), opt_d=SGD(),
                 scales=equal_timescale(constant(0.05)))
    P, A = grid
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


@pytest.mark.parametrize("bits", [8, 4])
def test_strategy_round_fused_matches_composed(bits):
    """Two full training rounds through FedAvgSync: the fused_sync=True and
    fused_sync=False trajectories are bit-identical — params, residuals,
    metrics."""
    base = FedAvgSync(codec=IntQuant(bits=bits, block=16, use_kernel=False),
                      average_opt_state=True)
    s_fused, m_fused = _run_rounds(dataclasses.replace(base,
                                                       fused_sync=True))
    s_comp, m_comp = _run_rounds(dataclasses.replace(base, fused_sync=False))
    for a, b in zip(jax.tree_util.tree_leaves((s_fused, m_fused)),
                    jax.tree_util.tree_leaves((s_comp, m_comp))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_sync_billed_bytes_unchanged():
    """The fused path is an execution detail: §3.2 wire accounting must not
    move by a single byte."""
    cfg = FedGANConfig(agent_grid=(1, 4), sync_interval=4)
    params = {"gen": {"w": jax.ShapeDtypeStruct((1, 4, 257), jnp.float32)},
              "disc": {"w": jax.ShapeDtypeStruct((1, 4, 64), jnp.float32)}}
    codec = IntQuant(bits=4)
    for fused in (True, False, None):
        s = FedAvgSync(codec=codec, fused_sync=fused)
        assert (s.bytes_per_round(cfg, params)
                == FedAvgSync(codec=codec).bytes_per_round(cfg, params))


def test_fused_sync_validation():
    cfg = FedGANConfig(agent_grid=(1, 4), sync_interval=4)
    with pytest.raises(ValueError, match="needs a codec"):
        FedAvgSync(fused_sync=True).validate(cfg)
    with pytest.raises(ValueError, match="fused_sync_spec"):
        FedAvgSync(fused_sync=True, codec=TopK()).validate(cfg)
    with pytest.raises(ValueError, match="robust reduce"):
        TrimmedMeanSync(fused_sync=True, codec=IntQuant()).validate(cfg)
    # the spec round-trips the codec's knobs into the fused call
    spec = IntQuant(bits=4, block=64, use_kernel=False).fused_sync_spec()
    assert spec == {"bits": 4, "block": 64, "use_kernel": False}
    assert TopK().fused_sync_spec() is None
