"""Compile the main path for a TPU v5e chip that is described, not attached.

The chip's compiler (Mosaic for the Pallas kernels, XLA:TPU for the round)
refuses things the CPU interpreter accepts: block shapes off the (8, 128)
tiling, casts it cannot lower, programs larger than the chip's memory.
These tests compile each kernel at real widths and two whole rounds against
the ``v5e:2x2`` topology, without running anything.

The topology is described inside a fixture: only one process may load the
TPU library, so nothing here touches it while modules are imported.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm import IntQuant
from repro.configs import get_config
from repro.core.strategies import FedAvgSync
from repro.data.federated import round_key_schedule
from repro.kernels.fedavg.kernel import fedavg_flat
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.qpack import kernel as qpack
from repro.kernels.qpack import ops as qpack_ops
from repro.kernels.qsync import kernel as qsync
from repro.kernels.qsync import ops as qsync_ops
from repro.kernels.ssd_scan.kernel import ssd_bthd
from repro.launch.train import arch_smoke_spec, experiment_spec

HBM_BYTES = 15.75 * 2 ** 30     # what XLA:TPU lets one v5e program use
# mamba2-2.7b's widest leaf: the in_proj of one layer, d_model 2560 ->
# 2 * d_inner + 2 * d_state + heads = 10576, as one agent's flat stream
LEAF = 2560 * 10576
AGENTS = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _steer_to_kernels(monkeypatch):
    """Steer the ops-layer backend pick to the compiled kernels, as a TPU
    backend would: under JAX_PLATFORMS=cpu it sees the CPU."""
    for ops in (qsync_ops, qpack_ops):
        monkeypatch.setattr(ops, "_use_kernel_default", lambda: True)
        monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _qsync(bits, ef):
    qmax = 2 ** (bits - 1) - 1
    if ef:
        return lambda w, x, e, d: qsync.qsync_flat(w, x, e, d, qmax=qmax)
    return lambda w, x: qsync.qsync_flat(w, x, qmax=qmax)


@pytest.mark.parametrize("ef", [True, False])
@pytest.mark.parametrize("bits", [8, 4])
def test_qsync_compiles(one_chip, bits, ef):
    args = [_sds(one_chip, (1, AGENTS), jnp.float32),
            _sds(one_chip, (AGENTS, LEAF), jnp.float32)]
    if ef:
        args += [_sds(one_chip, (AGENTS, LEAF), jnp.float32),
                 _sds(one_chip, (LEAF,), jnp.float32)]
    assert "tpu_custom_call" in _compile(_qsync(bits, ef), *args).as_text()



# f32 ops that move whole arrays: on the layout-native path none of them
# may write a leaf-sized array (the view and its inverse are bitcasts)
_MOVE = re.compile(r"= f32\[([\d,]*)\]\S* (reshape|copy|transpose)\(")


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("shape", [(2560, 25344), (2, 2560, 5120)])
def test_qsync_leaves_moves_no_leaf(one_chip, monkeypatch, shape, bits):
    """The kernel path of ``qsync_leaves`` on mamba2-2.7b leaves (the LM
    head, a stacked projection) with both residuals: the fused kernel runs
    on each leaf's own layout, and no reshape, copy or transpose rewrites
    a leaf- or agent-sized f32 array around it."""
    _steer_to_kernels(monkeypatch)
    grid = (1, AGENTS)
    args = [_sds(one_chip, grid, jnp.float32),
            _sds(one_chip, grid + shape, jnp.float32),
            _sds(one_chip, grid + shape, jnp.float32),
            _sds(one_chip, shape, jnp.float32)]
    hlo = _compile(lambda w, x, e, d: qsync_ops.qsync_leaves(
        [x], w, [e], [d], bits=bits), *args).as_text().splitlines()
    assert any("custom-call" in op and "qsync_flat" in op for op in hlo)
    n = math.prod(shape)
    moved = [op for op in hlo if (m := _MOVE.search(op))
             and math.prod(int(d) for d in m.group(1).split(",") if d)
             in (n, AGENTS * n)]
    assert not moved, moved[0][:200]

QPACK = {
    "quant": (functools.partial(qpack.quant_flat, qmax=127),
              [((AGENTS, LEAF), jnp.float32)]),
    "dequant": (qpack.dequant_flat,
                [((AGENTS, LEAF), jnp.int8),
                 ((AGENTS, LEAF // 128), jnp.float16)]),
    "pack4": (qpack.pack4_flat, [((AGENTS, LEAF), jnp.int8)]),
    "unpack4": (qpack.unpack4_flat, [((AGENTS, LEAF // 2), jnp.uint8)]),
}


@pytest.mark.parametrize("name", sorted(QPACK))
def test_qpack_compiles(one_chip, name):
    fn, shapes = QPACK[name]
    args = [_sds(one_chip, s, d) for s, d in shapes]
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def test_fedavg_compiles(one_chip):
    c = _compile(fedavg_flat, _sds(one_chip, (AGENTS,), jnp.float32),
                 _sds(one_chip, (AGENTS, LEAF), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(one_chip):
    qkv = [_sds(one_chip, (1, 32, 4096, 128), jnp.bfloat16)] * 3
    assert "tpu_custom_call" in _compile(flash_attention_bhsd,
                                         *qkv).as_text()


def test_ssd_compiles_at_mamba2_shapes(one_chip):
    """80 SSD heads of 64, state 128, chunk 128 (mamba2-2.7b)."""
    Bsz, T, nh, hd, ds = 2, 1024, 80, 64, 128
    c = _compile(functools.partial(ssd_bthd, chunk=128),
                 _sds(one_chip, (Bsz, T, nh, hd), jnp.bfloat16),
                 _sds(one_chip, (Bsz, T, nh), jnp.float32),
                 _sds(one_chip, (nh,), jnp.float32),
                 _sds(one_chip, (Bsz, T, ds), jnp.bfloat16),
                 _sds(one_chip, (Bsz, T, ds), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def _compile_round(spec, sharding):
    """Compile the device-data round chunk a RoundDriver would dispatch
    for ``spec``, from shapes placed on the described chip."""
    driver, _ = spec.round_driver()

    def place(tree):
        return jax.tree.map(
            lambda x: _sds(sharding, x.shape, x.dtype), tree)

    state = place(jax.eval_shape(driver.fed.init_state, jax.random.key(0)))
    keys = place(jax.eval_shape(lambda: jnp.stack(
        round_key_schedule(jax.random.key(1), spec.n_rounds))[:1]))
    return driver._device_chunk().lower(state, place(driver.data),
                                        keys).compile()


@pytest.mark.parametrize("wire", ["f32", "int8"])
def test_mamba2_round_fits_one_chip(one_chip, monkeypatch, wire):
    """The one-chip smoke rounds: mamba2-2.7b at its published widths, two
    layers, two agents stacked on the chip, K=5, per-agent batch 2 x 512.
    The int8+EF round holds three more parameter-sized residuals, so its
    vocabulary is one chip's share of a 2-way vocab-parallel deployment."""
    cut = {}
    strategy = None
    if wire == "int8":
        _steer_to_kernels(monkeypatch)
        cut = {"vocab_size": 25_140}
        strategy = FedAvgSync(codec=IntQuant(bits=8))
    cfg = get_config("mamba2-2.7b").scaled(num_layers=2, **cut)
    spec = arch_smoke_spec("mamba2-2.7b", cfg=cfg, steps=10, K=5, seed=0,
                           agents=2, batch_size=2, seq_len=512,
                           data_mode="device", log_every=0,
                           strategy=strategy)
    compiled = _compile_round(spec, one_chip)
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert ma.alias_size_in_bytes > 0        # the state is donated
    assert used <= HBM_BYTES, used / 2 ** 30
    assert ("tpu_custom_call" in compiled.as_text()) == (wire == "int8")


def test_int8_round_holds_qsync_kernel(one_chip, monkeypatch):
    """A coded round compiles with the fused qsync kernel in it."""
    _steer_to_kernels(monkeypatch)
    spec, _ = experiment_spec(
        "mixed_gaussian", K=2, steps=4, agents=2, log_every=0,
        data_mode="device", samples_per_agent=256,
        strategy=FedAvgSync(codec=IntQuant(bits=8)))
    hlo = _compile_round(spec, one_chip).as_text().splitlines()
    assert any("tpu_custom_call" in op and "qsync_flat" in op for op in hlo)
