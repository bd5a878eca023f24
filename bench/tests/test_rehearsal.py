"""Rehearse every cell without the chip.

For each cell of ``BENCHMARK.json``: its files load; one run of the whole
harness at ``cfg.smoke()`` size on the CPU comes out correct; and its
full-size round compiles for a described TPU v5e and fits the chip's
memory, as does the reference's step.  Run by hand before chip time:

    PYTHONPATH=src python -m pytest -q bench/tests/test_rehearsal.py

The topology is described inside a fixture (only one process may load the
TPU library, and never while modules are imported).
"""
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from harness import compare, flops
from harness.program import arch_config, build_round, config_dict
from harness.runner import run_cell
from harness.spec import cell_names, load_cell

HBM_BYTES = 15.75 * 2 ** 30     # what XLA:TPU lets one v5e program use
CELLS = cell_names()


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _steer_to_kernels(monkeypatch):
    """The ops layer picks the Pallas kernels on a TPU backend; under
    JAX_PLATFORMS=cpu it sees the CPU, so steer it as the chip would."""
    from repro.kernels.qpack import ops as qpack_ops
    from repro.kernels.qsync import ops as qsync_ops
    for ops in (qsync_ops, qpack_ops):
        monkeypatch.setattr(ops, "_use_kernel_default", lambda: True)
        monkeypatch.setattr(ops, "_interpret", lambda: False)


def _used(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


@pytest.mark.parametrize("name", CELLS)
def test_files_load(name):
    cell = load_cell(name)
    c = cell.config
    assert {"window_nonfinite", "agent_spread"} <= set(cell.limits)
    assert all(v == 0.0 for k, v in cell.limits.items()
               if k in ("window_nonfinite", "agent_spread"))
    cfg = arch_config(c)
    assert config_dict(cfg) == {**c["config"], "source": cfg.source,
                                "moe_group_size": cfg.moe_group_size,
                                "capacity_factor": cfg.capacity_factor}
    # the run differs from the published model only in its reduced keys
    for k, v in c["published"].items():
        if k in c["config"] and k not in c["reduced"]:
            assert c["config"][k] == v, k
    assert flops.round_flops(c["config"], cell.traffic) > 0


@pytest.mark.parametrize("name", CELLS)
def test_smoke_run_is_correct(name):
    """The whole run, chip check aside, at smoke size on the CPU."""
    out = run_cell(load_cell(name), 2 ** 31 + 12345, 0.5, False,
                   devices=jax.devices()[:1], t_start=time.perf_counter(),
                   smoke=True, log=lambda *a: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert {"tokens_per_s", "round_p90_ms", "setup_s"} <= set(out["metrics"])


@pytest.mark.parametrize("name", CELLS)
def test_full_size_compiles_and_fits(name, one_chip, monkeypatch,
                                     no_persistent_cache):
    cell = load_cell(name)
    t = cell.traffic
    if t["sync"].get("codec"):
        _steer_to_kernels(monkeypatch)
    cfg = arch_config(cell.config)
    tiny = [{"tokens": jnp.zeros((2, t["seq_len"]), jnp.int32)}
            for _ in range(t["agents"])]
    fed, driver = build_round(cfg, t, tiny)
    place = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), tree)
    data = dataclasses.replace(
        driver.data,
        data={"tokens": jax.ShapeDtypeStruct(
            (1, t["agents"], t["sequences_per_agent"], t["seq_len"]),
            jnp.int32, sharding=one_chip)},
        sizes=jax.ShapeDtypeStruct((1, t["agents"]), jnp.int32,
                                   sharding=one_chip))
    state = place(jax.eval_shape(fed.init_state, jax.random.key(0)))
    keys = place(jax.eval_shape(lambda: jax.random.split(
        jax.random.key(0), 1)))
    compiled = driver._device_chunk().lower(state, data, keys).compile()
    used = _used(compiled)
    print(f"{name}: round program {used / 2 ** 30:.2f} GiB")
    assert used <= HBM_BYTES, used / 2 ** 30
    assert (("qsync_flat" in compiled.as_text())
            == bool(t["sync"].get("codec")))

    # the reference's step, at full size in float32, with every agent's
    # parameters and Adam moments beside it
    from reference.train import Reference, grads
    import importlib
    gen = importlib.import_module("reference." + cell.config["reference"])
    params = place(jax.eval_shape(fed.task.init, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), jnp.int32,
                                  sharding=one_chip)
    ref = Reference(cell.config, t)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(functools.partial(
            grads, cfg=cell.config["config"], gen_module=gen,
            prec=ref.prec)).lower(params, tokens).compile()
    per_agent = sum(x.size * 4 for x in jax.tree.leaves(params))
    # params0, and each agent's parameters and Adam moments; under a
    # coded wire each agent's residual and the server's
    trees = 3 * t["agents"] + 1
    if t["sync"].get("codec"):
        trees += t["agents"] + 1
    ref_used = _used(step) + trees * per_agent
    print(f"{name}: reference step + state {ref_used / 2 ** 30:.2f} GiB")
    assert ref_used <= HBM_BYTES, ref_used / 2 ** 30
