"""Round-loop throughput: the pre-refactor blocking ``RunSpec.run()`` loop
vs the ``repro.run`` device-resident donated driver.

Two quantities per config, both old-vs-new:

  * ``steps_per_s`` — local training steps per wall second (warm, compile
    excluded);
  * ``round_gap_ms`` — host work per round (legacy: per-round host
    assembly of the (K, P, A, batch, ...) tensor, the forced ``float()``
    metric sync excluded; runtime: the driver's ``round_gap_s``, its spans
    less its waits for the device — key bookkeeping, dispatch, the fetch).

The legacy path below is a faithful replica of the seed-era loop
(host-assembled batches, non-donated jit, a blocking metric fetch every
round) kept here as the fixed baseline the perf trajectory is measured
against.  The runtime path samples minibatches inside the jitted round
(``DeviceFederatedData`` + ``FedGAN.round_from_data``), donates the state
buffers, and scans ``rounds_per_chunk`` rounds per dispatch.

The gap the new pipeline removes is per-ROUND host work, so the speedup is
largest where rounds are cheap or frequent: the paper's GAN workloads
(toy/MLP/conv nets) gain several-fold, and any accelerator-backed host
additionally saves the K× host->device transfer this container (CPU-only,
device==host) cannot exhibit — there the backbone smoke config is bound by
its in-round compute and shows the round-gap win instead.

Run directly (``python benchmarks/bench_rounds.py --json``) or as the
``rounds`` suite of ``benchmarks/run.py``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# support `python benchmarks/bench_rounds.py` directly (run.py does the
# same dance for the suite path)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks import common

tmap = jax.tree_util.tree_map


def _legacy_loop(spec, n_rounds: int):
    """The pre-refactor RunSpec.run() hot loop, replicated verbatim (minus
    prints/checkpoints): per-round host assembly, no donation, blocking
    per-round metric floats.  Returns (steps_per_s, round_gap_s)."""
    fed, rounds = spec.build()
    state = fed.init_state(jax.random.key(spec.seed))
    round_fn = jax.jit(fed.round)
    rng = jax.random.key(spec.seed + 1)

    def one_round(state, rng, t_host):
        # the host-assembly segment is genuine round-gap: the blocking
        # metric sync below means assembly can never overlap the previous
        # round, so the device sits idle for all of it.  (The float() wait
        # itself is NOT counted — that is the device finishing its round.)
        t0 = time.perf_counter()
        rng, rb = jax.random.split(rng)
        batches, seeds = rounds.round_batches(rb)
        t_host += time.perf_counter() - t0
        state, metrics = round_fn(state, batches, seeds)
        _ = tmap(lambda x: float(jnp.mean(x)), metrics)  # the forced sync
        return state, rng, t_host

    state, rng, _ = one_round(state, rng, 0.0)  # compile warmup
    gap = 0.0
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        state, rng, gap = one_round(state, rng, gap)
    total = time.perf_counter() - t0
    return n_rounds * spec.K / total, gap / n_rounds


def _runtime_loop(spec, n_rounds: int, rounds_per_chunk: int):
    """The repro.run driver on device-resident data, timed warm (the
    driver memoizes its jitted chunk executable, so the second run pays no
    compile)."""
    import dataclasses

    from repro.run.driver import RoundDriver
    spec = dataclasses.replace(spec, data_mode="device", log_every=0)
    fed, _ = spec.build()
    driver = RoundDriver(fed, spec.build_data(), n_rounds, log_every=0,
                         rounds_per_chunk=rounds_per_chunk, verbose=False)
    driver.run(jax.random.key(spec.seed + 1))            # compile warmup
    res = driver.run(jax.random.key(spec.seed + 1))      # timed, warm
    return res.timings["steps_per_s"], res.timings["round_gap_s"]


def _bench_pair(label: str, spec, *, n_rounds: int, rounds_per_chunk: int,
                **meta):
    legacy_sps, legacy_gap = _legacy_loop(spec, n_rounds)
    run_sps, run_gap = _runtime_loop(spec, n_rounds, rounds_per_chunk)
    speedup = run_sps / legacy_sps
    gap_ratio = legacy_gap / max(run_gap, 1e-9)
    us_per_step = 1e6 / run_sps
    common.emit(
        f"rounds_{label}", us_per_step,
        f"{speedup:.2f}x steps/s ({legacy_sps:.0f}->{run_sps:.0f}), "
        f"round-gap {legacy_gap * 1e3:.2f}->{run_gap * 1e3:.3f} ms "
        f"({gap_ratio:.0f}x)",
        steps_per_s_legacy=round(legacy_sps, 1),
        steps_per_s_runtime=round(run_sps, 1),
        speedup=round(speedup, 3),
        round_gap_ms_legacy=round(legacy_gap * 1e3, 3),
        round_gap_ms_runtime=round(run_gap * 1e3, 4),
        round_gap_ratio=round(gap_ratio, 1),
        K=spec.K, agents=spec.agent_grid[0] * spec.agent_grid[1],
        batch_size=spec.batch_size, n_rounds=n_rounds,
        rounds_per_chunk=rounds_per_chunk, **meta)
    return speedup


def bench_paper_workloads(*, fast: bool = False):
    """The paper's GAN experiments: cheap rounds, so the per-round host
    assembly + sync the runtime removes IS the bottleneck."""
    from repro.launch.train import experiment_spec
    n = 30 if fast else 100
    for name, K in (("toy_2d", 20), ("toy_2d", 1), ("mixed_gaussian", 20)):
        if fast and name == "mixed_gaussian":
            continue
        spec, _ = experiment_spec(name, K=K, steps=n * K, log_every=0)
        _bench_pair(f"{name}_K{K}", spec, n_rounds=n,
                    rounds_per_chunk=min(10, n), experiment=name)


def bench_arch_smoke(arch: str = "gemma3-4b", *, fast: bool = False):
    """The backbone smoke config (the serving-side generator).  On a
    CPU-only host this round is compute-bound (device==host: no transfer
    to remove), so steps/s moves modestly and the round-gap column carries
    the pipeline win; on accelerators the K× transfer savings move
    steps/s too."""
    from repro.launch.train import arch_smoke_spec
    cases = [(1, 4)] if fast else [(1, 8), (5, 8), (10, 8)]
    for K, bs in cases:
        n = 8 if fast else 10
        spec = arch_smoke_spec(arch, steps=n * K, K=K, seed=0,
                               batch_size=bs, log_every=0)
        _bench_pair(f"{arch}_smoke_K{K}", spec, n_rounds=n,
                    rounds_per_chunk=min(8, n), arch=arch)


def main(*, fast: bool = False, arch: str = "gemma3-4b"):
    bench_paper_workloads(fast=fast)
    bench_arch_smoke(arch, fast=fast)


if __name__ == "__main__":
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_rounds.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(fast=args.fast, arch=args.arch)
    if args.json:
        with open("BENCH_rounds.json", "w") as f:
            json.dump({"suite": "rounds", "fast": args.fast,
                       "records": common.drain_records()}, f, indent=1)
        print("# wrote BENCH_rounds.json", file=sys.stderr)
