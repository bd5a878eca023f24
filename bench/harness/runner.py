"""One run of one cell: set-up, the measured window, the traced rounds,
then the comparison with the reference.

Set-up builds the program's round and state from the seed and drives the
first rounds through the same driver call the window uses; their losses,
Adam moments and parameter changes are what the reference is compared
with.  The window then runs rounds back to back, each timed from dispatch
to ready, until ``seconds`` have passed; it ends when the last round
completes.  With ``trace`` a few more rounds run under the profiler after
the window.  Once the device memory's peak is read and the program's
state is freed, the reference follows the set-up rounds.

Each metric's reader (``bench/metrics/<name>.py``) gets the run's record:
``config`` (the ``ArchConfig`` as run, plain numbers), ``traffic``,
``chips``, ``setup_s``, ``compile_s``, ``window`` (``seconds`` and every
round's ``round_s``), ``trace`` (``harness.trace.reduce``'s numbers over
the traced rounds, or None untraced), ``traced_rounds``, ``leaf_sizes``
(the elements of every parameter leaf of one agent) and ``device_kind``.
"""
from __future__ import annotations

import gc
import importlib.util
import math
import os
import time

import jax
import numpy as np

from harness import compare, trace as tracing
from harness.clock import CompileClock, peak_bytes, round_summary
from harness.program import Program, agent_spread, arch_config, config_dict
from harness.spec import Cell, load_module

# what a smoke run (tiny widths, on the CPU) changes in a traffic mix
SMOKE_TRAFFIC = {"seq_len": 32, "sequences_per_agent": 64}


def readers(entries: list) -> dict:
    """{metric name: its reader module, ``bench/metrics/<name>.py``}.  A
    reader has ``read(record)``, which returns the metric or None where
    it finds nothing to read, and may declare the ``KERNELS`` whose device
    seconds and the host ``SPANS`` whose device idle time it needs from
    the trace."""
    return {m["name"]: load_module("metrics", m["name"]) for m in entries}


def _declared(mods, what: str) -> tuple:
    return tuple(sorted({x for m in mods for x in getattr(m, what, ())}))


def _losses(res) -> tuple:
    m = res.history[0]
    return tuple(float(m[k]) for k in compare.LOSSES)


def checked_readings(prog: Program, n: int) -> dict:
    """Drive the program through its first ``n`` rounds; the readings the
    reference's are compared with (``harness.compare.numbers``)."""
    losses, mu = [], None
    for r in range(n):
        losses.append(_losses(prog.run_round(prog.keys(r))))
        if r == 0:
            mu = prog.moment_norms()
    return {"losses": np.asarray(losses), "mu": mu,
            "change": prog.change_norms()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices, t_start: float, smoke: bool = False,
             log=print) -> dict:
    """Returns {"correct", "attempted", "failed", "metrics", "device",
    ["breakdown"], "checks"}: the result line without its key order."""
    traffic = dict(cell.traffic, **(SMOKE_TRAFFIC if smoke else {}))
    with CompileClock() as setup_clock:
        cfg = arch_config(cell.config, smoke=smoke)
        config = dict(cell.config, config=config_dict(cfg))
        prog = Program(cfg, config, traffic, seed)
        n_check = compare.checked_rounds(traffic["K"])
        readings = checked_readings(prog, n_check)
    setup_s = time.perf_counter() - t_start

    round_s, window_losses = [], []
    r = n_check
    with CompileClock() as window_clock:
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.key_prep"):
                key = prog.keys(r)
            ta = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.round"):
                res = prog.run_round(key)
            tb = time.perf_counter()
            round_s.append(tb - ta)
            window_losses.append(_losses(res))
            r += 1
            if tb - t0 >= seconds:
                break
        window_s = tb - t0
    log(f"window rounds={len(round_s)} seconds={window_s:.3f} "
        f"compiles_inside={window_clock.compiles}")
    log(f"window {round_summary(round_s)}")

    reduced = None
    if trace:
        n_traced = traffic["traced_rounds"]

        def traced():
            with jax.profiler.TraceAnnotation("bench.traced"):
                for i in range(n_traced):
                    with jax.profiler.TraceAnnotation("bench.key_prep"):
                        key = prog.keys(r + i)
                    with jax.profiler.TraceAnnotation("bench.round"):
                        window_losses.append(_losses(prog.run_round(key)))

        _, events = tracing.capture(traced)
        mods = readers(cell.per_layer).values()
        reduced = tracing.reduce(events, tracing.host_span(events,
                                                           "bench.traced"),
                                 kernels=_declared(mods, "KERNELS"),
                                 spans=_declared(mods, "SPANS"))

    memory = peak_bytes(devices)
    spread = float(agent_spread(prog.state["params"]))
    bad = sum(not all(map(math.isfinite, x)) for x in window_losses)

    shapes = jax.eval_shape(prog.fed.task.init, jax.random.key(0))
    leaf_sizes = [int(np.prod(x.shape))
                  for x in jax.tree_util.tree_leaves(shapes)]
    params0, shards, keys = prog.params0(), prog.shards, prog.keys
    prog.state = prog.driver = res = None      # free the program's buffers
    gc.collect()

    from reference.train import Reference
    t_ref = time.perf_counter()
    ref = Reference(config, traffic).run(params0, shards, keys, n_check)
    log(f"reference seconds={time.perf_counter() - t_ref:.3f}")
    values = compare.numbers(readings, ref)
    log(f"numbers {values}")
    log(f"worst leaves {compare.worst_leaves(readings, ref)}")
    values.update(window_nonfinite=float(bad), agent_spread=spread)
    correct, checks = compare.judge(values, cell.limits)

    record = {
        "config": config["config"], "traffic": traffic, "chips": cell.chips,
        "setup_s": setup_s, "compile_s": setup_clock.seconds,
        "window": {"seconds": window_s, "round_s": round_s},
        "trace": reduced, "traced_rounds": traffic["traced_rounds"],
        "leaf_sizes": leaf_sizes,
        "device_kind": devices[0].device_kind,
    }
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m, reader in zip(entries, readers(entries).values()):
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    out = {"correct": correct,
           "attempted": n_check + len(window_losses),
           "failed": bad, "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = reduced["breakdown"]
    out["checks"] = checks
    return out
