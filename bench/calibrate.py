"""The readings the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--faulted 3]

For each seed it builds the cell's weights, data and keys as a run does,
then prints one JSON line of the compared numbers (``harness.compare``)
for each of these, the faults on the first ``--faulted`` seeds only:

* ``program``: the program's set-up rounds against the float32 reference
  (the lower reading is the largest over a dozen seeds or more);
* ``control``: the reference with float8 operands and results, put in the
  program's place (the step below the bfloat16 the configurations state);
* ``half_batch``: the reference with half of each batch left out;
* ``no_sync``: the reference with the sync between agents left out;
* ``unchanged``: the reference with steps that leave the state unchanged.

Each line carries the verdict of the cell's own limits on its numbers
(``correct`` and the numbers ``over`` their limits); a missing sync is
caught in a run by the agents' spread besides.  The last line sums up,
per number and variant, the largest reading of the program and the
smallest of each other variant, and whether every program reading came
out correct and every other one not.

This runs on the chip at the cell's own size; it times nothing.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.join(ROOT, "src"))

VARIANTS = (("control", "fp8", ""), ("half_batch", "f32", "half_batch"),
            ("no_sync", "f32", "no_sync"), ("unchanged", "f32", "unchanged"))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faulted", type=int, default=3,
                    help="seeds (the first ones) that the faults run on")
    args = ap.parse_args()

    import jax
    import numpy as np
    from harness import compare
    from harness.program import Program, arch_config, config_dict
    from harness.runner import checked_readings
    from harness.spec import load_cell
    from reference.train import Reference
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = load_cell(args.workload)
    t = cell.traffic
    cfg = arch_config(cell.config)
    config = dict(cell.config, config=config_dict(cfg))
    n = compare.checked_rounds(t["K"])
    refs = {"f32": Reference(config, t)}
    refs.update((v, Reference(config, t, prec=p, fault=f))
                for v, p, f in VARIANTS)
    summary = {}
    for i, seed in enumerate(map(int, args.seeds.split(","))):
        prog = Program(cfg, config, t, seed)
        readings = checked_readings(prog, n)
        params0, shards, keys = prog.params0(), prog.shards, prog.keys
        del prog
        gc.collect()
        took = {}

        def timed(v):
            t0 = time.perf_counter()
            out = refs[v].run(params0, shards, keys, n)
            took[v] = time.perf_counter() - t0
            return out

        truth = timed("f32")
        got = {"program": readings, "control": timed("control")}
        if i < args.faulted:
            for v, _, _ in VARIANTS[1:]:
                got[v] = timed(v)
        for v, rd in got.items():
            values = compare.numbers(rd, truth)
            ok, checks = compare.judge(
                values, {k: x for k, x in cell.limits.items() if k in values})
            over = sorted(k for k, c in checks.items()
                          if not c["value"] <= c["limit"])
            s = summary.setdefault(v, {"runs": 0, "verdicts_right": True})
            s["runs"] += 1
            s["verdicts_right"] &= ok == (v == "program")
            pick = max if v == "program" else min
            for k, x in values.items():
                s[k] = pick(s.get(k, x), x)
            print(json.dumps({"cell": cell.name, "seed": seed, "variant": v,
                              "correct": ok, "over": over, **values,
                              "worst": compare.worst_leaves(rd, truth),
                              "losses": np.asarray(rd["losses"]).tolist(),
                              "truth_losses": truth["losses"].tolist(),
                              "seconds": took}), flush=True)
    print(json.dumps({"cell": cell.name, "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
