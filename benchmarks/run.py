# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
#   Fig 5  (2D system, K sweep)          -> bench_toy.bench_2d
#   Fig 6  (mixed Gaussian)              -> bench_toy.bench_mixed_gaussian
#   Fig 7  (Swiss roll)                  -> bench_toy.bench_swissroll
#   Fig 1b (CIFAR FID vs K + baseline)   -> bench_images.bench_fd_vs_k
#   Fig 2b (CelebA attribute split)      -> bench_images.bench_celeba_attributes
#   Fig 3  (PG&E household clusters)     -> bench_timeseries.bench_household
#   Fig 4  (EV charging clusters)        -> bench_timeseries.bench_ev
#   §3.2   (communication complexity)    -> bench_comm
#   rounds (legacy loop vs repro.run driver) -> bench_rounds
#   Lem1/2 (drift vs bounds)             -> bench_lemmas
#   (g)    (roofline from dry-run)       -> bench_roofline
#   kernels (Pallas vs oracle)           -> bench_kernels
#   serving (tok/s + tick latency vs occupancy) -> bench_serve
#   privacy (DP/secure-sum/robust cost surface) -> bench_privacy
#   agents (virtual-client fleet scaling)       -> bench_agents
#
# ``--json`` additionally writes one machine-readable BENCH_<suite>.json per
# executed suite (into --json-dir), so the bench trajectory is comparable
# across commits instead of living only in scrollback.
import argparse
import json
import os
import sys
import time
import traceback

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; the root must be importable for the `benchmarks.*` modules.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated bench subset")
    ap.add_argument("--fast", action="store_true", help="reduced step budgets")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<suite>.json artifacts")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the --json artifacts")
    args, _ = ap.parse_known_args()

    from benchmarks import (bench_agents, bench_comm, bench_images,
                            bench_kernels, bench_lemmas, bench_privacy,
                            bench_roofline, bench_rounds, bench_serve,
                            bench_timeseries, bench_toy, common)

    fast = args.fast
    suites = {
        "toy": lambda: (bench_toy.bench_2d(steps=800 if fast else 2500),
                        bench_toy.bench_mixed_gaussian(steps=600 if fast else 2000),
                        bench_toy.bench_swissroll(steps=600 if fast else 2000)),
        "images": lambda: (bench_images.bench_fd_vs_k(steps=120 if fast else 400),
                           bench_images.bench_celeba_attributes(steps=100 if fast else 300)),
        "timeseries": lambda: (bench_timeseries.bench_household(steps=200 if fast else 600),
                               bench_timeseries.bench_ev(steps=200 if fast else 600)),
        "comm": lambda: bench_comm.main(fast=fast),
        "lemmas": bench_lemmas.main,
        "roofline": lambda: bench_roofline.main(fast=fast),
        "kernels": bench_kernels.main,
        "serve": lambda: bench_serve.main(fast=fast),
        "rounds": lambda: bench_rounds.main(fast=fast),
        "privacy": lambda: bench_privacy.main(fast=fast),
        "agents": lambda: bench_agents.main(fast=fast),
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        common.drain_records()
        error = ""
        try:
            fn()
        except Exception:
            error = traceback.format_exc(limit=1).splitlines()[-1]
            print(f"{name}_SUITE_ERROR,0.0,{error}", flush=True)
            failed.append(name)
        print(f"# suite {name} finished in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)
        if args.json:
            artifact = {"suite": name, "fast": fast,
                        "seconds": round(time.time() - t0, 1),
                        "records": common.drain_records()}
            if error:
                artifact["error"] = error
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(artifact, f, indent=1)
            print(f"# wrote {path}", file=sys.stderr, flush=True)
    if failed:
        sys.exit(f"suites errored: {', '.join(failed)}")


if __name__ == "__main__":
    main()
