"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the root of the checkout;
its configuration, traffic mix, limits and metric readers are files under
``bench/`` found by name.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checks``: each number
compared with the reference beside its limit.  The same numbers close
standard error.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 2.  The compile cache is the program's
(``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(1, os.path.join(ROOT, "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(out: dict) -> str:
    """The result as one JSON line, ``checks`` last."""
    keys = [k for k in ("correct", "attempted", "failed", "metrics",
                        "device", "breakdown") if k in out] + ["checks"]
    return json.dumps({k: out[k] for k in keys})


def main(argv=None):
    args = parse(argv)
    from harness.spec import load_cell
    cell = load_cell(args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench/run.py needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    from harness.peaks import peak
    peak(devices[0].device_kind)           # an unknown chip is an error
    enable_compile_cache()
    # cache every program, however quick its compile, so that a second
    # run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from harness.runner import run_cell
    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices=devices[:cell.chips], t_start=T_START, log=log)
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    print(result_line(out), flush=True)


if __name__ == "__main__":
    main()
