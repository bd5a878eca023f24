"""Compile time from JAX's own monitoring events, the window's slow
rounds, and device memory.
(``CompileClock`` and the memory reading are copies of the bring-up
script's, ``chip_smoke.py``.)"""
from __future__ import annotations

import jax
import numpy as np

# lowering to StableHLO and the XLA compile; tracing is left out because
# its events nest (each inner jit reports its own trace inside the outer)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends lowering and compiling inside the block, and the
    number of backend compiles."""

    def __enter__(self):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._add)
        return self

    def _add(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == COMPILE_EVENTS[1]:
            self.compiles += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._add)


def round_summary(round_s: list) -> str:
    """The window's rounds in one line: median, p90 and slowest, the
    rounds over 1.5x the median with the seconds they took beyond it, and
    the first of them as (index in the window, ms): a host that stands
    still stretches single rounds."""
    x = np.asarray(round_s)
    med = float(np.median(x))
    slow = np.flatnonzero(x > 1.5 * med)
    at = [(int(i), round(float(x[i]) * 1e3, 1)) for i in slow[:8]]
    return (f"median_ms={med * 1e3:.3f} p90_ms="
            f"{float(np.percentile(x, 90)) * 1e3:.3f} "
            f"max_ms={float(x.max()) * 1e3:.3f} slow={slow.size} "
            f"slow_excess_s={float(np.sum(x[slow] - med)):.4f} slow_at={at}")


def peak_bytes(devices) -> int | None:
    """The peak of bytes in use on the fullest of ``devices`` since the
    process started, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
