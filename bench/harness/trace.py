"""From a profiler trace to device metrics.

``capture`` runs a callable under the JAX profiler and returns the trace
as plain events (``extract``): each device's operations, and every host
thread's spans, on one clock in nanoseconds.  ``reduce`` turns them into
numbers over a window given by one of the benchmark's own host spans:

* busy: the union of the intervals in which an operation ran on the
  device, clipped to the window, averaged over the devices;
* per-operation, per-kernel and per-collective device seconds;
* the idle gaps, each labelled by the innermost host span that covers
  its middle, i.e. what the host was doing while the device waited.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SHORT_GAP_NS = 5_000
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def extract(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]},
        "host": [[thread, name, start_ns, dur_ns], ...],
        "op_text": {name: the op's trace statistics, as one string}} from
    an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, text = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = []
                for e in line.events:
                    evs.append([e.name, float(e.start_ns),
                                float(e.duration_ns)])
                    if e.name not in text:
                        text[e.name] = " ".join(f"{k}={v}"
                                                for k, v in e.stats)
                devices[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [[line.name, e.name, float(e.start_ns),
                          float(e.duration_ns)]
                         for e in line.events if e.duration_ns > 0]
    return {"devices": devices, "host": host, "op_text": text}


def capture(fn):
    """Run ``fn()`` under the profiler; returns (fn's result, events)."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    # host spans from the runtime and the benchmark's annotations, without
    # the Python function tracer, whose cost per call would widen the
    # host's gaps beyond an untraced run's
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        return out, extract(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def host_span(events: dict, name: str) -> tuple[float, float]:
    """(start, end) in ns of the host span ``name``, which must occur once."""
    hits = [(s, s + d) for _, n, s, d in events["host"] if n == name]
    if len(hits) != 1:
        raise ValueError(f"host span {name!r} occurs {len(hits)} times")
    return hits[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _HostIndex:
    """Finds the innermost host span covering a time."""

    def __init__(self, host, t0, t1):
        import numpy as np
        keep = [h for h in host if h[2] <= t1 and h[2] + h[3] >= t0]
        self.names = [h[1] for h in keep]
        self.start = np.asarray([h[2] for h in keep], float)
        self.dur = np.asarray([h[3] for h in keep], float)

    def label(self, t: float) -> str:
        import numpy as np
        cover = (self.start <= t) & (self.start + self.dur >= t)
        if not cover.any():
            return "(no host span)"
        return self.names[int(np.argmin(np.where(cover, self.dur, np.inf)))]


def op_name(name: str) -> str:
    """An operation's HLO instruction name: a TPU trace names each
    operation by its whole instruction text."""
    return name.split(" = ")[0].lstrip("%")


def _self_times(evs, t0: float, t1: float):
    """[name, start, end, own ns] of the operations clipped to [t0, t1].
    An operation that contains others on its line (a while loop and its
    body) keeps only its own time."""
    out, stack = [], []
    for name, s, d in sorted(evs, key=lambda x: (x[1], -x[2])):
        s, e = max(s, t0), min(s + d, t1)
        if e <= s:
            continue
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][2]:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return out


def _covered(merged, s: float, e: float) -> float:
    """ns of [s, e] that the disjoint sorted intervals ``merged`` cover."""
    return sum(max(0.0, min(b, e) - max(a, s)) for a, b in merged)


def reduce(events: dict, window: tuple[float, float], *,
           kernels=(), spans=(), top: int = 10) -> dict:
    """Device numbers over ``window`` (ns, ns).  ``kernels`` are names
    whose operations' device seconds are summed apart: an operation is a
    kernel's when the name occurs in the operation's name or statistics.
    For each host span name in ``spans``, the device's idle seconds inside
    the span's occurrences in the window are summed too."""
    t0, t1 = window
    devs = events["devices"]
    text = events.get("op_text", {})
    owner = {}
    if not devs:
        raise ValueError("the trace holds no device operations")
    n = len(devs)
    busy = 0.0
    ops, kern, coll, calls = {}, {k: 0.0 for k in kernels}, {}, {}
    gaps = []
    occ = {name: [(s, s + d) for _, n_, s, d in events["host"]
                  if n_ == name and s >= t0 and s + d <= t1]
           for name in spans}
    span_idle = {name: 0.0 for name in spans}
    for plane in sorted(devs):
        iv = []
        for name, s, e, own in _self_times(devs[plane], t0, t1):
            iv.append((s, e))
            sec = own / 1e9 / n
            short = op_name(name)
            ops[short] = ops.get(short, 0.0) + sec
            if name not in owner:
                owner[name] = [k for k in kernels
                               if k in name or k in text.get(name, "")]
            for k in owner[name]:
                kern[k] += sec
                calls[k] = calls.get(k, 0) + 1
            for c in COLLECTIVES:
                if short.startswith(c):
                    coll[c] = coll.get(c, 0.0) + sec
        merged = _union(iv)
        busy += sum(e - s for s, e in merged) / 1e9 / n
        for name, where in occ.items():
            span_idle[name] += sum((e - s) - _covered(merged, s, e)
                                   for s, e in where) / 1e9 / n
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    idle = {}
    index = _HostIndex(events["host"], t0, t1)
    for s, e in gaps:
        # back-to-back operations leave gaps of a few microseconds; only
        # longer ones are worth asking the host about
        lab = (index.label((s + e) / 2) if e - s >= SHORT_GAP_NS
               else "(between operations)")
        idle[lab] = idle.get(lab, 0.0) + (e - s) / 1e9 / n
    by = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (t1 - t0) / 1e9, "busy_s": busy, "devices": n,
            "kernel_s": kern, "kernel_calls": {k: calls.get(k, 0) // n
                                               for k in kernels},
            "collective_s": coll, "span_idle_s": span_idle,
            "span_count": {k: len(v) for k, v in occ.items()},
            "breakdown": {"device_ops": [list(x) for x in by(ops)],
                          "idle_gaps": [list(x) for x in by(idle)]}}
