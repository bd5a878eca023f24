"""How a traced round splits by the program's own names: the driver's
host spans (read by ``driver.launch_idle_ms`` and ``driver.fetch_idle_ms``)
on a hand-made trace with known answers, and the round's device scopes on
one ``mamba2-k1-int8ef`` round traced on a TPU v5e."""
import gzip
import json
import os

import pytest

from harness import trace
from harness.spec import load_module

US = 1_000.0          # ns
READERS = ("driver.launch_idle_ms", "driver.fetch_idle_ms")
DRIVER = ("driver.prepare", "driver.dispatch", "driver.boundary",
          "driver.collect")
SCOPES = ("fedgan.sample", "fedgan.local_step", "fedgan.update",
          "fedgan.sync")


def hand_made():
    """One round: the device runs [100, 570] us; the driver prepares and
    dispatches before it, then waits in its boundary and its collect."""
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 100 * US, 400 * US],
            ["qsync_flat.4", 500 * US, 70 * US],
        ]},
        "host": [["python", "bench.traced", 0.0, 1000 * US],
                 ["python", "bench.round", 20 * US, 880 * US],
                 ["python", "driver.prepare", 20 * US, 40 * US],
                 ["python", "driver.dispatch", 60 * US, 40 * US],
                 ["python", "driver.boundary", 100 * US, 600 * US],
                 ["python", "driver.wait", 110 * US, 580 * US],
                 ["python", "driver.collect", 700 * US, 200 * US],
                 ["python", "driver.wait", 705 * US, 185 * US]],
    }


def record(events, traced_rounds=1):
    mods = [load_module("metrics", n)
            for n in READERS + ("driver.host_gap_ms",)]
    spans = tuple(sorted({s for m in mods for s in m.SPANS}))
    window = trace.host_span(events, "bench.traced")
    return {"trace": trace.reduce(events, window, spans=spans),
            "traced_rounds": traced_rounds}


def read(name, rec):
    return load_module("metrics", name).read(rec)


def test_driver_idle_on_a_hand_made_round():
    rec = record(hand_made())
    # idle in prepare [20, 60] and dispatch [60, 100]
    assert read("driver.launch_idle_ms", rec) == pytest.approx(0.08)
    # idle in boundary [570, 700] and collect [700, 900]; the waits nest
    # inside them and count once
    assert read("driver.fetch_idle_ms", rec) == pytest.approx(0.33)
    # together, the idle inside the whole driver call
    assert read("driver.launch_idle_ms", rec) + read(
        "driver.fetch_idle_ms", rec) == pytest.approx(
            read("driver.host_gap_ms", rec))


def test_driver_idle_is_per_round():
    rec = record(hand_made(), traced_rounds=2)
    assert read("driver.fetch_idle_ms", rec) == pytest.approx(0.165)


def test_a_driver_without_spans_reads_nothing():
    ev = hand_made()
    ev["host"] = [h for h in ev["host"] if not h[1].startswith("driver.")]
    assert [read(n, record(ev)) for n in READERS] == [None, None]
    assert [read(n, {"trace": None}) for n in READERS] == [None, None]


def recorded():
    """One round of ``mamba2-k1-int8ef`` (2 of 64 layers) traced on a TPU
    v5e: each device operation by instruction name, its ``op_text`` the
    ``tf_op`` statistic of its event metadata in the ``.xplane.pb`` (the
    HLO ``op_name``, which holds the scope path), and the host spans."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_int8ef_scoped_round.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def compiler_made(text: str | None) -> bool:
    """An operation whose ``op_name`` names no JAX primitive: none at all,
    a jit argument's path (a layout copy of the state), or only the loop
    it was moved into."""
    return (text is None or text.startswith("tf_op=st[")
            or text.endswith(("while:", "closed_call:")))


def test_recorded_round_is_split_by_the_scopes():
    ev = recorded()
    window = trace.host_span(ev, "bench.round")
    r = trace.reduce(ev, window, kernels=SCOPES + ("qsync_flat",),
                     spans=DRIVER + ("bench.round",), top=10 ** 6)
    text = ev["op_text"]
    owner = {name: [s for s in SCOPES if s in text.get(name, "")]
             for name, _, _ in ev["devices"]["/device:TPU:0"]}
    assert all(len(v) <= 1 for v in owner.values())
    ops = r["breakdown"]["device_ops"]
    held = sum(sec for name, sec in ops if owner[name])
    assert sum(r["kernel_s"][s] for s in SCOPES) == pytest.approx(held)
    assert all(r["kernel_s"][s] > 0 for s in SCOPES)
    # what the scopes miss the compiler made or moved, but for the key
    # splits and metric means between them
    made = sum(sec for name, sec in ops
               if not owner[name] and compiler_made(text.get(name)))
    assert held + made >= 0.999 * r["busy_s"]
    # one fused qsync call per parameter leaf; they and the reshapes of
    # their jitted wrapper, which an op_name also ties to ``qsync_flat``,
    # are all the sync's
    calls = [name for name, _, _ in ev["devices"]["/device:TPU:0"]
             if name.startswith("qsync_flat")]
    assert len(calls) == 30
    assert r["kernel_calls"]["qsync_flat"] > len(calls)
    assert all(owner[name] == ["fedgan.sync"] for name, _ in ops
               if "qsync_flat" in name + text.get(name, ""))
    # the driver's top-level spans hold the device's idle time inside the
    # call, short of a few gaps of microseconds between them; after the
    # call's last span the caller drops the donated state
    spans = sum(r["span_idle_s"][s] for s in DRIVER)
    end = max(s + d for _, n, s, d in ev["host"] if n in DRIVER)
    after = trace.reduce(ev, (end, window[1]))
    assert spans + after["window_s"] - after["busy_s"] == pytest.approx(
        r["span_idle_s"]["bench.round"], rel=0.02)
