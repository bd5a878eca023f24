"""The reduction from trace events to device metrics, on a hand-made trace
with known answers and on a small trace recorded on a TPU v5e."""
import gzip
import json
import os

import pytest

from harness import trace

US = 1_000.0          # ns


def hand_made():
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 100 * US, 50 * US],
            ["fusion.2", 120 * US, 50 * US],        # overlaps fusion.1
            ["custom-call.3", 300 * US, 100 * US],
            ["all-reduce.4", 500 * US, 100 * US],
            ["fusion.1", 999 * US, 10 * US],        # runs past the window
        ]},
        "host": [["python", "bench.traced", 0.0, 1000 * US],
                 ["python", "bench.round", 50 * US, 500 * US],
                 ["python", "bench.key_prep", 700 * US, 100 * US],
                 ["python", "outside", 2000 * US, 10 * US]],
        "op_text": {"custom-call.3": "long_name=%custom-call.3 = ... "
                                     "custom_call_target=\"tpu_custom_call\" "
                                     "op_name=\"jit(qsync_flat)/pallas_call\""},
    }


def test_hand_made():
    ev = hand_made()
    window = trace.host_span(ev, "bench.traced")
    assert window == (0.0, 1000 * US)
    r = trace.reduce(ev, window, kernels=("qsync_flat",))
    assert r["window_s"] == pytest.approx(1e-3)
    # union: [100, 170] + [300, 400] + [500, 600] + [999, 1000] us
    assert r["busy_s"] == pytest.approx(271e-6)
    assert r["kernel_s"] == {"qsync_flat": pytest.approx(100e-6)}
    assert r["kernel_calls"] == {"qsync_flat": 1}
    assert r["collective_s"] == {"all-reduce": pytest.approx(100e-6)}
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(51e-6)
    idle = dict(r["breakdown"]["idle_gaps"])
    # gaps [0,100], [170,300], [400,500] lie inside bench.round; [600,999]
    # has its middle in bench.key_prep
    assert idle == {"bench.round": pytest.approx(330e-6),
                    "bench.key_prep": pytest.approx(399e-6)}
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])


def test_idle_inside_spans():
    ev = hand_made()
    r = trace.reduce(ev, (0.0, 1000 * US), spans=("bench.round",
                                                   "bench.key_prep"))
    # bench.round [50, 550]: busy [100, 170] + [300, 400] + [500, 550]
    assert r["span_idle_s"]["bench.round"] == pytest.approx(280e-6)
    assert r["span_idle_s"]["bench.key_prep"] == pytest.approx(100e-6)
    assert r["span_count"] == {"bench.round": 1, "bench.key_prep": 1}


def test_short_gaps_are_not_labelled():
    ev = hand_made()
    ev["devices"]["/device:TPU:0"] = [["a", 0.0, 500 * US],
                                      ["b", 502 * US, 498 * US]]
    r = trace.reduce(ev, (0.0, 1000 * US))
    assert dict(r["breakdown"]["idle_gaps"]) == {
        "(between operations)": pytest.approx(2e-6)}


def test_devices_are_averaged():
    ev = hand_made()
    ev["devices"]["/device:TPU:1"] = [["fusion.9", 0.0, 1000 * US]]
    r = trace.reduce(ev, (0.0, 1000 * US))
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((271e-6 + 1e-3) / 2)


def test_no_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []}, (0.0, 1.0))


def recorded():
    """One round of ``mamba2-k1-int8ef`` (at 2 of 64 layers) traced on a
    TPU v5e (device operations by instruction name, host spans inside the
    round)."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_int8ef_round.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_round():
    import numpy as np
    ev = recorded()
    window = trace.host_span(ev, "bench.round")
    r = trace.reduce(ev, window, kernels=("qsync_flat",),
                     spans=("bench.round",))
    # the busy union against a timeline sampled every 100 ns
    t0, t1 = window
    grid = np.zeros(int((t1 - t0) // 100) + 1, bool)
    for _, s, d in ev["devices"]["/device:TPU:0"]:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int((a - t0) // 100):int(np.ceil((b - t0) / 100))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 100e-9, rel=2e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"])
    assert r["span_idle_s"]["bench.round"] == pytest.approx(idle)
    # one fused qsync per parameter leaf, 30 leaves
    assert r["kernel_calls"] == {"qsync_flat": 30}
    assert 0 < r["kernel_s"]["qsync_flat"] < r["busy_s"]
    # the driver's per-round metrics fetch is where the device waits most
    assert r["breakdown"]["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    # a while loop's own time excludes its body: operation seconds add up
    # to no more than the busy time
    ops = sum(v for _, v in trace.reduce(ev, window, top=10 ** 6)
              ["breakdown"]["device_ops"])
    assert ops == pytest.approx(r["busy_s"], rel=1e-6)
