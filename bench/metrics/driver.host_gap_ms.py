"""Device idle time inside each traced round's driver call
(``RoundDriver.run``: its key schedule, dispatch, per-round metrics fetch
and final block), per round: what the driver's host work costs the
device.  (The driver's own ``timings["round_gap_s"]`` is no host gap
here: with ``log_every=1`` it includes the metrics fetch that waits for
the round, so it reads the round's whole device time.)"""

SPANS = ("bench.round",)


def read(rec):
    t = rec["trace"]
    if t is None or not t["span_count"].get("bench.round"):
        return None
    return t["span_idle_s"]["bench.round"] / t["span_count"]["bench.round"] * 1e3
