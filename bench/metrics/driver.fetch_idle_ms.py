"""Device idle time inside the driver's spans after the round, per traced
round: ``driver.boundary`` (the per-round metrics fetch, evals,
checkpoints) and ``driver.collect`` (the final wait and the history
fetch).  With ``driver.launch_idle_ms`` it splits ``driver.host_gap_ms``."""

SPANS = ("driver.boundary", "driver.collect")


def read(rec):
    t = rec["trace"]
    if t is None or not any(t["span_count"].get(s) for s in SPANS):
        return None
    return sum(t["span_idle_s"][s] for s in SPANS) / rec["traced_rounds"] * 1e3
