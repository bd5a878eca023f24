"""Device idle time inside the driver's spans before the round, per
traced round: ``driver.prepare`` (the DP check, the key schedule, each
chunk's key slice) and ``driver.dispatch`` (the jitted call).  With
``driver.fetch_idle_ms`` it splits ``driver.host_gap_ms``."""

SPANS = ("driver.prepare", "driver.dispatch")


def read(rec):
    t = rec["trace"]
    if t is None or not any(t["span_count"].get(s) for s in SPANS):
        return None
    return sum(t["span_idle_s"][s] for s in SPANS) / rec["traced_rounds"] * 1e3
