"""Share of the traced rounds' window in which no operation ran on the
device: 1 - (union of operation intervals) / (window)."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
