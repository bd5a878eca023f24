"""The median wall time of the window's rounds, each timed from dispatch
to ready: beside ``tokens_per_s``, which takes every second of the window,
a reading that a few rounds the host stalls leave alone."""
import numpy as np


def read(rec):
    return float(np.median(rec["window"]["round_s"])) * 1e3
