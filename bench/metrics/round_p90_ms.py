"""The 90th percentile (linear between ranks) of the wall time of every
round in the window, each timed from dispatch to ready."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["window"]["round_s"], 90)) * 1e3
