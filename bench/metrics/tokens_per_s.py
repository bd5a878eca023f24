"""Training tokens of all agents in all rounds completed in the window,
over the window's seconds and the cell's chips."""
from harness.traffic import tokens_per_round


def read(rec):
    w = rec["window"]
    return (len(w["round_s"]) * tokens_per_round(rec["traffic"])
            / w["seconds"] / rec["chips"])
