"""The numbers that decide ``correct``, and their limits.

A training cell's set-up drives the program through its first rounds (at
least three local steps) and the plain reference follows the same rounds
from the same weights, data and keys.  The numbers, each the worst case
over agents:

* ``loss_gap``: |program - reference| / |reference| of each checked
  round's mean D loss and G loss, the worst; ``first_loss_gap`` the same
  of the first round alone (with K=1, the first step's losses, taken
  before any update); ``gen_loss_gap`` the same of G's loss alone over
  every checked round; ``lm_gap`` of the language-model part of G's loss
  alone;
* ``moment_gap``: per leaf, the gap between the norms of Adam's first
  moment after the first round (for K=1, (1 - b1) times the first
  gradient as the optimizer got it), over the larger of the reference's
  norm of that leaf and of the median leaf; the worst leaf's, and
  ``moment_median`` the median leaf's;
* ``change_gap`` and ``change_median``: the same for the parameters'
  change from their start after the last checked round.  Leaves whose
  reference moment is under a thousandth of the median leaf's move by
  round-off alone and are left out.

A cell's limits file names the numbers it compares.

Two guards are exact: every loss of every window round is finite, and the
agents hold identical parameters after the last sync.
"""
from __future__ import annotations

import numpy as np

STEPS_CHECKED = 3      # local steps the reference follows, at least
LOSSES = ("d_loss", "g_loss", "lm", "adv")   # the columns of ``losses``
NOUGHT = 1e-3          # a leaf moving under this share of the median's


def checked_rounds(K: int) -> int:
    return -(-STEPS_CHECKED // K)


def _leaf_gaps(prog: dict, ref: dict, leaves) -> dict:
    """{leaf: worst gap over agents}, each over the larger of the leaf's
    reference norm and the median leaf's."""
    ref_med = np.median(np.stack([ref[k] for k in ref]), axis=0)
    return {k: float(np.max(np.abs(prog[k] - ref[k])
                            / np.maximum(ref[k], ref_med))) for k in leaves}


def _moving(ref_mu: dict) -> list:
    med = np.median(np.stack([ref_mu[k] for k in ref_mu]), axis=0)
    return [k for k in ref_mu if np.all(ref_mu[k] >= NOUGHT * med)]


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers from the program's and the reference's
    readings (each: ``losses`` (rounds, LOSSES), ``mu`` and ``change``
    {leaf: per-agent norms})."""
    if set(prog["mu"]) != set(ref["mu"]):
        raise ValueError("the program's and the reference's parameter "
                         "trees differ")
    loss = np.abs(prog["losses"] - ref["losses"]) / np.abs(ref["losses"])
    mu = list(_leaf_gaps(prog["mu"], ref["mu"], ref["mu"]).values())
    change = list(_leaf_gaps(prog["change"], ref["change"],
                             _moving(ref["mu"])).values())
    return {"loss_gap": float(np.max(loss[:, :2])),
            "first_loss_gap": float(np.max(loss[0, :2])),
            "gen_loss_gap": float(np.max(loss[:, 1])),
            "lm_gap": float(np.max(loss[:, 2])),
            "moment_gap": max(mu), "moment_median": float(np.median(mu)),
            "change_gap": max(change),
            "change_median": float(np.median(change))}


def worst_leaves(prog: dict, ref: dict, n: int = 3) -> dict:
    """The ``n`` leaves with the largest moment and change gaps, for the
    log: {number: [[leaf, gap], ...]}."""
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:n]
    return {"moment_gap": top(_leaf_gaps(prog["mu"], ref["mu"], ref["mu"])),
            "change_gap": top(_leaf_gaps(prog["change"], ref["change"],
                                         _moving(ref["mu"])))}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): each number at or under its
    limit.  A number that is NaN fails."""
    out = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(bool(v["value"] <= v["limit"]) for v in out.values())
    return ok, out
