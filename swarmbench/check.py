"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, over the same inputs from the same seed.

Four numbers, each with its limit in ``swarmbench/limits/<cell>.json``
(PERF.md gives the readings each limit was set from). A number that has
no limit in a cell is printed but not compared there: no control or fault
reading lies far enough above its sound readings to hold a limit.

  loss_gap    the largest |program - reference| / |reference| over every
              local step's loss at every site in the compared rounds;
  mu_gap      by the worst leaf, the gap between the program's and the
              reference's norm of AdamW's first moment after round 1 (the
              gradients as the optimizer got them), over the larger of the
              reference's norm of that leaf and of the median leaf;
  update_gap  the same for the change of the parameters over the compared
              rounds (local steps, gates and commits). Leaves whose
              reference first moment is under a thousandth of the median
              leaf's are left out: their gradient is nought to rounding (a
              bias that batch norm cancels), and AdamW moves them by
              round-off alone;
  gate_flips  gates on which the program and the reference decide apart.
              Where the reference's merged AUC lies within ``GATE_BAND`` of
              the threshold times its local AUC, rounding may decide the
              gate either way, and the reference takes the program's
              decision (`reference.run_rounds`, ``follow``): a flip counts
              only outside that band. Its limit is 0.
"""
from __future__ import annotations

import numpy as np

# a leaf's gradient counts as nought below this share of the median leaf's
NOUGHT = 1e-3
# a gate within this much AUC of its threshold is a tie (PERF.md section 2)
GATE_BAND = 0.15


def _norms(leaves):
    return np.array([np.sqrt(np.sum(np.square(a.astype(np.float64))))
                     for a in leaves])


def _worst_leaf(prog, ref, keep=None):
    """(gap, index of the leaf that gives it)."""
    p, r = _norms(prog), _norms(ref)
    if keep is None:
        keep = np.ones(len(r), bool)
    gap = np.where(keep, np.abs(p - r) / np.maximum(r, np.median(r[keep])),
                   -np.inf)
    return float(gap.max()), int(gap.argmax())


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers of ``prog`` against ``ref`` (each a
    dict with ``losses``, ``mu1``, ``params0``, ``params`` and ``gates``)."""
    lp, lr = np.asarray(prog["losses"], np.float64), ref["losses"]
    loss_gap = (float(np.max(np.abs(lp - lr) / np.abs(lr)))
                if np.isfinite(lp).all() else float("inf"))
    mu_ref = _norms(ref["mu1"])
    keep = mu_ref >= NOUGHT * np.median(mu_ref)
    delta = lambda d: [a.astype(np.float64) - b for a, b in
                       zip(d["params"], d["params0"])]
    mu_gap, mu_leaf = _worst_leaf(prog["mu1"], ref["mu1"])
    update_gap, update_leaf = _worst_leaf(delta(prog), delta(ref), keep)
    flips = int(np.sum(np.asarray(prog["gates"]) != ref["gates"]))
    return {"loss_gap": loss_gap, "mu_gap": mu_gap, "update_gap": update_gap,
            "gate_flips": flips,
            "mu_leaf": mu_leaf, "update_leaf": update_leaf,
            "leaves_left_out": [int(i) for i in np.flatnonzero(~keep)]}


def judge(nums: dict, limits: dict):
    """(correct, [(name, value, limit)]): correct when every number with a
    limit is finite and at most its limit."""
    rows = [(k, nums[k], limits[k]) for k in sorted(limits)]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
