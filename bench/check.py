"""Decide ``correct``: the program's set-up trial against the reference.

The comparison itself belongs to the strategy and lives in
``bench/checks/<check>.py``, which the cell's traffic names. Such a
module gives ``outputs(strategy, hist)``, the program's set-up trial
copied to the host, and ``numbers(cell, prog, arrays, params0, key,
pseed, **ref_kw)``, the numbers of ``prog`` against the float32
reference run from the same inputs and seeds (or, with ``ref_kw``, of the
reference computed so in the program's place: the control and the planted
faults). This module holds what every check shares.
"""
from __future__ import annotations

import numpy as np


def leaf_norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def norm_gap(got: dict, want: dict, keep) -> float:
    """Worst leaf of |norm - norm_ref| over the larger of the reference
    leaf's norm and the median leaf's."""
    median = float(np.median([want[k] for k in keep]))
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in keep)


def changes(params: dict, params0: dict) -> dict:
    return {k: np.asarray(v, np.float64) - np.asarray(params0[k], np.float64)
            for k, v in params.items()}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, checks): every number that has a limit must not exceed
    it; a number that is not finite fails."""
    checks = {}
    for name, value in values.items():
        if name in limits:
            checks[name] = {"value": value, "limit": limits[name]}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks
