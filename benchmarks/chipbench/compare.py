"""The comparison that decides ``correct``: the number a run compares with
the plain reference.

score_gap: the largest |probability - reference probability| over every
class of every query of every batch the window answered, each times that
query's reference smx denominator ``sum relu(z) + 1e-2``.  The smx divides
every score by that denominator, which can be as small as 1e-2, so the
bare probability gap of a query whose scores are all near 0 is its
scores' fixed-point rounding magnified up to 100 times; the product undoes
that and reads the error in the scores' own units.
"""
from __future__ import annotations

import numpy as np

import reference


def serve_numbers(params: dict, queries, probs) -> dict:
    want, denominator = reference.predict(params, queries)
    gap = np.abs(np.asarray(probs) - want)
    return {"score_gap": float(np.max(gap * denominator))}


def passed(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
