"""The decision on ``correct``: each number that a cell's model compares
with its plain reference (``models/<model>.py`` ``numbers``) against its
limit in ``limits/<cell>.json``.
"""
from __future__ import annotations

import math

import numpy as np


def checks(numbers: dict, limits: dict) -> tuple[dict, list]:
    """(each limit's number beside it, the limits a run gave no number
    for).  A number that is missing reads as infinite: it missed its
    limit."""
    got = {k: {"value": numbers.get(k, math.inf), "limit": v}
           for k, v in limits["limits"].items()}
    return got, sorted(set(limits["limits"]) - set(numbers))


def passed(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
