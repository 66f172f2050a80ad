"""What the harness, its drivers and its models share: the cell a driver
runs, what it returns, and the run's sub-seeds.

A driver (``drivers/<driver>.py``, named by the traffic's ``"driver"``)
builds the program's objects from the run's seed, warms up (set-up), runs
the timed window and compares what the window produced with the plain
reference; it reaches the model only through ``Cell.model``, the module
``models/<model>.py`` that the configuration's ``"model"`` names.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import types
import zlib

import numpy as np


def sub_seed(seed: int, label: str) -> int:
    """A 31-bit seed for one use (``label``) of the run's seed, which may
    be any non-negative integer."""
    ss = np.random.SeedSequence([seed % (1 << 64), zlib.crc32(label.encode())])
    return int(ss.generate_state(1)[0] >> 1)


@dataclasses.dataclass
class Cell:
    """What a driver needs: the configuration, the traffic, the run."""
    config: dict
    traffic: dict
    model: types.ModuleType               # models/<config["model"]>.py
    seed: int
    seconds: float
    ring: object                          # the program's Ring to run on
    window: contextlib.AbstractContextManager  # wraps the timed window
    clock_start: float                    # perf_counter at process start


@dataclasses.dataclass
class Outcome:
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    numbers: dict                         # compared with the limits
    memory_peak_bytes: int | None
    end_to_end: dict                      # name -> value
    layer: dict                           # what per-layer readers read


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")
