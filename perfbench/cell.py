"""What a run hands to an entry, and what the entry hands back."""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    """One run of one cell: its BENCHMARK.json entry, configuration and
    traffic files, and the command-line settings."""

    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_process: float        # perf_counter when the process started
    trace_dir: str

    @property
    def name(self) -> str:
        return self.workload["name"]

    def limits(self) -> Dict[str, float]:
        """The limits of this cell's comparison (perfbench/limits/)."""
        with open(os.path.join(ROOT, "limits", self.name + ".json")) as f:
            return {k: float(v) for k, v in json.load(f)["limits"].items()}


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Outcome:
    """An entry's result: end-to-end values by metric name, per-layer
    inputs for the readers, and the comparison with the reference."""

    end_to_end: Dict[str, float]
    layer: Dict[str, object]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    setup_s: float
    trace: Optional[object] = None


def percentile(values, q: float) -> float:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, q)) if v.size else float("nan")
