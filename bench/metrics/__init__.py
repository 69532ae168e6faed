"""Per-layer metric readers: one file per metric, ``<metric name>.py``,
each with ``read(w: Window) -> float | None``. The harness finds a reader
by the metric's name in ``BENCHMARK.json``. A reader that finds nothing to
read returns None, and its metric is left out of the result line; a share
of a roofline or of a peak is never reported as 0 for want of data.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent


@dataclass
class Window:
    """What a traced window saw, for the readers."""
    seconds: float                      # traced span (host clock)
    stats: Dict[str, float]             # RolloutStats deltas over the span
    drops: Dict[str, int]               # manager drop-counter deltas
    commits: List[tuple]                # (time, tenant, rows, (plens, tlens))
    episodes: List[object]              # bench.harness.Episode finished
    compiles: int                       # backend compiles in the span
    trace: Optional[object]             # bench.trace.DeviceTrace
    ctx_sum: int                        # Σ live context over decoded rows
    row_steps: int                      # decoded row-steps counted
    shape: Dict[str, object]            # reference shape of the config
    rank: int                           # LoRA rank
    peaks: Optional[dict]
    kind: str                           # device kind

    def peak(self) -> dict:
        if self.peaks is None:
            from bench.flops import peaks
            self.peaks = peaks(self.kind)
        return self.peaks


def reader(name: str):
    path = HERE / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def read_all(bench: dict, workload: str, w: Window) -> Dict[str, dict]:
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, workload):
            continue
        v = reader(m["name"])(w)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
