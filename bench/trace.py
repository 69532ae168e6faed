"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

One traced window gives, per device plane (``/device:TPU:<n>``):

- busy seconds: the union of the intervals in which an operation ran
  (events of the plane's ``XLA Ops`` line; ``XLA Modules`` where a plane
  has no op line);
- device time by operation name, and by jitted program (``XLA Modules``);
- the idle gaps between operations, each labelled with the benchmark's own
  host span (``TraceAnnotation`` named ``bench:<what>``) that covers the
  gap's midpoint, or ``unattributed`` where the program's spans cannot say
  yet what the host was doing.

Only JAX is needed to read the trace (``jax.profiler.ProfileData``).
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_SPAN = "bench:"


@dataclass
class DeviceTrace:
    planes: int = 0
    busy_s: float = 0.0                   # mean over the device planes
    span_s: float = 0.0                   # first op start .. last op end
    op_s: Dict[str, float] = field(default_factory=dict)
    op_n: Dict[str, int] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, int] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def time_of(self, needle: str, modules: bool = False) -> Tuple[float, int]:
        """Device seconds and event count of every op (by its own name) or
        jitted program whose name contains ``needle``; (0, 0) where none
        does."""
        src_s = self.module_s if modules else self.op_s
        src_n = self.module_n if modules else self.op_n
        names = [k for k in src_s if needle in k]
        return (sum(src_s[k] for k in names), sum(src_n[k] for k in names))


def op_name(text: str) -> str:
    """An op's own name: TPU traces name each op by its HLO instruction
    text (``%paged_gqa_decode.10 = bf16[...] custom-call(...)``), whose
    operand list names other ops; keep what precedes `` = ``."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir) -> Optional[Path]:
    files = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    return files[-1] if files else None


def _union(intervals: List[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Total covered ns and the merged intervals."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def _label(spans: List[Tuple[int, int, str]], t: float) -> str:
    for s, e, name in spans:
        if s <= t <= e:
            return name
    return "unattributed"


def reduce_profile(pd, top_gaps: int = 10) -> DeviceTrace:
    """Reduce a loaded ``ProfileData``."""
    out = DeviceTrace()
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH_SPAN):
                        spans.append((ev.start_ns, ev.end_ns,
                                      ev.name[len(BENCH_SPAN):]))
    spans.sort(key=lambda x: x[1] - x[0])         # innermost span first
    op_s, op_n = defaultdict(float), defaultdict(int)
    mod_s, mod_n = defaultdict(float), defaultdict(int)
    busy, span, gaps = [], [], []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:
            continue
        iv = []
        for ev in ops.events:
            iv.append((ev.start_ns, ev.end_ns))
            name = op_name(ev.name)
            op_s[name] += ev.duration_ns * 1e-9
            op_n[name] += 1
        mods = lines.get(MODULES_LINE)
        if mods is not None:
            for ev in mods.events:
                mod_s[ev.name] += ev.duration_ns * 1e-9
                mod_n[ev.name] += 1
        if not iv:
            continue
        covered, merged = _union(iv)
        busy.append(covered * 1e-9)
        span.append((merged[-1][1] - merged[0][0]) * 1e-9)
        for (s0, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append(((s1 - e0) * 1e-9, _label(spans, (e0 + s1) / 2)))
    out.planes = len(busy)
    if busy:
        out.busy_s = sum(busy) / len(busy)
        out.span_s = sum(span) / len(span)
    out.op_s, out.op_n = dict(op_s), dict(op_n)
    out.module_s, out.module_n = dict(mod_s), dict(mod_n)
    gaps.sort(reverse=True)
    out.gaps = [(name, sec) for sec, name in gaps[:top_gaps]]
    return out


def reduce_file(path) -> DeviceTrace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


CONTAINERS = ("while", "conditional", "call")


def breakdown(tr: DeviceTrace, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line: the ops that took
    most device time (loops and calls, whose time is their body's, left
    out) and the longest idle gaps."""
    ops = sorted(((k, v) for k, v in tr.op_s.items()
                  if not k.startswith(CONTAINERS)),
                 key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in tr.gaps[:top]]}
