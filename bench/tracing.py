"""Reduce a profiler trace to what the per-layer metrics read.

The traced run brackets its window with a host span named ``WINDOW``
and wraps calls into the engine's layers in host spans of their own.
``reduce`` takes every device plane's operations (line ``XLA Ops``)
and programs (line ``XLA Modules``) and every host span, clips them to
the window, and gives:

* ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the devices that ran any;
* ``op_s`` / ``op_n``: seconds and count per kind of operation
  (``op_name``), loops and calls that hold other operations left out;
* ``module_s`` / ``module_n``: the same per program name, and
  ``programs``: each program run's seconds with the kinds of op it ran;
* ``idle_gaps``: device idle time within the window, attributed to the
  innermost host span that covers the middle of each gap.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW = "WINDOW"
SPAN_PREFIX = "engine."        # the host spans the benchmark records
NO_SPAN = "(no span)"


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    op_s: Dict[str, float] = field(default_factory=dict)
    op_n: Dict[str, int] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, int] = field(default_factory=dict)
    idle_gaps: Dict[str, float] = field(default_factory=dict)
    programs: List[tuple] = field(default_factory=list)  # (s, op kinds)

    def sum_ops(self, *patterns: str) -> Tuple[float, int]:
        """Seconds and count of the operations whose name contains any
        of ``patterns``."""
        return _match(self.op_s, self.op_n, patterns)

    def sum_programs(self, op: str) -> Tuple[float, int]:
        """Seconds and count of the program runs that ran an op of kind
        ``op`` (a program's own name is no help: a jitted partial is
        ``jit__unknown``)."""
        hits = [s for s, ops in self.programs if op in ops]
        return sum(hits), len(hits)


def _match(secs, counts, patterns):
    s, n = 0.0, 0
    for name, v in secs.items():
        if any(p in name for p in patterns):
            s += v
            n += counts[name]
    return s, n


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """The sub-intervals of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap_list, spans):
    """(span name, seconds) for each gap: the innermost (shortest) span
    that covers the gap's middle.  ``spans`` are (start, end, name),
    sorted; a sweep keeps only the spans open at each middle."""
    out, active, j = [], [], 0
    for a, b in sorted(gap_list):
        mid = 0.5 * (a + b)
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > mid]
        inner = min(active, key=lambda s: s[1] - s[0]) if active else None
        out.append((inner[2] if inner else NO_SPAN, (b - a) * 1e-9))
    return out


def reduce_events(device_lines, host_spans) -> Reduced:
    """``device_lines``: {device: {"XLA Ops": [(name, start, end)],
    "XLA Modules": [...]}}; ``host_spans``: [(name, start, end)].
    Times in nanoseconds on one clock."""
    win = [(a, b) for n, a, b in host_spans if n == WINDOW]
    if not win:
        raise ValueError("no WINDOW span in the trace")
    lo, hi = win[0]
    spans = sorted((a, b, n) for n, a, b in host_spans
                   if n.startswith(SPAN_PREFIX) and b > lo and a < hi)
    op_s, op_n = defaultdict(float), defaultdict(int)
    mod_s, mod_n = defaultdict(float), defaultdict(int)
    busy, n_dev, idle = 0.0, 0, defaultdict(float)
    programs = []
    for dev, lines in sorted(device_lines.items()):
        ivs = []
        mods = sorted((a, b, name) for name, a, b in
                      lines.get("XLA Modules", []) if min(b, hi) > max(a, lo))
        starts = [m[0] for m in mods]
        kinds = [set() for _ in mods]
        for name, a, b in lines.get("XLA Ops", []):
            j = bisect.bisect_right(starts, a) - 1
            if j >= 0 and a < mods[j][1]:
                kinds[j].add(name)
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            if name not in CONTAINERS:
                op_s[name] += (b - a) * 1e-9
                op_n[name] += 1
        for (a, b, name), ops in zip(mods, kinds):
            a, b = max(a, lo), min(b, hi)
            mod_s[name] += (b - a) * 1e-9
            mod_n[name] += 1
            programs.append(((b - a) * 1e-9, frozenset(ops)))
        if not ivs:
            continue
        n_dev += 1
        busy += union_length(ivs)
        for name, secs in attribute(gaps(ivs, lo, hi), spans):
            idle[name] += secs
    n = max(n_dev, 1)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   n_devices=n_dev, op_s=dict(op_s), op_n=dict(op_n),
                   module_s=dict(mod_s), module_n=dict(mod_n),
                   programs=programs,
                   idle_gaps={k: v / n for k, v in idle.items()})


CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


def op_name(text: str) -> str:
    """The kind of an XLA op from its trace name, which is the HLO
    instruction (``%fusion.96 = bf16[...] fusion(...)``): the name before
    ``=`` without ``%`` and the numeric suffix, so the kernel behind a
    custom call keeps the name of the function that called it
    (``_paged_decode_pallas``)."""
    head = text.split("=", 1)[0].strip().lstrip("%")
    base, dot, tail = head.rpartition(".")
    return base if dot and tail.isdigit() else head


def read_xplane(trace_dir: str):
    """(device_lines, host_spans) from the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device_lines, host_spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    lines[line.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            device_lines[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host_spans.append((e.name, e.start_ns,
                                       e.start_ns + e.duration_ns))
    return device_lines, host_spans


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]
