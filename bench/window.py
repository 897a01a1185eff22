"""What the metric readers see: the requests of the measured window,
as the client saw them, the counts taken around calls into the engine
(summed over replicas), the configuration's architecture module, and
the reduced trace of a traced run.

Every reader in ``metrics/`` is ``read(ctx) -> float | None``; None
means it found nothing to read, and the metric is left out of the
result line."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional


def percentile(vals, p: float) -> float:
    """Nearest-rank percentile (the smallest value with at least p% of
    the sample at or below it)."""
    vals = sorted(vals)
    if not vals:
        return float("nan")
    k = max(0, min(len(vals) - 1, math.ceil(p / 100.0 * len(vals)) - 1))
    return vals[k]


@dataclass
class Rec:
    """One request due in the window, as its client saw it (host clock,
    seconds).  ``first`` is None for a request that never produced a
    token before the run stopped waiting; ``wait_end`` is when it
    stopped."""
    due: float
    admit: Optional[float]
    first: Optional[float]
    token_times: List[float]
    prompt_len: int
    hit_tokens: int
    shed: bool
    wait_end: float

    @property
    def ttft(self) -> float:
        """Time to first token from the due time; a request that got no
        token counts with the time it was waited for, a lower bound."""
        end = self.first if self.first is not None else self.wait_end
        return end - self.due


@dataclass
class Ctx:
    cell: str
    d: dict                        # model sizes (arch.dims)
    peaks: dict
    setup_s: float
    w0: float                      # window, host clock
    w1: float
    arch: Optional[object] = None  # the configuration's arch/ module
    records: List[Rec] = field(default_factory=list)
    token_times: List[List[float]] = field(default_factory=list)
    counts: Dict[str, int] = field(default_factory=dict)   # window deltas
    decode_calls: List[tuple] = field(default_factory=list)   # traced
    prefill_calls: List[tuple] = field(default_factory=list)  # traced
    trace: Optional[object] = None  # tracing.Reduced, traced runs only

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    def in_window(self, t: float) -> bool:
        return self.w0 <= t < self.w1

    def itl_gaps(self) -> List[float]:
        """Every gap between consecutive tokens of one request that
        lies wholly in the window."""
        out = []
        for times in self.token_times:
            for a, b in zip(times, times[1:]):
                if self.w0 <= a and b < self.w1:
                    out.append(b - a)
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for times in self.token_times for t in times
                   if self.in_window(t))

    def device_trace(self):
        """The reduced trace when a device ran in it, else None."""
        if self.trace is None or self.trace.n_devices == 0:
            return None
        return self.trace
