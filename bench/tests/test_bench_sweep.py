"""The sweep's reading of a window: the per-quarter TTFT medians that
the run logs, and whether the queue grew through the window."""
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
import sweep

TAG = "ttft median per quarter of the window (s):"


def test_quarters_are_read_from_the_run_log():
    lines = ["window: 63 due", f"{TAG} 0.979 2.968 - 1.746", "check: 4"]
    assert sweep.quarters(lines) == [0.979, 2.968, None, 1.746]
    assert sweep.quarters(["window: 0 due"]) == []


@pytest.mark.parametrize("q, held", [
    ([1.334, 1.987, 1.334, 1.227], True),     # a burst in the second
    ([1.504, 4.286, 1.348, 1.300], True),     # quarter, then back down
    ([0.979, 2.968, 1.905, 1.746], False),    # last quarter 1.78x the first
    ([5.225, 8.963, 16.0, 28.025], False),    # a queue that grows
    ([0.5, 0.6, None, 0.7], False),           # a quarter with no request
    ([0.5, 0.6, 0.7], False),
])
def test_sustained_judges_the_last_quarter_against_the_first(q, held):
    assert sweep.sustained(q) is held
