"""Find the highest session-start rate a cell sustains, on the chip.

    python3 bench/sweep.py --workload glm4-9b.chat --seconds 51 --seed 7 --rates 0.45 0.6 0.75

In one process it runs the cell as ``run.py`` does at each rate in
turn, lowest first, and prints one JSON line per rate: the rate, the
median TTFT of the requests due in each quarter of the window, the
cell's end-to-end metrics and ``correct``.  A rate is sustained when
the last quarter's median is not above the first quarter's by more
than ``GROWTH``: the queue did not grow through the window.  The sweep
stops after the first rate that is not, since a higher one queues the
more.  The cell's own rate is then about 0.8x the highest sustained
one, fixed in its cell file; the benchmark's runs never sweep.
"""
from __future__ import annotations

import gc
import json
import sys

import run

GROWTH = 1.5       # last quarter's median over the first one's


def quarters(lines) -> list:
    """The per-quarter TTFT medians that ``run.run`` logs."""
    tag = "ttft median per quarter of the window (s):"
    for line in lines:
        if line.startswith(tag):
            return [None if v == "-" else float(v)
                    for v in line[len(tag):].split()]
    return []


def sustained(q: list) -> bool:
    return len(q) == 4 and None not in q and q[3] <= GROWTH * q[0]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    for rate in sorted(args.rates):
        rargs = run.parse(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", "0", "--rate", str(rate)])
        lines = []

        def log(*a):
            lines.append(" ".join(str(x) for x in a))
            print(*a, file=sys.stderr, flush=True)
        try:
            out = run.run(rargs, spec, log=log)
        except run.NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return 2
        q = quarters(lines)
        print(json.dumps({"rate": rate, "ttft_quarters_s": q,
                          "sustained": sustained(q),
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"],
                          "device": out["device"],
                          "compared": out["compared"]}), flush=True)
        gc.collect()
        if not sustained(q):
            break
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
