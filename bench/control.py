"""Readings for the limit of a cell's compared number, on the chip.

    python3 bench/control.py --workload glm4-9b.chat --seconds 20 --seeds 101 102 103

For each seed, in one process, it runs the cell as ``run.py`` does (the
mix's warm phase at the cell's load, a window of ``--seconds``, the same
sample of finished requests) and reads two numbers over the same
prompts and served tokens: the program's widest logit gap, and the
control's, the gap of the token that the reference computed with fp8
weights puts first.  The benchmark's own runs never run the control.
One JSON line per seed.
"""
from __future__ import annotations

import gc
import json
import sys

import run


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    for seed in args.seeds:
        rargs = run.parse(["--workload", args.workload, "--seed", str(seed),
                           "--seconds", str(args.seconds), "--trace", "0"])
        try:
            out = run.run(rargs, spec, control=True,
                          log=lambda *a: print(*a, file=sys.stderr))
        except run.NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "logit_gap": out["compared"]["logit_gap"]["value"],
                          "control_gap": out["control_gap"],
                          "metrics": out["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
