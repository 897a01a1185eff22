"""A tiny cell for CPU tests: ``tiny.json`` with the ``tiny_*`` mixes,
run through the harness with its look for a chip skipped."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import run  # noqa: E402

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}
LIMIT = 0.06        # logit gap of the tiny cell: the program reads up to
#                     0.02 on CPU, the fp8 control 0.14 and more
LOADS = {"chat": {"session_rate_per_s": 4.0}, "unique": {"clients": 8}}


def make_spec(mix: str, chips: int = 1, replicas=None) -> dict:
    """The tiny cell of ``mix``; with ``replicas``, ``chips`` replicas
    behind the cluster (on the CPU they share its one device)."""
    bench = harness.load_benchmark()
    cell = {"load": LOADS[mix], "limits": {"logit_gap": LIMIT}}
    if replicas is not None:
        cell["replicas"] = replicas
    return {"workload": {"name": f"tiny.{mix}", "config": "tiny",
                         "traffic": mix, "chips": chips},
            "config": json.loads((HERE / "tiny.json").read_text()),
            "traffic": json.loads((HERE / f"tiny_{mix}.json").read_text()),
            "cell": cell,
            # the GLM chat cell's metrics; the closed loop (no cell
            # yet) gets those that every cell reports
            "end_to_end": harness.cell_metrics(bench, f"glm4-9b.{mix}",
                                               "end_to_end"),
            "per_layer": harness.cell_metrics(bench, f"glm4-9b.{mix}",
                                              "per_layer")}


def run_tiny(mix: str, seed: int = 2**31 + 11, trace: int = 0,
             seconds: float = 1.5, spec=None, **kw) -> dict:
    args = run.parse(["--workload", f"tiny.{mix}", "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, spec or make_spec(mix), require_tpu=False,
                   peaks=PEAKS, log=lambda *a: None, **kw)
