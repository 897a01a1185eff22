"""Where the benchmark finds things by name: ``BENCHMARK.json`` at the
root of the checkout, a configuration in ``configs/<config>.json``, a
traffic mix in ``traffic/<mix>.json``, a cell's load in
``cells/<cell>.json``, one reader per metric in ``metrics/<metric>.py``
and the chip's peaks in ``peaks.json``.  Adding a cell or a metric adds
files; nothing here names one."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def load_cell(name: str) -> dict:
    return _json(BENCH / "cells" / f"{name}.json")


def load_peaks(device_kind: str) -> dict:
    table = _json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table["devices"][device_kind]


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that
    this cell reports: those without a ``workloads`` key, and those
    that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def model_config(conf: dict):
    from repro.config import FAMILY_DECODER, ModelConfig
    return ModelConfig(name=conf["name"], family=FAMILY_DECODER,
                       **conf["model"])


def engine_config(conf: dict):
    from repro.serving import EngineConfig
    return EngineConfig(**conf["engine"])
