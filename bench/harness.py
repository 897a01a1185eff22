"""Where the benchmark finds things by name: ``BENCHMARK.json`` at the
root of the checkout, a configuration in ``configs/<config>.json``, the
architecture that configuration names in ``arch/<arch>.py`` (its
seeded weights, plain reference, cost functions and kernel names), a
traffic mix in ``traffic/<mix>.json``, a cell's load, limits and
replicas in ``cells/<cell>.json``, one reader per metric in
``metrics/<metric>.py`` and the chip's peaks in ``peaks.json``.  Adding
a cell, a metric or an architecture adds files; nothing here names
one."""
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


def _module(kind: str, name: str, path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return _module("metric", metric,
                   BENCH / "metrics" / f"{metric}.py").read


ARCH_DIRS = [BENCH / "arch"]      # where architecture modules are found
_ARCHS: dict = {}                 # path -> module, loaded once a process


def load_arch(name: str):
    """The module ``arch/<name>.py``, loaded once, so that its jitted
    reference compiles once however many runs a process makes."""
    for d in ARCH_DIRS:
        path = d / f"{name}.py"
        if path.is_file():
            if path not in _ARCHS:
                _ARCHS[path] = _module("arch", name, path)
            return _ARCHS[path]
    raise KeyError(f"no architecture module {name}.py in "
                   f"{[str(d) for d in ARCH_DIRS]}")


def config_arch(conf: dict):
    """The architecture module that a configuration names."""
    if "arch" not in conf:
        raise KeyError(f"configuration {conf.get('name')!r} names no "
                       "architecture: give it \"arch\", the name of a "
                       "module in arch/")
    return load_arch(conf["arch"])


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that
    this cell reports: those without a ``workloads`` key, and those
    that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def model_config(conf: dict):
    """The program's ``ModelConfig`` from the ``model`` block; its
    ``family`` is the block's own, a dense decoder where it gives
    none."""
    from repro.config import FAMILY_DECODER, ModelConfig
    model = dict(conf["model"])
    family = model.pop("family", FAMILY_DECODER)
    return ModelConfig(name=conf["name"], family=family, **model)


def engine_config(conf: dict):
    from repro.serving import EngineConfig
    return EngineConfig(**conf["engine"])
