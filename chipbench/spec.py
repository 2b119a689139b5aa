"""Find a cell's configuration, traffic mix and per-layer metric readers
by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str, base: str = HERE) -> dict:
    path = os.path.join(base, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file named {name!r} at {path}")
    with open(path) as f:
        return json.load(f)


def config(name: str, base: str = HERE) -> dict:
    return _load_json("configs", name, base)


def traffic(name: str, base: str = HERE) -> dict:
    return _load_json("traffic", name, base)


def metric_reader(name: str, base: str = HERE):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = os.path.join(base, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(f"no metric reader named {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(name: str, bench: dict = None, base: str = HERE) -> dict:
    """The workload entry of ``name`` with its configuration and traffic
    loaded, and the metrics it reports."""
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    w = found[0]

    def reports(m):
        return name in m.get("workloads", [name])

    return {"workload": w, "config": config(w["config"], base),
            "traffic": traffic(w["traffic"], base),
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}
