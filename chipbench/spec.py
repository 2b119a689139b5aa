"""Find a cell's configuration, traffic mix, per-layer metric readers and
the reference's parts by the names ``BENCHMARK.json`` and those files give
them.

The reference's parts are code files of their own, so a new fabric or
aggressor profile enters as new files: a configuration's
``topology.family`` names ``families/<family>.py``, its ``cc.kind`` names
``cc/<kind>.py``, and a traffic mix's ``profile`` names
``profiles/<profile>.py``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the envelope of every baseline lane
BASELINE_PROFILE = "off"


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


@functools.lru_cache(maxsize=None)
def _exec(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _code(kind: str, name: str, base: str):
    """The module of ``<base>/<kind>/<name>.py``, loaded once per path, so
    that what it exports is the same object on every call (the reference
    binds rules and envelopes into its compiled lane by identity)."""
    path = os.path.abspath(os.path.join(base, kind, name + ".py"))
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file named {name!r} at {path}")
    return _exec(path, f"chipbench_{kind}_"
                 + name.replace(".", "_").replace("-", "_"))


def metric_reader(name: str, base: str = HERE):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return _code("metrics", name, base).read


def family(name: str, base: str = HERE):
    """The ``build(n, **params)`` of ``families/<name>.py``: a
    ``chipbench.topology.Topology`` of ``n`` nodes."""
    return _code("families", name, base).build


def cc_rule(kind: str, base: str = HERE):
    """The ``update(c, r, mark, can_dec, follow, inc, hc, p) -> (c, dec)``
    of ``cc/<kind>.py``: one rate-control step of every flow."""
    return _code("cc", kind, base).update


def profile(name: str, base: str = HERE):
    """The module of ``profiles/<name>.py``: ``envelope(t, **args)``, the
    aggressor's envelope in [0, 1] at simulated time ``t``, and
    ``PROGRAM``, the ``repro.core.congestion`` constructor of the same
    profile."""
    return _code("profiles", name, base)


def cell(name: str, bench: dict = None, base: str = HERE) -> dict:
    """The workload entry of ``name`` with its configuration and traffic
    loaded, the directory they came from, and the metrics it reports. The
    reference's parts that they name are found here, before any set-up: a
    missing one raises ``FileNotFoundError`` with its path."""
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")
    w = found[0]

    def reports(m):
        return name in m.get("workloads", [name])

    cfg, tr = config(w["config"], base), traffic(w["traffic"], base)
    family(cfg["topology"]["family"], base)
    cc_rule(cfg["cc"]["kind"], base)
    for envelope in (BASELINE_PROFILE, tr["profile"]):
        profile(envelope, base)
    return {"workload": w, "config": cfg, "traffic": tr, "base": base,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}
