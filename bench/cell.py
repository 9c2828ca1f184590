"""Find a benchmark cell and everything that belongs to it, by name.

``BENCHMARK.json`` at the checkout root lists configurations, cells
(``workloads``) and metrics.  Each piece lives in a file of its own, found
by the name the entry gives:

    configuration   the ``file`` of its ``configs`` entry (JSON)
    traffic mix     bench/traffic/<traffic>.json
    query schema    bench/schemas/<config "schema">.py      (``query``)
    driver          bench/drivers/<traffic "driver">.py     (``run``)
    check           bench/checks/<config "check" "kind">.py (``check``)
    metric          bench/metrics/<metric name>.py          (``read``)

so a later cell, mix or metric is new files and new entries, and no edit.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def module(kind: str, name: str):
    """Import bench/<kind>/<name>.py (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with a ``workloads`` list applies to the cells it names;
    without one, an end-to-end metric applies to every cell and a per-layer
    metric to every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic mix and metrics."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return {"name": name, "chips": int(w["chips"]), "config": config,
            "traffic": traffic, "end_to_end": e2e, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}

