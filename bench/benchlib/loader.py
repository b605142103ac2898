"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the harness finds

    bench/configs/<config>.json      the configuration as it is run
    bench/traffic/<traffic>.json     the mix: entry driver, scenes, budgets
    bench/drivers/<entry>.py         the driver of that entry point
    bench/archs/<arch>.py            a counter architecture: its FLOPs,
                                     weights and plain reference
    bench/metrics/<metric>.py        one reader per per-layer metric
    bench/limits/<workload>.json     the limits of the cell's comparison
    bench/peaks.json                 the chips' peaks, by device kind

so adding a cell, a configuration or a metric adds files and entries and
edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class MissingFile(LookupError):
    pass


def _path(kind: str, name: str, ext: str) -> str:
    p = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(p):
        raise MissingFile(f"no {kind[:-1] if kind.endswith('s') else kind} "
                          f"named {name!r} ({os.path.relpath(p, ROOT)})")
    return p


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise MissingFile(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(_path("configs", name, ".json"))


def traffic(name: str) -> dict:
    return _json(_path("traffic", name, ".json"))


def limits(name: str) -> dict:
    return _json(_path("limits", name, ".json"))


def module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` as a module of its own."""
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(entry: str):
    return module("drivers", entry)


def metric(name: str):
    return module("metrics", name)


@functools.cache  # one module per architecture: callers dispatch per call
def arch(name: str):
    return module("archs", name)


def per_layer_for(bench: dict, cell: str) -> list:
    """The per-layer metrics a cell reports: those that list it, and
    those that list no cells and move an end-to-end metric it reports."""
    reported = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def end_to_end_for(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
