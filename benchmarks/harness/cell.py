"""Find every piece of a cell by its name.

`BENCHMARK.json` (at the checkout's root) names each cell's
configuration and traffic mix and lists the metrics. Each piece lives in
a file of its own under this directory, so a later cell or metric is a
new file, never an edit:

    configs/<config>.json    one deployment: sizes, party layout, mesh
    traffic/<traffic>.json   one traffic mix; `driver` names the module
    drivers/<driver>.py      one general generator per kind of system path
    metrics/<metric>.py      one reducer per metric: `read(record)`
    peaks.json               published peaks, keyed by `device_kind`
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HARNESS_DIR))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownDevice(KeyError):
    """The device is not in `peaks.json`: no default peak is assumed."""


def _checked(name: str) -> str:
    if not _NAME.match(name or ""):
        raise ValueError(f"not a cell, config, traffic or metric name: {name!r}")
    return name


def _json(*parts: str) -> dict:
    with open(os.path.join(HARNESS_DIR, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json("configs", _checked(name) + ".json")


def traffic(name: str) -> dict:
    return _json("traffic", _checked(name) + ".json")


def _module(kind: str, name: str):
    path = os.path.join(HARNESS_DIR, kind, _checked(name) + ".py")
    spec = importlib.util.spec_from_file_location(
        f"harness_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver(name: str):
    return _module("drivers", name)


def metric(name: str):
    return _module("metrics", name)


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The entries of `section` (`end_to_end` or `per_layer`) that this
    cell reports: those without a `workloads` key, and those listing it."""
    return [
        m for m in bench[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def peaks(device_kind: str) -> dict:
    table = _json("peaks.json")
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in "
            "peaks.json"
        ) from None
