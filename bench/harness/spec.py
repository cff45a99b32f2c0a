"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout's
root, ``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``loops/<loop>.py`` under ``bench/``.

A later cell, mix, metric or loop kind is added as a file of its own
and an entry in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A cell, configuration, mix, metric or loop that cannot be found."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def config(name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def loop(kind: str):
    """The loop module for a traffic's ``loop`` kind: ``loops/<kind>.py``
    with ``setup``, ``window`` and ``check``."""
    return _load_module(os.path.join(BENCH_DIR, "loops", f"{kind}.py"),
                        f"bench_loop_{kind}")


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``: the number, or None where
    the run holds nothing to read."""
    mod = _load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))
    return mod.read


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its configuration, mix and the
    metrics it reports."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries of BENCHMARK.json
    per_layer: tuple


def _reported_in(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # no list: reported wherever the metric it moves is reported
    return metric.get("moves", metric["name"]) in e2e_names


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per = tuple(m for m in bench["per_layer"]
                if _reported_in(m, name, e2e_names))
    return Cell(name=name, chips=int(entry["chips"]),
                config=config(entry["config"]),
                traffic=traffic(entry["traffic"]),
                end_to_end=e2e, per_layer=per)
