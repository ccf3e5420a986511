"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration ``<c>`` is ``bench/configs/<c>.json``;
* a traffic mix ``<t>`` is ``bench/traffic/<t>.json``;
* a cell ``<w>`` (a workload) keeps its serving sizes in
  ``bench/cells/<w>.json``;
* a per-layer metric ``<m>`` is read by ``read(ctx)`` in
  ``bench/metrics/<m>.py``.

Adding any of them is adding a file: the harness needs no edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent

#: the engine as users get it by default; a cell file may only set the
#: batch slots, the longest sequence and the size of the correctness sample
ENGINE_DEFAULTS = {
    "paged": True,
    "page_size": 16,
    "prefill_chunk": 512,
    "prefix": True,
    "decode_fusion": 4,
    "temperature": 0.0,
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    serving: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path) -> dict:
    return _json(root / "BENCHMARK.json")


def config(name: str, bench: Path = BENCH) -> dict:
    """The model as it is run (``model``), with its correctness limit."""
    cfg = _json(bench / "configs" / f"{name}.json")
    return {**cfg["model"], "name": name, "check": cfg["check"]}


def mix(name: str, bench: Path = BENCH) -> dict:
    return _json(bench / "traffic" / f"{name}.json")


def serving(workload: str, bench: Path = BENCH) -> dict:
    cell = _json(bench / "cells" / f"{workload}.json")
    return {**ENGINE_DEFAULTS, **cell}


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell(root: Path, workload: str, bench: Path = BENCH) -> Cell:
    bm = benchmark(root)
    wl = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bm['workloads']]}")

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload, chips=wl["chips"],
        config=config(wl["config"], bench), mix=mix(wl["traffic"], bench),
        serving=serving(workload, bench),
        end_to_end=[m for m in bm["end_to_end"] if applies(m)],
        per_layer=[m for m in bm["per_layer"] if applies(m)],
    )
