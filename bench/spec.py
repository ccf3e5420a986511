"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration ``<c>`` is ``bench/configs/<c>.json``: its ``model``
  (every key is passed to the program's ``ArchConfig``; a group such as
  ``moe``, ``mla`` or ``ssm`` is an object of its dataclass's keys), its
  ``check`` limits, and ``arch``, the name of its architecture module;
* an architecture ``<a>`` is ``bench/archs/<a>.py``: ``FAMILY`` (the
  program's ``ArchConfig.family``), ``layout(cfg)`` and ``nest(flat)`` (the
  weights ``bench.weights`` draws), ``served_gaps`` and ``control_gaps``
  (the float32 reference and its fp8 control that ``bench.check`` compares
  with), and ``shapes(cfg)`` (the work counts that ``bench.counts`` and the
  metric readers take);
* a traffic mix ``<t>`` is ``bench/traffic/<t>.json``;
* a cell ``<w>`` (a workload) keeps its serving sizes in
  ``bench/cells/<w>.json``;
* a per-layer metric ``<m>`` is read by ``read(ctx)`` in
  ``bench/metrics/<m>.py``.  Besides the cell's sizes and the window's
  request records (``ctx.window``; each ``Record`` has its due, admission,
  first-token and finish times), a reader may read the reduced device
  trace (``ctx.reduced``, ``None`` without one: device seconds per program
  and, in ``scopes[<program>][<scope>]``, by every named scope of its
  operations, of every program where the trace names its operations'
  ``op_name``s and of the fused decode alone where it does not, as on the
  v5e; device idle seconds by the innermost host span in
  ``idle_by_span``) and the window's ledger counters (``ctx.counters``,
  keyed by the ledger's own names; see ``bench.run.counters``).  It
  returns ``None`` where it finds nothing to read.

Adding any of them is adding a file: the harness needs no edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent

#: keys of a configuration that the harness adds beside the model's own
HARNESS_KEYS = ("name", "arch", "check")

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
    arch: ModuleType
    mix: dict
    serving: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench: Path = BENCH               # where its files were found


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path) -> dict:
    return _json(root / "BENCHMARK.json")


def config(name: str, bench: Path = BENCH) -> dict:
    """The model as it is run (``model``), with its correctness limit and
    the name of its architecture module."""
    path = bench / "configs" / f"{name}.json"
    cfg = _json(path)
    if "arch" not in cfg:
        raise ValueError(f"{path} names no architecture module (\"arch\"); "
                         f"known: {sorted(p.stem for p in (bench / 'archs').glob('*.py'))}")
    return {**cfg["model"], "name": name, "arch": cfg["arch"], "check": cfg["check"]}


def model(cfg: dict) -> dict:
    """The model's own keys of a configuration."""
    return {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}


def model_key(cfg: dict) -> str:
    """The model's keys as one hashable value, the static key of the
    jitted weight draw and reference."""
    return json.dumps(model(cfg), sort_keys=True)


def _module(path: Path, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[mod_spec.name] = mod       # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


def arch(name: str, bench: Path = BENCH) -> ModuleType:
    """The architecture module ``bench/archs/<name>.py``."""
    return _module(bench / "archs" / f"{name}.py", "bench_arch_" + name)


def mix(name: str, bench: Path = BENCH) -> dict:
    return _json(bench / "traffic" / f"{name}.json")


def serving(workload: str, bench: Path = BENCH) -> dict:
    cell = _json(bench / "cells" / f"{workload}.json")
    return {**ENGINE_DEFAULTS, **cell}


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module(bench / "metrics" / f"{name}.py", "bench_metric_" + name).read


def cell(root: Path, workload: str, bench: Path = BENCH) -> Cell:
    bm = benchmark(root)
    wl = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in bm['workloads']]}")

    def applies(m):
        return workload in m.get("workloads", [workload])

    cfg = config(wl["config"], bench)
    return Cell(
        name=workload, chips=wl["chips"], config=cfg, arch=arch(cfg["arch"], bench),
        mix=mix(wl["traffic"], bench),
        serving=serving(workload, bench),
        end_to_end=[m for m in bm["end_to_end"] if applies(m)],
        per_layer=[m for m in bm["per_layer"] if applies(m)],
        bench=bench,
    )
