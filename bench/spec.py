"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration and traffic mix; each lives in a file of its own:

  * configuration ``<config>``: ``bench/configs/<config>.json`` (the
    ``file`` that ``BENCHMARK.json`` gives it);
  * traffic mix ``<traffic>``: ``bench/traffic/<traffic>.json``;
  * per-layer metric ``<name>``: ``bench/metrics/<name>.py``, a module
    with ``read(run) -> float | None``;
  * architecture ``<arch>``, which the configuration names under
    ``"architecture"``: ``bench/arch/<arch>.py``, a module with the
    functions ``bench/arch/__init__.py`` lists.

A later cell adds files; no file here needs an edit for it.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, parsed
    arch: types.ModuleType  # the architecture module it names
    traffic: dict         # the traffic mix's file, parsed
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration and traffic read from their files under ``root``."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    file = configs[w["config"]]["file"]
    with open(os.path.join(root, file)) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                arch=load_arch(root, config, file), traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def _load(path: str, name: str) -> types.ModuleType:
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: str, name: str):
    """``read`` of ``<root>/bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return _load(path, f"bench_metric_{name}").read


def load_arch(root: str, config: dict,
              file: str = "the configuration") -> types.ModuleType:
    """The module ``<root>/bench/arch/<architecture>.py`` that the
    configuration ``config`` (read from ``file``) names."""
    if "architecture" not in config:
        raise ValueError(f"{file} has no \"architecture\" key: it names "
                         f"the module bench/arch/<architecture>.py")
    path = os.path.join(root, "bench", "arch",
                        config["architecture"] + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{file} names architecture "
                                f"{config['architecture']!r}, and there "
                                f"is no {path}")
    return _arch_module(os.path.realpath(path))


@functools.cache
def _arch_module(path: str) -> types.ModuleType:
    # once per file: its jitted functions then compile once a process
    name = os.path.splitext(os.path.basename(path))[0]
    return _load(path, f"bench_arch_{name}")
