"""Find a cell's configuration, traffic mix and metric readers by name.

Everything a cell needs is data beside this file: ``BENCHMARK.json`` at
the checkout's root names the cells and metrics, ``configs/<name>.json``
holds a configuration (which may name its model module,
``models/<name>.py``), ``traffic/<name>.json`` a traffic mix (which may
name a ``base`` mix whose keys it overrides, and names the ``kind`` of
generator, ``traffic/<kind>.py``), and ``metrics/<name>.py`` the reader of
one metric. A new cell, configuration, model, mix or metric is new files
and entries.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for {name!r}")
    key = f"bench._loaded.{name}"
    mod = sys.modules.get(key)
    if mod is not None and mod.__file__ == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    traffic_name: str
    traffic: dict         # the mix, with its base's keys merged in
    end_to_end: list      # metric entries every cell reports, trace off
    per_layer: list       # entries listing this cell, trace on


def load_mix(name: str, root: Path = HERE) -> dict:
    """A traffic mix by name; ``base`` names a mix whose keys it extends."""
    mix = load_json(root / "traffic" / f"{name}.json")
    if "base" in mix:
        base = load_mix(mix["base"], root)
        mix = {**base, **{k: v for k, v in mix.items() if k != "base"}}
    return mix


def generator(kind: str, root: Path = HERE):
    """The traffic generator module a mix's ``kind`` names."""
    return _module(root / "traffic" / f"{kind}.py", f"traffic_{kind}")


def model(config: dict, root: Path = HERE):
    """The model module a configuration names (``"model"``; ``plain``
    where it names none): see ``models/__init__.py``."""
    name = config.get("model", "plain")
    return _module(root / "models" / f"{name}.py", f"model_{name}")


def reader(metric: str, root: Path = HERE):
    """The ``read(record)`` function of one metric."""
    return _module(root / "metrics" / f"{metric}.py",
                   "metric_" + metric.replace(".", "_").replace("-", "_")).read


def load_cell(name: str, manifest: dict | None = None,
              checkout: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with everything it uses."""
    if manifest is None:
        manifest = load_json(checkout / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    conf_entry = configs[w["config"]]
    config = load_json(checkout / conf_entry["file"])
    root = checkout / "bench"
    per_layer = [m for m in manifest["per_layer"] if name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"],
                traffic=load_mix(w["traffic"], root),
                end_to_end=list(manifest["end_to_end"]),
                per_layer=per_layer)
