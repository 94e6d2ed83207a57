"""Find a cell, its configuration and its traffic mix by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
serving geometry of a cell lives in ``cells/<cell>.json``, the model sizes
in the configuration's ``file`` and the traffic parameters in
``traffic/<mix>.json``.  The configuration's ``"blocks"`` names its block
module, ``blocks/<name>.py`` (``dense`` when the file names none), which
lays out the program's parameters and holds the plain reference.  Nothing
here knows a cell by name.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict

ROOT = Path(__file__).resolve().parents[1]

# keys of a configuration file that describe it rather than set a size
META_KEYS = ("name", "registry", "source", "reduced", "assumed",
             "departures", "deployment", "published", "blocks")
# keys of a configuration file that size one of ArchConfig's sub-configs
SUB_CONFIGS = ("moe", "mla", "mamba")


@dataclasses.dataclass(frozen=True)
class Cell:
    root: Path                 # the checkout: BENCHMARK.json and bench/
    name: str
    chips: int
    config: Dict[str, Any]     # the configuration file, as loaded
    traffic: Dict[str, Any]    # traffic/<mix>.json
    geometry: Dict[str, Any]   # cells/<cell>.json
    end_to_end: tuple          # metric entries the cell reports with --trace 0
    per_layer: tuple           # metric entries the cell reports with --trace 1

    @functools.cached_property
    def blocks(self):
        """The configuration's block module: ``layout(cfg)`` and the
        reference ``forward(w, tokens, cfg, precision)``."""
        name = self.config.get("blocks", "dense")
        path = self.root / "bench" / "blocks" / f"{name}.py"
        if not path.is_file():
            raise SystemExit(f"{self.config['name']}: no block module {name!r} "
                             f"({path})")
        return load_module(path, f"bench_blocks_{name}")


def load_module(path: Path, name: str):
    """The Python file ``path`` as a module of its own named ``name``."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def reports(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Cell ``name`` of ``root/BENCHMARK.json``, its files under ``root``."""
    bm = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    return Cell(
        root=root,
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        geometry=load_json(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=tuple(m for m in bm["end_to_end"] if reports(m, name)),
        per_layer=tuple(m for m in bm["per_layer"] if reports(m, name)),
    )


def arch_config(config: Dict[str, Any], smoke: bool = False):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry with every size the file gives.  A key that names a sub-config
    (``moe``, ``mla``, ``mamba``) takes a dict of that sub-config's fields,
    applied to the registry entry's sub-config.  ``smoke`` shrinks it to the
    repo's CPU test widths (rehearsal and tests only)."""
    from repro.configs import get_config, get_smoke_config

    base = (get_smoke_config if smoke else get_config)(config["registry"])
    sizes = {k: v for k, v in config.items() if k not in META_KEYS}
    nested = {k: sizes.pop(k) for k in SUB_CONFIGS if k in sizes}
    if smoke:
        # keep the smoke widths; take only what changes the math
        sizes = {k: v for k, v in sizes.items()
                 if k in ("qkv_bias", "norm_eps", "rotary_pct", "rope_theta",
                          "dtype")}
    else:
        sizes["name"] = config["name"]
    cfg = dataclasses.replace(base, **sizes)
    for key, fields in nested.items():
        sub = getattr(cfg, key)
        if sub is None:
            raise SystemExit(f"{config['name']}: registry entry "
                             f"{config['registry']!r} has no {key} sub-config")
        known = {f.name for f in dataclasses.fields(sub)}
        unknown = sorted(set(fields) - known)
        if unknown:
            raise SystemExit(f"{config['name']}: {key} has no field "
                             f"{', '.join(unknown)}; known: {sorted(known)}")
        if smoke:  # the smoke sub-config keeps its sizes (the integers)
            fields = {k: v for k, v in fields.items()
                      if isinstance(v, bool) or not isinstance(v, int)}
        cfg = dataclasses.replace(cfg, **{key: dataclasses.replace(sub, **fields)})
    return cfg
