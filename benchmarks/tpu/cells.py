"""Finds a cell's pieces by name: ``workloads/<cell>.json``,
``configs/<config>.json``, the model family that the configuration names
under ``"family"``, ``drivers/<kind>.py``, ``metrics/<metric>.py`` (or,
for a metric split by a suffix such as ``.chat``, the reader of the
unsplit name) and the cell's metric entries in ``BENCHMARK.json``.

A model family is three files of the same name:

- ``families/<family>.py``: ``program_config(cfg)``, the configuration's
  keys mapped onto the program's ``ArchConfig`` (refusing a registry
  entry the family does not cover); ``FIELDS``, the keys it copies as
  they are, ``{file key: ArchConfig field}``; and ``shapes(cfg)``, the
  weights tree as ``{path: (shape, fan_in or None for a norm)}``;
- ``reference/<family>.py``: the plain float32 reference and its
  control (``served_gaps``, ``forward_hidden``, ``loss_and_grads``);
- ``counts/<family>.py``: operations and bytes from the sizes alone
  (``forward_flops``, ``paged_decode_cost``, ``causal_pairs``).

Adding a cell, a configuration, a model family, a driver or a per-layer
metric is adding files: nothing here names one.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

__all__ = ["HERE", "Cell", "Family", "load_cell", "list_cells",
           "load_module", "family", "metric_reader", "program_config",
           "peaks"]

HERE = os.path.dirname(os.path.abspath(__file__))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _checkout(root: str) -> str:
    """The checkout that holds ``BENCHMARK.json`` above ``root``."""
    d = root
    while not os.path.exists(os.path.join(d, "BENCHMARK.json")):
        parent = os.path.dirname(d)
        if parent == d:
            raise FileNotFoundError(f"no BENCHMARK.json above {root}")
        d = parent
    return d


@dataclasses.dataclass(frozen=True)
class Family:
    """A model family's three modules (see the module docstring)."""
    name: str
    arch: object              # families/<family>.py
    reference: object         # reference/<family>.py
    counts: object            # counts/<family>.py


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict            # workloads/<cell>.json
    config: dict              # configs/<config>.json
    family: Family            # the model family the configuration names
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    root: str                 # the benchmark's directory
    checkout: str

    @property
    def driver(self) -> str:
        return self.workload["driver"]


def list_cells(root: str = HERE) -> list[str]:
    d = os.path.join(root, "workloads")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def _reports(entry: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in e2e_names


def load_cell(name: str, root: str = HERE) -> Cell:
    path = os.path.join(root, "workloads", f"{name}.json")
    if not os.path.exists(path):
        raise KeyError(f"no workload {name!r}; have {list_cells(root)}")
    wl = _json(path)
    if wl["name"] != name:
        raise ValueError(f"{path} names itself {wl['name']!r}")
    cfg_path = os.path.join(root, "configs", f"{wl['config']}.json")
    cfg = _json(cfg_path)
    if "family" not in cfg:
        raise KeyError(f"{cfg_path} names no model family (key 'family')")
    fam = family(cfg, root)
    checkout = _checkout(root)
    bench = _json(os.path.join(checkout, "BENCHMARK.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, wl, cfg, fam, e2e, per_layer, root, checkout)


def load_module(root: str, kind: str, name: str):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict, root: str = HERE) -> Family:
    """The model family that configuration ``cfg`` names, from ``root``."""
    return _family(os.path.abspath(root), cfg["family"])


_FAMILY_PARTS = ("families", "reference", "counts")


@functools.lru_cache(maxsize=None)
def _family(root: str, name: str) -> Family:
    # one module object per file: what a test or calibrate.py patches in a
    # family's module is what the run then calls
    for part in _FAMILY_PARTS:
        path = os.path.join(root, part, f"{name}.py")
        if not os.path.exists(path):
            raise FileNotFoundError(f"model family {name!r} has no {path}")
    return Family(name, *(load_module(root, part, name)
                          for part in _FAMILY_PARTS))


def metric_reader(root: str, name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or
    for ``<base>.<suffix>`` without a file of its own, ``<base>.py``."""
    if not os.path.exists(os.path.join(root, "metrics", f"{name}.py")):
        name = name.rsplit(".", 1)[0]
    return load_module(root, "metrics", name)


def peaks(device_kind: str, root: str = HERE) -> dict:
    table = _json(os.path.join(root, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def program_config(cfg: dict, root: str = HERE):
    """The program's ArchConfig for a configuration file, as its model
    family maps it."""
    return family(cfg, root).arch.program_config(cfg)
