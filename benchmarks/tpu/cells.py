"""Finds a cell's pieces by name: ``workloads/<cell>.json``,
``configs/<config>.json``, ``drivers/<kind>.py``, ``metrics/<metric>.py``
(or, for a metric split by a suffix such as ``.chat``, the reader of the
unsplit name) and the cell's metric entries in ``BENCHMARK.json``.  Adding a cell, a
configuration, a driver or a per-layer metric is adding files: nothing
here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

__all__ = ["HERE", "Cell", "load_cell", "list_cells", "load_module",
           "metric_reader", "program_config", "peaks"]

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


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict            # workloads/<cell>.json
    config: dict              # configs/<config>.json
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
    cfg = _json(os.path.join(root, "configs", f"{wl['config']}.json"))
    checkout = _checkout(root)
    bench = _json(os.path.join(checkout, "BENCHMARK.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, wl, cfg, e2e, per_layer, root, checkout)


def load_module(root: str, kind: str, name: str):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


# configuration file key → the program's ArchConfig field
_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
    "attention_bias": "attn_bias",
}


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file: its registry
    entry with every size set from the file."""
    from repro.configs.registry import get_config

    base = get_config(cfg["arch"])
    if base.family != "dense" or base.n_experts or base.attention != "full":
        raise ValueError(f"{cfg['arch']} is not a dense full-attention LM")
    if cfg["torch_dtype"] != base.dtype:
        raise ValueError(f"{cfg['name']} states {cfg['torch_dtype']}, the "
                         f"program runs {base.dtype}")
    return dataclasses.replace(
        base, **{f: cfg[k] for k, f in _FIELDS.items()})
