"""Finding a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
lists the metrics. Each piece sits in a file of its own under the
benchmark's directory, found by that name:

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<traffic>.json``: the traffic mix;
- ``cells/<workload>.json``: the cell's engine settings and limits;
- ``metrics/<metric>.py``: the reader of one metric;
- ``references/<reference>.py``: a configuration's plain reference;
- ``families/<family>.py``: an architecture, named by the ``family`` of
  a configuration's ``model`` group: its weight layout
  (``leaf_specs``), the program's config for it (``program_config``),
  the least work of a decode step and a prefill (``decode_step``,
  ``prefill``), and what a published configuration implies for the
  model group beyond the keys its file maps (``check_published``).

A new configuration, architecture, mix, cell or metric is added as new
files and entries; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
METRIC_KEYS = ("UNIT", "SOURCE", "BETTER", "read")
LAYER_KEYS = ("LAYER", "MOVES")
FAMILY_KEYS = ("leaf_specs", "program_config", "decode_step", "prefill",
               "check_published")

_FAMILIES: dict = {}     # family name -> its module


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _json(base: Path, kind: str, name: str) -> dict:
    path = Path(base) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def _module(base: Path, kind: str, name: str):
    path = Path(base) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, base: Path = HERE) -> dict:
    """``configs/<name>.json`` under ``base``, with the family its model
    group names loaded from ``base`` (``family``)."""
    cfg = _json(base, "configs", name)
    if "family" not in cfg.get("model", {}):
        raise KeyError(f"{Path(base) / 'configs' / f'{name}.json'}: its "
                       f"model group names no 'family' (a file "
                       f"families/<family>.py)")
    family(cfg["model"]["family"], base)
    return cfg


def traffic(name: str, base: Path = HERE) -> dict:
    return _json(base, "traffic", name)


def cell(name: str, base: Path = HERE) -> dict:
    return _json(base, "cells", name)


def reference(name: str, base: Path = HERE):
    return _module(base, "references", name)


def family(name: str, base: Path = HERE):
    """The architecture family ``families/<name>.py`` under ``base``.

    ``family_of`` finds a family by its name alone, so a name stands for
    one file's code in a process: a copy of the same code under another
    base is the module already loaded, and other code under a name that
    is taken raises."""
    path = Path(base) / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no families file {path}")
    mod = _FAMILIES.get(name)
    if mod is None:
        mod = _module(base, "families", name)
        missing = [k for k in FAMILY_KEYS if not hasattr(mod, k)]
        if missing:
            raise AttributeError(f"family {path} lacks {missing}")
        _FAMILIES[name] = mod
    elif Path(mod.__file__).read_bytes() != path.read_bytes():
        raise ValueError(f"family {name!r} is loaded from {mod.__file__}; "
                         f"{path} holds other code under its name")
    return mod


def family_of(m: dict):
    """The family that model group ``m`` names: as ``config`` loaded it,
    else ``families/<family>.py`` here."""
    return _FAMILIES.get(m["family"]) or family(m["family"])


def metric(entry: dict, base: Path = HERE):
    """The reader of a metric entry, checked against the entry."""
    mod = _module(base, "metrics", entry["name"])
    keys = METRIC_KEYS + (LAYER_KEYS if "layer" in entry else ())
    missing = [k for k in keys if not hasattr(mod, k)]
    if missing:
        raise AttributeError(f"metric {entry['name']} lacks {missing}")
    for key, attr in (("unit", "UNIT"), ("better", "BETTER"),
                      ("source", "SOURCE"), ("layer", "LAYER"),
                      ("moves", "MOVES")):
        if key in entry and entry[key] != getattr(mod, attr):
            raise ValueError(f"metric {entry['name']}: BENCHMARK.json "
                             f"says {key}={entry[key]!r}, its reader "
                             f"{getattr(mod, attr)!r}")
    return mod


def cell_metrics(bench: dict, workload_name: str, traced: bool) -> list:
    """The metric entries a run of this cell reports: the end-to-end
    ones untraced, the per-layer ones traced."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [e for e in entries
            if workload_name in e.get("workloads", [workload_name])]
