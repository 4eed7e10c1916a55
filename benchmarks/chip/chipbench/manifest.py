"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; a configuration file names
its reference model and its engine.  Each is a file of its own:

* ``configs/<config>.json``  (the path is the manifest's ``file``)
* ``traffic/<mix>.json``
* ``reference/<reference>.py``
* ``engines/<engine>.py``
* ``metrics/<metric>.py``, one reader per metric, end-to-end or per layer

so adding any of them is adding a file and a manifest entry.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

from chipbench import BENCH_DIR, REPO_ROOT


def load(root: Path = REPO_ROOT) -> Dict[str, Any]:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _one(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise KeyError(f"{what} {name!r}: {len(found)} entries in BENCHMARK.json")
    return found[0]


def cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _one(manifest["workloads"], name, "workload")


def config(manifest: Dict[str, Any], name: str,
           root: Path = REPO_ROOT) -> Dict[str, Any]:
    entry = _one(manifest["configs"], name, "config")
    cfg = json.loads((Path(root) / entry["file"]).read_text())
    if cfg.get("name") != name:
        raise ValueError(f"{entry['file']} names {cfg.get('name')!r}, not {name!r}")
    return cfg


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    return json.loads((Path(bench_dir) / "traffic" / f"{name}.json").read_text())


def module(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """``<bench_dir>/<kind>/<name>.py`` as a module; the name may hold
    dots (``metrics/mfu.serve.py``), so it is loaded by path."""
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(manifest: Dict[str, Any], cell_name: str,
               kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries that cell reports.

    An entry with a ``workloads`` list is reported in those cells; an
    end-to-end entry without one in every cell; a per-layer entry without
    one in every cell that reports the metric it ``moves``."""
    e2e = [m["name"] for m in metrics_of_e2e(manifest, cell_name)]
    if kind == "end_to_end":
        return metrics_of_e2e(manifest, cell_name)
    out = []
    for m in manifest["per_layer"]:
        cells: Optional[List[str]] = m.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def metrics_of_e2e(manifest: Dict[str, Any], cell_name: str) -> List[Dict[str, Any]]:
    return [m for m in manifest["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]
