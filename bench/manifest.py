"""Find a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load_manifest(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_module(path: Path) -> ModuleType:
    """Import a file by path; its name may hold '.' and '-'."""
    modname = "bench_file_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest: Dict[str, Any], workload: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in manifest['workloads']]}")


def load_config(name: str) -> Dict[str, Any]:
    return json.loads((BENCH_DIR / "configs" / f"{_checked(name)}.json")
                      .read_text())


def load_reference(config_name: str) -> ModuleType:
    return load_module(BENCH_DIR / "configs" / f"{_checked(config_name)}.ref.py")


def load_traffic(name: str) -> Dict[str, Any]:
    return json.loads((BENCH_DIR / "traffic" / f"{_checked(name)}.json")
                      .read_text())


def load_driver(kind: str) -> ModuleType:
    return load_module(BENCH_DIR / f"drive_{_checked(kind)}.py")


def load_metric(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{_checked(name)}.py")


def metrics_of(entries: List[Dict[str, Any]], workload: str) -> List[str]:
    """Names of the metrics among ``entries`` that ``workload`` reports."""
    return [m["name"] for m in entries
            if "workloads" not in m or workload in m["workloads"]]
