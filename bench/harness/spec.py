"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, the
configuration, traffic and limit files, the configuration's reference
module, the generator and the metric readers.  Nothing here knows a cell,
configuration, architecture or metric by name.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Tuple

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
REFERENCES = BENCH / "references"


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}".replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict:
    """Everything a run of cell ``name`` needs."""
    b = benchmark()
    cells = {w["name"]: w for w in b["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in b["configs"]}[w["config"]]
    config = _json(ROOT / conf["file"])
    traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json")
    ref = reference(config, conf["file"])
    per_layer = metrics_for(b["per_layer"], name, b["end_to_end"])
    for m, reader in per_layer:
        for count in getattr(reader, "NEEDS", ()):
            if not hasattr(ref, count):
                raise AttributeError(f"metric {m['name']} of cell {name} needs {count!r}, "
                                     f"which reference module {ref.__file__} does not define")
    return {
        "cell": w,
        "config": config,
        "reference": ref,
        "traffic": traffic,
        "limits": _json(BENCH / "limits" / f"{name}.json"),
        "generator": generator(traffic["generator"]),
        "end_to_end": metrics_for(b["end_to_end"], name, None),
        "per_layer": per_layer,
        "chips": int(w["chips"]),
    }


def reference(config: Dict, where: str = "the configuration") -> ModuleType:
    """The reference module the configuration names under ``reference``:
    ``bench/references/<name>.py`` (its interface: ``references/llama.py``)."""
    if "reference" not in config:
        raise KeyError(f"{where} names no reference module (key 'reference', a file in {REFERENCES})")
    path = REFERENCES / f"{config['reference']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{where} names reference {config['reference']!r}: no file {path}")
    return _module(path)


def generator(kind: str) -> ModuleType:
    return _module(BENCH / "generators" / f"{kind}.py")


def metrics_for(entries: List[Dict], cell_name: str, e2e: List[Dict] = None) -> List[Tuple[Dict, ModuleType]]:
    """The metrics a cell reports, each with its reader.  A metric with a
    ``workloads`` key belongs to the cells it lists; a per-layer metric
    without one belongs to every cell that reports the end-to-end metric it
    moves."""
    e2e_here = None
    if e2e is not None:
        e2e_here = {m["name"] for m in e2e if "workloads" not in m or cell_name in m["workloads"]}
    out = []
    for m in entries:
        if "workloads" in m:
            if cell_name not in m["workloads"]:
                continue
        elif e2e_here is not None and m["moves"] not in e2e_here:
            continue
        out.append((m, _module(BENCH / "metrics" / f"{m['name']}.py")))
    return out


def peaks(device_kind: str) -> Dict:
    table = _json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
