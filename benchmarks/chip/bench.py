"""Harness core: find a cell by name, check the device, load its pieces.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Its traffic
parameters are ``workloads/<cell>.json``, its model configuration
``configs/<config>.json`` (whose ``reference`` names its model family,
``refs/<reference>.py``), its timed loop ``kinds/<kind>.py`` and each of
its per-layer metrics ``metrics/<metric>.py``.  Nothing here names a cell,
a configuration or a metric: a later cell is added by adding files.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """The cell cannot run here (no chip, missing file, bad spec)."""


@dataclass
class Cell:
    name: str
    root: Path                      # checkout root (holds BENCHMARK.json)
    bench_dir: Path                 # the directory that holds this harness
    entry: dict                     # the BENCHMARK.json workloads entry
    workload: dict                  # workloads/<cell>.json
    config: dict                    # configs/<config>.json
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path}") from None


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(entries)})")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf_entry = configs[entry["config"]]
    config = _read_json(root / conf_entry["file"])
    workload = _read_json(bench_dir / "workloads" / f"{name}.json")
    if workload.get("config") != entry["config"]:
        raise BenchError(f"workloads/{name}.json names config "
                         f"{workload.get('config')!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    return Cell(
        name=name, root=root, bench_dir=bench_dir, entry=entry,
        workload=workload, config=config,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric names hold dots, so they are not
    importable module names)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    mod_name = "chipbench_" + "_".join(
        path.relative_to(path.parents[1]).with_suffix("").parts
    ).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _read_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r}; "
                         f"peaks.json has {sorted(table)}")
    return table[kind]


def require_chips(jax, chips: int):
    """The devices this cell runs on: TPUs only, at least ``chips``."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: jax sees {devices[0].platform}; this "
                         "benchmark measures the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, jax sees "
                         f"{len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
