"""What every cell shares: finding the cell's files by the names in
BENCHMARK.json, the chip, the peaks, the metric readers, the comparison
that decides ``correct`` and the result line. Nothing here names a cell,
a model or a metric.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
# scratch inside the checkout (traces are read and removed); .gitignore
WORK_DIR = os.path.join(ROOT, ".bench_work")
# a traced run profiles this much of the window, up to its end
TRACE_SECONDS = 10.0


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise BenchFailure(f"BENCHMARK.json has no workload {workload!r}")


def cell_files(bench: dict, cell: dict) -> Dict[str, Any]:
    """The cell's configuration, traffic mix and limits, by name."""
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "config": load_json(ROOT, entry["file"]),
        "mix": load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(BENCH_DIR, "limits", cell["name"] + ".json"),
    }


def make_ctx(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, override=None,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """What a driver is handed: the cell, its files (``override(files)``
    lets a rehearsal or a test shrink them; the command line never
    does), the devices and their peaks."""
    setup_environment()
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, workload)
    files = cell_files(bench, cell)
    if override is not None:
        override(files)
    devices = require_devices(int(cell["chips"]), require_chip)
    return dict(files, bench=bench, cell=cell, seed=int(seed),
                seconds=float(seconds), trace=bool(trace), devices=devices,
                peaks=peaks_for(devices, require_chip),
                trace_dir=os.path.join(WORK_DIR, "trace", workload),
                t_start=time.perf_counter() if t_start is None
                else t_start)


def driver_of(ctx: dict):
    return importlib.import_module("benchmark.drivers." + ctx["mix"]["kind"])


def setup_environment() -> None:
    """Before jax is imported: the sealed machine has no network, and the
    compile cache sits where JAX_COMPILATION_CACHE_DIR says, else where
    the program puts it (``<checkout>/.jax_cache``)."""
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    os.environ.setdefault("HF_DATASETS_OFFLINE", "1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_devices(chips: int, require_chip: bool = True) -> list:
    """The first ``chips`` devices. No TPU, or too few: no result."""
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise BenchFailure(
            f"jax attached platform {devices[0].platform!r}, not 'tpu': "
            "a benchmark number comes only from the chip")
    if len(devices) < chips:
        raise BenchFailure(
            f"the cell asks for {chips} chips, jax found {len(devices)}")
    return list(devices[:chips])


def peaks_for(devices: list, require_chip: bool = True) -> dict:
    table = load_json(BENCH_DIR, "peaks.json")
    kind = devices[0].device_kind
    if kind not in table:
        if not require_chip:
            # rehearsal off the chip: shares against these are not
            # measurements and are never written down as such
            return {"name": kind, "flops_bf16": 1e12,
                    "hbm_bytes_per_s": 1e11}
        raise BenchFailure(
            f"device_kind {kind!r} is not in benchmark/peaks.json: add "
            "its published peaks with their source")
    return table[kind]


def device_record(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# compilations: counted from the program's cache listener
# ---------------------------------------------------------------------------

class CompileCounter:
    """Every compilation asks the persistent cache first, so hits plus
    misses counts compilations; read before and after the window."""

    def __init__(self):
        from gke_ray_train_tpu.perf.cache import cache_stats
        self._stats = cache_stats
        self.start: Optional[dict] = None

    def snapshot(self) -> dict:
        s = self._stats()
        return {"hits": s["hits"], "misses": s["misses"],
                "retrieval_s": s["retrieval_s"], "dir": s["dir"]}

    def begin(self) -> None:
        self.start = self.snapshot()

    def in_window(self) -> int:
        now = self.snapshot()
        return (now["hits"] + now["misses"]
                - self.start["hits"] - self.start["misses"])


# ---------------------------------------------------------------------------
# metrics: one small reader each, found by name
# ---------------------------------------------------------------------------

def wanted_metrics(bench: dict, cell: dict, trace: bool) -> List[str]:
    out = []
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" not in m or cell["name"] in m["workloads"]:
            out.append(m["name"])
    return out


def read_metrics(names: List[str], facts: dict) -> Dict[str, dict]:
    """A reader that finds nothing to read returns None, and the metric
    is left out of the line."""
    out = {}
    for name in names:
        spec = load_json(BENCH_DIR, "metrics", name + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(facts, **spec.get("args", {}))
        if value is None:
            continue
        out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


# ---------------------------------------------------------------------------
# correct: each number compared stands beside its limit
# ---------------------------------------------------------------------------

def judge(readings: Dict[str, float], limits: dict) -> Dict[str, list]:
    """{name: [number, limit]}: a number with no limit is an error unless
    the limits file names it as not compared (it has no upper reading;
    the driver prints it on an earlier line), a number that is not
    finite fails."""
    out = {}
    for name, value in readings.items():
        key = name if name in limits["limits"] else name.rstrip("0123456789")
        if key in limits.get("not_compared", ()):
            continue
        if key not in limits["limits"]:
            raise BenchFailure(f"no limit for compared number {name!r}")
        out[name] = [float(value), float(limits["limits"][key])]
    return out


def is_correct(checks: Dict[str, list]) -> bool:
    return bool(checks) and all(
        v == v and v <= limit for v, limit in checks.values())


def emit(result: dict, checks: Dict[str, list], notes: List[dict]) -> None:
    """Earlier lines (notes) first; the compared numbers as the last
    lines of stderr; the result as the last line of stdout."""
    for note in notes:
        print(json.dumps(note), flush=True)
    result = dict(result)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    sys.stdout.flush()
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v:.6g} (limit {lim:.6g})"
              f"{'' if v == v and v <= lim else '  FAILED'}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

