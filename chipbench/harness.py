"""What every cell of the chip benchmark shares: finding a cell's files by
name, the device check, the peaks table, compile and memory readings, and the
result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its pieces are
found by name, never by code that lists them:

    configs     the ``file`` of the cell's ``configs`` entry (JSON sizes)
    traffic     ``chipbench/mixes/<traffic>.json`` (data; its ``kind`` names
                the driver ``chipbench/drivers/<kind>.py`` that runs it)
    per-layer   ``chipbench/metrics/<name>.py``, one reader per metric

so a later change adds a configuration, a mix or a metric by adding files and
entries only.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_T0 = time.perf_counter()


class CellError(RuntimeError):
    """The cell cannot be run here (no chip, unknown device, bad files)."""


def log(msg: str) -> None:
    """Progress on stderr, with seconds since the process started."""
    print(f"[chipbench {time.perf_counter() - _T0:8.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise CellError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, mix and
    the metrics that it reports."""

    def __init__(self, name: str, bench_path: Optional[str] = None,
                 bench_dir: str = BENCH_DIR) -> None:
        self.bench_dir = bench_dir
        self.root = os.path.dirname(bench_dir)
        bench_path = bench_path or os.path.join(self.root, "BENCHMARK.json")
        bench = load_json(bench_path)
        work = {w["name"]: w for w in bench["workloads"]}
        if name not in work:
            raise CellError(f"no workload {name!r} in {bench_path}")
        self.workload = work[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(os.path.join(self.root,
                                             self.config_entry["file"]))
        self.traffic = self.workload["traffic"]
        self.mix = load_json(os.path.join(bench_dir, "mixes",
                                          self.traffic + ".json"))
        self.chips = int(self.workload["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self):
        kind = self.mix["kind"]
        return _load_module(os.path.join(self.bench_dir, "drivers",
                                         kind + ".py"),
                            f"chipbench_driver_{kind}")

    def reader(self, metric: str):
        return _load_module(os.path.join(self.bench_dir, "metrics",
                                         metric + ".py"),
                            "chipbench_metric_" + metric.replace(".", "_"))


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def peaks_for(kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if kind not in table:
        raise CellError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def check_device(chips: int, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """Fail unless JAX sees at least ``chips`` TPUs of a known kind."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise CellError(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX found "
                        f"{len(devices)}")
    kind = devices[0].device_kind
    return {"platform": devices[0].platform, "kind": kind,
            "count": chips, "peaks": peaks_for(kind, bench_dir)}


def host_device() -> Dict[str, Any]:
    """The device line of a run that was told to skip the chip check (the
    CPU rehearsals in ``chipbench/tests``)."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": 1,
            "peaks": load_json(os.path.join(BENCH_DIR, "peaks.json"))
            ["TPU v5 lite"]}


def peak_bytes(n: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the first ``n`` chips."""
    import jax
    peaks = []
    for d in jax.local_devices()[:n]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileClock:
    """Counts XLA backend compiles and their seconds while installed."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def _on_event(self, event: str, seconds: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.seconds += seconds
            self.count += 1

    def __enter__(self) -> "CompileClock":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def enable_compile_cache() -> str:
    """The program's persistent cache (in the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), keeping every program, however
    short its compile, so that a cell's second run compiles nothing."""
    import jax
    from repro.common.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def dir_bytes(path: str) -> int:
    """Bytes of every file under ``path``."""
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path) for f in files)


def work_dir(root: str, cell: str) -> str:
    """The fixed directory a cell keeps its repositories in (emptied at the
    start and the end of every run)."""
    return os.path.join(root, ".chipbench_work", cell)


# ---------------------------------------------------------------------------
# result
# ---------------------------------------------------------------------------

def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                checks: Dict[str, Dict[str, float]],
                breakdown: Optional[Dict[str, List]] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks      # the numbers compared, each with its limit
    return json.dumps(out)
