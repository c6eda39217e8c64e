"""Run a cell of the benchmark on the CPU at a size only these tests use.

``rehearse`` writes a ``BENCHMARK.json`` that holds the real cells plus
``tiny.*`` cells (the test configurations in ``tests/data``, under the real
mixes), skips the harness's look for a chip, runs the Pallas kernels in
interpret mode, and returns the run's exit code and result line.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"tiny.lineage-g2": ("tiny-f32", "lineage-g2"),
        "tiny.lineage-fanout": ("tiny-bf16", "lineage-fanout"),
        "tiny.train-ckpt": ("tiny-f32", "train-ckpt")}


def tiny_bench(path: str, extra: Optional[Dict[str, Any]] = None) -> str:
    """A ``BENCHMARK.json`` at ``path``: the real one plus the tiny cells."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in ("tiny-f32", "tiny-bf16"):
        bench["configs"].append(
            {"name": name, "source": "test-only", "reduced": [], "why": "t",
             "file": f"chipbench/tests/data/{name}.json"})
    real = {w["traffic"]: w["name"] for w in bench["workloads"]}
    for cell, (config, traffic) in TINY.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test-only"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real.get(traffic) in m.get("workloads", ()):
                m["workloads"].append(cell)
    for key, items in (extra or {}).items():
        bench[key] += items
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def rehearse(tmp_path, argv: List[str], *, bench_path: Optional[str] = None,
             bench_dir: Optional[str] = None):
    """Run ``chipbench/run.py`` in this process without the chip check;
    return ``(exit code, result dict or None, stderr text)``."""
    from repro.kernels import ops
    from chipbench import run
    bench_path = bench_path or tiny_bench(str(tmp_path / "BENCHMARK.json"))
    out, err = io.StringIO(), io.StringIO()
    saved = ops.default_backend
    ops.default_backend = lambda: "interpret"
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            code = run.main(argv, bench_path=bench_path, require_chip=False,
                            bench_dir=bench_dir)
    finally:
        ops.default_backend = saved
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith(
        '{"correct"') else None
    return code, result, err.getvalue()


def args(cell: str, seed: int = 2 ** 31 + 11, seconds: float = 3,
         trace: int = 0) -> List[str]:
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
