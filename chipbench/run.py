#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The run makes its
inputs and weights on the device from ``--seed``, sets up and warms up every
program its window uses, measures for ``--seconds``, and then checks what the
window produced against the plain reference. With ``--trace 0`` the last line
of standard output carries the cell's end-to-end metrics; with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window and the
program's spans, and the device's busy seconds and a breakdown.

The run fails (exit 2, no result) when JAX finds no TPU, fewer chips than the
cell asks for, or a device kind that ``chipbench/peaks.json`` does not hold.
JAX's compilation cache is kept in the checkout, so only the first run of a
cell there compiles. The numbers compared for ``correct`` are printed, each
with its limit, as the last lines of standard error and under ``checks`` at
the end of the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# the TPU runtime would log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402
from chipbench.harness import Cell, CellError, log  # noqa: E402


class Run:
    """What a driver gets: the cell, the run's arguments and the clocks."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 work: str, clock: harness.CompileClock,
                 moved: Dict[str, int]) -> None:
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.work, self.clock = trace, work, clock
        self.moved = moved

    def window(self):
        """Context of the measured window: a trace capture when tracing."""
        if self.trace:
            from chipbench.trace import Capture
            return Capture(os.path.join(self.work, "profile"), self.moved)
        return contextlib.nullcontext(None)

    def read_memory(self) -> Optional[int]:
        return harness.peak_bytes(self.cell.chips)


def _counters() -> Dict[str, Any]:
    from repro.kernels.ops import DISPATCHES
    from repro.store.checkpoint import CKPT_STATS
    return {"mgit_kernel_dispatches": DISPATCHES.snapshot(),
            "mgit_ckpt": CKPT_STATS.snapshot()}


def per_layer(cell: Cell, out: Dict[str, Any], device: Dict[str, Any]):
    """Reduce the window's trace; read every per-layer metric of the cell."""
    from chipbench import costs, trace
    cap = out["capture"]
    evs = trace.events(cap.xplane())
    red = trace.reduce(evs, n_chips=cell.chips, kernels=costs.KERNELS,
                       host_spans=cap.spans,
                       exclude=out.get("generator_modules", ()))
    shutil.rmtree(cap.logdir, ignore_errors=True)
    moved = cap.moved
    rec = {"e2e": out["e2e"], "spans": cap.spans, "device": red,
           "kernel_bytes": moved, "record": out["record"],
           "peaks": device["peaks"], "model": cell.config["model"],
           "config": cell.config, "mix": cell.mix}
    log(f"kernel seconds {red['kernel_s']}, calls {red['kernel_calls']}, "
        f"bytes {moved}")
    metrics = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def main(argv: Optional[List[str]] = None, *, bench_path: Optional[str] = None,
         bench_dir: Optional[str] = None, require_chip: bool = True) -> int:
    """Run one cell. The keywords serve the CPU rehearsals in
    ``chipbench/tests``: another ``BENCHMARK.json`` or benchmark directory,
    and no look for a chip."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload, bench_path, bench_dir or harness.BENCH_DIR)
        device = (harness.check_device(cell.chips) if require_chip
                  else harness.host_device())
    except CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    from chipbench import costs
    work = harness.work_dir(cell.root, cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    log(f"{cell.name}: seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}, device {device['kind']}")
    try:
        recorder = (costs.record_calls() if args.trace
                    else contextlib.nullcontext({}))
        with harness.CompileClock() as clock, recorder as moved:
            run = Run(cell, args.seed, args.seconds, bool(args.trace), work,
                      clock, moved)
            out = cell.driver().run(run)
        setup_s = out["setup_end"] - T_START
        log(f"set-up {setup_s:.3f} s (compiles {clock.count}, "
            f"{clock.seconds:.3f} s); window {out['window_s']:.3f} s")
        print(json.dumps({"counters": _counters(), "record": out["record"]}),
              flush=True)
        dev = {"platform": device["platform"], "kind": device["kind"],
               "count": device["count"], "memory_peak_bytes": out["memory"]}
        breakdown = None
        if args.trace:
            metrics, red = per_layer(cell, out, device)
            dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
            breakdown = red["breakdown"]
        else:
            values = dict(out["e2e"], setup_s=setup_s)
            missing = [m["name"] for m in cell.end_to_end
                       if m["name"] not in values]
            if missing:
                print(f"chipbench: the window produced no {missing}",
                      file=sys.stderr)
                return 1
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        shutil.rmtree(work, ignore_errors=True)
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(harness.result_line(correct=correct, attempted=out["attempted"],
                              failed=out["failed"], metrics=metrics,
                              device=dev, checks=checks,
                              breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
