"""From a profiler trace and the program's spans to per-layer numbers.

``capture`` records one window: JAX's profiler (device operations and the
benchmark's own ``TraceAnnotation`` spans) and the program's
``repro.obs.trace`` spans together. ``events`` flattens the profiler's
``.xplane.pb`` into plain records; ``reduce`` turns them into device busy and
idle seconds, device seconds per Pallas kernel (by the ``name=`` of each
``pallas_call``; the time of the device program each call runs in, which
stages the kernel's operands and results) and the breakdown: the device operations that took most time
and the idle gaps, each named by the innermost host span open at the time.

The program's spans run on the host's ``perf_counter`` clock and the trace on
the profiler's; the window's own annotation is on both, which puts them on
one clock.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import time
from bisect import bisect_right
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench.window"
#: Gaps shorter than this are dispatch latency between back-to-back device
#: operations and are summed under one name; longer ones are named by the
#: host span open at their middle. Host events shorter than
#: ``MIN_NAMING_SPAN_NS`` name nothing.
MIN_NAMED_GAP_NS = 100_000
MIN_NAMING_SPAN_NS = 50_000


class Capture:
    """Profiler trace plus program spans of one window."""

    def __init__(self, logdir: str, moved: Optional[Dict[str, int]] = None
                 ) -> None:
        self.logdir = logdir
        self._moved = moved if moved is not None else {}
        self.moved: Dict[str, int] = {}
        self.anchor_ns: Optional[int] = None
        self.obs_t0_ns: Optional[int] = None
        self.spans: List[Dict[str, Any]] = []
        self._annotation = None

    def __enter__(self) -> "Capture":
        import jax
        from repro import obs
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir)
        # no Python tracer: it would slow the host code it measures; the
        # benchmark's annotations and the program's spans name the gaps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        for k in self._moved:          # kernel bytes of this window only
            self._moved[k] = 0
        obs.reset_trace()
        obs.enable()
        self.obs_t0_ns = obs.trace._state.t0_ns
        self._annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._annotation.__enter__()
        self.anchor_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        import jax
        from repro import obs
        self._annotation.__exit__(*exc)
        self.moved = dict(self._moved)
        obs.disable()
        doc = obs.export_chrome_trace()
        jax.profiler.stop_trace()
        self.spans = [
            {"name": e["name"], "tid": e["tid"],
             # program spans: microseconds since obs's t0 -> ns since anchor
             "start_ns": int(e["ts"] * 1000) + self.obs_t0_ns - self.anchor_ns,
             "dur_ns": int(e["dur"] * 1000),
             "id": e["args"].get("span_id"),
             "parent": e["args"].get("parent_id")}
            for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_id = {sp["id"]: sp for sp in self.spans}
        for sp in self.spans:        # the outermost span each one runs under
            root, seen = sp, set()
            while root["parent"] in by_id and root["id"] not in seen:
                seen.add(root["id"])
                root = by_id[root["parent"]]
            sp["root"] = root["name"]

    def xplane(self) -> str:
        paths = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return paths[0]


def events(xplane_path: str) -> List[Dict[str, Any]]:
    """Every event of the trace as ``{plane, line, name, start_ns, dur_ns,
    hlo_op}``, times on the profiler's clock."""
    from jax.profiler import ProfileData
    out = []
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns),
                            "hlo_op": str(stats.get("hlo_op", ""))})
    return out


def _is_device(ev: Dict[str, Any], n_chips: int, line: str) -> bool:
    plane = ev["plane"]
    if not plane.startswith("/device:TPU:"):
        return False
    try:
        chip = int(plane.rsplit(":", 1)[1])
    except ValueError:
        return False
    return chip < n_chips and ev["line"] == line


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(ev: Dict[str, Any]) -> str:
    """The HLO instruction's own name: a TPU op event is named by its HLO
    text, ``%name.N = shape op(operands...)``."""
    return ev["name"].split(" = ", 1)[0].lstrip("%")


def kernel_of(ev: Dict[str, Any], kernels: Sequence[str]) -> Optional[str]:
    """The Pallas kernel an event ran: a custom call whose instruction is
    named after the kernel (the ``name=`` of its ``pallas_call``), with or
    without a ``.N`` suffix."""
    if "custom-call" not in ev["name"] and not ev["hlo_op"]:
        return None
    base = re.sub(r"\.\d+$", "", ev["hlo_op"] or op_name(ev))
    return base if base in kernels else None


def _innermost(spans: List[Dict[str, Any]], t: int) -> str:
    best = None
    for sp in spans:
        if sp["start_ns"] <= t < sp["start_ns"] + sp["dur_ns"]:
            if best is None or sp["dur_ns"] < best["dur_ns"]:
                best = sp
    return best["name"] if best is not None else "no span"


def _within(intervals: List[Tuple[int, int]], t: int) -> bool:
    i = bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def reduce(evs: List[Dict[str, Any]], *, n_chips: int,
           kernels: Sequence[str],
           host_spans: Sequence[Dict[str, Any]] = (),
           window: Optional[Tuple[int, int]] = None,
           exclude: Sequence[str] = (), top: int = 10) -> Dict[str, Any]:
    """Device busy/idle, kernel seconds and the breakdown of one window.

    ``window`` is ``(start_ns, end_ns)`` on the profiler's clock; without it
    the window is the ``WINDOW_SPAN`` annotation. ``host_spans`` (start
    relative to that annotation's start) name the idle gaps, beside the
    profiler's own host events. Device programs whose module name starts
    with one of ``exclude`` (the benchmark's own load generator) count as
    neither busy nor idle time of the system: their ops are left out."""
    anchor = None
    for ev in evs:
        if ev["name"] == WINDOW_SPAN:
            anchor = ev
    if window is None:
        if anchor is None:
            raise ValueError(f"no {WINDOW_SPAN} event in the trace")
        window = (anchor["start_ns"], anchor["start_ns"] + anchor["dur_ns"])
    w0, w1 = window
    per_chip: Dict[str, List[Tuple[int, int]]] = {}
    modules: Dict[str, List[Tuple[int, int]]] = {}
    kernel_ops: List[Tuple[str, str, int]] = []
    op_ns: Dict[str, int] = {}
    skip: Dict[str, List[Tuple[int, int]]] = {}
    for ev in evs:
        if _is_device(ev, n_chips, "XLA Modules"):
            span_ = (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])
            modules.setdefault(ev["plane"], []).append(span_)
            if any(ev["name"].startswith(x) for x in exclude):
                skip.setdefault(ev["plane"], []).append(span_)
    for iv in skip.values():
        iv.sort()
    for ev in evs:
        if not _is_device(ev, n_chips, "XLA Ops"):
            continue
        if _within(skip.get(ev["plane"], []), ev["start_ns"]):
            continue
        s = max(ev["start_ns"], w0)
        e = min(ev["start_ns"] + ev["dur_ns"], w1)
        if e <= s:
            continue
        per_chip.setdefault(ev["plane"], []).append((s, e))
        name = op_name(ev)
        op_ns[name] = op_ns.get(name, 0) + (e - s)
        k = kernel_of(ev, kernels)
        if k is not None:
            kernel_ops.append((k, ev["plane"], ev["start_ns"]))
    # a kernel's device time is that of the whole program it runs in (the
    # kernel, and the copies that stage its operands and results)
    kernel_ns: Dict[str, int] = {}
    kernel_calls: Dict[str, int] = {}
    seen = set()
    for plane in modules:
        modules[plane].sort()
    for k, plane, t in kernel_ops:
        mods = modules.get(plane, [])
        i = bisect_right(mods, (t, float("inf"))) - 1
        if i < 0 or mods[i][1] < t or (plane, i) in seen:
            continue
        seen.add((plane, i))
        s, e = max(mods[i][0], w0), min(mods[i][1], w1)
        kernel_ns[k] = kernel_ns.get(k, 0) + max(0, e - s)
        kernel_calls[k] = kernel_calls.get(k, 0) + 1
    busy = {p: union_ns(iv) for p, iv in per_chip.items()}
    busy_ns = [sum(e - s for s, e in iv) for iv in busy.values()]
    busy_s = (sum(busy_ns) / n_chips) / 1e9

    # idle gaps on chip 0, named by the innermost host span open at the
    # gap's middle: the profiler's host events and the program's spans
    spans = [dict(sp, start_ns=sp["start_ns"] + (anchor["start_ns"]
                                                 if anchor else 0))
             for sp in host_spans]
    spans += [{"name": ev["name"], "start_ns": ev["start_ns"],
               "dur_ns": ev["dur_ns"]}
              for ev in evs if ev["plane"].startswith("/host:")
              and ev["dur_ns"] > 0 and ev["name"] != WINDOW_SPAN]
    spans.sort(key=lambda sp: sp["start_ns"])
    chip0 = busy.get(sorted(busy)[0], []) if busy else []
    gaps: List[Tuple[int, int]] = []
    cur = w0
    for s, e in chip0:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    spans = [sp for sp in spans if sp["dur_ns"] >= MIN_NAMING_SPAN_NS]
    starts = [sp["start_ns"] for sp in spans]
    idle: Dict[str, int] = {}
    for s, e in gaps:
        if e - s < MIN_NAMED_GAP_NS:
            name = "between device ops"
        else:
            mid = (s + e) // 2
            name = _innermost(spans[:bisect_right(starts, mid)], mid)
        idle[name] = idle.get(name, 0) + (e - s)

    def _top(d: Dict[str, int]) -> List[List[Any]]:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
            "kernel_calls": kernel_calls,
            "breakdown": {"device_ops": _top(op_ns), "idle_gaps": _top(idle)}}


def _under(sp: Dict[str, Any], root: Optional[str]) -> bool:
    return root is None or sp.get("root") == root


def span_seconds(spans: Sequence[Dict[str, Any]], name: str,
                 root: Optional[str] = None) -> float:
    """Summed seconds of every span called ``name`` (under an outermost
    span called ``root``, if given), over all threads."""
    return sum(sp["dur_ns"] for sp in spans
               if sp["name"] == name and _under(sp, root)) / 1e9


def span_count(spans: Sequence[Dict[str, Any]], name: str,
               root: Optional[str] = None) -> int:
    return sum(1 for sp in spans if sp["name"] == name and _under(sp, root))


@contextlib.contextmanager
def op_span(name: str):
    """A benchmark operation, on the profiler's host timeline and as the
    outermost span of the program's spans beneath it."""
    import jax
    from repro.obs import span
    with jax.profiler.TraceAnnotation(name), span(name, cat="chipbench"):
        yield
