"""The trace reduction, on a trace recorded on a TPU v5e and on events made
by hand.

``data/trace_fixture.json`` holds the device events and the benchmark's
spans of a short recorded window: two ``snapshot_fused`` calls under a
``chipbench.commit`` span, a ``chain_apply`` and a ``dequant_apply`` under a
``chipbench.checkout`` span, then a ``fingerprint``, each on one 768 x 768
float32 leaf, with the bytes ``costs.record_calls`` counted for them.
"""

import json
import os

import pytest

from helpers import BENCH_DIR

from chipbench import costs, trace


def _ev(line, name, start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "hlo_op": ""}


def test_union_merges_overlaps():
    assert trace.union_ns([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]


def test_reduce_by_hand():
    us = 1000                       # times in microseconds
    evs = [_ev("python3", trace.WINDOW_SPAN, 0, 1000 * us, plane="/host:CPU"),
           _ev("XLA Modules", "jit__chain_apply_dev(1)", 100 * us, 300 * us),
           _ev("XLA Ops", "%copy.1 = f32[8] copy(%p)", 100 * us, 50 * us),
           _ev("XLA Ops", "%chain_apply.1 = f32[8] custom-call(%x), "
               "custom_call_target=\"tpu_custom_call\"", 150 * us, 200 * us),
           # names the kernel's output but is not the kernel
           _ev("XLA Ops", "%slice.2 = f32[4] slice(f32[8] %chain_apply.1)",
               350 * us, 50 * us),
           _ev("XLA Ops", "%fusion.3 = f32[8] fusion(%a)", 380 * us, 40 * us),
           _ev("XLA Ops", "%other = f32[8] add(%a, %b)", 900 * us, 50 * us),
           _ev("XLA Ops", "%other = f32[8] add(%a, %b)", 960 * us, 100 * us)]
    spans = [{"name": "commit.encode", "start_ns": 500 * us,
              "dur_ns": 300 * us}]
    red = trace.reduce(evs, n_chips=1, kernels=costs.KERNELS,
                       host_spans=spans)
    # busy: [100, 420), [900, 950) and [960, 1000): the window cuts the last
    assert red["busy_s"] == pytest.approx(410e-6)
    assert red["window_s"] == pytest.approx(1000e-6)
    assert red["kernel_s"] == {"chain_apply": pytest.approx(300e-6)}
    assert red["kernel_calls"] == {"chain_apply": 1}
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["no span"] == pytest.approx(100e-6)          # [0, 100)
    assert gaps["commit.encode"] == pytest.approx(480e-6)    # [420, 900)
    assert gaps["between device ops"] == pytest.approx(10e-6)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["chain_apply.1"] == pytest.approx(200e-6)
    assert ops["other"] == pytest.approx(90e-6)


def test_generator_programs_are_left_out():
    us = 1000
    evs = [_ev("python3", trace.WINDOW_SPAN, 0, 1000 * us, plane="/host:CPU"),
           _ev("XLA Modules", "jit__finetune(7)", 100 * us, 300 * us),
           _ev("XLA Ops", "%fusion.15 = f32[8] fusion(%a)", 100 * us, 300 * us),
           _ev("XLA Modules", "jit__chain_apply_dev(1)", 500 * us, 100 * us),
           _ev("XLA Ops", "%chain_apply.1 = f32[8] custom-call(%x)",
               500 * us, 100 * us)]
    red = trace.reduce(evs, n_chips=1, kernels=costs.KERNELS,
                       exclude=("jit__finetune",))
    assert red["busy_s"] == pytest.approx(100e-6)
    assert "fusion.15" not in dict(red["breakdown"]["device_ops"])


def test_recorded_fixture():
    with open(os.path.join(BENCH_DIR, "tests", "data",
                           "trace_fixture.json")) as f:
        fx = json.load(f)
    red = trace.reduce(fx["events"], n_chips=1, kernels=costs.KERNELS,
                       host_spans=fx["spans"])
    assert red["kernel_calls"] == {"snapshot_fused": 2, "chain_apply": 1,
                                   "dequant_apply": 1, "fingerprint": 1}
    # each call is timed by the device program it runs in
    assert red["kernel_s"]["chain_apply"] == pytest.approx(23303e-9)
    assert red["kernel_s"]["fingerprint"] == pytest.approx(12638e-9)
    assert 0 < red["busy_s"] < red["window_s"] == pytest.approx(0.02242895)
    n = 768 * 768
    rec = {"device": red, "kernel_bytes": {
               "snapshot_fused": 2 * costs.kernel_bytes("snapshot_fused", n),
               "chain_apply": costs.kernel_bytes("chain_apply", n, hops=3)},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    from chipbench.metrics_common import roofline
    for k in ("snapshot_fused", "chain_apply"):
        share = roofline(rec, k)
        assert 0 < share <= 100, (k, share)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert "chipbench.commit" in gaps and "chipbench.checkout" in gaps
