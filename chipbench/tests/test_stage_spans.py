"""Traced CPU rehearsals read the per-layer metrics of the program's stage
spans: the chunk layer's stages and device->host copies in lineage commits
and checkouts, the snapshot's transfers and the final drain in training.

Run explicitly: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""

import os

from helpers import BENCH_DIR, ROOT, args, rehearse


LINEAGE_STAGES = ("commit.d2h_busy_s", "commit.chunk_parent_busy_s",
                  "commit.chunk_hash_busy_s", "commit.chunk_quantize_busy_s",
                  "commit.chunk_encode_busy_s", "commit.chunk_write_busy_s",
                  "checkout.chunk_read_busy_s",
                  "checkout.chunk_decode_busy_s")


def test_traced_lineage_reports_every_stage(tmp_path):
    code, result, err = rehearse(tmp_path, args("tiny.lineage-g2", trace=1))
    assert code == 0, err[-3000:]
    m = result["metrics"]
    for name in LINEAGE_STAGES:
        assert m[name]["value"] > 0, name
    # the stages run inside the spans the older metrics read
    assert (m["commit.chunk_hash_busy_s"]["value"]
            < m["commit.chunk_stream_busy_s"]["value"])
    assert (m["checkout.chunk_read_busy_s"]["value"]
            < m["checkout.param_busy_s"]["value"])


def test_traced_training_reports_transfer_and_drain(tmp_path):
    code, result, err = rehearse(tmp_path, args("tiny.train-ckpt", trace=1))
    assert code == 0, err[-3000:]
    m = result["metrics"]
    assert m["ckpt.drain_s"]["value"] > 0
    assert 0 < m["ckpt.transfer_s"]["value"] <= m["ckpt.snapshot_s"]["value"]


def test_a_program_without_the_spans_reports_nothing():
    """The parent program opens none of these spans: its line leaves the
    metrics out rather than reading 0."""
    from chipbench.harness import Cell
    cell = Cell("bert-base.lineage-g2", os.path.join(ROOT, "BENCHMARK.json"),
                BENCH_DIR)
    rec = {"spans": [{"name": "commit.chunk_stream", "dur_ns": 5,
                      "root": "chipbench.commit"}],
           "record": {"commits": 1, "checkouts": 1}}
    for name in LINEAGE_STAGES + ("ckpt.transfer_s", "ckpt.drain_s"):
        assert cell.reader(name).read(rec) is None, name
