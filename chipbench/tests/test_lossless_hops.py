"""The bf16 fan-out cell at a tiny size: its output head reaches the chunk
threshold, so each window commit stores the head's changed chunks as
lossless bit-pattern hops against the base, and the line reports what
they cost and what they saved.

Run explicitly: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""

from helpers import args, rehearse


def test_fanout_reports_stored_bytes_and_hop_seconds(tmp_path):
    code, result, err = rehearse(tmp_path, args("tiny.lineage-fanout"))
    assert code == 0, err[-3000:]
    assert result["correct"], result["checks"]
    # the frozen embedding dedups, the head's chunks are small hops
    assert 0 < result["metrics"]["stored_bytes_ratio"]["value"] < 0.1

    code, result, err = rehearse(tmp_path,
                                 args("tiny.lineage-fanout", trace=1))
    assert code == 0, err[-3000:]
    m = result["metrics"]
    for name in ("commit.chunk_xdelta_busy_s", "commit.chunk_parent_busy_s",
                 "checkout.chunk_decode_busy_s"):
        assert m[name]["value"] > 0, name
    assert "commit.chunk_quantize_busy_s" not in m
