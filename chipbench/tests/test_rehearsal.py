"""Each mix end to end on the CPU at a tiny size (kernels in interpret
mode), and the harness finding a new configuration, mix and metric by name.

Run explicitly: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests``.
"""

import json
import shutil

import pytest

from helpers import BENCH_DIR, args, rehearse, tiny_bench


@pytest.mark.parametrize("cell", ["tiny.lineage-g2", "tiny.lineage-fanout",
                                  "tiny.train-ckpt"])
def test_mix_end_to_end(tmp_path, cell):
    code, result, err = rehearse(tmp_path, args(cell))
    assert code == 0, err[-3000:]
    checks = result["checks"]
    if cell == "tiny.train-ckpt":
        # the limits of the gaps are set for the chip's numerics; on the
        # CPU the program and the reference agree far closer than that
        assert checks["restored_leaves_differing"]["value"] == 0
        assert checks["restored_step_off"]["value"] == 0
        for gap in ("loss_gap", "grad_gap", "change_gap"):
            assert checks[gap]["value"] < 1e-5, checks
    else:
        assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert list(result)[-1] == "checks"
    assert "compiles_in_window\": 0" in err or "0 compiles inside" in err


def test_traced_lineage_reports_per_layer_metrics(tmp_path):
    code, result, err = rehearse(tmp_path, args("tiny.lineage-g2", trace=1))
    assert code == 0, err[-3000:]
    m = result["metrics"]
    assert m["commit.chunk_stream_busy_s"]["value"] > 0
    assert m["checkout.param_busy_s"]["value"] > 0
    assert "setup_s" not in m          # a traced run carries per-layer ones
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_means_no_result(tmp_path, capsys):
    from chipbench import run
    code = run.main(args("bert-base.lineage-g2"))
    out = capsys.readouterr().out
    assert code == 2 and '"correct"' not in out


def test_new_config_mix_and_metric_are_files_and_entries_only(tmp_path):
    """A later change adds a cell by adding files and entries: copy the
    benchmark directory, add a configuration, a mix and a per-layer metric
    as new files, name them in BENCHMARK.json, and run the new cell."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(bench_dir / "tests" / "data" / "tiny-f32.json") as f:
        config = json.load(f)
    config["model"]["n_layers"] = 1
    with open(bench_dir / "configs" / "tiny-one.json", "w") as f:
        json.dump(config, f)
    with open(bench_dir / "mixes" / "lineage-g2.json") as f:
        mix = json.load(f)
    mix.update(chain_depth=2, commit_parents=[0, 1], checkout_depths=[1, 2])
    with open(bench_dir / "mixes" / "lineage-short.json", "w") as f:
        json.dump(mix, f)
    with open(bench_dir / "metrics" / "commit.count.py", "w") as f:
        f.write("def read(rec):\n    return rec['record']['commits']\n")
    bench = tiny_bench(str(tmp_path / "BENCHMARK.json"), extra={
        "configs": [{"name": "tiny-one", "source": "test-only",
                     "file": "chipbench/configs/tiny-one.json",
                     "reduced": [], "why": "t"}],
        "workloads": [{"name": "tiny-one.lineage-short",
                       "config": "tiny-one", "traffic": "lineage-short",
                       "chips": 1, "why": "t"}],
        "per_layer": [{"name": "commit.count", "unit": "1",
                       "better": "higher", "source": "host_clock",
                       "layer": "t", "moves": "commit_s",
                       "workloads": ["tiny-one.lineage-short"]}]})
    with open(bench) as f:
        doc = json.load(f)
    for m in doc["end_to_end"]:
        if "workloads" in m and "tiny.lineage-g2" in m["workloads"]:
            m["workloads"].append("tiny-one.lineage-short")
    with open(bench, "w") as f:
        json.dump(doc, f)
    code, result, err = rehearse(
        tmp_path, args("tiny-one.lineage-short", trace=1), bench_path=bench,
        bench_dir=str(bench_dir))
    assert code == 0, err[-3000:]
    assert result["correct"]
    assert result["metrics"]["commit.count"]["value"] >= 1
