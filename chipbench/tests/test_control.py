"""The controls must fail the cells' limits, at a size a test run holds.

The same functions read the controls on the chip at each cell's own size
(``chipbench/control.py``); PERF.md has those readings.
"""


from helpers import BENCH_DIR, tiny_bench

from chipbench import control
from chipbench.harness import Cell


def _cell(tmp_path, name):
    return Cell(name, tiny_bench(str(tmp_path / "BENCHMARK.json")),
                BENCH_DIR)


def test_lineage_control_differs_from_the_reference(tmp_path):
    for name in ("tiny.lineage-g2", "tiny.lineage-fanout"):
        got = control.lineage_control(_cell(tmp_path, name), 2 ** 31 + 3)
        assert got["compared"] > 0
        assert got["control_mismatched_elements"] > 0     # limit: 0


def test_train_control_and_half_batch_fail_the_limits(tmp_path):
    cell = _cell(tmp_path, "tiny.train-ckpt")
    got = control.train_control(cell, 2 ** 31 + 3)
    lim = cell.mix["limits"]
    for fault in ("control", "half_batch"):
        assert any(got[f"{fault}.{k}"] > lim[k]
                   for k in ("loss_gap", "grad_gap", "change_gap")), got
