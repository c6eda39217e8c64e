"""Paper Figure 3: average per-model auto-insertion time vs lineage-graph size.

Larger graphs are built by replicating the G2 pool (the paper's method).
``run_store_backed`` additionally commits every inserted model through the
packfile-backed ArtifactStore and times the accounting queries, checking that
``object_count``/``physical_bytes`` stay O(1) as the store grows."""

from __future__ import annotations

import tempfile
import time
from typing import Dict, List

from benchmarks.pools import g2_adaptation
from repro.core import LineageGraph
from repro.core.auto import auto_insert
from repro.store import ArtifactStore


def run(scales=(1, 2, 4)) -> List[Dict]:
    rows = []
    for scale in scales:
        pool, _, _ = g2_adaptation(scale=scale)
        g = LineageGraph()
        t_per_model = []
        for name, artifact in pool:
            t0 = time.perf_counter()
            auto_insert(g, artifact, name)
            t_per_model.append(time.perf_counter() - t0)
        rows.append({"n_models": len(pool),
                     "avg_insert_s": sum(t_per_model) / len(t_per_model),
                     "max_insert_s": max(t_per_model)})
    return rows


def run_store_backed(scales=(1, 2)) -> List[Dict]:
    """Insertion + storage commit through the lazy/packfile engine."""
    rows = []
    for scale in scales:
        pool, _, _ = g2_adaptation(scale=scale)
        with tempfile.TemporaryDirectory() as tmp:
            store = ArtifactStore(root=tmp, t_thr=float("inf"))
            g = LineageGraph(path=tmp, store=store)
            t_per_model = []
            for name, artifact in pool:
                t0 = time.perf_counter()
                auto_insert(g, artifact, name)
                t_per_model.append(time.perf_counter() - t0)
            # accounting queries must be O(1), not directory scans
            t0 = time.perf_counter()
            for _ in range(1000):
                store.cas.object_count()
                store.cas.physical_bytes()
            t_account = (time.perf_counter() - t0) / 2000
            rows.append({"n_models": len(pool),
                         "avg_insert_s": sum(t_per_model) / len(t_per_model),
                         "max_insert_s": max(t_per_model),
                         "objects": store.cas.object_count(),
                         "ratio": store.compression_ratio(),
                         "accounting_us": t_account * 1e6})
    return rows


def main():
    rows = run()
    print(f"{'n_models':>9} {'avg_insert_s':>13} {'max_insert_s':>13}")
    for r in rows:
        print(f"{r['n_models']:9d} {r['avg_insert_s']:13.3f} {r['max_insert_s']:13.3f}")
    srows = run_store_backed()
    print(f"\n{'n_models':>9} {'avg_insert_s':>13} {'objects':>8} "
          f"{'ratio':>7} {'account_us':>11}")
    for r in srows:
        print(f"{r['n_models']:9d} {r['avg_insert_s']:13.3f} {r['objects']:8d} "
              f"{r['ratio']:7.2f} {r['accounting_us']:11.2f}")
    return rows + srows


if __name__ == "__main__":
    main()
