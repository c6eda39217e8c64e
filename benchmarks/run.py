"""Benchmark harness entry: one function per paper table/figure.

Prints a ``name,us_per_call,derived`` CSV summary line per benchmark plus
each benchmark's own table, and writes the machine-readable perf
trajectory CI and future PRs diff against: ``BENCH_PR4.json`` (commit
throughput, warm/cold checkout latency, dedup ratio) and
``BENCH_PR6.json`` (chunk-level dedup, streaming RSS, ranged pull) and
``BENCH_PR7.json`` (serving resident density, hot-swap latency) and
``BENCH_PR8.json`` (observability overhead: disabled-path commit cost) and
``BENCH_PR9.json`` (continuous checkpointing: overhead per cadence/tier,
bytes/step vs full snapshots) and
``BENCH_PR10.json`` (hub under load: live-traffic GC reclaim, replica
reads, saturation throughput and 503 shed rate).
Usage: PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import json
import sys
import time


def _csv(name: str, us: float, derived: str) -> None:
    print(f"CSV,{name},{us:.1f},{derived}")


def main() -> None:
    sys.path.insert(0, ".")
    from benchmarks import (bench_autoconstruct, bench_compression,
                            bench_functionality, bench_insertion,
                            bench_kernels)

    print("=" * 72)
    print("Table 4 — compression ratio / accuracy delta / runtime")
    print("=" * 72)
    t0 = time.perf_counter()
    rows = bench_compression.main()
    lzma_rows = [r for r in rows if r["technique"] == "MGit (LZMA + Hash)"]
    best = max(lzma_rows, key=lambda r: r["ratio"])
    _csv("table4_compression", (time.perf_counter() - t0) * 1e6 / max(len(rows), 1),
         f"best_ratio={best['ratio']:.2f}@{best['graph']}")
    pipe = next(r for r in rows if r["technique"] == "pipeline")
    _csv("pipeline", pipe["commit_s"] * 1e6 / pipe["n_nodes"],
         f"models_per_s={pipe['commit_models_per_s']:.1f}")
    with open("BENCH_PR4.json", "w") as f:
        json.dump({
            "pool": {"n_nodes": pipe["n_nodes"], "d": pipe["d"]},
            "commit": {"s": pipe["commit_s"],
                       "models_per_s": pipe["commit_models_per_s"]},
            "checkout": {"warm_s": pipe["warm_checkout_s"],
                         "cold_s": pipe["cold_checkout_s"]},
            "dedup_ratio": pipe["ratio"],
            "fold": {"depth5_chain_hops": pipe["fold_chain_hops"],
                     "dequants": 1},
        }, f, indent=1)
    print("wrote BENCH_PR4.json")

    print("=" * 72)
    print("Figure 3 — auto-insertion scaling")
    print("=" * 72)
    t0 = time.perf_counter()
    rows = bench_insertion.main()
    _csv("fig3_insertion", rows[-1]["avg_insert_s"] * 1e6,
         f"n={rows[-1]['n_models']}")

    print("=" * 72)
    print("§6.1 — automated graph construction accuracy")
    print("=" * 72)
    t0 = time.perf_counter()
    rows = bench_autoconstruct.main()
    g1 = [r for r in rows if r["graph"] == "G1"]
    _csv("g1_autoconstruct", (time.perf_counter() - t0) * 1e6,
         f"paper={g1[0]['accuracy']:.3f},improved={g1[-1]['accuracy']:.3f}")

    print("=" * 72)
    print("§6.4 — bisect + update cascade")
    print("=" * 72)
    rows = bench_functionality.main()
    _csv("bisect", rows[0]["bisect_s"] * 1e6,
         f"probe_speedup={rows[0]['probe_speedup']:.1f}x")
    _csv("cascade", rows[1]["cascade_s"] * 1e6,
         f"models={rows[1]['created']}")
    _csv("test_sweep", rows[2]["memo_warm_s"] * 1e6,
         f"warm_speedup={rows[2]['warm_speedup']:.1f}x,"
         f"hit_ratio={rows[2]['cache_hit_ratio']:.2f}")

    print("=" * 72)
    print("§4 diagnostics — memoized runner ledger (cache hits, 0-IO warm sweep)")
    print("=" * 72)
    from benchmarks import bench_diag
    row = bench_diag.main(smoke=True)
    _csv("diag_runner", row["warm_s"] * 1e6,
         f"hit_ratio={row['cache_hit_ratio']:.2f},"
         f"speedup={row['speedup']:.1f}x,"
         f"scoped_skips={row['scoped_skips']}")

    print("=" * 72)
    print("§5 collaboration — sync negotiation dedup (objects moved vs total)")
    print("=" * 72)
    from benchmarks import bench_sync
    rows = bench_sync.main()
    incr = next(r for r in rows if r["step"] == "incremental push")
    _csv("sync_dedup", incr["seconds"] * 1e6,
         f"dedup={incr['dedup_ratio']:.2%},"
         f"moved={incr['objects_transferred']}/{incr['objects_total']}")

    print("=" * 72)
    print("§11 hub — HTTP transport vs LocalTransport (bit-identity + wire cost)")
    print("=" * 72)
    from benchmarks import bench_hub
    rows = bench_hub.main()
    http_push = next(r for r in rows if r["transport"] == "http"
                     and r["step"] == "initial push")
    local_push = next(r for r in rows if r["transport"] == "local"
                      and r["step"] == "initial push")
    _csv("hub_http_push", http_push["seconds"] * 1e6,
         f"http_over_local={http_push['seconds']/max(local_push['seconds'], 1e-9):.2f}x,"
         f"bytes={http_push['bytes_transferred']}")

    print("=" * 72)
    print("§12 chunk layer — dedup ratio, streaming RSS, parallel ranged pull")
    print("=" * 72)
    from benchmarks import bench_chunks
    dedup, rss, pull = bench_chunks.main()
    _csv("chunk_dedup", dedup["edit_commit_s"] * 1e6,
         f"added_frac={dedup['added_frac']:.2%},"
         f"chunks={dedup['chunks']}")
    _csv("chunk_rss", rss["commit_s"] * 1e6,
         f"chunked_mb={rss['chunked_rss_delta_mb']},"
         f"dense_mb={rss['dense_rss_delta_mb']},"
         f"budget_mb={rss['rss_budget_mb']}")
    _csv("chunk_pull", pull["parallel_s"] * 1e6,
         f"speedup={pull['speedup']:.2f}x,"
         f"parallel_mb_per_s={pull['parallel_mb_per_s']}")
    with open("BENCH_PR6.json", "w") as f:
        json.dump({
            "edit_dedup": {
                "tensor_mb": dedup["tensor_mb"],
                "added_bytes": dedup["added_bytes"],
                "added_frac": dedup["added_frac"],
                "chunks": dedup["chunks"],
            },
            "streaming_rss": {
                "tensor_mb": rss["tensor_mb"],
                "window_mb": rss["window_mb"],
                "budget_mb": rss["rss_budget_mb"],
                "chunked_delta_mb": rss["chunked_rss_delta_mb"],
                "dense_delta_mb": rss["dense_rss_delta_mb"],
                "commit_mb_per_s": rss["commit_mb_per_s"],
            },
            "ranged_pull": {
                "payload_mb": pull["payload_mb"],
                "rtt_ms": pull["rtt_ms"],
                "link_mb_per_s": pull["link_mb_per_s"],
                "single_s": pull["single_s"],
                "parallel_s": pull["parallel_s"],
                "speedup": pull["speedup"],
            },
        }, f, indent=1)
    print("wrote BENCH_PR6.json")

    print("=" * 72)
    print("§13 lineage-native serving — resident density + hot swap")
    print("=" * 72)
    from benchmarks import bench_serve
    serve = bench_serve.main()
    _csv("serve_density", serve["build_s"] * 1e6 / serve["n_models"],
         f"density_x={serve['density_x']:.2f},"
         f"models_per_gb={serve['models_per_gb_pool']}")
    _csv("serve_swap", serve["swap_mean_s"] * 1e6,
         f"naive_load_us={serve['naive_load_s']*1e6:.1f},"
         f"inflight_errors={serve['inflight_errors']}")
    with open("BENCH_PR7.json", "w") as f:
        json.dump({
            "resident_density": {
                "n_models": serve["n_models"],
                "model_mb": serve["model_mb"],
                "resident_mb": serve["resident_mb"],
                "naive_mb": serve["naive_mb"],
                "density_x": serve["density_x"],
                "models_per_gb_pool": serve["models_per_gb_pool"],
                "models_per_gb_naive": serve["models_per_gb_naive"],
            },
            "hot_swap": {
                "swaps": serve["swaps"],
                "swap_mean_s": serve["swap_mean_s"],
                "swap_max_s": serve["swap_max_s"],
                "naive_load_s": serve["naive_load_s"],
                "inflight_errors": serve["inflight_errors"],
            },
        }, f, indent=1)
    print("wrote BENCH_PR7.json")

    print("=" * 72)
    print("§14 observability — disabled-path overhead on commit throughput")
    print("=" * 72)
    from benchmarks import bench_obs
    obs = bench_obs.main()
    _csv("obs_overhead", obs["commit_disabled_s"] * 1e6 / obs["n_models"],
         f"disabled_pct={obs['disabled_overhead_pct']:.2f},"
         f"bound_pct={obs['disabled_overhead_bound_pct']:.4f},"
         f"span_ns={obs['disabled_span_ns']:.0f}")
    with open("BENCH_PR8.json", "w") as f:
        json.dump({
            "commit_overhead": {
                "n_models": obs["n_models"],
                "stripped_s": obs["commit_stripped_s"],
                "disabled_s": obs["commit_disabled_s"],
                "enabled_s": obs["commit_enabled_s"],
                "disabled_overhead_pct": obs["disabled_overhead_pct"],
                "enabled_overhead_pct": obs["enabled_overhead_pct"],
                "models_per_s_disabled": obs["models_per_s_disabled"],
            },
            "disabled_path": {
                "span_call_ns": obs["disabled_span_ns"],
                "spans_per_commit": obs["spans_per_commit"],
                "overhead_bound_pct": obs["disabled_overhead_bound_pct"],
            },
        }, f, indent=1)
    print("wrote BENCH_PR8.json")

    print("=" * 72)
    print("Storage kernels — CPU wall-time + TPU roofline bound")
    print("=" * 72)
    rows = bench_kernels.main()
    _csv("kernels", rows[0]["cpu_s"] * 1e6,
         f"tpu_bound_us={rows[0]['tpu_roofline_s']*1e6:.1f}")

    print("=" * 72)
    print("§15 continuous checkpointing — commit at training speed")
    print("=" * 72)
    from benchmarks import bench_checkpoint
    ck = bench_checkpoint.main()
    e10 = next(r for r in ck["rows"] if r["config"] == "exact@10")
    l1 = next(r for r in ck["rows"] if r["config"] == "lossy@1")
    _csv("ckpt_overhead", ck["base_step_s"] * 1e6,
         f"exact10_pct={e10['overhead_pct']:.2f},"
         f"lossy1_pct={l1['overhead_pct']:.2f},"
         f"exact10_bytes_ratio={e10['bytes_vs_full_snapshot']:.3f}")
    with open("BENCH_PR9.json", "w") as f:
        json.dump(ck, f, indent=1)
    print("wrote BENCH_PR9.json")

    print("=" * 72)
    print("§16 hub under production load — GC live, replicas, saturation")
    print("=" * 72)
    from benchmarks import bench_hub_load
    hub = bench_hub_load.run(smoke=True)
    _csv("hub_load", hub["push_p50_s"] * 1e6,
         f"ok={hub['ok']},"
         f"reclaimed={hub['gc']['bytes_reclaimed']}"
         f"/floor={hub['gc']['reclaim_floor_bytes']},"
         f"sat_ok_per_s={hub['saturation']['ok_per_s']},"
         f"shed_503={hub['overload']['shed_503']}")
    with open("BENCH_PR10.json", "w") as f:
        json.dump(hub, f, indent=1)
    print("wrote BENCH_PR10.json")

    print("=" * 72)
    print("Roofline (from dry-run artifact, single-pod) — see EXPERIMENTS.md")
    print("=" * 72)
    try:
        from benchmarks import bench_roofline
        table = bench_roofline.main()
        ok = [r for r in table if r["status"] == "ok"]
        if ok:
            avg = sum(r["roofline_frac"] for r in ok) / len(ok)
            _csv("roofline", 0.0, f"cells={len(ok)},avg_compute_frac={avg:.3f}")
    except FileNotFoundError:
        print("experiments/dryrun.json missing — run "
              "`PYTHONPATH=src python -m repro.launch.dryrun` first")


if __name__ == "__main__":
    main()
