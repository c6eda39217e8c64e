"""Paper Table 4: compression ratio / accuracy delta / per-model runtime for
every (graph x technique) combination.

Techniques:
  MGit (LZMA + Hash)      delta compression with LZMA + content hashing
  MGit (RLE + Hash)       delta compression with RLE + content hashing
  MGit (Hash)             content-based hashing only (lossless)
  Full                    quantize + LZMA of FULL models (no deltas)
  Full w/o quantization   LZMA of raw full models
  MGit (sparse + Hash)    beyond-paper sparse codec
"""

from __future__ import annotations

import lzma
import time
from typing import Dict, List

import numpy as np

from benchmarks.pools import GRAPHS
from repro.core import LineageGraph
from repro.core.lineage import RegisteredTest
from repro.kernels import ops
from repro.kernels.ref import quant_scale
from repro.store import ArtifactStore

from repro.core.artifact import ModelArtifact


def probe_score(model: ModelArtifact) -> float:
    """Deterministic accuracy stand-in: probe activations through the chain."""
    first = next(iter(model.params))
    d = model.params[first].shape[0]
    x = np.linspace(-1, 1, 2 * d, dtype=np.float32).reshape(2, d)
    for name in model.graph.topo_order():
        w = model.params.get(f"{name}/w")
        if w is None or w.shape[0] != x.shape[1]:
            continue
        x = np.tanh(x @ w)
    return float(np.mean(np.abs(x)) * 100)


def _full_codec_baseline(pool, quantize: bool, eps: float = 1e-4):
    """'Full' rows: LZMA over (optionally quantized) full models."""
    raw = comp = 0
    acc_deltas = []
    t0 = time.perf_counter()
    for _, m in pool:
        before = probe_score(m)
        rec_params = {}
        for k, v in m.params.items():
            raw += v.nbytes
            if quantize:
                q = np.floor(v / quant_scale(eps) + 0.5).astype(np.int32)
                comp += len(lzma.compress(q.tobytes(), preset=1))
                rec_params[k] = (q * quant_scale(eps)).astype(v.dtype)
            else:
                comp += len(lzma.compress(np.ascontiguousarray(v).tobytes(),
                                          preset=1))
                rec_params[k] = v
        after = probe_score(m.replace_params(rec_params))
        acc_deltas.append(abs(after - before))
    dt = time.perf_counter() - t0
    return {"ratio": raw / comp, "acc_max": max(acc_deltas),
            "acc_avg": float(np.mean(acc_deltas)),
            "s_per_model": dt / len(pool)}


def _mgit_run(pool, gold, codec: str, delta: bool, tmp=None):
    store = ArtifactStore(root=tmp, codec=codec, t_thr=float("inf"),
                          delta_enabled=delta)
    g = LineageGraph(store=store)
    g.tests.append(RegisteredTest(name="probe", fn=probe_score,
                                  model_type="toy"))
    acc_deltas = []
    t0 = time.perf_counter()
    for name, m in pool:
        parent = gold.get(name)
        if parent is not None and parent in g.nodes:
            g.add_edge(parent, name)
        g.add_node(m, name)
        before = probe_score(m)
        after = probe_score(g.get_model(name))
        acc_deltas.append(abs(after - before))
    dt = time.perf_counter() - t0
    return {"ratio": store.compression_ratio(), "acc_max": max(acc_deltas),
            "acc_avg": float(np.mean(acc_deltas)),
            "s_per_model": dt / len(pool)}


def bench_chain_reconstruction(depth: int = 8, d: int = 256,
                               repeats: int = 20) -> Dict[str, float]:
    """Plan-based lazy engine vs the eager recursive loader on a deep chain.

    Builds a ``depth``-long delta chain, then repeatedly reconstructs the
    chain tip both ways:
      * ``eager``: ``load_artifact_recursive`` — materializes every FULL
        ancestor artifact per load (the pre-plan reference path);
      * ``lazy``: per-parameter plan execution through the byte-budget tensor
        cache (``load_artifact`` + param access).
    Also reports single-parameter access cost: bytes materialized to produce
    ONE tensor from the chain tip, cold, vs the full-model bytes the eager
    path forces.
    """
    import tempfile

    from benchmarks.pools import base_model, finetune

    with tempfile.TemporaryDirectory() as tmp:
        store = ArtifactStore(root=tmp, codec="lzma", t_thr=float("inf"),
                              max_chain_depth=depth)
        model = base_model(seed=0, d=d)
        refs = [store.commit_artifact("v0", model)]
        for v in range(1, depth + 1):
            model = finetune(model, seed=v)
            refs.append(store.commit_artifact(f"v{v}", model,
                                              parent_ref=refs[-1]))
        tip = refs[-1]
        model_bytes = store.load_artifact(tip).nbytes()

        # cold single-param access through the plan engine
        store.cache.clear()
        store.reset_io_stats()
        art = store.load_artifact(tip)
        key = next(iter(art.params))
        art.params[key]
        single_param_bytes = store.io_stats["bytes_materialized"]

        t0 = time.perf_counter()
        for _ in range(repeats):
            eager = store.load_artifact_recursive(tip)
            for k in eager.params:
                np.asarray(eager.params[k])
        t_eager = time.perf_counter() - t0

        store.cache.clear()
        t0 = time.perf_counter()
        for _ in range(repeats):
            lazy = store.load_artifact(tip)
            for k in lazy.params:
                np.asarray(lazy.params[k])
        t_lazy = time.perf_counter() - t0

    return {
        "depth": depth,
        "repeats": repeats,
        "eager_s": t_eager,
        "lazy_s": t_lazy,
        "speedup": t_eager / max(t_lazy, 1e-9),
        "model_bytes": model_bytes,
        # peak-materialization comparison for ONE parameter at the chain tip:
        # the plan engine touches O(tensor x depth); the recursive loader
        # forces O(model x depth)
        "single_param_bytes": single_param_bytes,
        "eager_chain_bytes": model_bytes * (depth + 1),
    }


def _chain_pool(n: int = 20, d: int = 256):
    """n-node finetune chain (the PR-4 throughput pool)."""
    from benchmarks.pools import base_model, finetune
    m = base_model(seed=0, d=d)
    pool = [("v0", m)]
    for i in range(1, n):
        m = finetune(m, seed=i)
        pool.append((f"v{i}", m))
    return pool


def bench_pipeline(n_nodes: int = 20, d: int = 256, reps: int = 3,
                   smoke: bool = False) -> Dict[str, float]:
    """The commit and batched checkout engines (DESIGN.md §10).

    Commits an ``n_nodes`` finetune chain and re-materializes a deep-chain
    tip, reporting best-of-``reps`` wall times (min is robust to scheduler
    noise on shared CI boxes). Asserts the §10 invariants while it's at it:

    * batched ``materialize_artifact`` is bit-identical to per-param
      ``materialize_param`` on store-loaded values;
    * a depth-5 same-eps chain folds into ONE dequant (``io_stats``);
    * ``fsck`` is clean after the commits + gc.
    """
    import tempfile

    from repro.store import ArtifactStore

    if smoke:
        n_nodes, d, reps = min(n_nodes, 8), min(d, 128), 2
    pool = _chain_pool(n_nodes, d)
    depth_cap = 8
    tip_index = depth_cap  # deepest chain node in the pool
    out: Dict[str, float] = {"n_nodes": n_nodes, "d": d}

    def one_run():
        with tempfile.TemporaryDirectory() as tmp:
            store = ArtifactStore(root=tmp, t_thr=float("inf"),
                                  max_chain_depth=depth_cap)
            t0 = time.perf_counter()
            refs = [store.commit_artifact("v0", pool[0][1])]
            for name, m in pool[1:]:
                refs.append(store.commit_artifact(name, m,
                                                  parent_ref=refs[-1]))
            commit_s = time.perf_counter() - t0
            tip = refs[min(tip_index, len(refs) - 1)]

            # warm checkout: OS cache + manifests hot, tensor caches cold
            t0 = time.perf_counter()
            for _ in range(3):
                store.cache.clear()
                store.fold_cache.clear()
                store.materialize_artifact(tip)
            warm_s = (time.perf_counter() - t0) / 3
            ratio = store.compression_ratio()

            # cold checkout: a fresh store process (no manifest cache, no
            # tensor/fold caches; OS page cache stays warm)
            store2 = ArtifactStore(root=tmp, t_thr=float("inf"),
                                   max_chain_depth=depth_cap)
            t0 = time.perf_counter()
            store2.materialize_artifact(tip)
            cold_s = time.perf_counter() - t0

            # invariant: batch == per-param, both store-loaded
            batch = store.materialize_artifact(tip)
            store.cache.clear()
            store.fold_cache.clear()
            for k in batch.params:
                pp = store.materialize_param(tip, k)
                assert np.array_equal(np.asarray(batch.params[k]), pp), k
            # invariant: same-eps chain folds to ONE dequant per param
            store.cache.clear()
            store.fold_cache.clear()
            store.reset_io_stats()
            depth5 = refs[min(5, len(refs) - 1)]
            store.materialize_param(depth5, next(iter(batch.params)))
            io = store.io_stats
            assert io["dequant_calls"] == 1, io
            fold_chain_hops = io["chain_hops"]
            # invariant: fsck clean after the commits + gc
            store.gc()
            rep = store.fsck(roots=refs)
            assert rep["ok"], {k: rep[k] for k in
                               ("corrupt", "missing_objects",
                                "refcount_drift")}
            return commit_s, warm_s, cold_s, ratio, fold_chain_hops

    runs = [one_run() for _ in range(reps)]
    out["commit_s"] = min(r[0] for r in runs)
    out["warm_checkout_s"] = min(r[1] for r in runs)
    out["cold_checkout_s"] = min(r[2] for r in runs)
    out["ratio"] = runs[0][3]
    out["fold_chain_hops"] = runs[0][4]
    out["commit_models_per_s"] = n_nodes / out["commit_s"]
    return out


def bench_lzma_presets(d: int = 256) -> List[Dict]:
    """Satellite: ratio/speed tradeoff of the configurable LZMA preset."""
    import lzma

    from benchmarks.pools import base_model, finetune
    from repro.store.delta import host_snapshot

    parent = base_model(seed=0, d=d)
    child = finetune(parent, seed=1)
    rows = []
    for preset in (0, 1, 6):
        enc = dec = raw = comp = 0.0
        for k in parent.params:
            q, _, _ = host_snapshot(np.asarray(parent.params[k]),
                                    np.asarray(child.params[k]), 1e-4)
            data = np.ascontiguousarray(q).tobytes()
            t0 = time.perf_counter()
            blob = lzma.compress(data, preset=preset)
            enc += time.perf_counter() - t0
            t0 = time.perf_counter()
            lzma.decompress(blob)
            dec += time.perf_counter() - t0
            raw += len(data)
            comp += len(blob)
        rows.append({"preset": preset, "ratio": raw / comp,
                     "encode_s": enc, "decode_s": dec})
    return rows


def run(graphs: List[str] = ("G1", "G2", "G3", "G4", "G5")) -> List[Dict]:
    rows = []
    for gname in graphs:
        pool, gold, gtype = GRAPHS[gname]()
        techniques = {
            "MGit (LZMA + Hash)": lambda: _mgit_run(pool, gold, "lzma", True),
            "MGit (RLE + Hash)": lambda: _mgit_run(pool, gold, "rle", True),
            "MGit (sparse + Hash)": lambda: _mgit_run(pool, gold, "sparse", True),
            "MGit (Hash)": lambda: _mgit_run(pool, gold, "raw", False),
            "Full": lambda: _full_codec_baseline(pool, quantize=True),
            "Full w/o quantization": lambda: _full_codec_baseline(pool, quantize=False),
        }
        if gname == "G5":  # paper reports Hash only for G5
            techniques = {"MGit (Hash)": techniques["MGit (Hash)"],
                          "MGit (LZMA + Hash)": techniques["MGit (LZMA + Hash)"]}
        for tech, fn in techniques.items():
            r = fn()
            rows.append({"graph": gname, "type": gtype, "technique": tech, **r})
    return rows


def main():
    rows = run()
    print(f"{'graph':5} {'technique':24} {'ratio':>7} {'accD_max':>9} "
          f"{'accD_avg':>9} {'s/model':>8}")
    for r in rows:
        print(f"{r['graph']:5} {r['technique']:24} {r['ratio']:7.2f} "
              f"{r['acc_max']:9.4f} {r['acc_avg']:9.4f} {r['s_per_model']:8.2f}")
    chain = bench_chain_reconstruction()
    print(f"\nchain reconstruction (depth={chain['depth']}, "
          f"x{chain['repeats']} repeats):")
    print(f"  eager recursive: {chain['eager_s']:.3f}s   "
          f"lazy plan engine: {chain['lazy_s']:.3f}s   "
          f"speedup: {chain['speedup']:.1f}x")
    print(f"  single-param cold access: {chain['single_param_bytes']:,} bytes "
          f"materialized (tensor x chain) vs {chain['eager_chain_bytes']:,} "
          f"(model x chain) on the eager path")
    pipe = bench_pipeline()
    print(f"\ncommit & batched checkout "
          f"({pipe['n_nodes']}-node pool, d={pipe['d']}):")
    print(f"  commit:   {pipe['commit_s']:.2f}s "
          f"({pipe['commit_models_per_s']:.1f} models/s)")
    print(f"  checkout: warm {pipe['warm_checkout_s']*1000:.1f}ms, cold "
          f"{pipe['cold_checkout_s']*1000:.1f}ms (depth-8 tip)")
    print(f"  ratio: {pipe['ratio']:.1f} (preset 0); depth-5 chain "
          f"folded {pipe['fold_chain_hops']} hops into 1 dequant")
    presets = bench_lzma_presets()
    print("  lzma presets: " + "  ".join(
        f"p{p['preset']}: ratio {p['ratio']:.1f} enc {p['encode_s']*1000:.0f}ms "
        f"dec {p['decode_s']*1000:.0f}ms" for p in presets))
    return rows + [{"technique": "chain_reconstruction", **chain},
                   {"technique": "pipeline", **pipe}]


def perf_smoke() -> None:
    """CI gate: the commit and checkout engines' invariants on a small
    pool (batch == per-param bits, one dequant per folded chain, fsck
    clean). Times are printed, not asserted: shared CI boxes are noisy."""
    pipe = bench_pipeline(smoke=True)
    print(f"perf-smoke: commit {pipe['commit_s']:.2f}s "
          f"warm-checkout {pipe['warm_checkout_s']*1000:.1f}ms "
          f"cold-checkout {pipe['cold_checkout_s']*1000:.1f}ms "
          f"(fold: {pipe['fold_chain_hops']} hops -> 1 dequant)")
    print("perf-smoke OK: batch == per-param, fold + fsck invariants hold")


if __name__ == "__main__":
    import sys
    if "--perf-smoke" in sys.argv:
        perf_smoke()
    else:
        main()
