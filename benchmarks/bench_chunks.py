"""Chunk layer (DESIGN.md §12): dedup ratio, bounded-RSS streaming, ranged pull.

Three measurements, one per acceptance criterion of the chunk layer:

* **edit dedup** — commit a large tensor, apply a 0.1% localized edit,
  re-commit: the second version must re-store < 5% of the tensor's bytes
  (content-defined chunking keeps every untouched chunk's key);
* **streaming RSS** — commit + file-checkout a tensor larger than the
  configured chunk window through a procedural source (the tensor never
  exists in memory); the process RSS high-water delta must stay under
  2x the window budget. Measured in a fresh subprocess so this process's
  allocation history cannot mask the result;
* **ranged pull** — pull one tensor's chunks from a loopback hub emulating
  a WAN path (per-request RTT, per-connection bandwidth cap): a single
  sequential stream vs chunk-parallel ranged connections.

Run directly (CI chunk-smoke job asserts the same bounds):
``PYTHONPATH=src:. python -m benchmarks.bench_chunks``
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from repro.core import LayerGraph, LayerNode, ModelArtifact
from repro.store import ArtifactStore

EDIT_MB = 64                   # edit-dedup tensor size
STREAM_MB = 256                # streaming tensor size (logical)
WINDOW_MB = 32                 # chunk window budget for the RSS run
PULL_MB = 48                   # ranged-pull payload


def _artifact(w: np.ndarray) -> ModelArtifact:
    g = LayerGraph.chain([LayerNode("big", "linear",
                                    params={"w": (w.shape, "float32")})])
    return ModelArtifact(g, {"big/w": w})


def bench_edit_dedup() -> Dict:
    rows = EDIT_MB * 2 ** 20 // (1024 * 4)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((rows, 1024)).astype(np.float32)
    with tempfile.TemporaryDirectory() as tmp:
        store = ArtifactStore(root=tmp)
        t0 = time.perf_counter()
        r1 = store.commit_artifact("m", _artifact(w))
        commit_s = time.perf_counter() - t0
        base_bytes = store.cas.physical_bytes()

        w2 = w.copy()
        n = max(1, w.size // 1000)             # 0.1% localized edit
        w2.reshape(-1)[w.size // 3:w.size // 3 + n] += 0.5
        t0 = time.perf_counter()
        r2 = store.commit_artifact("m", _artifact(w2), parent_ref=r1)
        edit_commit_s = time.perf_counter() - t0
        added = store.cas.physical_bytes() - base_bytes

        t0 = time.perf_counter()
        got = store.materialize_param(r2, "big/w")
        checkout_s = time.perf_counter() - t0
        # delta children reconstruct within the quantization step (eps);
        # bit-identity holds for full commits (checked in streaming_rss)
        assert np.allclose(got, w2, atol=store.eps), "checkout out of eps"
        report = store.fsck([r1, r2])
        assert report["ok"] and not report["chunk_damage"], "fsck failed"
        e = store.get_manifest(r2)["params"]["big/w"]
        return {"step": "edit_dedup", "tensor_mb": EDIT_MB,
                "chunks": len(e["chunks"]),
                "reused": sum(1 for it in e["chunks"]
                              if "c" not in it or store.cas.refcounts.get(
                                  it.get("c", ""), 0) > 1),
                "added_bytes": int(added),
                "added_frac": round(added / w.nbytes, 5),
                "commit_s": round(commit_s, 3),
                "edit_commit_s": round(edit_commit_s, 3),
                "checkout_s": round(checkout_s, 3)}


# Runs in a fresh interpreter per mode: ru_maxrss is a process-lifetime
# high-water mark, so the parent's (or the other mode's) allocation history
# would hide the result. "chunked" streams a FnSource through the chunk
# window; "dense" materializes the same tensor in memory and commits it with
# chunking disabled — the pre-chunk-layer baseline.
_RSS_SCRIPT = r"""
import json, resource, sys, time
import numpy as np
from repro.core import LayerGraph, LayerNode, ModelArtifact
from repro.store import ArtifactStore
from repro.store.chunks import FnSource

mode, stream_mb, window_mb, tmp = (sys.argv[1], int(sys.argv[2]),
                                   int(sys.argv[3]), sys.argv[4])
pat = np.random.default_rng(7).bytes(1 << 20)

def read(off, size):
    parts, p = [], off
    while size > 0:
        i = p % len(pat)
        n = min(size, len(pat) - i)
        # mix the MiB index in so consecutive blocks differ (defeats
        # trivial whole-stream dedup while staying allocation-free)
        blk = bytearray(pat[i:i + n])
        blk[0] = (p >> 20) & 0xFF
        parts.append(bytes(blk))
        p += n
        size -= n
    return b"".join(parts)

rows = stream_mb * (1 << 20) // 4096
shape = (rows, 1024)
g = LayerGraph.chain([LayerNode("big", "linear",
                                params={"w": (shape, "float32")})])
if mode == "chunked":
    store = ArtifactStore(root=tmp, chunk_mode="fixed",
                          chunk_window_bytes=window_mb * (1 << 20))
    value = FnSource(read, shape, "float32")
else:
    store = ArtifactStore(root=tmp, chunk_threshold=0)  # chunking off
    value = np.frombuffer(read(0, rows * 4096),
                          dtype=np.float32).reshape(shape)
art = ModelArtifact(g, {"big/w": value})

base_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
t0 = time.perf_counter()
ref = store.commit_artifact("m", art)
commit_s = time.perf_counter() - t0
t0 = time.perf_counter()
digest = store.materialize_param_to_file(ref, "big/w", tmp + "/w.bin")
checkout_s = time.perf_counter() - t0
entry = store.get_manifest(ref)["params"]["big/w"]
assert digest == entry["hash"], "streamed checkout not bit-identical"
peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({"delta_mb": (peak_kb - base_kb) / 1024.0,
                  "commit_s": commit_s, "checkout_s": checkout_s,
                  "chunks": len(entry.get("chunks", []))}))
"""


def _rss_run(mode: str) -> Dict:
    with tempfile.TemporaryDirectory() as tmp:
        env = dict(os.environ)
        env["PYTHONPATH"] = "src:" + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"  # the child never takes the chip
        out = subprocess.run(
            [sys.executable, "-c", _RSS_SCRIPT, mode, str(STREAM_MB),
             str(WINDOW_MB), tmp],
            env=env, capture_output=True, text=True, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])


def bench_streaming_rss() -> Dict:
    chunked = _rss_run("chunked")
    dense = _rss_run("dense")
    return {"step": "streaming_rss", "tensor_mb": STREAM_MB,
            "window_mb": WINDOW_MB, "rss_budget_mb": 2 * WINDOW_MB,
            "chunked_rss_delta_mb": round(chunked["delta_mb"], 1),
            "dense_rss_delta_mb": round(dense["delta_mb"], 1),
            "chunks": chunked["chunks"],
            "commit_s": round(chunked["commit_s"], 3),
            "checkout_s": round(chunked["checkout_s"], 3),
            "commit_mb_per_s": round(
                STREAM_MB / max(chunked["commit_s"], 1e-9), 1),
            "within_budget": chunked["delta_mb"] < 2 * WINDOW_MB}


PULL_CHUNK_MB = 1              # chunk object size on the hub
PULL_RTT_MS = 5                # simulated per-request RTT
PULL_BPS = 100 * 2 ** 20       # simulated per-connection bandwidth cap
PULL_WORKERS = 8


def bench_ranged_pull() -> Dict:
    """Chunk-parallel ranged pull vs single-stream pull of one tensor.

    The hub emulates a WAN path (per-request RTT + per-connection
    bandwidth cap via ``HubServer.delay_s`` / ``throttle_bps``) because a
    raw loopback socket has neither property, and parallelism only pays
    where they exist. ``single`` is one mget stream over one connection
    (the strongest sequential baseline); ``parallel`` fans the tensor's
    chunks across ranged connections the way ``fetch_param_shard`` does.
    Unthrottled loopback numbers ride along for calibration.
    """
    from concurrent.futures import ThreadPoolExecutor
    from repro.hub import HubApp, start_in_thread
    from repro.remote.http import HttpTransport
    rng = np.random.default_rng(1)
    n_chunks = PULL_MB // PULL_CHUNK_MB
    with tempfile.TemporaryDirectory() as tmp:
        app = HubApp(os.path.join(tmp, "hub"))
        chunks = {app.store.cas.put_bytes(rng.bytes(PULL_CHUNK_MB * 2 ** 20)):
                  PULL_CHUNK_MB * 2 ** 20 for _ in range(n_chunks)}
        keys = list(chunks)
        server, _ = start_in_thread(app)
        try:
            t = HttpTransport(server.url)
            t.read_objects(keys[:1])  # warm connection path + page cache

            def single():
                return t.read_objects(keys)

            def parallel():
                with ThreadPoolExecutor(max_workers=PULL_WORKERS) as pool:
                    return dict(zip(keys, pool.map(
                        lambda k: t.read_object_range(k, 0, chunks[k]),
                        keys)))

            def best(fn, reps=3):
                times, out = [], None
                for _ in range(reps):
                    t0 = time.perf_counter()
                    out = fn()
                    times.append(time.perf_counter() - t0)
                return min(times), out

            raw_single, _ = best(single)
            raw_par, _ = best(parallel)
            server.delay_s = PULL_RTT_MS / 1000.0
            server.throttle_bps = PULL_BPS
            wan_single, a = best(single, reps=2)
            wan_par, b = best(parallel, reps=2)
            assert a == b and sorted(a) == sorted(keys), "pull mismatch"
        finally:
            server.shutdown()
            server.server_close()
    return {"step": "ranged_pull", "payload_mb": PULL_MB,
            "chunks": n_chunks, "workers": PULL_WORKERS,
            "rtt_ms": PULL_RTT_MS,
            "link_mb_per_s": PULL_BPS // 2 ** 20,
            "single_s": round(wan_single, 4),
            "parallel_s": round(wan_par, 4),
            "speedup": round(wan_single / max(wan_par, 1e-9), 2),
            "single_mb_per_s": round(PULL_MB / max(wan_single, 1e-9), 1),
            "parallel_mb_per_s": round(PULL_MB / max(wan_par, 1e-9), 1),
            "loopback_single_s": round(raw_single, 4),
            "loopback_parallel_s": round(raw_par, 4)}


def main() -> List[Dict]:
    rows = [bench_edit_dedup(), bench_streaming_rss(), bench_ranged_pull()]
    for r in rows:
        print(" ".join(f"{k}={v}" for k, v in r.items()))
    dedup, rss, pull = rows
    assert dedup["added_frac"] < 0.05, \
        f"0.1% edit re-stored {dedup['added_frac']:.1%} of the tensor"
    assert rss["within_budget"], \
        f"streaming RSS {rss['chunked_rss_delta_mb']} MB over 2x window"
    assert pull["speedup"] > 1.0, \
        f"parallel ranged pull slower than single-stream ({pull['speedup']}x)"
    return rows


if __name__ == "__main__":
    main()
