"""Step-delta commit engine + continuous-checkpointing manager (DESIGN.md §15).

Covers the four layers of the engine: lossless xdelta storage (bit-identical
resume), the lossy int8 tier with exact keyframes and nearest-exact restore,
the fingerprint skip path, async double-buffering (coalesce, error
propagation, crash atomicity), and elastic restore over chunked manifests.
"""

import json
import os

import jax
import numpy as np
import pytest

from repro.store import ArtifactStore
from repro.store.checkpoint import CKPT_STATS, CheckpointManager
from repro.store.codecs import (bitpattern_apply, bitpattern_delta,
                                get_codec)
from repro.store.manifest_walk import parse_manifest


def _state(seed=0, n=64, step=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.standard_normal((n, n)).astype(np.float32)},
        "opt": {
            "mu": {"w": rng.standard_normal((n, n)).astype(np.float32) * 1e-3},
            "nu": {"w": (rng.random((n, n)).astype(np.float32) * 1e-2)},
            "count": np.asarray(step, np.int32),
        },
        "step": np.asarray(step, np.int32),
    }


def _perturb(state, scale=1e-4, seed=1):
    rng = np.random.default_rng(seed)

    def bump(x):
        if x.dtype == np.float32:
            return x + rng.normal(scale=scale, size=x.shape).astype(np.float32)
        return x + 1
    return jax.tree_util.tree_map(bump, state)


def _leaves(state):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]


# ---------------------------------------------------------------------------
# codecs: byte-plane codec + bitpattern arithmetic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "float16", "int32", "int8"])
def test_bitpattern_delta_roundtrip_bit_exact(dtype):
    rng = np.random.default_rng(0)
    parent = rng.standard_normal((37, 11)).astype(dtype) \
        if dtype.startswith("float") else \
        rng.integers(-100, 100, (37, 11)).astype(dtype)
    child = parent.copy()
    child.flat[::7] += np.asarray(3, dtype)
    d = bitpattern_delta(child, parent)
    back = bitpattern_apply(parent, d, dtype, child.shape)
    assert back.tobytes() == child.tobytes()  # bit-exact, not just close


def test_byteplane_codec_roundtrip_and_ratio():
    cod = get_codec("xd")
    rng = np.random.default_rng(1)
    base = rng.standard_normal(4096).astype(np.float32)
    child = base + np.float32(1e-6)
    d = bitpattern_delta(child, base)
    blob = cod.encode(d)
    out = cod.decode(blob, d.size, dtype=str(d.dtype))
    assert out.tobytes() == d.tobytes()
    # near-identical steps: exponent/high-mantissa planes are ~constant
    assert len(blob) < d.nbytes


# ---------------------------------------------------------------------------
# storage: commit_step manifests
# ---------------------------------------------------------------------------


def test_commit_step_exact_bit_identity(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    s = _state(0)
    states = []
    for i in range(5):  # deeper than one hop: chained xdelta entries
        states.append(s)
        cm.save(i, s, blocking=True)
        s = _perturb(s, seed=i + 1)
    for i, si in enumerate(states):
        restored, step = cm.restore(step=i, template=si)
        assert step == i
        for a, b in zip(_leaves(si), _leaves(restored)):
            assert a.tobytes() == b.tobytes()  # bit-identical resume


def test_commit_step_manifest_kinds_and_parents(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    cm.save(0, _state(0), blocking=True)
    cm.save(1, _perturb(_state(0)), blocking=True)
    ref0 = cm.lineage.nodes["m/step0"].artifact_ref
    ref1 = cm.lineage.nodes["m/step1"].artifact_ref
    m1 = cm.store.get_manifest(ref1)
    kinds = {e["kind"] for e in m1["params"].values()}
    assert "xdelta" in kinds
    # manifest_walk sees xdelta parent edges (sync/fsck closure correctness)
    info = parse_manifest(json.dumps(m1).encode())
    assert ref0 in info.parents
    xe = next(e for e in m1["params"].values() if e["kind"] == "xdelta")
    assert xe["parent_ref"] == ref0 and xe["d"] >= 1


def test_commit_step_chain_gate_resets_to_full(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           max_chain_depth=2)
    s = _state(0)
    for i in range(6):
        cm.save(i, s, blocking=True)
        s = _perturb(s, seed=i + 1)
    for i in range(6):
        ref = cm.lineage.nodes[f"m/step{i}"].artifact_ref
        m = cm.store.get_manifest(ref)
        assert all(e.get("d", 0) <= 2 for e in m["params"].values())
        restored, _ = cm.restore(step=i, template=_state())


def test_fingerprint_skip_reuses_parent_entries(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           fingerprint_min_bytes=0, fingerprint_device=False)
    s = _state(0)
    cm.save(0, s, blocking=True)
    before = int(CKPT_STATS["leaves_skipped"])
    cm.save(1, s, blocking=True)  # identical state: every leaf skips
    assert int(CKPT_STATS["leaves_skipped"]) - before == len(_leaves(s))
    m0 = cm.store.get_manifest(cm.lineage.nodes["m/step0"].artifact_ref)
    m1 = cm.store.get_manifest(cm.lineage.nodes["m/step1"].artifact_ref)
    for k, e in m1["params"].items():
        assert e["kind"] == m0["params"][k]["kind"]
        assert e.get("tensor") == m0["params"][k].get("tensor")
    restored, _ = cm.restore(step=1, template=s)
    for a, b in zip(_leaves(s), _leaves(restored)):
        assert a.tobytes() == b.tobytes()


def test_fingerprint_partial_skip_only_changed_leaves_ship(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           fingerprint_min_bytes=0, fingerprint_device=False)
    s = _state(0)
    cm.save(0, s, blocking=True)
    s2 = {**s, "params": {"w": s["params"]["w"] + np.float32(1e-4)},
          "step": np.asarray(1, np.int32)}
    cm.save(1, s2, blocking=True)
    m1 = cm.store.get_manifest(cm.lineage.nodes["m/step1"].artifact_ref)
    assert m1["params"]["params/w"]["kind"] == "xdelta"
    m0 = cm.store.get_manifest(cm.lineage.nodes["m/step0"].artifact_ref)
    # untouched optimizer leaves re-reference the parent's objects verbatim
    assert (m1["params"]["opt/nu/w"].get("tensor")
            == m0["params"]["opt/nu/w"].get("tensor"))
    restored, _ = cm.restore(step=1, template=s2)
    for a, b in zip(_leaves(s2), _leaves(restored)):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# lossy tier: keyframes, nearest-exact restore, nu log-domain
# ---------------------------------------------------------------------------


def test_lossy_tier_keyframes_and_nearest_exact_restore(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           tier="lossy", keyframe_every=3)
    s = _state(0)
    live = {}
    for i in range(6):
        live[i] = s
        cm.save(i, s, blocking=True)
        s = _perturb(s, scale=1e-3, seed=i + 1)
    lossy_flags = {}
    for i in range(6):
        ref = cm.lineage.nodes[f"m/step{i}"].artifact_ref
        md = cm.store.get_manifest(ref).get("metadata") or {}
        lossy_flags[i] = bool(md.get("lossy"))
    # commit 0 is a full base, every keyframe_every-th commit is exact
    assert lossy_flags == {0: False, 1: True, 2: True, 3: False,
                           4: True, 5: True}
    # default restore at a lossy step resolves to the nearest exact ancestor
    _, step = cm.restore(step=5)
    assert step == 3
    _, step = cm.restore(step=4)
    assert step == 3
    _, step = cm.restore(step=3)
    assert step == 3
    # keyframes are unquantized: bit-identical except nu, which lives in
    # the log domain and roundtrips through log1p/expm1 (~1 ulp)
    flat, _ = cm.restore(step=3)
    from repro.store.checkpoint import flatten_state
    live_flat = flatten_state(live[3])
    for k, a in live_flat.items():
        if k == "opt/nu/w":
            np.testing.assert_allclose(flat[k], a, rtol=3e-7, atol=0)
        else:
            assert flat[k].tobytes() == a.tobytes(), k


def test_lossy_tier_allow_lossy_within_ef_bound(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           tier="lossy", keyframe_every=4)
    s = _state(0)
    live = {}
    for i in range(4):
        live[i] = s
        cm.save(i, s, blocking=True)
        s = _perturb(s, scale=1e-3, seed=i + 1)
    restored, step = cm.restore(step=2, template=live[2], allow_lossy=True)
    assert step == 2
    for a, b in zip(_leaves(live[2]), _leaves(restored)):
        if a.dtype != np.float32:
            assert a.tobytes() == b.tobytes()
            continue
        # int8 grid over the per-leaf diff range; error feedback keeps the
        # committed truth within one quantization cell of the live value
        err = np.abs(a.astype(np.float64) - b.astype(np.float64))
        amax = float(np.abs(a).max())
        assert float(err.max()) <= max(amax / 32.0, 1e-6)


def test_lossy_tier_nu_log_domain_transform(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           tier="lossy", keyframe_every=4)
    s = _state(0)
    cm.save(0, s, blocking=True)
    s2 = _perturb(s, scale=1e-3, seed=1)
    cm.save(1, s2, blocking=True)
    ref = cm.lineage.nodes["m/step1"].artifact_ref
    md = cm.store.get_manifest(ref).get("metadata") or {}
    assert md.get("transforms", {}).get("opt/nu/w") == "log1p"
    # raw stored value is in the log domain; restore() inverts it
    raw = cm.lineage.nodes["m/step1"].get_model().params["opt/nu/w"]
    restored, _ = cm.restore(step=1, allow_lossy=True)
    nu_live = np.asarray(s2["opt"]["nu"]["w"], np.float64)
    assert np.allclose(np.expm1(np.asarray(raw, np.float64)),
                       restored["opt/nu/w"], rtol=1e-6, atol=1e-9)
    # absolute bound: the int8 grid spans the per-leaf diff range, so the
    # cell size is ~amax(diff)/127 regardless of the value's own magnitude
    assert np.allclose(restored["opt/nu/w"], nu_live,
                       rtol=5e-2, atol=1e-4)


# ---------------------------------------------------------------------------
# async engine: coalesce, error propagation, crash atomicity
# ---------------------------------------------------------------------------


def test_merge_coalesce_keeps_changed_leaf_values():
    old = (1, "m/step1", {"a": np.ones(4), "b": np.full(4, 2.0), "c": None},
           frozenset({"c"}))
    # leaf "b" changed between snapshots but fingerprint-matched the OLD
    # snapshot at enqueue time -> the merge must ship old's value for it
    new = (2, "m/step2", {"a": np.zeros(4), "b": None, "c": None},
           frozenset({"b", "c"}))
    step, name, flat, skip = CheckpointManager._merge(old, new)
    assert (step, name) == (2, "m/step2")
    assert skip == frozenset({"c"})  # only skipped-in-BOTH stays skipped
    assert np.array_equal(flat["a"], np.zeros(4))  # newest value wins
    assert np.array_equal(flat["b"], np.full(4, 2.0))  # backfilled from old
    assert flat["c"] is None


def test_async_coalesce_to_latest(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=True)
    s = _state(0)
    for i in range(8):
        cm.save(i, s)
        s = _perturb(s, seed=i + 1)
    cm.wait()
    steps = sorted(cm._steps())
    assert steps[-1] == 7  # the latest save always lands, coalesced or not
    last = _state(0)
    for i in range(7):
        last = _perturb(last, seed=i + 1)
    restored, _ = cm.restore(step=7, template=last)
    for a, b in zip(_leaves(last), _leaves(restored)):
        assert a.tobytes() == b.tobytes()
    cm.close()


def test_async_error_surfaces_on_next_save(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=True)

    def boom(*a, **k):
        raise RuntimeError("injected commit failure")

    cm._commit = boom
    cm.save(0, _state(0))
    deadline = 100
    while cm._error is None and deadline:
        import time
        time.sleep(0.02)
        deadline -= 1
    assert cm._error is not None
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        cm.save(1, _state(1))
    # the failed baseline was dropped: the next save re-fingerprints fresh
    assert cm._last_fps == {} and cm._prev_flat is None


def test_async_error_surfaces_on_close(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=True)

    def boom(*a, **k):
        raise RuntimeError("injected commit failure")

    cm._commit = boom
    cm.save(0, _state(0))
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        cm.close()


def test_crash_between_manifest_and_lineage_rolls_back(tmp_path):
    """Kill between object land and the lineage pointer move: restart
    resumes the previous step and fsck is clean (satellite b)."""
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    cm.save(1, _state(1), blocking=True)

    real_save = cm.lineage.save

    def killed(*a, **k):
        raise OSError("simulated kill mid-commit")

    cm.lineage.save = killed
    with pytest.raises(OSError):
        cm.save(2, _state(2), blocking=True)
    cm.lineage.save = real_save
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt_journal.json"))

    # "restart": a fresh manager over the same directory
    before = int(CKPT_STATS["journal_rollbacks"])
    cm2 = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    assert int(CKPT_STATS["journal_rollbacks"]) - before == 1
    assert not os.path.exists(os.path.join(str(tmp_path),
                                           "ckpt_journal.json"))
    assert cm2.latest_step() == 1
    restored, step = cm2.restore(template=_state())
    assert step == 1
    roots = [n.artifact_ref for n in cm2.lineage.nodes.values()
             if n.artifact_ref]
    report = cm2.store.fsck(roots)
    assert report["ok"], report
    # and the rolled-back step can be committed again cleanly
    cm2.save(2, _state(2), blocking=True)
    assert cm2.latest_step() == 2


def test_lossy_rollback_recommit_releases_superseded_manifests(tmp_path):
    """Lossy-tier crash/restart flow (review: re-commit ref leak): restore
    rolls the lossy head back to the keyframe, training re-runs forward,
    and the re-committed steps must release their superseded manifests —
    otherwise fsck reports refcount drift."""
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           tier="lossy", keyframe_every=3)
    s = _state(0)
    states = {}
    for i in range(5):  # exact keyframes at steps 0 and 3; 4 is lossy
        states[i] = s
        cm.save(i, s, blocking=True)
        s = _perturb(s, scale=1e-3, seed=i + 1)

    # "restart": the lossy head resolves back to the step-3 keyframe
    cm2 = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                            tier="lossy", keyframe_every=3)
    _, start = cm2.restore(template=_state())
    assert start == 3
    old4 = cm2.lineage.nodes["m/step4"].artifact_ref
    s4 = _perturb(states[3], scale=1e-3, seed=41)
    cm2.save(4, s4, blocking=True)  # re-commit of an existing step
    cm2.save(5, _perturb(s4, scale=1e-3, seed=42), blocking=True)
    assert cm2.lineage.nodes["m/step4"].artifact_ref != old4

    roots = [n.artifact_ref for n in cm2.lineage.nodes.values()
             if n.artifact_ref]
    report = cm2.store.fsck(roots)
    assert report["ok"], report
    # the re-committed step 4 is this run's keyframe: the new lossy head
    # resolves to it, bit-identical except nu's log-domain roundtrip
    from repro.store.checkpoint import flatten_state
    flat4, st = cm2.restore()
    assert st == 4
    for k, a in flatten_state(s4).items():
        if k != "opt/nu/w":
            assert flat4[k].tobytes() == a.tobytes(), k


def test_recommit_crash_before_stale_release_recovers(tmp_path):
    """Kill after the lineage landed on a re-committed manifest but before
    the superseded one was released: the journal still names it, so a
    restart finishes the release and fsck stays clean."""
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    cm.save(0, _state(0), blocking=True)
    cm.save(1, _state(1), blocking=True)
    old1 = cm.lineage.nodes["m/step1"].artifact_ref

    def killed():
        raise OSError("simulated kill before stale release")

    cm._journal_clear = killed
    with pytest.raises(OSError):
        cm.save(1, _state(2), blocking=True)  # re-commit of step 1
    assert os.path.exists(os.path.join(str(tmp_path), "ckpt_journal.json"))

    before = int(CKPT_STATS["journal_rollbacks"])
    cm2 = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    assert int(CKPT_STATS["journal_rollbacks"]) - before == 1
    assert cm2.lineage.nodes["m/step1"].artifact_ref != old1
    restored, step = cm2.restore(template=_state())
    assert step == 1
    for a, b in zip(_leaves(_state(2)), _leaves(restored)):
        assert a.tobytes() == b.tobytes()
    roots = [n.artifact_ref for n in cm2.lineage.nodes.values()
             if n.artifact_ref]
    report = cm2.store.fsck(roots)
    assert report["ok"], report


def test_async_failure_drops_poisoned_pending(tmp_path):
    """A snapshot enqueued while a commit is failing skipped leaves against
    a baseline that never landed; committing it would silently re-reference
    stale parent values. The worker must drop it with the baseline."""
    import threading

    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=True,
                           fingerprint_min_bytes=0, fingerprint_device=False)
    real_commit_step = cm.store.commit_step
    entered, release = threading.Event(), threading.Event()

    def boom(*a, **k):
        entered.set()
        release.wait(5)
        raise RuntimeError("injected commit failure")

    cm.store.commit_step = boom
    s = _state(0)
    cm.save(0, s)
    assert entered.wait(5)
    # identical state: every leaf fingerprint-matches the in-flight
    # snapshot, so the pending item carries only skips (values are None)
    cm.save(1, s)
    assert cm._pending is not None
    release.set()
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        cm.wait()
    assert cm._pending is None  # poisoned snapshot dropped, not committed
    assert cm._last_fps == {} and cm._prev_flat is None
    assert cm._steps() == []

    # the engine heals: the next save re-fingerprints and commits fully
    cm.store.commit_step = real_commit_step
    s2 = _perturb(s, seed=3)
    cm.save(2, s2)
    cm.wait()
    restored, step = cm.restore(template=s2)
    assert step == 2
    for a, b in zip(_leaves(s2), _leaves(restored)):
        assert a.tobytes() == b.tobytes()
    roots = [n.artifact_ref for n in cm.lineage.nodes.values()
             if n.artifact_ref]
    assert cm.store.fsck(roots)["ok"]
    cm.close()


@pytest.mark.parametrize("dtype", ["complex64", "complex128"])
def test_commit_step_odd_itemsize_dtype_roundtrip(tmp_path, dtype):
    """complex128 (itemsize 16) has no native unsigned width: the
    bitpattern path deltas a byte-wise view with nbytes elements, and the
    decode side must size the blob by bytes, not element count (review:
    latent xdelta restore failure)."""
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    rng = np.random.default_rng(0)
    base = (rng.standard_normal((64, 8))
            + 1j * rng.standard_normal((64, 8))).astype(dtype)
    cm.save(0, {"w": base, "step": np.asarray(0, np.int32)}, blocking=True)
    child = base.copy()
    child.flat[::9] += np.asarray(3 + 1j, dtype)
    s1 = {"w": child, "step": np.asarray(1, np.int32)}
    cm.save(1, s1, blocking=True)
    m1 = cm.store.get_manifest(cm.lineage.nodes["m/step1"].artifact_ref)
    assert m1["params"]["w"]["kind"] == "xdelta"
    restored, step = cm.restore(step=1, template=s1)
    assert step == 1
    for a, b in zip(_leaves(s1), _leaves(restored)):
        assert a.tobytes() == b.tobytes()


def test_commit_step_chunked_bf16_roundtrip(tmp_path):
    """A bf16 leaf above the chunk threshold (a published model's
    embedding and its moments) commits chunked, full and then against its
    parent's grid, and a fresh manager restores it bit for bit."""
    import ml_dtypes
    store = ArtifactStore(root=str(tmp_path), t_thr=float("inf"),
                          chunk_threshold=64 * 1024, chunk_min=16 * 1024,
                          chunk_avg=32 * 1024, chunk_max=64 * 1024)
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           store=store)
    rng = np.random.default_rng(0)
    big = rng.standard_normal((256, 300)).astype(ml_dtypes.bfloat16)
    states = [{"embed": big, "step": np.asarray(0, np.int32)},
              {"embed": edit_bf16(big), "step": np.asarray(1, np.int32)}]
    for i, s in enumerate(states):
        cm.save(i, s, blocking=True)
        e = cm.store.get_manifest(
            cm.lineage.nodes[f"m/step{i}"].artifact_ref)["params"]["embed"]
        assert e["kind"] == "chunked" and e["dtype"] == "bfloat16"
    cold = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    for i, s in enumerate(states):
        restored, step = cold.restore(step=i, template=s)
        assert step == i and restored["embed"].dtype == big.dtype
        for a, b in zip(_leaves(s), _leaves(restored)):
            assert a.tobytes() == b.tobytes()


def edit_bf16(x):
    out = x.copy()
    out.reshape(-1)[1000:1100] += np.asarray(0.5, x.dtype)
    return out


def test_crash_before_manifest_lands_is_a_noop_recovery(tmp_path):
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    cm.save(1, _state(1), blocking=True)
    # journal with ref=None: crash mid-commit_step, nothing durable yet
    cm._journal_write({"name": "m/step2", "step": 2, "ref": None})
    cm2 = CheckpointManager(str(tmp_path), model_name="m", async_save=False)
    assert cm2.latest_step() == 1
    assert not os.path.exists(os.path.join(str(tmp_path),
                                           "ckpt_journal.json"))


# ---------------------------------------------------------------------------
# elastic restore over chunked manifests (satellite c)
# ---------------------------------------------------------------------------


def test_restore_sharded_chunked_manifest_new_mesh(tmp_path):
    """Large leaves chunk with grids aligned to the TARGET mesh's shard
    cuts, and restore_sharded lays them out per the new mesh's sharding."""
    from repro.dist.sharding import shard_cuts
    n_shards = 4
    store = ArtifactStore(root=str(tmp_path), t_thr=float("inf"),
                          chunk_threshold=64 * 1024, chunk_min=16 * 1024,
                          chunk_avg=32 * 1024, chunk_max=64 * 1024,
                          chunk_shards=n_shards)
    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=False,
                           store=store)
    rng = np.random.default_rng(0)
    big = rng.standard_normal((256, 300)).astype(np.float32)  # ≥ threshold
    s = {"params": {"big": {"w": big}}, "step": np.asarray(0, np.int32)}
    cm.save(0, s, blocking=True)
    s2 = {"params": {"big": {"w": big + np.float32(1e-4)}},
          "step": np.asarray(1, np.int32)}
    cm.save(1, s2, blocking=True)

    for node in ("m/step0", "m/step1"):
        m = cm.store.get_manifest(cm.lineage.nodes[node].artifact_ref)
        e = m["params"]["params/big/w"]
        assert e["kind"] == "chunked" and len(e["chunks"]) > 1
        cuts = set(np.cumsum([int(it["n"]) for it in e["chunks"]]).tolist())
        expected = shard_cuts("params/big/w", big.shape, 4, n_shards)
        # no chunk straddles a boundary of the mesh the restore targets
        assert expected and set(expected) <= cuts

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", "model"))
    template = {
        "params": {"big": {"w": jax.ShapeDtypeStruct(
            big.shape, np.float32, sharding=sharding)}},
        "step": jax.ShapeDtypeStruct((), np.int32),
    }
    restored, step = cm.restore_sharded(template)
    assert step == 1
    w = restored["params"]["big"]["w"]
    assert w.sharding.is_equivalent_to(sharding, len(big.shape))
    assert np.asarray(w).tobytes() == s2["params"]["big"]["w"].tobytes()
    assert int(restored["step"]) == 1
