"""Continuous checkpointing — MGit versioning at training speed (§15).

Every ``save(step, state)`` cut becomes a *version node* in a lineage graph
whose storage flows through the step-delta commit engine
(:meth:`ArtifactStore.commit_step`): consecutive training states differ by
one optimizer excursion, so each commit moves only the changed leaves and
stores them as deltas against the previous step's committed truth.

The manager layers four things over the store engine:

* **fingerprint short-circuit** — leaves above ``fingerprint_min_bytes``
  are fingerprinted before transfer (device-side via the fingerprint
  kernel when ``ops.default_backend()`` is not ``ref`` — 8 bytes cross the
  link instead of the tensor; sharded leaves shard by shard — or a host
  CRC pair otherwise). A leaf whose fingerprint matches the last
  enqueued snapshot is *skipped*: no host copy, no encode, its manifest
  entry re-references the parent's.
* **tiers** — ``tier="exact"`` (default) stores lossless bitpattern
  deltas; resume is bit-identical. ``tier="lossy"`` stores int8
  error-feedback-grid deltas (``repro.dist.compression.ef_eps``) with an
  unquantized keyframe every ``keyframe_every`` commits (bit-exact up to
  the log-domain roundtrip on nu leaves, ~1 ulp); intermediate
  manifests carry ``lossy: true`` and ``restore`` resolves to the nearest
  exact ancestor unless ``allow_lossy``. In the lossy tier AdamW second
  moments (``state_regime == "moment2"``) are committed in the log domain
  (``log1p``/``expm1``), turning uniform quantization into relative
  precision for the all-positive, high-dynamic-range nu leaves.
* **double-buffered async commit** — ``save()`` never blocks on storage:
  one commit may be in flight while one snapshot waits; enqueueing onto
  an occupied slot *coalesces* (the waiting snapshot is replaced by the
  newer one, with skip-sets merged so no stale leaf survives). Training
  therefore never stalls more than one commit behind, and backpressure
  degrades commit *frequency*, not step time.
* **crash atomicity** — a journal records the in-flight commit; the
  lineage file is written once per commit (fsync'd, atomic), *after* the
  manifest is durable. Recovery on construction rolls back any orphaned
  manifest, so a kill at any point resumes from the previous committed
  step with a clean ``fsck``.

Fault tolerance beyond that is unchanged from the snapshot era:
``restore(verify=True)`` recomputes content hashes, and
``restore_sharded`` re-lays the checkpoint out on a *different* mesh.
"""

from __future__ import annotations

import json
import os
import threading
import time
import zlib
from typing import Any, Dict, FrozenSet, Optional, Tuple

import jax
import numpy as np

from repro.common.hashing import tensor_hash
from repro.core.graphir import LayerGraph, LayerNode
from repro.core.lineage import LineageGraph
from repro.obs import REGISTRY, span
from repro.optim.adamw import state_regime
from repro.store.artifact_store import ArtifactStore

#: Histogram buckets for save()-side blocking time: sub-ms (pure enqueue)
#: through seconds (blocking full snapshot).
_OVERHEAD_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                     0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

#: save()-side blocking seconds per checkpoint cut, labeled by tier.
#: Module-level registration: ``repro.store`` imports this module, so the
#: family is visible to `cli obs metrics` and both daemons' /api/metrics
#: in any process that touches the store layer.
CKPT_OVERHEAD = {
    tier: REGISTRY.histogram(
        "checkpoint_overhead_seconds",
        help="training-loop blocking time spent in CheckpointManager.save",
        buckets=_OVERHEAD_BUCKETS, tier=tier)
    for tier in ("exact", "lossy")
}

#: Engine accounting, scrapeable as mgit_ckpt_* (DESIGN.md §15).
CKPT_STATS = REGISTRY.group(
    "mgit_ckpt",
    keys=("saves", "commits", "coalesced", "leaves_skipped",
          "leaves_transferred", "journal_rollbacks"),
    help="continuous checkpointing engine accounting")


def _keystr(path) -> str:
    """A leaf's key in a checkpoint: its pytree path joined with "/"."""
    return jax.tree_util.keystr(path, simple=True, separator="/")


def _transfer(key: str, leaf, nbytes: int) -> np.ndarray:
    """One leaf of a snapshot, copied device->host."""
    with span("ckpt.transfer", cat="ckpt", key=key, n=nbytes):
        return np.asarray(jax.device_get(leaf))


def flatten_state(state) -> Dict[str, np.ndarray]:
    """Pytree -> flat {path: host ndarray}. Gathers from device (blocking)."""
    flat = {}
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in leaves:
        key = _keystr(path)
        flat[key] = np.asarray(jax.device_get(leaf))
    return flat


def unflatten_state(template, flat: Dict[str, np.ndarray]):
    """Inverse of flatten_state given a structure/ShapeDtypeStruct template."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in paths:
        key = _keystr(path)
        value = flat[key]
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and str(value.dtype) != str(dtype):
            value = value.astype(dtype)
        shape = getattr(leaf, "shape", None)
        if shape is not None and tuple(value.shape) != tuple(shape):
            value = np.asarray(value).reshape(shape)  # stored scalars are 1-D
        leaves.append(value)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def spec_graph(specs: Dict[str, Tuple[Tuple[int, ...], str]],
               model_type: str) -> LayerGraph:
    """Chain LayerGraph over (shape, dtype) specs keyed by state path."""
    g = LayerGraph()
    prev = None
    for key, (shape, dtype) in specs.items():
        layer, _, param = key.rpartition("/")
        layer, param = layer or key, param or "value"
        if layer not in g.nodes:
            g.add_node(LayerNode(layer, "state"))
            if prev is not None:
                g.add_edge(prev, layer)
            prev = layer
        g.nodes[layer].params[param] = (tuple(shape), str(dtype))
    return g


def state_graph(flat: Dict[str, np.ndarray], model_type: str) -> LayerGraph:
    """Chain LayerGraph over state entries (checkpoints are sequenced by path)."""
    return spec_graph(
        {k: (tuple(np.shape(v)), str(np.asarray(v).dtype))
         for k, v in flat.items()}, model_type)


class CheckpointManager:
    def __init__(self, directory: Optional[str], model_name: str = "model",
                 codec: str = "lzma", eps: float = 1e-4,
                 delta_enabled: bool = True, async_save: bool = True,
                 max_chain_depth: int = 8,
                 store: Optional[ArtifactStore] = None,
                 lineage: Optional[LineageGraph] = None,
                 tier: str = "exact", keyframe_every: int = 8,
                 fingerprint_min_bytes: int = 1 << 16,
                 fingerprint_device: Optional[bool] = None) -> None:
        if tier not in ("exact", "lossy"):
            raise ValueError(f"unknown checkpoint tier {tier!r}")
        self.model_name = model_name
        self.store = store or ArtifactStore(
            root=directory, codec=codec, eps=eps, t_thr=float("inf"),
            delta_enabled=delta_enabled, max_chain_depth=max_chain_depth)
        self.lineage = lineage or LineageGraph(path=directory,
                                               store=self.store)
        self.async_save = async_save
        self.tier = tier
        self.keyframe_every = max(1, int(keyframe_every))
        self.fingerprint_min_bytes = int(fingerprint_min_bytes)
        self.fingerprint_device = fingerprint_device
        self._journal_path = (os.path.join(directory, "ckpt_journal.json")
                              if directory else None)
        # double-buffer slots: at most one commit in flight, one pending
        self._cond = threading.Condition()
        self._pending: Optional[tuple] = None
        self._inflight = False
        self._worker: Optional[threading.Thread] = None
        self._worker_dead = True
        self._closed = False
        self._error: Optional[BaseException] = None
        # step-delta engine state (worker-thread owned after __init__)
        self._last_fps: Dict[str, int] = {}
        self._prev_flat: Optional[Dict[str, np.ndarray]] = None
        self._prev_flat_ref: Optional[str] = None
        self._commits = 0
        self._recover_journal()

    # -- naming ----------------------------------------------------------------
    def _node_name(self, step: int) -> str:
        return f"{self.model_name}/step{step}"

    def _steps(self):
        return [
            int(n.rsplit("step", 1)[1]) for n in self.lineage.nodes
            if n.startswith(self.model_name + "/step")
            and self.lineage.nodes[n].artifact_ref is not None
        ]

    def latest_step(self) -> Optional[int]:
        steps = self._steps()
        return max(steps) if steps else None

    # -- crash recovery ----------------------------------------------------------
    def _journal_write(self, payload: Dict[str, Any]) -> None:
        if self._journal_path is None:
            return
        tmp = self._journal_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._journal_path)

    def _journal_clear(self) -> None:
        if self._journal_path and os.path.exists(self._journal_path):
            os.remove(self._journal_path)

    def _recover_journal(self) -> None:
        """Roll back a commit interrupted between manifest land and the
        lineage pointer move (DESIGN.md §15: the LATEST-equivalent here is
        the lineage file, written once per commit AFTER the manifest is
        durable)."""
        if not self._journal_path or not os.path.exists(self._journal_path):
            return
        try:
            with open(self._journal_path) as f:
                j = json.load(f)
        except Exception:
            j = {}
        ref = j.get("ref")
        stale = j.get("stale")
        referenced = {n.artifact_ref for n in self.lineage.nodes.values()}
        if ref is not None and ref not in referenced:
            # manifest (possibly partially) landed but lineage never saw
            # it: drop the orphan so refcounts match the reachable graph
            self.store.release(ref)
            self.store.cas.flush()
            CKPT_STATS["journal_rollbacks"] += 1
        elif (ref is not None and stale is not None
              and stale not in referenced):
            # re-commit of an existing step where the lineage DID land on
            # the new manifest: the superseded one is now orphaned, and the
            # journal's presence proves its release never ran (_commit
            # releases only after clearing the journal) — finish it here
            self.store.release(stale)
            self.store.cas.flush()
            CKPT_STATS["journal_rollbacks"] += 1
        self._journal_clear()

    # -- snapshot (fingerprint short-circuit) -------------------------------------
    def _use_device_fp(self) -> bool:
        if self.fingerprint_device is not None:
            return self.fingerprint_device
        from repro.kernels import ops
        return ops.default_backend() != "ref"

    @staticmethod
    def _device_fp(leaf) -> int:
        """Device fingerprint of one leaf. A sharded leaf is hashed shard by
        shard: GSPMD cannot partition a ``pallas_call``, and gathering would
        copy the whole leaf onto one chip. Replicas are hashed once."""
        from repro.kernels import ops
        shards = [s for s in getattr(leaf, "addressable_shards", ())
                  if s.replica_id == 0]
        if not shards:
            return ops.fingerprint(leaf)
        if len(shards) == 1:
            return ops.fingerprint(shards[0].data)
        return hash(tuple((str(s.index), ops.fingerprint(s.data))
                          for s in shards))

    @staticmethod
    def _host_fp(arr: np.ndarray) -> int:
        """64-bit host fingerprint: CRC32/Adler32 pair over the raw bytes,
        salted with shape+dtype. No jit dispatch — on CPU hosts the device
        kernel's dispatch overhead would exceed the hash itself."""
        a = np.ascontiguousarray(arr)
        view = a.view(np.uint8).reshape(-1)
        salt = repr((a.shape, str(a.dtype))).encode()
        return (zlib.crc32(view, zlib.crc32(salt)) << 32) | zlib.adler32(view)

    def _snapshot(self, state) -> Tuple[Dict[str, Optional[np.ndarray]],
                                        FrozenSet[str]]:
        """Flatten ``state``, skipping leaves whose fingerprint matches the
        last enqueued snapshot. Device fingerprints are computed BEFORE the
        host transfer — an unchanged leaf moves 8 bytes, not the tensor."""
        flat: Dict[str, Optional[np.ndarray]] = {}
        fps: Dict[str, int] = {}
        skip = set()
        device_fp = self._use_device_fp()
        leaves = jax.tree_util.tree_flatten_with_path(state)[0]
        for path, leaf in leaves:
            key = _keystr(path)
            shape = tuple(np.shape(leaf))
            dt = getattr(leaf, "dtype", None)
            nbytes = (int(np.prod(shape, dtype=np.int64))
                      * np.dtype(dt).itemsize) if dt is not None else 0
            if nbytes < self.fingerprint_min_bytes:
                flat[key] = _transfer(key, leaf, nbytes)
                continue
            if device_fp:
                fp = self._device_fp(leaf)
                fps[key] = fp
                if self._last_fps.get(key) == fp:
                    flat[key] = None
                    skip.add(key)
                    continue
                flat[key] = _transfer(key, leaf, nbytes)
            else:
                arr = _transfer(key, leaf, nbytes)
                fp = self._host_fp(arr)
                fps[key] = fp
                if self._last_fps.get(key) == fp:
                    flat[key] = None
                    skip.add(key)
                    continue
                flat[key] = arr
        self._last_fps = fps
        return flat, frozenset(skip)

    # -- save ---------------------------------------------------------------------
    def save(self, step: int, state: Any,
             blocking: Optional[bool] = None) -> str:
        """Snapshot ``state`` (pytree) as version ``step``. Returns node name.

        The fingerprint pass + device->host gather of changed leaves happens
        synchronously (the snapshot is immutable after that point); encode +
        IO runs on the worker thread. Async saves never block here: if a
        commit is already in flight AND one is pending, the pending snapshot
        is replaced (coalesce-to-latest) — the training loop stalls at most
        one commit behind storage."""
        self._check_error()
        t0 = time.perf_counter()
        name = self._node_name(step)
        with span("ckpt.snapshot", cat="ckpt", step=step,
                  model=self.model_name):
            flat, skip = self._snapshot(state)
        if blocking is None:
            blocking = not self.async_save
        if blocking:
            self._commit(step, name, flat, skip)
        else:
            self._enqueue((step, name, flat, skip))
        CKPT_STATS["saves"] += 1
        CKPT_STATS["leaves_skipped"] += len(skip)
        CKPT_STATS["leaves_transferred"] += len(flat) - len(skip)
        CKPT_OVERHEAD[self.tier].observe(time.perf_counter() - t0)
        return name

    @staticmethod
    def _merge(old: tuple, new: tuple) -> tuple:
        """Coalesce a pending snapshot with a newer one.

        The merged commit keeps the NEW step/values but may only skip a
        leaf that BOTH snapshots skipped: the eventual delta parent is the
        one the old snapshot was fingerprinted against, so a leaf that
        changed in between must ship the old snapshot's value (present
        there by construction — it wasn't skipped)."""
        _, _, old_flat, old_skip = old
        step, name, flat, skip = new
        merged_skip = frozenset(skip & old_skip)
        merged = dict(flat)
        for k in skip - merged_skip:
            merged[k] = old_flat[k]
        return (step, name, merged, merged_skip)

    def _enqueue(self, item: tuple) -> None:
        start = False
        with self._cond:
            if self._pending is not None:
                self._pending = self._merge(self._pending, item)
                CKPT_STATS["coalesced"] += 1
            else:
                self._pending = item
            self._cond.notify_all()
            if (self._worker_dead or self._worker is None
                    or not self._worker.is_alive()):
                self._worker_dead = False
                self._worker = threading.Thread(target=self._drain,
                                                daemon=True)
                start = True
        if start:
            self._worker.start()

    def _drain(self) -> None:
        while True:
            with self._cond:
                while self._pending is None:
                    if self._closed or not self._cond.wait(timeout=0.2):
                        if self._pending is None:  # idle or closing: die
                            self._worker_dead = True
                            return
                item, self._pending = self._pending, None
                self._inflight = True
            try:
                self._commit(*item)
            except BaseException as e:  # surfaced on next save()/wait()
                self._error = e
                with self._cond:
                    # a snapshot enqueued while this commit was failing
                    # skipped leaves against a baseline that never landed;
                    # its None leaves are unrecoverable, so committing it
                    # would silently re-reference stale parent values —
                    # drop it along with the baseline
                    self._pending = None
                # the fingerprint baseline now references a commit that
                # never landed — next save must transfer everything
                self._last_fps = {}
                self._prev_flat = None
            finally:
                with self._cond:
                    self._inflight = False
                    self._cond.notify_all()

    def wait(self) -> None:
        with span("ckpt.wait", cat="ckpt"), self._cond:
            while self._pending is not None or self._inflight:
                self._cond.wait(timeout=0.05)
        self._check_error()

    def close(self) -> None:
        """Drain pending commits and surface any async failure."""
        self.wait()
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def _check_error(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("async checkpoint save failed") from err

    # -- commit -------------------------------------------------------------------
    def _commit(self, step: int, name: str,
                flat: Dict[str, Optional[np.ndarray]],
                skip: FrozenSet[str] = frozenset()) -> None:
        commit_tier = "exact"
        prev_step = None
        for s in self._steps():
            if s < step and (prev_step is None or s > prev_step):
                prev_step = s
        parent_ref = (self.lineage.nodes[self._node_name(prev_step)]
                      .artifact_ref if prev_step is not None else None)
        if (self.tier == "lossy" and parent_ref is not None
                and self._commits % self.keyframe_every != 0):
            commit_tier = "lossy"
        # Re-commit of an already-committed step (restore rolled back to an
        # exact ancestor, then training re-ran forward past it): the node's
        # current manifest is superseded and must be released once the
        # lineage points at the new one, or its refs leak (fsck
        # refcount_drift). The journal carries it so a crash after the
        # lineage save still releases it on recovery.
        stale_node = self.lineage.nodes.get(name)
        stale_ref = (stale_node.artifact_ref if stale_node is not None
                     else None)
        with span("ckpt.commit", cat="ckpt", step=step, tier=commit_tier):
            work, transforms = self._apply_transforms(flat)
            metadata: Dict[str, Any] = {"step": step}
            if commit_tier == "lossy":
                metadata["lossy"] = True
            if transforms:
                metadata["transforms"] = transforms
            self._journal_write({"name": name, "step": step, "ref": None,
                                 "stale": stale_ref})
            parent_manifest = (self.store.get_manifest(parent_ref)
                               if parent_ref else None)
            graph_json = None
            if (parent_manifest is None
                    or set(work) != set(parent_manifest["params"])):
                graph_json = self._graph_json(work, parent_manifest)
            ref = self.store.commit_step(
                name, work, parent_ref, skip=skip, tier=commit_tier,
                model_type=self.model_name, metadata=metadata,
                graph_json=graph_json,
                # the live-flat shortcut is only the parent's committed
                # truth when the parent IS the commit it was captured from
                # (not after a rollback re-commit, where prev_step jumps
                # back past the step _prev_flat came from)
                parent_hint=(self._prev_flat
                             if (self.tier == "exact"
                                 and parent_ref is not None
                                 and self._prev_flat_ref == parent_ref)
                             else None),
                flush=False)
            # journal carries the ref BEFORE the durability point: a crash
            # on either side of the flush leaves either nothing visible or
            # an orphan the journal can roll back
            self._journal_write({"name": name, "step": step, "ref": ref,
                                 "stale": stale_ref})
            with span("commit.pack_fsync", cat="store"):
                self.store.cas.flush()
            # one lineage save per commit: batch the node + version edge +
            # artifact pointer, then write the (fsync'd, atomic) file once.
            # The artifact_ref lands AFTER the version edge so the edge
            # hook never re-compresses a node that is already step-encoded.
            prev_autosave = self.lineage.autosave
            self.lineage.autosave = False
            try:
                node = self.lineage.add_node(None, name,
                                             model_type=self.model_name)
                # detach the superseded ref first so the version-edge hook
                # can never re-compress the manifest we're about to replace
                node.artifact_ref = None
                if prev_step is not None:
                    self.lineage.add_version_edge(
                        self._node_name(prev_step), name)
                node.artifact_ref = ref
            finally:
                self.lineage.autosave = prev_autosave
            self.lineage.save()
            self._journal_clear()
            if stale_ref is not None:
                # only AFTER the (fsync'd) lineage points at the new
                # manifest — releasing earlier could leave the durable
                # lineage referencing a released ref after a crash. Holds
                # for stale_ref == ref too (bit-identical re-commit): the
                # commit re-increffed every object the manifest owns, and
                # this release undoes exactly that duplicate set.
                self.store.release(stale_ref)
                self.store.cas.flush()
        self._commits += 1
        CKPT_STATS["commits"] += 1
        if self.tier == "exact":
            base = (self._prev_flat
                    if self._prev_flat is not None
                    and self._prev_flat_ref == parent_ref else {})
            self._prev_flat = {k: (v if v is not None else base.get(k))
                               for k, v in flat.items()}
            self._prev_flat_ref = ref

    def _apply_transforms(self, flat: Dict[str, Optional[np.ndarray]]
                          ) -> Tuple[Dict[str, Optional[np.ndarray]],
                                     Dict[str, str]]:
        """Per-regime leaf transforms (lossy tier only): AdamW nu commits
        as log1p(v) so the uniform int8 grid quantizes *relative* error —
        exactly what a smooth nonnegative second moment wants. Applied to
        keyframes too: the whole lossy chain lives in one domain, so
        consecutive hops stay small. Exact tier stores raw bits."""
        if self.tier != "lossy":
            return flat, {}
        work: Dict[str, Optional[np.ndarray]] = {}
        transforms: Dict[str, str] = {}
        for k, v in flat.items():
            if state_regime(k) == "moment2" and (
                    v is None or v.dtype == np.float32):
                transforms[k] = "log1p"
                work[k] = None if v is None else np.log1p(v)
            else:
                work[k] = v
        return work, transforms

    def _graph_json(self, work: Dict[str, Optional[np.ndarray]],
                    parent_manifest: Optional[Dict[str, Any]]) -> str:
        specs: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        for k, v in work.items():
            if v is not None:
                specs[k] = (tuple(v.shape), str(v.dtype))
            else:
                pe = parent_manifest["params"][k]
                specs[k] = (tuple(pe.get("shape", ())),
                            pe.get("dtype", "float32"))
        return spec_graph(specs, self.model_name).to_json()

    # -- restore ---------------------------------------------------------------------
    def restore(self, step: Optional[int] = None, template: Any = None,
                verify: bool = False, allow_lossy: bool = False):
        """Load flat state (or a full pytree if ``template`` given).

        Returns ``(state, step)``. When the resolved step is a lossy
        intermediate and ``allow_lossy`` is False (the default — and the
        only safe choice for resuming training), the restore walks back to
        the nearest bit-exact ancestor and returns THAT step."""
        self.wait()
        # a restore may rewind training: the fingerprint/skip baseline and
        # live-flat shortcut describe the pre-restore head, not whatever
        # the caller resumes from — drop them (next save transfers fully)
        self._last_fps = {}
        self._prev_flat = None
        self._prev_flat_ref = None
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError("no committed checkpoint found")
        steps = sorted(self._steps())
        if step not in steps:
            raise FileNotFoundError(f"no committed checkpoint at step {step}")
        while not allow_lossy:
            node = self.lineage.nodes[self._node_name(step)]
            manifest = self.store.get_manifest(node.artifact_ref)
            if not (manifest.get("metadata") or {}).get("lossy"):
                break
            prior = [s for s in steps if s < step]
            if not prior:
                break  # first commit is always exact; defensive
            step = max(prior)
        node = self.lineage.nodes[self._node_name(step)]
        artifact = node.get_model()
        manifest = self.store.get_manifest(node.artifact_ref)
        if verify:
            # Bit-rot check against commit-time content hashes. The lazy view
            # materializes one tensor at a time, so verification streams at
            # O(tensor) peak memory. Delta entries are covered too: plan
            # execution is bit-exact w.r.t. the commit-time reconstruction.
            for key, e in manifest["params"].items():
                expected = e.get("hash") or e.get("tensor")
                if expected is None:
                    continue  # pre-hash manifest (older store version)
                if tensor_hash(artifact.params[key]) != expected:
                    raise IOError(f"checkpoint corruption detected in {key!r}")
        transforms = (manifest.get("metadata") or {}).get("transforms") or {}
        if transforms:
            flat: Dict[str, np.ndarray] = {}
            for key in manifest["params"]:
                v = np.asarray(artifact.params[key])
                if transforms.get(key) == "log1p":
                    v = np.expm1(v)
                flat[key] = v
        else:
            flat = artifact.params
        if template is None:
            return flat, step
        return unflatten_state(template, flat), step

    def restore_sharded(self, template: Any, step: Optional[int] = None,
                        verify: bool = False, allow_lossy: bool = False):
        """Elastic restore: lay the checkpoint out per ``template``'s shardings.

        ``template`` leaves are jax.ShapeDtypeStruct with ``.sharding`` set for
        the TARGET mesh — which may differ from the mesh that wrote the
        checkpoint (scale-up/down after failure)."""
        state, step = self.restore(step=step, template=template,
                                   verify=verify, allow_lossy=allow_lossy)

        def _place(leaf, tmpl):
            sharding = getattr(tmpl, "sharding", None)
            if sharding is not None:
                return jax.device_put(leaf, sharding)
            return jax.numpy.asarray(leaf)

        return jax.tree_util.tree_map(_place, state, template), step
