"""Lineage traffic: commits and checkouts of derivatives, one client.

The mix file sets the shape of the lineage and of the traffic:

    chain_depth      set-up commits the base and a version chain this deep
    commit_parents   chain depths a window commit derives from
    checkout         "chain": check out a chain node (``checkout_depths``);
                     "last_commit": check out the node just committed
    finetune         density, scale and frozen fraction of every derivative
    sample           window answers per operation kind compared afterwards

The window is a closed loop of one client that alternates a commit and a
checkout, in cycles of two of each, until ``--seconds`` have passed; the
last cycle finishes. The depths of each kind come in antithetic pairs drawn
from the seed (``plan``), so every window holds whole pairs: every seed does
the same work in another order, however many cycles fit.

A commit is what a user's ``commit`` process does: a fresh ``ArtifactStore``
and ``LineageGraph`` on the repository, then ``add_node`` of the derivative
(held on the device) as the next version of its parent. A checkout is a
fresh ``ArtifactStore`` → ``materialize_artifact`` → ``jax.device_put`` of
every tensor → ``block_until_ready``. Both start with the store's caches
cold and the operating system's page cache warm.

``correct`` holds when every sampled answer equals the plain reference
(``chipbench/reference/lineage.py``) bit for bit: the checkouts as the
window returned them, and the window's commits checked out again after it
closed; their truth hashes must match their manifests too.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from chipbench import costs, generate, trace
from chipbench.harness import dir_bytes, log
from chipbench.reference import lineage as ref


#: Operations per cycle: a pair of commits and a pair of checkouts.
CYCLE = 4


class Op:
    __slots__ = ("kind", "depth", "name", "parent", "tag", "seconds",
                 "moved", "of")

    def __init__(self, kind: str, depth: int) -> None:
        self.kind, self.depth = kind, depth
        self.name = self.parent = None
        self.of: Optional["Op"] = None    # the commit a checkout reads
        self.tag = 0
        self.seconds = 0.0
        self.moved = 0


def plan(mix: Dict[str, Any], seed: int, n: int = 4096) -> List[Op]:
    """The window's operations, in order: commits and checkouts alternate.
    Each kind's depths come in blocks: the mix's list paired off as
    ``(d, lo + hi - d)``, the pairs in an order and each pair in a direction
    drawn from the seed. Any even number of one kind's operations then has
    the same mean depth, so a run's mix does not depend on the seed or on
    where its window ends."""
    rng = np.random.default_rng(seed)

    def depths(values):
        lo, hi = min(values), max(values)
        pairs = sorted({tuple(sorted((v, lo + hi - v))) for v in values})
        while True:
            for i in rng.permutation(len(pairs)):
                a, b = pairs[i]
                yield from ((a, b) if rng.random() < 0.5 else (b, a))

    commits = depths(mix["commit_parents"])
    checkouts = depths(mix.get("checkout_depths", [0]))
    return [Op("commit", int(next(commits))) if i % 2 == 0
            else Op("checkout", int(next(checkouts))) for i in range(n)]


def _close(store) -> None:
    pool = getattr(store, "_pool", None)
    if pool is not None:
        pool.shutdown(wait=True)


class Lineage:
    """Set-up state of one lineage cell."""

    def __init__(self, run) -> None:
        from repro.core import LineageGraph, ModelArtifact
        from repro.store import ArtifactStore
        from repro.store.checkpoint import spec_graph
        self._Graph, self._Store, self._Artifact = (LineageGraph,
                                                    ArtifactStore,
                                                    ModelArtifact)
        self.run = run
        self.mix = run.cell.mix
        self.model = run.cell.config["model"]
        self.arch = run.cell.config["arch"]
        self.specs = generate.leaf_specs(self.model)
        self.graph_ir = spec_graph({k: (s, d) for k, s, d in self.specs},
                                   self.arch)
        self.ft = self.mix["finetune"]
        self.repo = os.path.join(run.work, "repo")
        os.makedirs(self.repo)
        self.gens = [generate.base_weights(self.specs, run.seed)]
        for k in range(1, self.mix["chain_depth"] + 1):
            self.gens.append(self.derive(self.gens[-1], k))
        jax.block_until_ready(self.gens)
        self.refs: List[str] = []
        self.names: List[str] = []
        store = ArtifactStore(root=self.repo)
        graph = LineageGraph(path=self.repo, store=store)
        for k, params in enumerate(self.gens):
            name = f"chain{k}"
            self.refs.append(self._commit(graph, name,
                                          self.names[-1] if k else None,
                                          params))
            self.names.append(name)
            log(f"set-up: committed {name}")
        _close(store)
        self.last: Optional[Op] = None

    def derive(self, params, tag: int):
        return generate.finetune(params, self.run.seed, tag,
                                 density=self.ft["density"],
                                 scale=self.ft["scale"],
                                 freeze_frac=self.ft["freeze_frac"])

    def _commit(self, graph, name: str, parent: Optional[str], params
                ) -> str:
        graph.add_node(None, name, model_type=self.arch)
        if parent is not None:
            graph.add_version_edge(parent, name)
        node = graph.add_node(self._Artifact(self.graph_ir, dict(params),
                                             model_type=self.arch), name)
        node.artifact = None          # the graph need not hold the arrays
        return node.artifact_ref

    # -- the two operations ----------------------------------------------------
    def commit(self, op: Op, params) -> None:
        """One derivative of chain node ``op.depth``, committed as a new
        process would."""
        t0 = time.perf_counter()
        store = self._Store(root=self.repo)
        graph = self._Graph(path=self.repo, store=store)
        ref_ = self._commit(graph, op.name, self.names[op.depth], params)
        op.seconds = time.perf_counter() - t0
        _close(store)
        op.parent = ref_
        op.moved = costs.commit_bytes(self.specs)
        self.last = op

    def checkout(self, op: Op) -> Dict[str, jax.Array]:
        if self.mix["checkout"] == "last_commit":
            ref_, depth = self.last.parent, self.last.depth + 1
            op.name, op.of = self.last.name, self.last
        else:
            ref_, depth = self.refs[op.depth], op.depth
            op.name = self.names[op.depth]
        t0 = time.perf_counter()
        store = self._Store(root=self.repo)
        params = store.materialize_artifact(ref_).params
        out = {k: jax.device_put(v) for k, v in params.items()}
        jax.block_until_ready(out)
        op.seconds = time.perf_counter() - t0
        _close(store)
        op.parent = ref_
        op.moved = costs.checkout_bytes(self.specs, depth)
        return out


def _reservoir(rng, kept: list, item, seen: int, k: int) -> None:
    """Keep a uniform sample of ``k`` of the items seen so far."""
    if len(kept) < k:
        kept.append(item)
    else:
        j = int(rng.integers(0, seen))
        if j < k:
            kept[j] = item


def run(run) -> Dict[str, Any]:
    mix = run.cell.mix
    lin = Lineage(run)
    ops = plan(mix, run.seed)
    n_sample = int(mix.get("sample", 3))

    # warm-up: every program the window can run, compiled or loaded now
    warm = [Op("commit", d) for d in sorted(set(mix["commit_parents"]))[:1]]
    if mix["checkout"] == "chain":
        warm += [Op("checkout", d) for d in sorted(set(mix["checkout_depths"]))]
    else:
        warm.append(Op("checkout", 0))
    for i, op in enumerate(warm):
        if op.kind == "commit":
            op.name, op.tag = f"warm{i}", 10_000 + i
            lin.commit(op, lin.derive(lin.gens[op.depth], op.tag))
        else:
            lin.checkout(op)
        log(f"set-up: warm {op.kind} at depth {op.depth}: "
            f"{op.seconds:.3f} s")
    setup_s = time.perf_counter()

    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, 7])
    kept_checkouts: List[Any] = []
    kept_commits: List[Op] = []
    done: List[Op] = []
    phys0 = dir_bytes(lin.repo)
    compiles0 = run.clock.count
    with run.window() as win:
        w0 = time.perf_counter()
        for i, op in enumerate(ops):
            if i % CYCLE == 0 and time.perf_counter() - w0 >= run.seconds:
                break
            if op.kind == "commit":
                op.name, op.tag = f"w{i}", 1 + i
                with trace.op_span("chipbench.generate"):
                    child = lin.derive(lin.gens[op.depth], op.tag)
                    jax.block_until_ready(child)
                with trace.op_span("chipbench.commit"):
                    lin.commit(op, child)
                del child
                _reservoir(rng, kept_commits, op,
                           sum(o.kind == "commit" for o in done) + 1,
                           n_sample)
            else:
                with trace.op_span("chipbench.checkout"):
                    out = lin.checkout(op)
                _reservoir(rng, kept_checkouts, (op, out),
                           sum(o.kind == "checkout" for o in done) + 1,
                           n_sample)
                del out
            done.append(op)
        window_s = time.perf_counter() - w0
    compiles = run.clock.count - compiles0
    phys1 = dir_bytes(lin.repo)
    memory = run.read_memory()

    commits = [o for o in done if o.kind == "commit"]
    checkouts = [o for o in done if o.kind == "checkout"]
    logical = sum(generate.nbytes(s) for s in lin.specs) * len(commits)
    e2e = {}
    if commits:
        e2e["commit_s"] = sum(o.seconds for o in commits) / len(commits)
        e2e["stored_bytes_ratio"] = (phys1 - phys0) / logical
    if checkouts:
        e2e["checkout_s"] = sum(o.seconds for o in checkouts) / len(checkouts)
    log(f"window: {len(commits)} commits, {len(checkouts)} checkouts in "
        f"{window_s:.3f} s; {compiles} compiles inside it; stored "
        f"{phys1 - phys0} B for {logical} B; commit depths "
        f"{[o.depth for o in commits]}, checkout depths "
        f"{[o.depth for o in checkouts]}")

    # -- correctness, once the window has closed -------------------------------
    answers = [(op, {k: np.asarray(v) for k, v in out.items()})
               for op, out in kept_checkouts]
    kept_checkouts.clear()
    for op in kept_commits:
        store = lin._Store(root=lin.repo)
        answers.append((op, dict(store.materialize_artifact(op.parent)
                                 .params)))
        _close(store)
    checks = compare(lin, answers, run)
    return {"setup_end": setup_s, "attempted": len(done), "failed": 0,
            "e2e": e2e, "checks": checks, "memory": memory,
            "window_s": window_s, "capture": win,
            # the benchmark's own derivative generator, on the device in
            # the window: not the system's busy or idle time
            "generator_modules": ("jit__finetune",),
            "record": {"commits": len(commits), "checkouts": len(checkouts),
                       "commit_moved": sum(o.moved for o in commits),
                       "checkout_moved": sum(o.moved for o in checkouts),
                       "compiles_in_window": compiles}}


def compare(lin: Lineage, answers, run) -> Dict[str, Dict[str, float]]:
    """Elements of the sampled answers whose bits differ from the reference,
    and manifest truth hashes that differ from the answers."""
    from repro.common.hashing import tensor_hash
    eps = run.cell.mix.get("eps", 1e-4)
    # an answer is a window commit's derivative or a chain node
    made = {id(op): (op if op.kind == "commit" else op.of)
            for op, _ in answers}
    need_chain = max([op.depth for op, _ in answers] + [0])
    children = {}
    for c in made.values():       # one derivative on the device at a time
        if c is not None and c.name not in children:
            children[c.name] = jax.device_get(lin.derive(lin.gens[c.depth],
                                                         c.tag))
    store = lin._Store(root=lin.repo)
    manifests = {op.parent: store.get_manifest(op.parent) for op, _ in answers}
    _close(store)
    def leaf(key: str):
        truth = [np.asarray(lin.gens[0][key])]
        fold: List[ref.Fold] = [None]
        for k in range(1, need_chain + 1):
            t, f = ref.truth(truth[-1], fold[-1],
                             np.asarray(lin.gens[k][key]), eps)
            truth.append(t)
            fold.append(f)
        bad = hashes = compared = 0
        for op, params in answers:
            c = made[id(op)]
            if c is not None:
                expect, _ = ref.truth(truth[c.depth], fold[c.depth],
                                      np.asarray(children[c.name][key]), eps)
            else:
                expect = truth[op.depth]
            got = np.asarray(params[key])
            bad += ref.mismatches(got, expect)
            hashes += tensor_hash(got) != manifests[op.parent]["params"][key][
                "hash"]
            compared += expect.size
        return bad, hashes, compared

    # leaf by leaf on a few threads: NumPy and SHA-256 release the GIL
    with ThreadPoolExecutor(max_workers=8) as pool:
        counts = list(pool.map(leaf, [k for k, _, _ in lin.specs]))
    bad, hashes, compared = (sum(c[i] for c in counts) for i in range(3))
    log(f"compared {len(answers)} answers, {compared} elements")
    return {"mismatched_elements": {"value": bad, "limit": 0},
            "truth_hash_mismatches": {"value": hashes, "limit": 0},
            "answers_missing": {"value": 0 if answers else 1, "limit": 0}}
