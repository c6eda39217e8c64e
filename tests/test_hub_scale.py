"""Production-scale hub (DESIGN.md §16): multi-tenant routing, live-traffic
GC with reader leases, read replicas with staleness fallback, worker-pool
backpressure — every fault scenario driven through the deterministic
kill-point harness and closed with the §16 invariant bundle."""

import collections
import http.client
import shutil
import tempfile
import threading
from urllib.parse import urlsplit

import pytest

from repro.core import LineageGraph
from repro.hub import HubService, start_in_thread
from repro.hub.replica import ReplicaHub, ReplicaSetTransport
from repro.remote import HttpTransport, RemoteState, clone, pull, push
from repro.store import ArtifactStore

from harness import (KillPointError, AppTransport, assert_bit_identical,
                     check_service, crash_at, fired)
from helpers import finetune_like, make_chain_model
from hyp_compat import given, settings, st


def _repo(path, **store_kw):
    path = str(path)
    return LineageGraph(path=path, store=ArtifactStore(root=path, **store_kw))


def _seed(path, seed=0, name="m@v1", d=32):
    g = _repo(path)
    g.add_node(make_chain_model(seed=seed, d=d), name)
    return g


@pytest.fixture
def service_hub(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    server, _ = start_in_thread(service)
    yield service, server.url
    server.shutdown()
    server.server_close()


# ---------------------------------------------------------------------------
# Multi-tenant routing over one shared CAS
# ---------------------------------------------------------------------------


def test_multi_tenant_routing_shared_cas_dedup(tmp_path, service_hub):
    service, url = service_hub
    ga = _seed(tmp_path / "a", seed=0)
    rep_a = push(ga, HttpTransport(url + "/r/alpha"),
                 state=RemoteState(ga.path, "origin"))
    # same base bits into a second tenant: the shared CAS dedups the transfer
    gb = _seed(tmp_path / "b", seed=0)
    base = gb.store.load_artifact(gb.nodes["m@v1"].artifact_ref)
    gb.add_node(finetune_like(base, seed=7), "m@v2")
    rep_b = push(gb, HttpTransport(url + "/r/beta"),
                 state=RemoteState(gb.path, "origin"))
    assert rep_a.published and rep_b.published
    # beta re-sent only its finetuned half; the shared base deduped away
    assert rep_b.objects_transferred < rep_b.objects_total

    names = {r["name"] for r in HttpTransport(url).list_repos()}
    assert {"alpha", "beta"} <= names

    # tenants are isolated: alpha never sees beta's lineage
    doc_a = HttpTransport(url + "/r/alpha").fetch_lineage()
    assert {n["name"] for n in doc_a["nodes"]} == {"m@v1"}

    clone(url + "/r/alpha", str(tmp_path / "ca"))
    assert_bit_identical(ga, _repo(tmp_path / "ca"))
    cb = _repo(tmp_path / "cb")
    pull(cb, HttpTransport(url + "/r/beta"))
    assert_bit_identical(gb, cb)
    check_service(service)


_HUB_PROCESS = """
import sys
from repro.cli import main
rc = main(["-C", sys.argv[1], "hub", "serve", "--port", "0"])
import jax._src.xla_bridge as xla_bridge
print("JAX_BACKENDS", sorted(xla_bridge._backends), flush=True)
sys.exit(rc)
"""


def test_hub_process_starts_no_jax_backend(tmp_path):
    """A hub only moves bytes. Starting a JAX backend would take the chip
    from a ``serve`` or trainer on the same TPU host."""
    import os
    import signal
    import subprocess
    import sys
    g = _seed(tmp_path / "a", seed=0)
    base = g.store.load_artifact(g.nodes["m@v1"].artifact_ref)
    g.add_node(finetune_like(base, seed=3), "m@v2")
    g.add_version_edge("m@v1", "m@v2")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    proc = subprocess.Popen(
        [sys.executable, "-c", _HUB_PROCESS, str(tmp_path / "hub")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        url = proc.stdout.readline().split(" at ")[1].split()[0]
        t = HttpTransport(url)
        assert push(g, t, state=RemoteState(g.path, "origin")).published
        clone(url, str(tmp_path / "c"))
        assert_bit_identical(g, _repo(tmp_path / "c"))
        t.run_gc(confirm_cycles=1)
        assert t.server_stats()["errors_500"] == 0
    finally:
        proc.send_signal(signal.SIGINT)
        out, err = proc.communicate(timeout=60)
    assert proc.returncode == 0, err[-2000:]
    assert out.strip().splitlines()[-1] == "JAX_BACKENDS []"


def test_token_hub_never_creates_repos_for_bad_tokens(tmp_path):
    service = HubService(str(tmp_path / "hub"), token="sekrit")
    server, _ = start_in_thread(service)
    try:
        bad = HttpTransport(server.url + "/r/newrepo", token="wrong")
        with pytest.raises(PermissionError):
            bad.fetch_lineage_versioned()
        assert "newrepo" not in service.repo_names()
        ok = HttpTransport(server.url + "/r/newrepo", token="sekrit")
        ok.fetch_lineage_versioned()
        assert "newrepo" in service.repo_names()
    finally:
        server.shutdown()
        server.server_close()


def test_delete_repo_then_gc_reclaims_only_its_bytes(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    ga = _seed(tmp_path / "a", seed=0)
    push(ga, AppTransport(service.repo("alpha")),
         state=RemoteState(ga.path, "origin"))
    gb = _seed(tmp_path / "b", seed=99)  # disjoint bits: all beta-private
    push(gb, AppTransport(service.repo("beta")),
         state=RemoteState(gb.path, "origin"))
    check_service(service)

    service.delete_repo("beta")
    assert "beta" not in service.repo_names()
    # published keys graduated out of import grace at finalize, so the
    # deleted repo's privates go candidate -> confirmed in two cycles
    reports = [service.run_gc() for _ in range(3)]
    assert sum(r["reclaimed_bytes"] for r in reports) > 0
    assert any(r["confirmed_orphans"] > 0 for r in reports)

    # alpha unscathed, bit-for-bit
    g2 = _repo(tmp_path / "chk")
    pull(g2, AppTransport(service.repo("alpha")))
    assert_bit_identical(ga, g2)
    # compaction then rewrites the dead pack payload away
    before = service.store.cas.pack_stats()["pack_dead_bytes"]
    report = service.compact()
    assert report["dead_bytes_after"] <= before
    check_service(service, converged=True)


# ---------------------------------------------------------------------------
# Kill-point fault injection: publish, mget, GC, replica sync
# ---------------------------------------------------------------------------


def test_publish_crash_before_commit_point_loses_nothing(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    app = service.repo("alpha")
    g = _seed(tmp_path / "src", seed=3)
    with crash_at("hub.publish.pre_replace"):
        with pytest.raises(KillPointError):
            push(g, AppTransport(app), state=RemoteState(g.path, "origin"))
    payload, _ = app.lineage()
    assert payload is None          # the swap never happened
    rep = push(g, AppTransport(app), state=RemoteState(g.path, "origin"))
    assert rep.published            # resume lands cleanly
    check_service(service)


def test_publish_crash_after_commit_point_is_already_durable(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    app = service.repo("alpha")
    g = _seed(tmp_path / "src", seed=4)
    with crash_at("hub.publish.post_replace"):
        with pytest.raises(KillPointError):
            push(g, AppTransport(app), state=RemoteState(g.path, "origin"))
    payload, _ = app.lineage()
    assert payload is not None      # os.replace is the commit point
    assert {n["name"] for n in payload["nodes"]} == {"m@v1"}
    # the client believed it failed; its retry must converge, not duplicate
    rep = push(g, AppTransport(app), state=RemoteState(g.path, "origin"))
    assert rep.published
    check_service(service)


def test_mget_mid_stream_abort_retried_to_bit_identity(tmp_path, service_hub):
    service, url = service_hub
    g = _repo(tmp_path / "src")
    g.add_node(make_chain_model(seed=0, d=48, n_layers=6), "m@v1")
    push(g, HttpTransport(url + "/r/alpha"),
         state=RemoteState(g.path, "origin"))
    g2 = _repo(tmp_path / "dst")
    with crash_at("hub.mget.record", after=2):
        # the hub aborts the connection mid-pack; the short read rides the
        # client's ordinary retry path and the second attempt is clean
        pull(g2, HttpTransport(url + "/r/alpha", retries=3, backoff=0.01))
    assert fired("hub.mget.record") == 1
    assert_bit_identical(g, g2)
    check_service(service)


def test_gc_crash_before_zeroing_never_loses_objects(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    ga = _seed(tmp_path / "a", seed=0)
    push(ga, AppTransport(service.repo("alpha")),
         state=RemoteState(ga.path, "origin"))
    gb = _seed(tmp_path / "b", seed=99)
    push(gb, AppTransport(service.repo("beta")),
         state=RemoteState(gb.path, "origin"))
    service.delete_repo("beta")
    with crash_at("hub.gc.pre_zero"):
        with pytest.raises(KillPointError):
            service.run_gc()                    # dies holding nothing zeroed
    check_service(service)                      # crash was side-effect free
    total = sum(service.run_gc()["reclaimed_bytes"] for _ in range(4))
    assert total > 0                            # later cycles still converge
    g2 = _repo(tmp_path / "chk")
    pull(g2, AppTransport(service.repo("alpha")))
    assert_bit_identical(ga, g2)
    check_service(service, converged=True)


def test_reader_lease_defers_physical_reclaim(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    ga = _seed(tmp_path / "a", seed=0)
    push(ga, AppTransport(service.repo("alpha")),
         state=RemoteState(ga.path, "origin"))
    gb = _seed(tmp_path / "b", seed=99)
    push(gb, AppTransport(service.repo("beta")),
         state=RemoteState(gb.path, "origin"))
    store = service.store
    beta_only = (set(store.expected_refcounts(service.repo("beta").roots()))
                 - set(store.expected_refcounts(service.repo("alpha").roots())))
    assert beta_only
    service.delete_repo("beta")
    with store.cas.pin():                       # an in-flight reader
        for _ in range(3):
            service.run_gc()
        assert store.cas.deferred_dead_bytes() > 0
        for k in beta_only:                     # logically dead, still readable
            assert store.cas.get_bytes(k)
    assert store.cas.deferred_dead_bytes() == 0  # reclaimed at lease release
    for k in beta_only:
        assert not store.cas.has(k)
    check_service(service, converged=True)


def test_replica_crash_stays_stale_and_clients_fall_back(tmp_path, service_hub):
    service, url = service_hub
    g = _seed(tmp_path / "src", seed=0)
    push(g, HttpTransport(url + "/r/alpha"),
         state=RemoteState(g.path, "origin"))

    replica = ReplicaHub(str(tmp_path / "rep"), url)
    with crash_at("replica.sync.pre_publish"):
        with pytest.raises(KillPointError):
            replica.sync_once()
    rserver, _ = start_in_thread(replica.service)
    try:
        # replica holds objects but no document: stale by etag, so every
        # read falls back to the primary — and stays bit-identical
        rs = ReplicaSetTransport(HttpTransport(url + "/r/alpha"),
                                 [HttpTransport(rserver.url + "/r/alpha")])
        g2 = _repo(tmp_path / "d1")
        pull(g2, rs)
        assert rs.fallbacks > 0 and rs.replica_reads == 0
        assert_bit_identical(g, g2)

        # after a clean sync the replica serves reads (same etag as primary)
        replica.sync_once()
        rs = ReplicaSetTransport(HttpTransport(url + "/r/alpha"),
                                 [HttpTransport(rserver.url + "/r/alpha")])
        g3 = _repo(tmp_path / "d2")
        pull(g3, rs)
        assert rs.replica_reads > 0
        assert_bit_identical(g, g3)
        check_service(replica.service)
        # a client mutation against the replica is refused, not mirrored
        with pytest.raises(Exception):
            HttpTransport(rserver.url + "/r/alpha").publish_lineage(
                {"nodes": []})
    finally:
        rserver.shutdown()
        rserver.server_close()
    check_service(service)


# ---------------------------------------------------------------------------
# Worker pool: bounded concurrency + load shedding
# ---------------------------------------------------------------------------


def test_backpressure_sheds_503_with_retry_after(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    server, _ = start_in_thread(service, max_workers=2, queue_depth=1)
    server.delay_s = 0.2
    host = urlsplit(server.url)
    codes = collections.Counter()
    retry_after = []
    lock = threading.Lock()

    def hit():
        conn = http.client.HTTPConnection(host.hostname, host.port)
        try:
            conn.request("GET", "/api/ping")
            resp = conn.getresponse()
            resp.read()
            with lock:
                codes[resp.status] += 1
                if resp.status == 503:
                    retry_after.append(resp.getheader("Retry-After"))
        finally:
            conn.close()

    try:
        threads = [threading.Thread(target=hit) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        server.shutdown()
        server.server_close()
    # 3 slots (2 workers + 1 queued); the other 9 must shed, not queue
    assert codes[200] == 3 and codes[503] == 9, codes
    assert set(retry_after) == {"1"}
    assert service.default.stats["sheds_503"] == 9
    assert service.default.stats["errors_500"] == 0


# ---------------------------------------------------------------------------
# Property test: random op sequences preserve the §16 invariants
# ---------------------------------------------------------------------------

TENANTS = ("alpha", "beta", "gamma")


def _run_op_sequence(ops):
    """Interpret (op, tenant_idx) pairs against a fresh HubService and
    close with the full invariant bundle + per-tenant bit-identity."""
    root = tempfile.mkdtemp(prefix="mgit-hubprop-")
    try:
        service = HubService(root + "/hub")
        mirrors = {}
        version = 0
        for op, idx in ops:
            tenant = TENANTS[idx % len(TENANTS)]
            if op == "push":
                version += 1
                g = mirrors.get(tenant)
                if g is None:
                    g = _seed(f"{root}/{tenant}-{version}", seed=0,
                              name=f"{tenant}@v1")
                    mirrors[tenant] = g
                else:
                    head = sorted(g.nodes)[-1]
                    art = g.store.load_artifact(g.nodes[head].artifact_ref)
                    g.add_node(finetune_like(art, seed=version),
                               f"{tenant}@v{version}")
                push(g, AppTransport(service.repo(tenant)),
                     state=RemoteState(g.path, "origin"))
            elif op == "delete":
                if tenant in service.repo_names():
                    service.delete_repo(tenant)
                mirrors.pop(tenant, None)
            elif op == "gc":
                service.run_gc()
            elif op == "compact":
                service.compact()
        # drain: with no further traffic, a handful of quiescent cycles must
        # reclaim every orphan — check_service then proves full convergence
        for _ in range(4):
            service.run_gc()
        check_service(service, converged=True)
        for tenant, g in mirrors.items():
            g2 = _repo(f"{root}/verify-{tenant}")
            pull(g2, AppTransport(service.repo(tenant)))
            assert_bit_identical(g, g2)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_scripted_hub_op_sequence():
    """Deterministic pass through the property interpreter (runs in tier-1
    even where hypothesis is absent): exercises push/delete/gc/compact
    interleavings including post-delete re-creation of a tenant."""
    _run_op_sequence([
        ("push", 0), ("push", 1), ("push", 0), ("gc", 0), ("delete", 1),
        ("gc", 0), ("compact", 0), ("gc", 0), ("gc", 0), ("push", 1),
        ("gc", 0), ("compact", 0), ("push", 2), ("delete", 0), ("gc", 0),
        ("gc", 0), ("gc", 0), ("compact", 0),
    ])


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(
    st.sampled_from(["push", "delete", "gc", "compact"]),
    st.integers(min_value=0, max_value=len(TENANTS) - 1)),
    min_size=1, max_size=12))
def test_random_hub_op_sequences_hold_invariants(ops):
    _run_op_sequence(ops)


# ---------------------------------------------------------------------------
# Stress: 64 threads racing GC/compaction over HTTP (tier-2, -m slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_stress_64_threads_racing_gc(tmp_path):
    service = HubService(str(tmp_path / "hub"))
    server, _ = start_in_thread(service, max_workers=16, queue_depth=64)
    stop = threading.Event()
    errors = []

    def maintenance():
        while not stop.is_set():
            try:
                service.run_gc()
                service.compact()
            except Exception as exc:  # pragma: no cover - diagnostic aid
                errors.append(("maintenance", exc))
            stop.wait(0.05)

    def worker(i):
        tenant = TENANTS[i % len(TENANTS)]
        try:
            g = _repo(tmp_path / f"w{i}")
            g.add_node(make_chain_model(seed=i, d=16, n_layers=2),
                       f"w{i}@v1")
            t = HttpTransport(f"{server.url}/r/{tenant}",
                              retries=6, backoff=0.05)
            push(g, t, state=RemoteState(g.path, "origin"))
            g2 = _repo(tmp_path / f"v{i}")
            pull(g2, HttpTransport(f"{server.url}/r/{tenant}",
                                   retries=6, backoff=0.05))
            assert_bit_identical(g, g2, names=[f"w{i}@v1"])
        except Exception as exc:
            errors.append((i, exc))

    gc_thread = threading.Thread(target=maintenance, daemon=True)
    gc_thread.start()
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set()
        gc_thread.join(10)
        server.shutdown()
        server.server_close()
    assert not errors, errors[:3]
    stats = service.default.stats
    assert stats["errors_500"] == 0          # 503s are fine; 500s are not
    for _ in range(4):                       # quiescent drain, then converge
        service.run_gc()
    check_service(service, converged=True)
