"""Content-addressed pull-on-demand object plane (transport/objectstore).

Covers: fingerprint determinism across controllers (the handle
contract), the bounded LRU's byte-budget eviction + pin/unpin,
concurrent-fetch single-transfer dedup, corrupt-blob verify-on-arrival
with loud re-fetch from a different holder, dead-holder fast-fail
(``Mailbox.get``'s ``src_party`` poison covering blob pulls), the
``fed.get`` handle-offer broadcast (warm receivers transfer ~zero
payload bytes), welcome-by-handle byte-identity vs the eager-push
path, the welcome-carried server-opt state (the ``join_ticket`` x
``server_opt`` composition row), and checkpoint restore via a content-
cache hit with the disk state deleted.

All tests are in-process (real loopback sockets, toy payloads) — no
party subprocesses, per the ROADMAP tier-1 budget note.  The pull path
also rides the EXISTING test_quorum chaos e2e child (the rejoiner's
welcome resolves by fingerprint there).
"""

import logging
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu import objects
from rayfed_tpu.checkpoint import FedCheckpointer
from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
from rayfed_tpu.fl import compression as fl_comp
from rayfed_tpu.objects import ObjectPlaneError
from rayfed_tpu.transport import wire
from rayfed_tpu.transport.manager import TransportManager
from rayfed_tpu.transport.objectstore import BlobStore, ObjectPlane
from tests.multiproc import get_free_ports


def _mk_manager(party, cluster_ports, **job_kw):
    cc = ClusterConfig(
        parties={
            p: PartyConfig.from_dict({"address": f"127.0.0.1:{port}"})
            for p, port in cluster_ports.items()
        },
        current_party=party,
    )
    job = dict(
        device_put_received=False,
        cross_silo_timeout_s=20,
    )
    job.update(job_kw)
    return TransportManager(cc, JobConfig(**job))


@pytest.fixture()
def manager_trio():
    ports = dict(zip(("alice", "bob", "carol"), get_free_ports(3)))
    mgrs = {p: _mk_manager(p, ports) for p in ports}
    for m in mgrs.values():
        m.start()
    yield mgrs
    for m in mgrs.values():
        m.stop()


def _tree(seed=0, n=1 << 13):
    rng = np.random.default_rng(seed)
    return fl_comp.pack_tree(
        {"w": jnp.asarray(rng.standard_normal(n).astype(np.float32))}
    )


# ---------------------------------------------------------------------------
# Fingerprints + handle schema
# ---------------------------------------------------------------------------


def test_fingerprint_determinism_across_controllers(manager_trio):
    """Two controllers publishing value-identical trees derive the SAME
    fingerprint (handle equality must mean content equality), and
    different content gets a different one."""
    tree = _tree(1)
    fp_a, n_a = manager_trio["alice"].objects.publish(tree)
    fp_b, n_b = manager_trio["bob"].objects.publish(tree)
    assert (fp_a, n_a) == (fp_b, n_b)
    fp_c, _ = manager_trio["carol"].objects.publish(_tree(2))
    assert fp_c != fp_a


def test_blob_fingerprint_shares_delta_cache_machinery():
    """The handle fingerprint's first field IS the delta-cache base
    fingerprint word (crc_fingerprint over the same chunk CRCs) — one
    producer, directly cross-checkable against delta-cache state."""
    data = os.urandom(3 * 4096)
    fp = wire.blob_fingerprint(data)
    base = wire.crc_fingerprint(wire.chunk_crcs(memoryview(data)))
    parts = fp.split(".")
    assert parts[0] == "b1"
    assert parts[1] == f"{base:08x}"
    assert int(parts[2], 16) == len(data)


def test_handle_schema_roundtrip_and_validation():
    h = objects.make_blob_handle("b1.xx", 10, ["alice"])
    assert objects.is_blob_handle(h)
    assert objects.check_blob_handle(h)["fp"] == "b1.xx"
    assert not objects.is_blob_handle({"fp": "b1.xx"})
    with pytest.raises(ValueError, match="at least one holder"):
        objects.make_blob_handle("b1.xx", 10, [])
    with pytest.raises(ObjectPlaneError, match="no holders"):
        objects.check_blob_handle(
            {objects.BLOB_HANDLE_MARK: 1, "fp": "x", "n": 1, "holders": []}
        )
    with pytest.raises(ObjectPlaneError, match="understands up to"):
        objects.check_blob_handle(
            {objects.BLOB_HANDLE_MARK: 99, "fp": "x", "n": 1,
             "holders": ["a"]}
        )
    with pytest.raises(ObjectPlaneError, match="not a blob handle"):
        objects.check_blob_handle([1, 2])


def test_resolve_without_plane_is_loud():
    class _NoPlane:
        objects = None

    h = objects.make_blob_handle("b1.xx", 10, ["alice"])
    with pytest.raises(ObjectPlaneError, match="no object plane"):
        objects.maybe_resolve_handle(_NoPlane(), h)
    # Non-handles pass through untouched.
    assert objects.maybe_resolve_handle(_NoPlane(), {"a": 1}) == {"a": 1}


# ---------------------------------------------------------------------------
# BlobStore: LRU eviction + pinning
# ---------------------------------------------------------------------------


def test_lru_eviction_and_pinning():
    store = BlobStore(budget_bytes=1000)
    store.put("a", b"x" * 400)
    store.put("b", b"y" * 400)
    store.put("p", b"z" * 300, pin=True)  # over budget: evicts LRU "a"
    assert store.get("a") is None
    assert store.get("b") is not None and store.get("p") is not None
    assert store.stats["blob_store_evictions"] == 1
    # Another put: the next LRU unpinned entry ("b") goes; the pinned
    # entry and the just-added entry both stay.
    store.put("c", b"w" * 400)
    assert store.get("b") is None
    assert store.get("p") is not None and store.get("c") is not None
    # A put larger than the remaining room keeps the pinned entry AND
    # the new entry (the working set may exceed the budget; unpinned
    # LRU entries are what pay).
    store.put("d", b"v" * 900)
    assert store.get("c") is None
    assert store.get("p") is not None and store.get("d") is not None
    assert store.total_bytes() == 1200
    # Unpinning under pressure evicts the ex-pinned entry promptly.
    store.unpin("p")
    assert store.get("p") is None
    assert store.total_bytes() == 900
    assert store.pinned_bytes() == 0
    # Re-putting identical content refreshes, never duplicates.
    store.put("d", b"v" * 900)
    assert store.total_bytes() == 900
    with pytest.raises(KeyError):
        store.pin("missing")


# ---------------------------------------------------------------------------
# Pull protocol: dedup, failover, corruption
# ---------------------------------------------------------------------------


def test_pull_roundtrip_and_content_cache(manager_trio):
    mgrs = manager_trio
    tree = _tree(3)
    fp, n = mgrs["alice"].objects.publish(tree)
    handle = mgrs["alice"].objects.handle_for(fp, n)
    got = mgrs["bob"].objects.fetch(handle, timeout_s=30)
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(tree.buf)
    )
    # Raw stored bytes are byte-identical on both ends (content cache).
    assert (
        mgrs["bob"].objects.fetch_local_bytes(fp)
        == mgrs["alice"].objects.fetch_local_bytes(fp)
    )
    # Second fetch: pure cache hit, no second transfer.
    mgrs["bob"].objects.fetch(handle, timeout_s=30)
    assert mgrs["alice"].objects.stats["blob_serves"] == 1
    assert mgrs["bob"].objects.stats["blob_cache_hits"] == 1


def test_concurrent_fetch_single_transfer(manager_trio):
    """N concurrent local waiters on one fingerprint trigger ONE wire
    transfer (in-flight dedup), and all decode the same bytes."""
    mgrs = manager_trio
    tree = _tree(4, n=1 << 15)
    fp, n = mgrs["alice"].objects.publish(tree)
    handle = mgrs["alice"].objects.handle_for(fp, n)
    results, errors = [], []

    # The holder serves only once the five other fetchers are parked
    # on the owner's in-flight entry: a fetcher that started after the
    # 128 KiB pull had landed would take the cache-hit branch, and the
    # exact counts below would depend on thread start-up order.
    serve = mgrs["alice"].objects._serve
    bob_stats = mgrs["bob"].objects.stats

    def _serve_when_all_parked(requester, req):
        deadline = time.monotonic() + 20
        while (bob_stats["blob_dedup_waits"] < 5
               and time.monotonic() < deadline):
            time.sleep(0.005)
        serve(requester, req)

    mgrs["alice"].objects._serve = _serve_when_all_parked

    def _fetch():
        try:
            results.append(mgrs["bob"].objects.fetch(handle, timeout_s=30))
        except Exception as exc:  # pragma: no cover - fail loudly below
            errors.append(exc)

    threads = [threading.Thread(target=_fetch) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 6
    for got in results:
        np.testing.assert_array_equal(
            np.asarray(got.buf), np.asarray(tree.buf)
        )
    assert mgrs["alice"].objects.stats["blob_serves"] == 1
    assert mgrs["bob"].objects.stats["blob_fetches"] == 1
    assert mgrs["bob"].objects.stats["blob_dedup_waits"] == 5


def test_miss_reply_fails_over_to_next_holder(manager_trio):
    """A holder that does not hold the bytes replies an immediate miss
    notice; the pull fails over to the next named holder instead of
    waiting out the recv backstop."""
    mgrs = manager_trio
    tree = _tree(5)
    fp, n = mgrs["alice"].objects.publish(tree)
    handle = objects.make_blob_handle(fp, n, ["bob", "alice"])
    got = mgrs["carol"].objects.fetch(handle, timeout_s=30)
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(tree.buf)
    )
    assert mgrs["bob"].objects.stats["blob_serve_misses"] == 1
    assert mgrs["alice"].objects.stats["blob_serves"] == 1


def test_corrupt_blob_refetches_from_different_holder(
    manager_trio, caplog
):
    """Verify-on-arrival: a holder serving corrupted bytes is detected
    (recomputed fingerprint mismatch), reported LOUDLY, and the pull
    re-fetches from a different holder."""
    mgrs = manager_trio
    tree = _tree(6)
    fp, n = mgrs["alice"].objects.publish(tree)
    good = mgrs["alice"].objects.fetch_local_bytes(fp)
    # bob holds CORRUPT bytes under the same fingerprint (simulates
    # silent store rot — exactly what verify-on-arrival exists for).
    bad = bytearray(good)
    bad[len(bad) // 2] ^= 0xFF
    mgrs["bob"].objects.store._entries.clear()
    mgrs["bob"].objects.store._bytes = 0
    from rayfed_tpu.transport.objectstore import _Entry

    mgrs["bob"].objects.store._entries[fp] = _Entry(bytes(bad), False)
    handle = objects.make_blob_handle(fp, n, ["bob", "alice"])
    with caplog.at_level(logging.WARNING):
        got = mgrs["carol"].objects.fetch(handle, timeout_s=30)
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(tree.buf)
    )
    assert mgrs["carol"].objects.stats["blob_corrupt_refetches"] == 1
    assert any(
        "FAILED content verification" in r.message for r in caplog.records
    )
    # The verified bytes (not the corrupt ones) were cached.
    assert mgrs["carol"].objects.fetch_local_bytes(fp) == good


def test_dead_holder_fast_failover(manager_trio):
    """Satellite: the Mailbox.get dead-party fast-fail covers blob
    pulls — a pull aimed at a monitor-declared-dead holder fails over
    to the next named holder immediately (the mirror of the PR 3
    chunk-sink registration fix), not at the recv backstop."""
    import time

    mgrs = manager_trio
    tree = _tree(7)
    fp, n = mgrs["alice"].objects.publish(tree)
    # Declare bob dead on carol (what the health monitor does).
    from rayfed_tpu.exceptions import RemoteError

    err = RemoteError("bob", "ConnectionError", "declared dead").to_wire()
    loop = mgrs["carol"]._loop
    done = threading.Event()
    loop.call_soon_threadsafe(
        lambda: (mgrs["carol"]._mailbox.fail_party("bob", err),
                 done.set())
    )
    assert done.wait(5)
    handle = objects.make_blob_handle(fp, n, ["bob", "alice"])
    t0 = time.monotonic()
    got = mgrs["carol"].objects.fetch(handle, timeout_s=120)
    elapsed = time.monotonic() - t0
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(tree.buf)
    )
    # The dead-holder leg must fail fast (well under the 120s window).
    assert elapsed < 30, elapsed
    assert mgrs["carol"].objects.stats["blob_dead_holder_failovers"] == 1


def test_no_live_holder_raises_loudly(manager_trio):
    mgrs = manager_trio
    handle = objects.make_blob_handle("b1.0.0.deadbeef", 4, ["bob"])
    with pytest.raises(ObjectPlaneError, match="every named holder"):
        mgrs["carol"].objects.fetch(handle, timeout_s=30)


# ---------------------------------------------------------------------------
# fed.get handle-offer broadcast
# ---------------------------------------------------------------------------


def test_broadcast_offer_warm_receiver_skips_payload():
    """The fed.get broadcast path (send_many(blob_offer=True)): a large
    immutable PackedTree ships as a fingerprint handle; a WARM receiver
    (content-cache hit) transfers ~zero payload bytes; byte-identity
    with the eager push holds throughout."""
    ports = dict(zip(("alice", "bob"), get_free_ports(2)))
    mgrs = {
        p: _mk_manager(p, ports, blob_broadcast_min_bytes=1024)
        for p in ports
    }
    for m in mgrs.values():
        m.start()
    try:
        tree = _tree(8, n=1 << 14)
        # Cold: handle + pull.  The decoded value equals the eager path.
        ref = mgrs["alice"].send_many(
            ["bob"], tree, "u1", "d1", blob_offer=True
        )["bob"]
        got = mgrs["bob"].recv("alice", "u1", "d1").resolve(timeout=30)
        assert ref.resolve(timeout=30)
        assert objects.is_blob_handle(got)
        val = objects.maybe_resolve_handle(mgrs["bob"], got)
        np.testing.assert_array_equal(
            np.asarray(val.buf), np.asarray(tree.buf)
        )
        assert mgrs["alice"].objects.stats["blob_offers"] == 1
        # Warm: same content again — the receiver resolves from cache,
        # zero pull, and the wire moved only the tiny handle frame.
        sent0 = mgrs["alice"].get_stats()["send_bytes"]
        ref2 = mgrs["alice"].send_many(
            ["bob"], tree, "u2", "d2", blob_offer=True
        )["bob"]
        got2 = mgrs["bob"].recv("alice", "u2", "d2").resolve(timeout=30)
        assert ref2.resolve(timeout=30)
        val2 = objects.maybe_resolve_handle(mgrs["bob"], got2)
        np.testing.assert_array_equal(
            np.asarray(val2.buf), np.asarray(tree.buf)
        )
        warm_bytes = mgrs["alice"].get_stats()["send_bytes"] - sent0
        assert warm_bytes < 0.1 * int(tree.buf.nbytes), warm_bytes
        assert mgrs["alice"].objects.stats["blob_serves"] == 1
        # Below the floor / non-PackedTree: no offer, eager push.
        assert mgrs["alice"].objects.maybe_offer({"x": 1}, 1024) is None
        assert (
            mgrs["alice"].objects.maybe_offer(_tree(9, n=8), 1024) is None
        )
        # Offers disabled: no handle regardless of size.
        assert mgrs["alice"].objects.maybe_offer(tree, None) is None
    finally:
        for m in mgrs.values():
            m.stop()


# ---------------------------------------------------------------------------
# Welcome-by-handle + server-opt state (join_ticket x server_opt row)
# ---------------------------------------------------------------------------


def test_welcome_by_handle_rejoin_byte_identity(manager_trio):
    """A welcome that names the model by fingerprint resolves to BYTE-
    identical state vs the eager-push welcome (receiver-decoded wire
    bytes on both paths)."""
    mgrs = manager_trio
    model = _tree(10, n=1 << 14)

    def bob_received():
        # The joiner's side of the link: complete once its recv
        # resolves, and nothing else of this test is sent to bob.
        return mgrs["bob"].get_stats()["receive_bytes"]

    # Eager path: coordinator pushes the params inline.
    received0 = bob_received()
    mgrs["alice"].send("bob", {"params": model}, "w.eager", "roster")
    eager = mgrs["bob"].recv("alice", "w.eager", "roster").resolve(
        timeout=30
    )["params"]
    eager_bytes = bob_received() - received0
    # Handle path: coordinator publishes + sends the handle; the joiner
    # pulls (cold) and decodes.  Residency-canonicalized, exactly like
    # the quorum loop's publish sites.
    fp, n = mgrs["alice"].objects.publish(objects.canonical_host(model))
    welcome = {
        "round": 3, "epoch": 2, "members": ["alice", "bob"],
        "coordinator": "alice",
        "model": mgrs["alice"].objects.handle_for(fp, n, ["bob"]),
    }
    mgrs["alice"].send("carol", welcome, "w.handle", "roster")
    got = mgrs["carol"].recv("alice", "w.handle", "roster").resolve(
        timeout=30
    )
    resolved = objects.maybe_resolve_handle(mgrs["carol"], got["model"])
    np.testing.assert_array_equal(
        np.asarray(resolved.buf), np.asarray(eager.buf)
    )
    assert resolved.spec.entries == eager.spec.entries
    # Warm rejoin: a party already holding the content (bob got the
    # eager push's VALUE — its canonical publish derives the SAME
    # fingerprint the coordinator's handle names, despite the two
    # controllers holding different residencies) resolves with zero
    # transfer.
    mgrs["bob"].objects.publish(objects.canonical_host(eager))
    serves0 = mgrs["alice"].objects.stats["blob_serves"]
    received0 = bob_received()
    mgrs["alice"].send("bob", welcome, "w.warm", "roster")
    got_warm = mgrs["bob"].recv("alice", "w.warm", "roster").resolve(
        timeout=30
    )
    resolved_warm = mgrs["bob"].objects.fetch(
        got_warm["model"], timeout_s=30
    )
    np.testing.assert_array_equal(
        np.asarray(resolved_warm.buf), np.asarray(eager.buf)
    )
    assert mgrs["alice"].objects.stats["blob_serves"] == serves0
    # rejoin_welcome_bytes_frac: the warm welcome moves the handle
    # alone, at most a tenth of the eager welcome's bytes.
    warm_bytes = bob_received() - received0
    assert 0 < warm_bytes <= 0.1 * eager_bytes, (warm_bytes, eager_bytes)


def test_welcome_server_opt_state_roundtrip(manager_trio):
    """The welcome-carried server-opt state decodes byte-identical to
    the coordinator's replica, and _apply_ticket_server_opt loads it
    into the joiner's optimizer (join_ticket x server_opt row)."""
    from rayfed_tpu.fl.quorum import _apply_ticket_server_opt
    from rayfed_tpu.fl.server_opt import (
        PackedServerOptimizer,
        PackedServerState,
        describe_server_opt,
    )
    from rayfed_tpu.fl import fedac

    mgrs = manager_trio
    spec = fedac(1.0, 3.0, 0.5)
    state = PackedServerState(
        spec.kind, spec.hyper,
        (np.linspace(-1, 1, 256).astype(np.float32),),
    )
    fp, n = mgrs["alice"].objects.publish(state)
    ticket = {
        "server_opt": describe_server_opt(spec),
        "server_state": mgrs["alice"].objects.handle_for(fp, n),
    }
    joiner = PackedServerOptimizer(spec)
    _apply_ticket_server_opt(
        mgrs["bob"], ticket, joiner, describe_server_opt(spec)
    )
    np.testing.assert_array_equal(
        np.asarray(joiner.state.bufs[0]), np.asarray(state.bufs[0])
    )
    assert (joiner.state.kind, joiner.state.hyper) == (
        state.kind, state.hyper,
    )


def test_ticket_server_opt_mismatch_is_loud(manager_trio):
    """Spec mismatches and missing state both refuse LOUDLY, naming
    both sides — a silent mismatch would reset the run's optimizer
    trajectory on the joiner's first coordinator lease."""
    from rayfed_tpu.fl.quorum import (
        QuorumRoundError,
        _apply_ticket_server_opt,
    )
    from rayfed_tpu.fl.server_opt import (
        PackedServerOptimizer,
        describe_server_opt,
    )
    from rayfed_tpu.fl import fedac, server_momentum

    mgrs = manager_trio
    mine = fedac(1.0, 3.0, 0.5)
    sopt = PackedServerOptimizer(mine)
    descr = describe_server_opt(mine)
    # Welcome stamped with a DIFFERENT spec.
    with pytest.raises(QuorumRoundError, match="server_opt mismatch"):
        _apply_ticket_server_opt(
            mgrs["bob"],
            {"server_opt": describe_server_opt(server_momentum(0.5, 0.9))},
            sopt, descr,
        )
    # Welcome from a pre-object-plane coordinator: no stamp at all.
    with pytest.raises(QuorumRoundError, match="no server_opt stamp"):
        _apply_ticket_server_opt(mgrs["bob"], {}, sopt, descr)
    # Stamp matches but the state handle is missing.
    with pytest.raises(QuorumRoundError, match="no server_state"):
        _apply_ticket_server_opt(
            mgrs["bob"], {"server_opt": descr}, sopt, descr
        )
    # Plain runs entering a plain-stamped welcome stay clean.
    _apply_ticket_server_opt(
        mgrs["bob"], {"server_opt": {"kind": "none"}}, None,
        {"kind": "none"},
    )


# ---------------------------------------------------------------------------
# Checkpoint restore via content-cache hit
# ---------------------------------------------------------------------------


def test_checkpoint_restore_via_cache_hit(tmp_path, manager_trio):
    """save() stamps the snapshot's content fingerprint and publishes
    the bytes; restore() resolves by fingerprint BEFORE touching disk —
    demonstrated by deleting the on-disk state files and still
    restoring byte-identically."""
    plane = manager_trio["alice"].objects
    ckpt = FedCheckpointer(
        str(tmp_path / "ckpt"), "alice", use_orbax=False,
        object_plane=plane,
    )
    state = {
        "params": {"w": np.linspace(0, 1, 512).astype(np.float32)},
        "round": 7,
    }
    ckpt.save(7, state, metadata={"quorum_session": "s"})
    meta = ckpt.load_metadata(7)
    assert meta["blob_fp"].startswith("b1.")
    # Disk restore first (fresh checkpointer, NO plane): the baseline.
    disk_ckpt = FedCheckpointer(
        str(tmp_path / "ckpt"), "alice", use_orbax=False,
        object_plane=BlobStorePlaneStub(),
    )
    target = {"params": {"w": np.zeros(512, np.float32)}, "round": 0}
    r_disk, s_disk = disk_ckpt.restore(7, target=target)
    # Now delete the state file: only meta.json + the content cache
    # remain — restore must resolve from the cache.
    state_file = os.path.join(ckpt._round_dir(7), "state.npz")
    os.remove(state_file)
    r_hit, s_hit = ckpt.restore(7, target=target)
    assert (r_disk, r_hit) == (7, 7)
    np.testing.assert_array_equal(
        s_hit["params"]["w"], s_disk["params"]["w"]
    )
    assert s_hit["round"] == 7
    # A checkpointer whose plane misses falls back to disk — which is
    # gone here, so it raises (proving the hit path never read disk).
    with pytest.raises(FileNotFoundError):
        disk_ckpt.restore(7, target=target)


class BlobStorePlaneStub:
    """A plane that never hits — forces the disk path."""

    def fetch_local_bytes(self, fp):
        return None

    def publish(self, value=None, data=None, pin=False):
        return ("", 0)


def test_checkpoint_without_plane_unchanged(tmp_path):
    """No runtime, no plane: the durable disk path works exactly as
    before (no stamp, no publish, no errors)."""
    ckpt = FedCheckpointer(str(tmp_path / "c"), "bob", use_orbax=False)
    state = {"w": np.arange(8, dtype=np.float32)}
    ckpt.save(1, state)
    r, s = ckpt.restore(target={"w": np.zeros(8, np.float32)})
    assert r == 1
    np.testing.assert_array_equal(s["w"], state["w"])
    assert "blob_fp" not in ckpt.load_metadata(1)


def test_stats_snapshot_surfaces_plane_counters(manager_trio):
    stats = manager_trio["alice"].get_stats()["object_plane"]
    for key in ("blob_cache_hits", "blob_serves", "blob_cache_bytes",
                "blob_store_evictions", "blob_pinned_bytes"):
        assert key in stats
