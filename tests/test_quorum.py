"""Quorum (k-of-n) rounds, elastic membership, and the chaos harness e2e.

Unit layer: StreamingAggregator quorum cutoffs (no sockets).  Integration
layer: multiprocess parties over the real transport — full-participation
parity (quorum=n is byte-identical to the classic streaming path), and
THE chaos round: a seeded schedule injects one straggler past the round
deadline and one hard party crash at N=4; the surviving controllers must
complete every round with the documented reweighted result, the late
contribution must fold into the next round via dga_correct, the crashed
party must rejoin through ``fed.join`` (roster epoch advances, no
surviving runtime restarts), and a ``fed.leave`` departure must drop the
leaver at a round boundary.  The survivors' results are asserted
BIT-EXACTLY against an in-process replay of the FedAvg recurrence driven
by the recorded per-round member log.
"""

import json
import os

import numpy as np
import pytest

from tests.multiproc import make_cluster, run_parties

jnp = pytest.importorskip("jax.numpy")


# ---------------------------------------------------------------------------
# Unit: StreamingAggregator quorum cutoff
# ---------------------------------------------------------------------------


def _packed(trees):
    from rayfed_tpu.fl import compression as C

    return [C.compress(t, packed=True) for t in trees]


def _trees(n=3):
    return [
        {"w": jnp.arange(10, dtype=jnp.float32) * 0.1 + i,
         "n": np.arange(4, dtype=np.int32) + i}
        for i in range(n)
    ]


def test_quorum_all_arrived_is_byte_identical():
    from rayfed_tpu.fl.fedavg import packed_weighted_sum
    from rayfed_tpu.fl.streaming import StreamingAggregator

    packed = _packed(_trees())
    agg = StreamingAggregator(3, quorum=3, labels=["a", "b", "c"])
    for i, p in enumerate(packed):
        agg.add_local(i, p)
    r = agg.result(timeout=30, deadline_s=30)
    ref = packed_weighted_sum(packed, None)
    assert np.array_equal(np.asarray(r.buf), np.asarray(ref.buf))
    assert agg.quorum_members == [0, 1, 2]
    assert agg.stats["quorum_excluded"] == 0


def test_quorum_deadline_cutoff_matches_subset_reduce():
    from rayfed_tpu.fl.fedavg import packed_weighted_sum
    from rayfed_tpu.fl.streaming import StreamingAggregator

    packed = _packed(_trees())
    agg = StreamingAggregator(3, quorum=2, labels=["a", "b", "c"])
    agg.add_local(0, packed[0])
    agg.add_local(2, packed[2])
    r = agg.result(timeout=30, deadline_s=0.3)
    ref = packed_weighted_sum([packed[0], packed[2]], None)
    assert np.array_equal(np.asarray(r.buf), np.asarray(ref.buf))
    np.testing.assert_array_equal(
        np.asarray(r.passthrough[0]), np.asarray(ref.passthrough[0])
    )
    assert agg.quorum_members == [0, 2]
    assert agg.stats["quorum_excluded"] == 1


def test_quorum_failed_stream_completes_without_deadline_burn():
    import time

    from rayfed_tpu.fl.fedavg import packed_weighted_sum
    from rayfed_tpu.fl.streaming import StreamingAggregator

    packed = _packed(_trees())
    agg = StreamingAggregator(
        3, quorum=2, labels=["a", "b", "c"], weights=[1.0, 2.0, 3.0]
    )
    agg.add_local(0, packed[0])
    agg.add_local(2, packed[2])
    agg._on_error(1, RuntimeError("injected death"))
    t0 = time.monotonic()
    r = agg.result(timeout=30, deadline_s=25)
    assert time.monotonic() - t0 < 10  # not the 25s deadline
    ref = packed_weighted_sum([packed[0], packed[2]], [1.0, 3.0])
    assert np.array_equal(np.asarray(r.buf), np.asarray(ref.buf))


def test_errored_stream_recovers_on_clean_completion():
    """A stream that failed (corrupt mid-fold / transient death) and
    then delivered clean bytes rejoins the fold pool: the round must
    include all contributions, not stall the ordered chain at the
    recovered index or cut it out (code-review finding)."""
    from rayfed_tpu.fl.fedavg import packed_weighted_sum
    from rayfed_tpu.fl.streaming import StreamingAggregator

    packed = _packed(_trees())
    agg = StreamingAggregator(3, quorum=2, labels=["a", "b", "c"])
    agg.add_local(0, packed[0])
    agg._on_error(1, RuntimeError("transient"))
    # The sender's retry delivers the full clean payload.
    from rayfed_tpu.transport import wire as wire_mod

    payload = b"".join(
        bytes(b.produce() if isinstance(b, wire_mod.LazyBuffer) else b)
        for b in wire_mod.encode_payload(packed[1])
    )
    agg._on_complete(1, payload)
    agg.add_local(2, packed[2])
    r = agg.result(timeout=30, deadline_s=20)
    ref = packed_weighted_sum(packed, None)
    assert np.array_equal(np.asarray(r.buf), np.asarray(ref.buf))
    assert agg.quorum_members == [0, 1, 2]


def test_quorum_unreachable_fails_loudly():
    from rayfed_tpu.fl.streaming import StreamingAggregator

    packed = _packed(_trees())
    agg = StreamingAggregator(3, quorum=3, labels=["a", "b", "c"])
    agg.add_local(0, packed[0])
    agg._on_error(1, RuntimeError("dead"))
    agg._on_error(2, RuntimeError("dead too"))
    with pytest.raises(RuntimeError, match="quorum 3/3 unreachable"):
        agg.result(timeout=10, deadline_s=1)


def test_transient_error_recovers_before_deadline_verdict():
    """The unreachable verdict is deadline-gated: a stream error that
    clears (clean retry) BEFORE the deadline must not kill a round
    whose quorum it makes (code-review finding: the eager verdict
    defeated the recovery path)."""
    from rayfed_tpu.fl.fedavg import packed_weighted_sum
    from rayfed_tpu.fl.streaming import StreamingAggregator
    from rayfed_tpu.transport import wire as wire_mod

    packed = _packed(_trees())
    agg = StreamingAggregator(3, quorum=3, labels=["a", "b", "c"])
    agg.add_local(0, packed[0])
    # Two failures make the quorum transiently unreachable (1 alive of
    # a 3-quorum)...
    agg._on_error(1, RuntimeError("transient"))
    agg._on_error(2, RuntimeError("transient"))
    # ...but both recover with clean retries before the deadline.
    for i in (1, 2):
        payload = b"".join(
            bytes(b.produce() if isinstance(b, wire_mod.LazyBuffer) else b)
            for b in wire_mod.encode_payload(packed[i])
        )
        agg._on_complete(i, payload)
    r = agg.result(timeout=30, deadline_s=10)
    ref = packed_weighted_sum(packed, None)
    assert np.array_equal(np.asarray(r.buf), np.asarray(ref.buf))
    assert agg.quorum_members == [0, 1, 2]


def test_timeout_names_missing_parties():
    from rayfed_tpu.exceptions import PartyWaitTimeout
    from rayfed_tpu.fl.streaming import StreamingAggregator

    packed = _packed(_trees(2))
    agg = StreamingAggregator(2, labels=["alice", "bob"])
    agg.add_local(0, packed[0])
    with pytest.raises(PartyWaitTimeout) as ei:
        agg.result(timeout=0.4)
    assert ei.value.missing_parties == ["bob"]


def test_quorum_validation():
    from rayfed_tpu.fl.streaming import StreamingAggregator

    with pytest.raises(ValueError, match="quorum"):
        StreamingAggregator(3, quorum=4)
    with pytest.raises(ValueError, match="labels"):
        StreamingAggregator(3, labels=["a"])
    agg = StreamingAggregator(2, labels=["a", "b"])
    with pytest.raises(ValueError, match="deadline_s needs quorum"):
        agg.result(timeout=1, deadline_s=1)


def test_run_fedavg_rounds_quorum_validation():
    from rayfed_tpu.fl import run_fedavg_rounds
    from rayfed_tpu.fl.fedopt import server_sgd

    trainers = {"a": object(), "b": object()}
    with pytest.raises(ValueError, match="quorum must be in"):
        run_fedavg_rounds(trainers, {}, 1, quorum=3, compress_wire=True,
                          packed_wire=True)
    with pytest.raises(ValueError, match="compress_wire"):
        run_fedavg_rounds(trainers, {}, 1, quorum=2)
    with pytest.raises(ValueError, match="incompatible"):
        run_fedavg_rounds(trainers, {}, 1, quorum=2, compress_wire=True,
                          packed_wire=True, server_opt=server_sgd(0.1))
    with pytest.raises(ValueError, match="round_deadline_s only"):
        run_fedavg_rounds(trainers, {}, 1, round_deadline_s=5.0)
    with pytest.raises(ValueError, match="join_ticket only"):
        run_fedavg_rounds(trainers, {}, 1, join_ticket={})
    with pytest.raises(ValueError, match="round_log only"):
        run_fedavg_rounds(trainers, {}, 1, round_log=[])


def test_quorum_composes_with_checkpointer_validation():
    """quorum= × checkpointer= is no longer mutually exclusive — the
    validation must accept the pair (the resume story is tested e2e)."""
    from rayfed_tpu.fl import run_fedavg_rounds

    # checkpoint_every without a checkpointer still fails first; pairing
    # quorum with a checkpointer must NOT hit the incompat arm (the call
    # proceeds past validation and fails later for runtime reasons).
    with pytest.raises(ValueError, match="checkpoint_every set without"):
        run_fedavg_rounds({"a": object()}, {}, 1, quorum=1,
                          compress_wire=True, packed_wire=True,
                          checkpoint_every=2)


# ---------------------------------------------------------------------------
# Unit: deterministic coordinator succession
# ---------------------------------------------------------------------------


def test_roster_successor_rule():
    from rayfed_tpu.transport.manager import roster_successor

    members = ["alice", "bob", "carol", "dave"]
    # Next alive after the coordinator on the sorted ring.
    assert roster_successor(members, "alice") == "bob"
    assert roster_successor(members, "alice", dead=["bob"]) == "carol"
    assert roster_successor(members, "dave") == "alice"  # wraps
    # The departed coordinator keeps its canonical position even when it
    # is already off the roster, so iterated successions (alice dies,
    # then bob dies) agree with a one-shot derivation from the pinned
    # coordinator over the surviving roster.
    assert roster_successor(["bob", "carol"], "alice") == "bob"
    s1 = roster_successor(members, "alice", dead=["alice"])
    s2 = roster_successor(["bob", "carol", "dave"], s1, dead=[s1])
    assert (s1, s2) == ("bob", "carol")
    assert roster_successor(["carol", "dave"], "alice") == s2
    # Nobody left alive.
    assert roster_successor(["alice"], "alice") is None
    assert roster_successor([], "alice") is None
    assert roster_successor(["alice", "bob"], "alice", dead=["bob"]) is None


# ---------------------------------------------------------------------------
# Integration: parity + the chaos round
# ---------------------------------------------------------------------------

PARTIES4 = ["alice", "bob", "carol", "dave"]
DELTAS = {"alice": 0.25, "bob": 0.5, "carol": 1.0, "dave": 2.0}
DIM = 8


def _define_trainers(fed, parties):
    import jax.numpy as jnp

    @fed.remote
    class Trainer:
        def __init__(self, delta):
            self._d = float(delta)

        def train(self, params):
            from rayfed_tpu.fl import compression as C

            tree = C.decompress(params, jnp.float32)
            out = {"w": tree["w"] + self._d}
            return C.compress(out, packed=True, wire_dtype=jnp.float32)

    return {p: Trainer.party(p).remote(DELTAS[p]) for p in parties}


def _replay(round_log, start_params):
    """The documented quorum recurrence, replayed from the member log:
    weighted mean over each round's members (sorted-party fold order),
    DGA late folds for active-but-excluded parties, welcome resync for
    (re)joining parties.  Bit-exact against the transport path."""
    import jax.numpy as jnp

    from rayfed_tpu.fl import compression as C
    from rayfed_tpu.fl.fedavg import packed_weighted_sum
    from rayfed_tpu.fl.overlap import dga_correct

    current = C.compress(start_params, packed=True, wire_dtype=jnp.float32)
    late = {}
    history = [current]
    for entry in round_log:
        active, members = entry["active"], entry["members"]
        for p in list(late):
            if p not in active:
                late.pop(p)
        inputs = {p: late.pop(p, current) for p in active}
        ups = {}
        for p in active:
            tree = C.decompress(inputs[p], jnp.float32)
            ups[p] = C.compress(
                {"w": tree["w"] + DELTAS[p]}, packed=True,
                wire_dtype=jnp.float32,
            )
        current = packed_weighted_sum(
            [ups[p] for p in sorted(members)], None
        )
        for p in active:
            if p not in members:
                late[p] = dga_correct(current, ups[p], inputs[p])
        history.append(current)
    return current, history


def _run_parity(party, cluster, outdir):
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import run_fedavg_rounds

    fed.init(address="local", cluster=cluster, party=party,
             enable_waiting_for_other_parties_ready=True)
    trainers = _define_trainers(fed, list(cluster))
    params = {"w": jnp.zeros((DIM,), jnp.float32)}

    classic = run_fedavg_rounds(
        trainers, params, rounds=3, compress_wire=True, packed_wire=True,
        streaming_agg=True, wire_dtype=jnp.float32,
    )
    log = []
    quorate = run_fedavg_rounds(
        trainers, params, rounds=3, compress_wire=True, packed_wire=True,
        wire_dtype=jnp.float32, quorum=len(cluster),
        round_deadline_s=30.0, round_log=log,
    )
    assert np.array_equal(np.asarray(classic["w"]), np.asarray(quorate["w"]))
    assert all(sorted(e["members"]) == sorted(cluster) for e in log)

    # Hierarchy x quorum composition (same child): quantized rounds run
    # the two-level tree (region_size=1 -> one region per party, so the
    # cross-region partial-sum streaming + announce frame all run for
    # real), the bootstrap round stays the flat quorum path, and every
    # controller must byte-agree.  A hierarchy abort would fall back to
    # the flat quorum path — assert none was needed.
    from rayfed_tpu.fl.hierarchy import HIER_STATS

    done_before = HIER_STATS["rounds_completed"]
    fb_before = HIER_STATS["fallback_rounds"]
    hlog = []
    hier = run_fedavg_rounds(
        trainers, params, rounds=3, compress_wire=True, packed_wire=True,
        mode="hierarchy", region_size=1, wire_quant="uint8",
        # region_branch threads through the quorum loop to
        # region_layout (2 singleton regions under one branch-2
        # interior node — the identical tree the default derives, so
        # the byte-agreement assertions below also pin the explicit
        # multi-level path against it).
        region_branch=2,
        # The chunk override must reach the quorum loop's grid
        # derivation too (a default-chunked grid over this toy model
        # would collapse to one block).
        ring_chunk_elems=16,
        quorum=len(cluster), round_deadline_s=30.0, round_log=hlog,
    )
    # Rounds 2..3 ran hierarchically (round 1 is the unquantized
    # bootstrap), with zero fallbacks.
    assert HIER_STATS["rounds_completed"] - done_before == 2
    assert HIER_STATS["fallback_rounds"] == fb_before
    assert all(sorted(e["members"]) == sorted(cluster) for e in hlog)

    # Quorum x ring x quant (ROADMAP item 1c — the last loud topology
    # exclusion, lifted; composition-matrix triple row's runtime
    # verifier): the quorum loop derives the round grid on the ring's
    # own stripe chunking and the quorum ring arm runs the quantized
    # ring fold.  At full participation the result must be BYTE-
    # identical to the classic (non-quorum) quantized ring over the
    # same rounds — same grid derivation, same codes (EF residuals
    # evolve identically from a reset registry), same integer stripe
    # fold — and no round may have silently fallen back to the flat
    # path.
    from rayfed_tpu.fl import quantize as _qz
    from rayfed_tpu.fl.ring import RING_STATS

    rq_fb_before = RING_STATS["fallback_rounds"]
    _qz.reset_compressors()
    ring_classic = run_fedavg_rounds(
        trainers, params, rounds=3, compress_wire=True, packed_wire=True,
        mode="ring", wire_quant="uint8", ring_chunk_elems=16,
    )
    _qz.reset_compressors()
    rqlog = []
    ring_quorum = run_fedavg_rounds(
        trainers, params, rounds=3, compress_wire=True, packed_wire=True,
        mode="ring", wire_quant="uint8", ring_chunk_elems=16,
        quorum=len(cluster), round_deadline_s=30.0, round_log=rqlog,
    )
    assert RING_STATS["fallback_rounds"] == rq_fb_before
    assert np.array_equal(
        np.asarray(ring_classic["w"]), np.asarray(ring_quorum["w"])
    )
    assert all(sorted(e["members"]) == sorted(cluster) for e in rqlog)

    with open(os.path.join(outdir, f"{party}.json"), "w") as f:
        json.dump({
            "final": np.asarray(quorate["w"]).tolist(),
            "hier_final": np.asarray(hier["w"]).tolist(),
            "ring_quant_final": np.asarray(ring_quorum["w"]).tolist(),
        }, f)
    fed.shutdown()


def test_quorum_full_participation_parity(tmp_path_factory):
    outdir = str(tmp_path_factory.mktemp("quorum_parity"))
    cluster = make_cluster(["alice", "bob"])
    run_parties(_run_parity, ["alice", "bob"], args=(cluster, outdir))
    finals, hier_finals, ring_quant_finals = [], [], []
    for p in ("alice", "bob"):
        with open(os.path.join(outdir, f"{p}.json")) as f:
            rec = json.load(f)
        finals.append(rec["final"])
        hier_finals.append(rec["hier_final"])
        ring_quant_finals.append(rec["ring_quant_final"])
    assert finals[0] == finals[1]
    # Hierarchy x quorum: every controller holds the identical bytes.
    assert hier_finals[0] == hier_finals[1]
    # Quorum x ring x quant: ditto (plus the classic-ring parity and
    # zero-fallback assertions inside the child).
    assert ring_quant_finals[0] == ring_quant_finals[1]


def _run_coord_leave(party, cluster, outdir):
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import run_fedavg_rounds
    from rayfed_tpu.fl.quorum import QUORUM_STATS

    fed.init(address="local", cluster=cluster, party=party,
             enable_waiting_for_other_parties_ready=True,
             recv_backstop_in_seconds=120)
    trainers = _define_trainers(fed, list(cluster))
    if party == "alice":  # the coordinator
        fed.leave()
    log: list = []
    # A coordinator fed.leave() is a GRACEFUL handover now (PR 6 poisoned
    # the peers here): alice completes round 0, its announcement names
    # bob as the successor, alice exits with the round-0 broadcast, and
    # bob finishes the remaining rounds as the new coordinator.
    final = run_fedavg_rounds(
        trainers, {"w": jnp.zeros((DIM,), jnp.float32)}, rounds=3,
        compress_wire=True, packed_wire=True,
        wire_dtype=jnp.float32, quorum=1, round_deadline_s=20.0,
        round_log=log,
    )
    with open(os.path.join(outdir, f"{party}.json"), "w") as f:
        json.dump({
            "final": np.asarray(final["w"]).tolist(),
            "round_log": log,
            "handovers": QUORUM_STATS["graceful_handovers"],
        }, f)
    fed.shutdown()


def test_coordinator_leave_hands_over_gracefully(tmp_path_factory):
    """A coordinator ``fed.leave()`` completes the in-flight round and
    announces its successor (no poison, no lost round): the leaver
    returns the last broadcast, the survivor coordinates the remaining
    rounds, and the member-log replay stays bit-exact across the
    handover boundary."""
    outdir = str(tmp_path_factory.mktemp("coord_leave"))
    cluster = make_cluster(["alice", "bob"])
    run_parties(_run_coord_leave, ["alice", "bob"], args=(cluster, outdir))
    reports = {}
    for p in ("alice", "bob"):
        with open(os.path.join(outdir, f"{p}.json")) as f:
            reports[p] = json.load(f)
    log = reports["bob"]["round_log"]
    assert len(log) == 3
    # Round 0 was coordinated by the leaver; the handover rotates the
    # lease from round 1 on, and the roster drops alice at the boundary.
    assert [e["coordinator"] for e in log] == ["alice", "bob", "bob"]
    assert sorted(log[0]["members"]) == ["alice", "bob"]
    assert log[1]["active"] == ["bob"] and log[1]["epoch"] >= 1
    assert reports["bob"]["handovers"] >= 1
    assert reports["alice"]["handovers"] >= 1
    # alice's loop ended at the handover with the round-0 broadcast;
    # bob's final follows the replayed recurrence over the shrunk roster.
    assert reports["alice"]["round_log"] == log[:1]
    from rayfed_tpu.fl import compression as C

    start = {"w": jnp.zeros((DIM,), jnp.float32)}
    expect, history = _replay(log, start)
    np.testing.assert_array_equal(
        np.asarray(reports["bob"]["final"], dtype=np.float32),
        np.asarray(C.decompress(expect)["w"], dtype=np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(reports["alice"]["final"], dtype=np.float32),
        np.asarray(C.decompress(history[1])["w"], dtype=np.float32),
    )


def test_coordinator_leave_without_successor_fails_loudly(tmp_path_factory):
    """The loud failure survives ONLY where it belongs: a leaving
    coordinator with no live successor cannot hand the run to anyone."""
    import rayfed_tpu as fed
    from rayfed_tpu.fl import run_fedavg_rounds
    from rayfed_tpu.fl.quorum import QuorumRoundError

    cluster = make_cluster(["alice"])
    fed.init(address="local", cluster=cluster, party="alice")
    try:
        trainers = _define_trainers(fed, ["alice"])
        fed.leave()
        with pytest.raises(
            QuorumRoundError, match="no live established successor"
        ):
            run_fedavg_rounds(
                trainers, {"w": jnp.zeros((DIM,), jnp.float32)}, rounds=2,
                compress_wire=True, packed_wire=True,
                wire_dtype=jnp.float32, quorum=1, round_deadline_s=10.0,
            )
    finally:
        fed.shutdown()


FAILOVER_ROUNDS = 5


def _run_coord_crash(party, cluster, outdir):
    import time

    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu import chaos
    from rayfed_tpu.fl import run_fedavg_rounds
    from rayfed_tpu.fl.quorum import QUORUM_STATS

    # Flight recorder (satellite of the telemetry work): armed via env
    # exactly like RAYFED_CHAOS — fed.init adopts the party — so THIS
    # existing chaos e2e doubles as the cross-party collection test
    # with zero new party subprocesses (the tier-1 budget note).
    os.environ["RAYFED_TRACE"] = "1"

    chaos.install({
        "seed": 5,
        "rules": [
            # Kill the coordinator MID-round: after round 1's quorum
            # cutoff pinned the members, before anyone heard the result.
            # The survivors' only way out is monitor-declared death +
            # deterministic failover to bob, who must re-establish the
            # round from re-pushed contributions.
            {"hook": "announce", "party": "alice", "match": {"round": 1},
             "op": "crash_party"},
            # A harmless injected straggle on a SURVIVOR (well under the
            # deadline): the merged trace must show an injected chaos
            # event from a ring that outlives the injection — the
            # coordinator's own crash event dies with its ring.
            {"hook": "round", "party": "carol", "match": {"round": 3},
             "op": "delay_ms", "value": 200},
        ],
    })
    params = {"w": jnp.zeros((DIM,), jnp.float32)}
    _warm_jits(params)
    fed.init(
        address="local", cluster=cluster, party=party,
        enable_waiting_for_other_parties_ready=True,
        peer_health_interval_in_seconds=1.0, peer_death_pings=3,
        cross_silo_timeout_in_seconds=15,
        cross_silo_retry_policy={
            "maxAttempts": 2, "initialBackoff": "0.2s",
            "maxBackoff": "0.5s",
        },
        recv_backstop_in_seconds=120,
    )
    trainers = _define_trainers(fed, PARTIES4)
    log: list = []
    try:
        final = run_fedavg_rounds(
            trainers, params, rounds=FAILOVER_ROUNDS, compress_wire=True,
            packed_wire=True, wire_dtype=jnp.float32, quorum=2,
            round_deadline_s=3.0, round_log=log, coordinator="alice",
        )
    except chaos.ChaosPartyCrash:
        # The coordinator dies for real: sockets vanish, no goodbyes —
        # the survivors' failover is the test.
        with open(os.path.join(outdir, f"{party}.json"), "w") as f:
            json.dump({"crashed": True}, f)
            f.flush()
            os.fsync(f.fileno())
        os._exit(0)
    with open(os.path.join(outdir, f"{party}.json"), "w") as f:
        json.dump({
            "crashed": False,
            "final": np.asarray(final["w"]).tolist(),
            "round_log": log,
            "failovers": QUORUM_STATS["coordinator_failovers"],
        }, f)
    # Cross-party trace collection over the surviving cluster: bob (the
    # post-failover coordinator) pulls every peer's ring window — the
    # dead coordinator must land in ``missing``, not hang the pull.
    # The other survivors park on a marker so their transports stay up
    # to serve their TRACE_GET requests.
    traced_marker = os.path.join(outdir, "traced.marker")
    if party == "bob":
        trace = fed.trace_collect(timeout=30)
        with open(os.path.join(outdir, "trace.json"), "w") as f:
            json.dump(trace, f)
        with open(traced_marker, "w") as f:
            f.write("done")
    else:
        deadline = time.monotonic() + 90
        while not os.path.exists(traced_marker):
            if time.monotonic() > deadline:
                raise AssertionError("collector never wrote the trace")
            time.sleep(0.1)
    fed.shutdown()


def test_quorum_coordinator_crash_failover(tmp_path_factory):
    """THE tentpole e2e: the coordinator hard-crashes between round 1's
    cutoff and its broadcast (N=4, quorum=2).  Every survivor must
    derive the same successor, re-establish round 1 there, and finish
    all rounds with bit-identical models; the recorded member log must
    replay the recurrence bit-exactly ACROSS the failover boundary, and
    every survivor must report ``coordinator_failovers >= 1``."""
    outdir = str(tmp_path_factory.mktemp("coord_crash"))
    cluster = make_cluster(PARTIES4)
    # Aggressive per-party death detection only for the party that will
    # actually crash — failover latency is bounded by ITS deadline.
    cluster["alice"]["transport_options"] = {
        "heartbeat_interval_s": 0.3, "death_deadline_s": 0.9,
    }
    run_parties(
        _run_coord_crash, PARTIES4, args=(cluster, outdir), timeout=300,
    )
    reports = {}
    for p in PARTIES4:
        with open(os.path.join(outdir, f"{p}.json")) as f:
            reports[p] = json.load(f)
    assert reports["alice"]["crashed"]
    survivors = ["bob", "carol", "dave"]
    logs = {p: reports[p]["round_log"] for p in survivors}
    log = logs["bob"]
    assert len(log) == FAILOVER_ROUNDS
    by_round = {e["round"]: e for e in log}
    # Round 0 ran under the pinned coordinator; from the failover round
    # on, every survivor agrees the lease moved to bob — the next alive
    # party after alice on the sorted roster ring.
    assert by_round[0]["coordinator"] == "alice"
    assert all(
        by_round[r]["coordinator"] == "bob"
        for r in range(1, FAILOVER_ROUNDS)
    ), log
    # The re-established round 1 excluded the dead coordinator but made
    # quorum over the re-pushed survivor contributions.
    m1 = by_round[1]["members"]
    assert "alice" not in m1 and 2 <= len(m1) <= 3, log
    # The successor's first announcement dropped the corpse: the epoch
    # advanced and alice left the active set from round 2 on.
    assert by_round[1]["epoch"] == 0 and by_round[2]["epoch"] >= 1, log
    assert "alice" not in by_round[2]["active"], log
    for p in survivors:
        assert logs[p] == log, p
        assert reports[p]["failovers"] >= 1, (p, reports[p])
        assert reports[p]["final"] == reports["bob"]["final"], p
    # Bit-exact replay of the recurrence from the member log, straight
    # through the failover boundary.
    from rayfed_tpu.fl import compression as C

    start = {"w": jnp.zeros((DIM,), jnp.float32)}
    expect, _history = _replay(log, start)
    np.testing.assert_array_equal(
        np.asarray(reports["bob"]["final"], dtype=np.float32),
        np.asarray(C.decompress(expect)["w"], dtype=np.float32),
    )

    # Flight recorder (rayfed_tpu/telemetry.py): the merged cross-party
    # timeline bob collected over the surviving cluster.
    from rayfed_tpu import telemetry
    from tool.trace_report import round_report

    with open(os.path.join(outdir, "trace.json")) as f:
        trace = json.load(f)
    records = trace["records"]
    assert trace["collector"] == "bob"
    # The dead coordinator cannot serve its window — it lands in
    # ``missing``; every survivor's ring contributes spans.
    assert "alice" in trace["missing"], trace["missing"]
    spans_from = {r["party"] for r in records}
    assert {"bob", "carol", "dave"} <= spans_from, sorted(spans_from)
    phases = {r["phase"] for r in records}
    # Driver + transport + aggregation views joined on one timeline...
    assert "driver.round" in phases and "wire.send" in phases, phases
    assert any(p.startswith("agg.") for p in phases), phases
    # ...with the coordinator-kill failover event and the injected
    # chaos fault on the SAME timeline (every survivor recorded the
    # failover; carol recorded her injected round-3 straggle).
    failovers = [r for r in records if r["phase"] == "quorum.failover"]
    assert {r["party"] for r in failovers} >= {"bob", "carol", "dave"}
    assert all(r["detail"]["to"] == "bob" for r in failovers), failovers
    chaos_evs = [r for r in records if r["phase"].startswith("chaos.")]
    assert any(
        r["party"] == "carol" and r["outcome"] == "injected"
        for r in chaos_evs
    ), chaos_evs
    # Round/epoch tags stay consistent across parties: every tagged
    # round is one the member log knows.
    tagged = {r["round"] for r in records if r["round"] is not None}
    assert tagged and tagged <= set(by_round), (sorted(tagged), log)
    # The merged timeline exports as valid Perfetto trace_event JSON
    # (one process per party, spans as "X", instants as "i").
    perfetto = telemetry.to_trace_events(records, trace["clock_offsets"])
    events = perfetto["traceEvents"]
    assert events and json.loads(json.dumps(perfetto))
    assert {e["ph"] for e in events} >= {"M", "X"}
    proc_names = {
        e["args"]["name"] for e in events if e["name"] == "process_name"
    }
    assert {"bob", "carol", "dave"} <= proc_names, proc_names
    # Critical-path report: on the clean (post-failover-recovery)
    # rounds the report's wall reconciles with the driver's own
    # measured wall; the failover round is bounded by health-monitor
    # waits the ring records too, so it must at least be present.
    report = round_report(records, tolerance=0.25)
    assert set(report) == tagged
    clean = [r for r in sorted(tagged) if r >= 2]
    assert clean and all(report[r]["wall_agrees"] for r in clean), {
        r: (report[r]["wall_s"], report[r]["driver_wall_s"])
        for r in sorted(report)
    }
    for r in clean:
        assert report[r]["bounded_by"] is not None


def _run_ckpt_roundtrip(party, cluster, outdir):
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.checkpoint import FedCheckpointer
    from rayfed_tpu.fl import run_fedavg_rounds

    params = {"w": jnp.zeros((DIM,), jnp.float32)}
    kwargs = dict(
        compress_wire=True, packed_wire=True, wire_dtype=jnp.float32,
        quorum=2, round_deadline_s=30.0, checkpoint_every=1,
    )

    def _init():
        fed.init(address="local", cluster=cluster, party=party,
                 enable_waiting_for_other_parties_ready=True,
                 recv_backstop_in_seconds=120)

    # Phase A: two rounds, snapshotting every boundary, then a FULL
    # cluster stop (both parties down — the crash scenario).
    _init()
    ckpt = FedCheckpointer(os.path.join(outdir, "ckpt"), party)
    log_a: list = []
    run_fedavg_rounds(
        _define_trainers(fed, list(cluster)), params, rounds=2,
        checkpointer=ckpt, round_log=log_a, **kwargs,
    )
    fed.shutdown()

    # All-down barrier: phase B must model the full-cluster restart —
    # no party may re-enter while a peer's phase-A server still owns
    # its port (a round-2 push ACKed by the dying runtime would vanish
    # with it, and the resumed round would wait out its backstop).
    import time

    open(os.path.join(outdir, f"down.{party}"), "w").close()
    deadline = time.monotonic() + 60
    while any(
        not os.path.exists(os.path.join(outdir, f"down.{p}"))
        for p in cluster
    ):
        if time.monotonic() > deadline:
            raise AssertionError("peers never finished phase A")
        time.sleep(0.05)

    # Phase B: fresh runtimes resume the SAME run from the snapshots —
    # round index, roster epoch, member log and rendezvous session all
    # come back — and finish rounds 2..3.
    _init()
    ckpt = FedCheckpointer(os.path.join(outdir, "ckpt"), party)
    log_b: list = []
    final = run_fedavg_rounds(
        _define_trainers(fed, list(cluster)), params, rounds=4,
        checkpointer=ckpt, round_log=log_b, **kwargs,
    )
    with open(os.path.join(outdir, f"{party}.json"), "w") as f:
        json.dump({
            "final": np.asarray(final["w"]).tolist(),
            "log_a": log_a, "log_b": log_b,
        }, f)
    fed.shutdown()


def test_quorum_checkpoint_restore_roundtrip(tmp_path_factory):
    """quorum × checkpointer (the lifted mutual exclusion): a fully
    crashed 2-party cluster resumes its quorum run from the snapshots —
    the restored member log spans the restart, and the final model is
    bit-identical to the recurrence replayed over all four rounds."""
    outdir = str(tmp_path_factory.mktemp("quorum_ckpt"))
    cluster = make_cluster(["alice", "bob"])
    run_parties(
        _run_ckpt_roundtrip, ["alice", "bob"], args=(cluster, outdir),
        timeout=240,
    )
    reports = {}
    for p in ("alice", "bob"):
        with open(os.path.join(outdir, f"{p}.json")) as f:
            reports[p] = json.load(f)
    log_b = reports["alice"]["log_b"]
    # The resumed log holds all 4 rounds: 2 restored + 2 freshly run.
    assert [e["round"] for e in log_b] == [0, 1, 2, 3]
    assert log_b[:2] == reports["alice"]["log_a"]
    assert reports["bob"]["log_b"] == log_b
    assert reports["bob"]["final"] == reports["alice"]["final"]
    from rayfed_tpu.fl import compression as C

    start = {"w": jnp.zeros((DIM,), jnp.float32)}
    expect, _history = _replay(log_b, start)
    np.testing.assert_array_equal(
        np.asarray(reports["alice"]["final"], dtype=np.float32),
        np.asarray(C.decompress(expect)["w"], dtype=np.float32),
    )


CHAOS_ROUNDS = 10
CHAOS_QUORUM = 2
CHAOS_DEADLINE_S = 3.0


def _warm_jits(params):
    """Compile every jitted program the round loop touches BEFORE the
    clock starts: the first quorum deadline must measure the protocol,
    not XLA compile times under 4-process contention."""
    import jax
    import jax.numpy as jnp

    from rayfed_tpu.fl import compression as C
    from rayfed_tpu.fl.fedavg import (
        finalize_packed_stripe,
        packed_weighted_sum,
    )
    from rayfed_tpu.fl.overlap import dga_correct
    from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS, _accum_kernel

    packed = C.compress(params, packed=True, wire_dtype=jnp.float32)
    tree = C.decompress(packed, jnp.float32)
    p2 = C.compress({"w": tree["w"] + 1.0}, packed=True,
                    wire_dtype=jnp.float32)
    for n in (2, 3, 4):
        packed_weighted_sum([p2] * n, None)
    jax.block_until_ready(dga_correct(p2, p2, packed).buf)
    kern = _accum_kernel(DEFAULT_CHUNK_ELEMS, "float32", "float32")
    acc = jnp.zeros(DEFAULT_CHUNK_ELEMS, jnp.float32)
    acc = kern(acc, np.zeros(DEFAULT_CHUNK_ELEMS, np.float32),
               np.int32(0), np.float32(1.0))
    jax.block_until_ready(
        finalize_packed_stripe(acc, 2.0, DIM, jnp.float32)
    )


def _run_chaos(party, cluster, outdir):
    import time

    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu import chaos
    from rayfed_tpu.fl import run_fedavg_rounds

    chaos.install({
        "seed": 7,
        "rules": [
            # carol straggles past the round deadline in round 1...
            # (8s against a 3s deadline: the margin absorbs CI load, so
            # the cutoff verdict is deterministic)
            {"hook": "round", "party": "carol", "match": {"round": 1},
             "op": "delay_ms", "value": 8000},
            # ...and dave hard-crashes at the same round boundary.
            {"hook": "round", "party": "dave", "match": {"round": 1},
             "op": "crash_party"},
        ],
    })

    def _init(wait_ready=True):
        fed.init(
            address="local", cluster=cluster, party=party,
            enable_waiting_for_other_parties_ready=wait_ready,
            # Tolerant DEFAULT death deadline (1s × 3 pings): a loaded
            # but healthy coordinator must never be falsely declared
            # dead mid-round.  The party that actually crashes (dave)
            # carries aggressive per-party knobs in the cluster config
            # instead — exercising the heartbeat_interval_s /
            # death_deadline_s transport options end to end.
            peer_health_interval_in_seconds=1.0,
            peer_death_pings=3,
            cross_silo_timeout_in_seconds=15,
            cross_silo_retry_policy={
                "maxAttempts": 2, "initialBackoff": "0.2s",
                "maxBackoff": "0.5s",
            },
            recv_backstop_in_seconds=120,
        )

    params = {"w": jnp.zeros((DIM,), jnp.float32)}
    _warm_jits(params)
    _init()
    trainers = _define_trainers(fed, PARTIES4)
    log: list = []
    left_early = False
    drop_marker = os.path.join(outdir, "dave_dropped.marker")

    def _on_round(r, _p):
        # carol leaves gracefully late in the run (round boundary after
        # round 6) — exercising fed.leave on top of the crash/rejoin.
        if party == "carol" and r == 6:
            fed.leave()
        # alice signals (via the shared tmpdir) that the crashed party
        # has been dropped — the test's deterministic rejoin trigger.
        if party == "alice" and r == 2:
            with open(drop_marker, "w") as f:
                f.write("dropped")

    kwargs = dict(
        rounds=CHAOS_ROUNDS, compress_wire=True, packed_wire=True,
        wire_dtype=jnp.float32, quorum=CHAOS_QUORUM,
        round_deadline_s=CHAOS_DEADLINE_S, round_log=log,
        on_round=_on_round, coordinator="alice",
    )
    try:
        final = run_fedavg_rounds(trainers, params, **kwargs)
    except chaos.ChaosPartyCrash:
        # Hard-crash simulation: the transport dies abruptly (peers see
        # EOF and failed pings, exactly like a SIGKILL), then the party
        # comes back as a FRESH runtime and rejoins the in-progress run.
        from rayfed_tpu.runtime import get_runtime, set_current_runtime

        rt = get_runtime()
        rt.transport.stop()
        rt.executor.shutdown(wait=False)
        set_current_runtime(None)
        deadline = time.monotonic() + 90
        while not os.path.exists(drop_marker):
            if time.monotonic() > deadline:
                raise AssertionError("never saw the dropped marker")
            time.sleep(0.2)
        # Rejoin: fresh runtime on the same address; no all-party ready
        # ping (the roster may legitimately be smaller now).
        _init(wait_ready=False)
        ticket = fed.join(coordinator="alice", timeout=120)
        assert ticket["epoch"] >= 2, ticket  # drop (+1) then rejoin (+1)
        # Pull-path leg (object plane): the welcome named the model by
        # content fingerprint, and fed.join resolved it through a
        # BLOB_GET pull — this fresh runtime's cache was cold, so the
        # bytes crossed the wire exactly once, by pull not push.
        assert "model" in ticket, sorted(ticket)
        trainers = _define_trainers(fed, PARTIES4)
        final = run_fedavg_rounds(
            trainers, params, join_ticket=ticket, **kwargs
        )
    if party == "carol":
        left_early = len(log) < CHAOS_ROUNDS

    from rayfed_tpu.runtime import get_runtime as _get_rt

    blob_stats = _get_rt().transport.get_stats()["object_plane"]
    with open(os.path.join(outdir, f"{party}.json"), "w") as f:
        json.dump({
            "final": np.asarray(final["w"]).tolist(),
            "round_log": log,
            "left_early": left_early,
            "blob": {
                "fetches": blob_stats["blob_fetches"],
                "fetch_bytes": blob_stats["blob_fetch_bytes"],
                "serves": blob_stats["blob_serves"],
                "hits": blob_stats["blob_cache_hits"],
            },
        }, f)
    fed.shutdown()


def test_quorum_chaos_straggler_crash_rejoin_leave(tmp_path_factory):
    """THE acceptance round: seeded chaos (1 straggler past deadline +
    1 hard crash, N=4), quorum=2 — every surviving controller completes
    every round with the reweighted result, the straggler's late
    contribution folds into the next round via dga_correct, the crashed
    party rejoins (roster epoch advances; no surviving runtime
    restarts), and a fed.leave drops the leaver at a round boundary.
    Survivor results are replayed bit-exactly from the member log."""
    outdir = str(tmp_path_factory.mktemp("quorum_chaos"))
    cluster = make_cluster(PARTIES4)
    # Fast death detection ONLY for the party that will actually crash
    # (per-party health knobs — the satellite under test); everyone
    # else keeps the tolerant defaults.
    cluster["dave"]["transport_options"] = {
        "heartbeat_interval_s": 0.3, "death_deadline_s": 0.9,
    }
    run_parties(
        _run_chaos, PARTIES4, args=(cluster, outdir), timeout=300,
    )
    reports = {}
    for p in PARTIES4:
        with open(os.path.join(outdir, f"{p}.json")) as f:
            reports[p] = json.load(f)

    alice = reports["alice"]
    log = alice["round_log"]
    assert len(log) == CHAOS_ROUNDS
    by_round = {e["round"]: e for e in log}
    # Round 0: clean, everyone in.
    assert sorted(by_round[0]["members"]) == PARTIES4
    # Round 1: the straggler and the crashed party miss the quorum but
    # the round still completes over a strict subset (exact membership
    # of the healthy pair is timing-dependent under CI load — the
    # PROTOCOL assertions are: cutoff fired, the faulted parties are
    # out, the straggler stays on the roster).
    m1 = by_round[1]["members"]
    assert 2 <= len(m1) < 4 and "dave" not in m1 and "carol" not in m1, log
    assert "carol" in by_round[1]["active"]  # straggler stays a member
    # The crashed party is dropped (dead + missed) — epoch advanced —
    # and rejoins later: present in some later round's members.
    assert "dave" not in by_round[2]["active"]
    assert any("dave" in by_round[r]["members"]
               for r in range(3, CHAOS_ROUNDS)), log
    # carol left gracefully (leave requested after round 6): her loop
    # ended early and the final rounds ran without her on the roster.
    assert reports["carol"]["left_early"]
    assert "carol" not in by_round[CHAOS_ROUNDS - 1]["active"], log
    # Epochs advanced without any surviving runtime restarting: drop,
    # rejoin, leave = at least 3 transitions.
    assert by_round[CHAOS_ROUNDS - 1]["epoch"] >= 3, log
    # Pull-path leg (object plane): the rejoiner resolved its welcome's
    # model FINGERPRINT by pulling the blob (cold cache → >= 1 fetch
    # with real bytes), and some holder served it.
    dave_blob = reports["dave"]["blob"]
    assert dave_blob["fetches"] >= 1, dave_blob
    assert dave_blob["fetch_bytes"] > 0, dave_blob
    assert sum(
        reports[p]["blob"]["serves"] for p in PARTIES4 if p != "dave"
    ) >= 1, {p: reports[p]["blob"] for p in PARTIES4}

    # Every controller's log agrees with alice's for the rounds it ran
    # (the coordinator's announcements are the one truth; dave's log
    # restarts at its rejoin round), and every full-run controller
    # lands on identical bytes.
    for p in ("bob", "carol", "dave"):
        for entry in reports[p]["round_log"]:
            assert entry == by_round[entry["round"]], (p, entry)
    assert reports["bob"]["final"] == alice["final"]
    assert reports["dave"]["final"] == alice["final"]

    # Bit-exact replay of the documented recurrence from the member log
    # (weighted mean over members + DGA late folds + welcome resyncs).
    start = {"w": jnp.zeros((DIM,), jnp.float32)}
    from rayfed_tpu.fl import compression as C

    expect, history = _replay(log, start)
    expect_w = np.asarray(C.decompress(expect)["w"], dtype=np.float32)
    np.testing.assert_array_equal(
        np.asarray(alice["final"], dtype=np.float32), expect_w
    )
    # carol holds the model as of its last completed round.
    carol_rounds = len(reports["carol"]["round_log"])
    carol_expect = np.asarray(
        C.decompress(history[carol_rounds])["w"], dtype=np.float32
    )
    np.testing.assert_array_equal(
        np.asarray(reports["carol"]["final"], dtype=np.float32),
        carol_expect,
    )
