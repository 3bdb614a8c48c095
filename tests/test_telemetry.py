"""Federated flight recorder (rayfed_tpu/telemetry.py).

Unit: the bounded ring + emission helpers, the trace-collection
schemas (single producers, fingerprinted by tool/check_wire_format.py),
clock-offset estimation, the merge, the Perfetto export, and the
critical-path report (tool/trace_report.py).

Integration (in-process managers, real loopback sockets): the
TRACE_GET/TRACE_PUT collection round trip, the per-manager TransferLog
(multi-party tests must not conflate parties in one module-global
ring), and the ``metrics_snapshot`` schema-stability contract —
schema drift fails CI the way wire drift already does.
"""

import json
import time

import pytest

from rayfed_tpu import telemetry
from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
from rayfed_tpu.transport.manager import TransportManager
from tests.multiproc import get_free_ports


@pytest.fixture(autouse=True)
def _fresh_recorder():
    telemetry.uninstall()
    yield
    telemetry.uninstall()


# ---------------------------------------------------------------------------
# Recorder ring
# ---------------------------------------------------------------------------


def test_disarmed_emission_is_a_noop():
    assert telemetry.active() is None
    telemetry.emit("wire.send", round=1)  # must not raise, must not arm
    telemetry.event("quorum.cutoff")
    with telemetry.span("agg.finalize"):
        pass
    assert telemetry.installed() is None


def test_ring_bounds_and_drop_accounting():
    rec = telemetry.install(party="alice", capacity=4)
    for i in range(10):
        rec.emit("wire.send", round=i)
    recs = rec.records()
    assert len(recs) == 4
    assert [r.round for r in recs] == [6, 7, 8, 9]  # oldest evicted
    stats = rec.stats()
    assert stats["trace_total_recorded"] == 10
    assert stats["trace_dropped"] == 6
    assert stats["trace_capacity"] == 4


def test_round_filter_keeps_untagged_records():
    rec = telemetry.install(party="alice")
    rec.emit("wire.send", round=1)
    rec.emit("chaos.partition")  # no round tag: cross-cutting context
    rec.emit("wire.send", round=5)
    win = rec.records(rounds=(4, 9))
    assert [r.phase for r in win] == ["chaos.partition", "wire.send"]
    assert rec.records(rounds=1)[0].round == 1


def test_emit_never_raises_on_malformed_fields():
    rec = telemetry.install(party="alice")
    rec.emit("wire.send", round="not-an-int")
    (bad,) = rec.records()
    assert bad.outcome == "bad-record"
    assert "error" in bad.detail


def test_span_helper_times_and_stamps_errors():
    rec = telemetry.install(party="alice")
    with telemetry.span("agg.finalize", round=2):
        time.sleep(0.01)
    with pytest.raises(ValueError):
        with telemetry.span("agg.fold", round=2):
            raise ValueError("boom")
    ok, err = rec.records()
    assert ok.phase == "agg.finalize" and ok.dur_s >= 0.01
    assert ok.outcome == "ok" and ok.round == 2
    assert err.phase == "agg.fold" and err.outcome == "error"


def test_env_arming_adopts_party(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_VAR, "1")
    rec = telemetry.maybe_install_from_env()
    assert rec is not None and rec.party is None
    # fed.init arms again, now knowing who this party is.
    rec2 = telemetry.maybe_install_from_env(party="alice")
    assert rec2 is rec and rec.party == "alice"
    monkeypatch.setenv(telemetry.ENV_VAR, "0")
    telemetry.uninstall()
    assert telemetry.maybe_install_from_env() is None


# ---------------------------------------------------------------------------
# Wire schemas (single producers — fingerprinted by check_wire_format)
# ---------------------------------------------------------------------------


def test_trace_request_reply_schemas_roundtrip():
    req = telemetry.make_trace_request("trace.put.a.n1", rounds=(2, 5))
    parsed = telemetry.check_trace_request(json.loads(json.dumps(req)))
    assert parsed["rk"] == "trace.put.a.n1"
    assert parsed["rnd"] == [2, 5]
    assert parsed["v"] == telemetry.TELEMETRY_VERSION
    rep = telemetry.make_trace_reply_meta("bob", 3, armed=True)
    parsed = telemetry.check_trace_reply_meta(json.loads(json.dumps(rep)))
    assert parsed["party"] == "bob" and parsed["n"] == 3 and parsed["armed"]
    with pytest.raises(telemetry.TelemetryError):
        telemetry.check_trace_request({"no": "reply key"})
    with pytest.raises(telemetry.TelemetryError):
        telemetry.check_trace_request({"rk": "k", "rnd": [1]})
    with pytest.raises(telemetry.TelemetryError):
        telemetry.check_trace_reply_meta({"n": 1})


def test_record_encoding_roundtrip_and_field_order_guard():
    rec = telemetry.install(party="alice")
    rec.emit(
        "wire.send", round=3, epoch=1, peer="bob", stream="fedavg",
        nbytes=1024, dur_s=0.5, detail={"x": (1, 2)},
    )
    payload = telemetry.encode_records(rec.records())
    (back,) = telemetry.decode_records(payload)
    assert back.phase == "wire.send" and back.peer == "bob"
    assert back.nbytes == 1024 and back.round == 3
    assert back.detail == {"x": [1, 2]}  # JSON-safe coercion
    doc = json.loads(payload)
    assert doc["fields"] == list(telemetry.SPAN_FIELDS)
    doc["fields"] = doc["fields"][::-1]
    with pytest.raises(telemetry.TelemetryError, match="field order"):
        telemetry.decode_records(json.dumps(doc).encode())
    doc = json.loads(payload)
    doc["v"] = telemetry.TELEMETRY_VERSION + 1
    with pytest.raises(telemetry.TelemetryError, match="protocol"):
        telemetry.decode_records(json.dumps(doc).encode())
    with pytest.raises(telemetry.TelemetryError, match="fields"):
        telemetry.record_from_list([1, 2, 3])


# ---------------------------------------------------------------------------
# Clock alignment, merge, Perfetto export, report
# ---------------------------------------------------------------------------


def test_clock_offset_estimate_and_bound():
    # Peer clock 10s ahead, symmetric 2ms RTT: recover the offset with
    # the documented RTT/2 bound.
    t_send, rtt, skew = 1000.0, 0.002, 10.0
    t_peer = t_send + rtt / 2 + skew
    off = telemetry.estimate_clock_offset(t_send, t_send + rtt, t_peer)
    assert off["offset_s"] == pytest.approx(skew, abs=1e-9)
    assert off["rtt_s"] == pytest.approx(rtt)
    assert off["bound_s"] == pytest.approx(rtt / 2)


def _rec(party, phase, t, dur=0.0, rnd=None, **kw):
    return telemetry.SpanRecord(
        party=party, round=rnd, epoch=None, phase=phase,
        peer=kw.get("peer"), stream=None, nbytes=kw.get("nbytes", 0),
        t_start=t, dur_s=dur, outcome=kw.get("outcome", "ok"),
        detail=kw.get("detail"),
    )


def test_merge_applies_offsets_and_fills_party():
    merged = telemetry.merge_records(
        {
            "alice": [_rec("alice", "wire.send", 100.0, 0.1, rnd=0)],
            # bob's clock runs 50s ahead; his record happened FIRST on
            # the collector's timeline once the offset is applied.
            "bob": [_rec(None, "wire.deliver", 149.9, 0.1, rnd=0)],
        },
        {"bob": {"offset_s": 50.0, "rtt_s": 0.001, "bound_s": 0.0005}},
    )
    assert [d["party"] for d in merged] == ["bob", "alice"]
    assert merged[0]["t_start"] == pytest.approx(99.9)


def test_perfetto_export_shape():
    merged = telemetry.merge_records({
        "alice": [
            _rec("alice", "wire.send", 100.0, 0.25, rnd=1, peer="bob",
                 nbytes=2048),
            _rec("alice", "quorum.failover", 100.3, 0.0, rnd=1,
                 detail={"to": "bob"}),
        ],
        "bob": [_rec("bob", "agg.finalize", 100.1, 0.05, rnd=1)],
    })
    doc = telemetry.to_trace_events(
        merged, {"bob": {"offset_s": 0.0, "rtt_s": 0.0, "bound_s": 0.0}}
    )
    events = doc["traceEvents"]
    json.dumps(doc)  # valid JSON end to end
    names = {e["args"]["name"] for e in events if e["name"] == "process_name"}
    assert names == {"alice", "bob"}
    spans = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in spans} == {"wire.send", "agg.finalize"}
    assert [e["name"] for e in instants] == ["quorum.failover"]
    # Timestamps are µs relative to the earliest record.
    send = next(e for e in spans if e["name"] == "wire.send")
    assert send["ts"] == 0.0 and send["dur"] == pytest.approx(0.25e6)
    assert send["args"]["nbytes"] == 2048
    # Distinct phase families land on distinct named threads.
    tids = {e["args"]["name"] for e in events if e["name"] == "thread_name"}
    assert {"wire", "quorum", "agg"} <= tids


def test_trace_report_critical_path_and_straggler():
    from tool.trace_report import format_report, round_report

    records = [dict(zip(telemetry.SPAN_FIELDS, telemetry.record_to_list(r)))
               for r in [
        _rec("alice", "driver.round", 100.0, 1.0, rnd=0, peer="alice",
             detail={"local_s": 0.3}),
        _rec("bob", "driver.round", 100.0, 0.98, rnd=0, peer="alice",
             detail={"local_s": 0.7}),
        _rec("bob", "wire.send", 100.7, 0.2, rnd=0, peer="alice"),
        _rec("alice", "agg.finalize", 100.92, 0.08, rnd=0),
        _rec("alice", "chaos.delay_ms", 100.5, 0.0, outcome="injected"),
    ]]
    rep = round_report(records, tolerance=0.25)
    info = rep[0]
    assert info["wall_s"] == pytest.approx(1.0)
    assert info["driver_wall_s"] == pytest.approx(1.0)
    assert info["wall_agrees"]
    # bob's local compute bounded the wall; he is also the straggler.
    assert info["bounded_by"]["party"] == "bob"
    assert info["bounded_by"]["phase"] == "driver.local"
    assert info["straggler"] == "bob"
    # The chain covers the full wall, chronologically.
    assert sum(s["dur_s"] for s in info["chain"]) == pytest.approx(1.0)
    # The untagged chaos injection inside the window rides along.
    assert [e["phase"] for e in info["events"]] == ["chaos.delay_ms"]
    text = format_report(records)
    assert "bounded by bob" in text and "chaos.delay_ms" in text


def test_trace_report_flags_wall_disagreement():
    from tool.trace_report import round_report

    records = [dict(zip(telemetry.SPAN_FIELDS, telemetry.record_to_list(r)))
               for r in [
        _rec("alice", "driver.round", 100.0, 0.2, rnd=0),
        _rec("bob", "wire.send", 100.0, 1.0, rnd=0),
    ]]
    assert not round_report(records, tolerance=0.25)[0]["wall_agrees"]


# ---------------------------------------------------------------------------
# In-process managers: collection round trip + per-manager TransferLog
# ---------------------------------------------------------------------------


def _pair_cluster(parties=("alice", "bob")):
    ports = get_free_ports(len(parties))
    return {
        p: ClusterConfig(
            parties={
                q: PartyConfig(address=f"127.0.0.1:{port}")
                for q, port in zip(parties, ports)
            },
            current_party=p,
        )
        for p in parties
    }


@pytest.fixture()
def manager_pair():
    mgrs = {
        p: TransportManager(cc, JobConfig(device_put_received=False))
        for p, cc in _pair_cluster().items()
    }
    for m in mgrs.values():
        m.start()
    yield mgrs
    for m in mgrs.values():
        m.stop()


def test_collect_trace_round_trip(manager_pair):
    import numpy as np

    mgrs = manager_pair
    telemetry.install()  # party=None: every seam stamps its own party
    ref = mgrs["alice"].send(
        "bob", np.arange(64, dtype=np.float32), "t1", "0",
        stream="unit", round_tag=7,
    )
    assert mgrs["bob"].recv("alice", "t1", "0").resolve(timeout=30) is not None
    assert ref.resolve(timeout=30)

    records, offset, rep = mgrs["alice"].collect_trace("bob", timeout_s=30)
    assert rep["party"] == "bob" and rep["armed"]
    assert rep["n"] == len(records) > 0
    # Only bob's own view crosses the wire; alice's spans stay home.
    assert all(r.party == "bob" for r in records)
    phases = {r.phase for r in records}
    assert "wire.deliver" in phases, phases
    assert any(r.round == 7 for r in records)
    # Loopback round trip: offset ~0 within the documented RTT/2 bound.
    assert offset["rtt_s"] < 5.0
    assert abs(offset["offset_s"]) <= offset["bound_s"] + 0.5
    # Round-bounded window: a round-99 filter keeps only untagged
    # context records.
    windowed, _, _ = mgrs["alice"].collect_trace(
        "bob", rounds=(99, 99), timeout_s=30
    )
    assert all(r.round is None for r in windowed)


def test_collect_trace_from_disarmed_peer_is_loud_not_hung(manager_pair):
    mgrs = manager_pair
    assert telemetry.installed() is None
    records, _offset, rep = mgrs["alice"].collect_trace("bob", timeout_s=30)
    assert records == [] and not rep["armed"]


def test_transfer_log_is_per_manager(manager_pair):
    import numpy as np

    from rayfed_tpu import metrics

    mgrs = manager_pair
    global_before = len(metrics._global_transfer_log.records())
    ref = mgrs["alice"].send(
        "bob", np.arange(32, dtype=np.float32), "tl1", "0"
    )
    assert mgrs["bob"].recv("alice", "tl1", "0").resolve(timeout=30) is not None
    assert ref.resolve(timeout=30)
    deadline = time.time() + 30
    while (
        not mgrs["alice"].transfer_log.records() and time.time() < deadline
    ):
        time.sleep(0.02)
    sends = mgrs["alice"].transfer_log.records()
    recvs = mgrs["bob"].transfer_log.records()
    # Each party's ring holds ITS view only — nothing leaked into the
    # module-global runtime-less fallback, and nothing conflated.
    assert [r.direction for r in sends] == ["send"]
    assert sends[0].peer == "bob" and sends[0].nbytes > 0
    assert [r.direction for r in recvs] == ["recv"]
    assert recvs[0].peer == "alice"
    assert len(metrics._global_transfer_log.records()) == global_before
    # Runtime-less processes still get the documented fallback.
    assert metrics.get_transfer_log() is metrics._global_transfer_log


# ---------------------------------------------------------------------------
# metrics_snapshot schema stability (the wire-drift discipline, applied
# to the stats surface)
# ---------------------------------------------------------------------------


def test_metrics_snapshot_empty_before_init():
    from rayfed_tpu.metrics import metrics_snapshot

    assert metrics_snapshot() == {}


def test_metrics_snapshot_schema():
    from tests.multiproc import make_cluster, run_parties

    cluster = make_cluster(["alice", "bob"])
    run_parties(_snapshot_party_run, ["alice", "bob"], args=(cluster,))


def _snapshot_party_run(party, cluster):
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.metrics import METRICS_SCHEMA

    fed.init(address="local", cluster=cluster, party=party)

    @fed.remote
    def produce():
        return np.arange(100, dtype=np.float32)

    fed.get(produce.party("alice").remote())
    snap = fed.metrics_snapshot()
    # Every documented section and key exists with the documented type
    # — renaming/retyping a counter fails here the way frame drift
    # fails check_wire_format.  Sections may carry EXTRA keys freely.
    assert set(METRICS_SCHEMA) <= set(snap), sorted(snap)
    for section, keys in METRICS_SCHEMA.items():
        for key, typ in keys.items():
            assert key in snap[section], (section, key, sorted(snap[section]))
            assert isinstance(snap[section][key], typ), (
                section, key, type(snap[section][key]),
            )
    assert snap["telemetry"]["trace_armed"] is False  # disarmed run
    # The async section snapshots fl.async_rounds.ASYNC_STATS; the
    # histogram must be a copy, never an alias of the live counter.
    from rayfed_tpu.fl.async_rounds import ASYNC_STATS

    assert snap["async"]["versions_emitted"] == 0  # no async run here
    snap["async"]["staleness_hist"]["poison"] = 1
    assert "poison" not in ASYNC_STATS["staleness_hist"]
    fed.shutdown()


# ---------------------------------------------------------------------------
# Review-hardening regressions: party attribution, disjoint
# parties/missing, multi-host leader delegation
# ---------------------------------------------------------------------------


def test_streaming_aggregator_spans_carry_party():
    """In-process multi-party runs share ONE process-global recorder;
    the aggregation spans must stamp their acting party or every
    manager's trace window would serve (and the merge would duplicate)
    them."""
    import jax.numpy as jnp

    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl.streaming import StreamingAggregator

    rec = telemetry.install()  # party=None: the stamp must come from the seam
    agg = StreamingAggregator(1, party="alice")
    agg.add_local(0, fl_comp.pack_tree({"w": jnp.ones((8,))}))
    agg.result(timeout=30)
    finalize = [r for r in rec.records() if r.phase == "agg.finalize"]
    assert finalize and all(r.party == "alice" for r in finalize)


def test_trace_collect_disarmed_peer_lands_in_missing_only(
    manager_pair, monkeypatch,
):
    """api.trace_collect: 'parties' (collected) and 'missing' (failed /
    disarmed) are disjoint — a disarmed peer must not count as
    collected."""
    from types import SimpleNamespace

    from rayfed_tpu import api

    mgrs = manager_pair
    assert telemetry.installed() is None  # both ends disarmed
    fake_rt = SimpleNamespace(
        party="alice",
        transport=mgrs["alice"],
        cluster_config=SimpleNamespace(parties=["alice", "bob"]),
    )
    monkeypatch.setattr(api, "get_runtime", lambda: fake_rt)
    out = api.trace_collect(timeout=30)
    assert out["missing"] == {"bob": "recorder not armed"}
    assert out["parties"] == ["alice"]
    assert set(out["parties"]).isdisjoint(out["missing"])
    assert "bob" not in out["clock_offsets"]


def test_multihost_transport_delegates_collect_trace():
    """fed.trace_collect on a multi-host party LEADER must work (the
    inner manager holds the wire clients); a non-leader has no
    cross-party transport and fails loudly with the run-on-the-leader
    pointer."""
    from types import SimpleNamespace

    from rayfed_tpu.distributed import MultiHostTransport

    group = SimpleNamespace(num_processes=1, is_leader=True)
    mht = MultiHostTransport(None, group)
    with pytest.raises(telemetry.TelemetryError, match="party leader"):
        mht.collect_trace("bob")

    calls = {}

    class _Inner:
        def collect_trace(self, peer, rounds=None, timeout_s=None):
            calls["args"] = (peer, rounds, timeout_s)
            return ([], {"offset_s": 0.0}, {"party": peer, "armed": True})

    mht._inner = _Inner()
    out = mht.collect_trace("bob", rounds=(1, 2), timeout_s=5.0)
    assert calls["args"] == ("bob", (1, 2), 5.0)
    assert out[2]["party"] == "bob"


def test_recorder_resize_preserves_newest_records():
    """fed.init(trace_capacity=) against an already-armed (env-armed)
    recorder must honor the explicit request — resize in place, newest
    records kept, instead of silently keeping the old bound."""
    rec = telemetry.install(party="alice", capacity=4)
    for i in range(6):
        rec.emit("wire.send", round=i)
    rec.resize(2)
    assert rec.capacity == 2
    assert [r.round for r in rec.records()] == [4, 5]  # newest kept
    rec.resize(8)
    assert rec.capacity == 8
    rec.emit("wire.send", round=99)
    assert [r.round for r in rec.records()] == [4, 5, 99]
    with pytest.raises(ValueError):
        rec.resize(0)
    # Drop accounting stays consistent across resizes.
    assert rec.stats()["trace_total_recorded"] == 7


def test_malformed_trace_request_gets_fast_error_reply(
    manager_pair, monkeypatch,
):
    """A request the server cannot parse must produce an err-marked
    reply (the object-plane holder-miss shape) so the collector fails
    FAST with the real reason instead of waiting out its full per-peer
    timeout."""
    mgrs = manager_pair

    def bad_request(reply_key, rounds=None, t_send=None):
        return {"v": telemetry.TELEMETRY_VERSION, "rk": str(reply_key),
                "rnd": "bogus", "ts": float(t_send or 0.0)}

    from rayfed_tpu.transport import manager as manager_mod

    monkeypatch.setattr(
        manager_mod.telemetry, "make_trace_request", bad_request
    )
    t0 = time.perf_counter()
    with pytest.raises(telemetry.TelemetryError, match="malformed"):
        mgrs["alice"].collect_trace("bob", timeout_s=30)
    # Fast-fail: one round trip, nowhere near the 30s park.
    assert time.perf_counter() - t0 < 10.0


# ---------------------------------------------------------------------------
# The round engine's own spans (driver, codec, task), the kernels' names
# and the device-trace clock: two in-process parties, real loopback TCP
# ---------------------------------------------------------------------------

PARTIES = ("alice", "bob")
UINT8 = dict(compress_wire=True, packed_wire=True, wire_quant="uint8",
             streaming_agg=True)
BF16_STREAM = dict(compress_wire=True, packed_wire=True, streaming_agg=True)
PIPELINED = dict(compress_wire=True, packed_wire=True)


def _run_rounds(round_kw, rounds=3, in_train=None):
    """``rounds`` FedAvg rounds of a toy model between two in-process
    parties; ``in_train(owner, round)`` runs at every entry into
    ``train``.  Returns ``{party: (final flat f32, get_stats())}``,
    read after ``wait_sending()`` and a barrier over the parties."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu import inprocess
    from rayfed_tpu.fl import compression
    from rayfed_tpu.fl.trainer import run_fedavg_rounds
    from rayfed_tpu.metrics import get_stats
    from rayfed_tpu.runtime import get_runtime

    all_done = threading.Barrier(len(PARTIES))

    def party_main(party):
        @fed.remote
        class Trainer:
            def __init__(self, owner):
                self._owner, self._round = owner, 0

            def train(self, bundle):
                if in_train is not None:
                    in_train(self._owner, self._round)
                self._round += 1
                step = 0.01 if self._owner == "alice" else 0.03
                tree = compression.decompress(bundle, jnp.float32)
                tree = jax.tree_util.tree_map(lambda x: x + step, tree)
                return compression.compress(tree, packed=True)

        trainers = {p: Trainer.party(p).remote(p) for p in PARTIES}
        params = {"w": jnp.linspace(-1.0, 1.0, 3000), "b": jnp.zeros((7,))}
        final = run_fedavg_rounds(trainers, params, rounds, **round_kw)
        flat = np.concatenate([
            np.asarray(x, np.float32).ravel()
            for x in jax.tree_util.tree_leaves(final)
        ])
        get_runtime().cleanup_manager.wait_sending()
        all_done.wait(timeout=60)
        return flat, get_stats()

    return inprocess.run_parties(
        party_main, inprocess.loopback_cluster(PARTIES), timeout=120,
        logging_level="warning",
    )


def _by_phase(records):
    out = {}
    for r in records:
        out.setdefault(r.phase, []).append(r)
    return out


def test_uint8_round_emits_every_seam_span():
    rec = telemetry.install(capacity=1 << 16)
    _run_rounds(UINT8)
    spans = _by_phase(rec.records())
    assert {
        "task.wait", "task.run", "driver.round", "fl.pack", "fl.unpack",
        "fl.quant.ref", "fl.quant.grid", "fl.quant.encode",
        "fl.quant.decode", "fl.quant.recode", "fl.quant.delta",
    } <= set(spans), sorted(spans)
    # Every new span names the party that did the work.
    for phase in ("task.wait", "task.run", "driver.round", "fl.pack",
                  "fl.quant.encode", "fl.quant.delta"):
        assert {r.party for r in spans[phase]} == set(PARTIES), phase
    # Children carry the span that caused them and inherit its round.
    for phase in ("fl.quant.ref", "fl.quant.delta", "fl.quant.recode"):
        for r in spans[phase]:
            assert r.detail["parent"] == "driver.round", (phase, r)
            assert r.round in (0, 1, 2), (phase, r)
    # nbytes is what crossed to the host inside the span (re-pinned in
    # PR 27: the reference and the delta stay on the device, a grid
    # fetches 12 bytes a block, a recode the codes).
    for phase, crossed in (("fl.quant.ref", 0), ("fl.quant.delta", 0),
                           ("fl.quant.grid", 12), ("fl.quant.recode", 3007)):
        assert all(r.nbytes == crossed for r in spans[phase]), phase
    # The downlink recode is the parent of its grid, encode and decode;
    # the round comes down two levels.
    down = [r for r in spans["fl.quant.grid"] if r.detail["side"] == "down"]
    assert down and all(
        r.detail["parent"] == "fl.quant.recode" and r.round in (1, 2)
        for r in down
    )
    assert any(r.detail["side"] == "up" for r in spans["fl.quant.grid"])
    under_recode = {
        r.phase for rs in spans.values() for r in rs
        if (r.detail or {}).get("parent") == "fl.quant.recode"
    }
    assert under_recode == {
        "fl.quant.grid", "fl.quant.encode", "fl.quant.decode"
    }
    assert all(r.nbytes == 3007 for r in spans["fl.quant.encode"])
    # Pack and unpack are told apart by parent: the engine's or the
    # trainer's own.
    for phase in ("fl.pack", "fl.unpack"):
        parents = {r.detail["parent"] for r in spans[phase]}
        assert parents == {"driver.round", "task.run"}, (phase, parents)
    # task.*: what the trainer waited for and its own time, by name.
    trains = [r for r in spans["task.wait"]
              if r.detail["name"].endswith("train")]
    assert len(trains) == 3 * len(PARTIES)
    assert all(r.detail["queue_ms"] >= 0.0 for r in trains)
    assert any(r.detail["name"] == "Trainer.train" for r in spans["task.run"])
    # agg.fold keeps its duration and says what one fold kernel moves.
    for r in spans["agg.fold"]:
        assert r.detail["chunk_elems"] > 0 and "drain_ms" in r.detail
        assert r.detail["acc"] in ("int32", "float32")
    # The f32 fold of the bootstrap round is jitted: its drain is timed.
    assert any(
        r.detail["fold"] == "jit" and r.detail["drain_ms"] >= 0.0
        for r in spans["agg.fold"]
    )


def test_recorder_armed_mid_call_is_seen_from_the_next_round():
    """The benchmark (and an operator) arms the recorder in the middle
    of ``run_fedavg_rounds``: the armed state is read once a round."""
    import threading

    lock = threading.Lock()

    def arm_in_round_one(owner, round_):
        with lock:
            if round_ == 1 and telemetry.installed() is None:
                telemetry.install(capacity=1 << 16)

    _run_rounds(BF16_STREAM, rounds=4, in_train=arm_in_round_one)
    rounds_seen = {
        (r.party, r.round) for r in telemetry.installed().records()
        if r.phase == "driver.round"
    }
    assert {(p, r) for p in PARTIES for r in (2, 3)} <= rounds_seen
    assert not any(r == 0 for _, r in rounds_seen)


def test_pipelined_path_emits_dispatch_and_stays_lazy():
    import numpy as np

    def slow_first_round(owner, round_):
        if round_ == 0:
            time.sleep(0.3)

    plain = _run_rounds(PIPELINED, rounds=4, in_train=slow_first_round)
    rec = telemetry.install(capacity=1 << 16)
    traced = _run_rounds(PIPELINED, rounds=4, in_train=slow_first_round)
    for p in PARTIES:  # tracing changes no result
        assert np.array_equal(plain[p][0], traced[p][0])
    spans = _by_phase(rec.records())
    assert "driver.round" not in spans  # no round boundary on this path
    for p in PARTIES:
        dispatch = {r.round: r for r in spans["driver.dispatch"]
                    if r.party == p}
        assert sorted(dispatch) == [0, 1, 2, 3]
        first_train_end = min(
            r.t_start + r.dur_s for r in spans["task.run"]
            if r.party == p and r.detail["name"].endswith("train")
        )
        # Rounds 0-2 were enqueued while the first train still ran:
        # nothing was materialized for a span's sake.  Only the last
        # round waits for its aggregate, as before.
        for r in (0, 1, 2):
            assert (dispatch[r].t_start + dispatch[r].dur_s
                    < first_train_end), (p, r)
        assert dispatch[3].t_start + dispatch[3].dur_s > first_train_end
    # On this path the trainer's wait for the aggregate is its task.wait.
    waits = [r.dur_s for r in spans["task.wait"]
             if r.detail["name"].endswith("train")]
    assert max(waits) > 0.0


def test_disarmed_round_records_nothing_and_builds_no_annotation(monkeypatch):
    import jax.profiler

    built = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        built.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    _run_rounds(UINT8)
    assert built == [] and telemetry.installed() is None
    # The same patch does see an armed span's annotation.
    rec = telemetry.install()
    with telemetry.span("fl.pack"):
        pass
    assert built == ["fl.pack"] and len(rec.records()) == 1


def test_scoped_spans_share_the_device_trace_clock(tmp_path):
    """One profile, host spans and device operations together: a scoped
    span's annotation sits where its record says, measured from the
    ``trace.anchor`` pair."""
    import glob

    from jax.profiler import ProfileData

    rec = telemetry.install(capacity=1 << 16)
    telemetry.start_profile(str(tmp_path))
    try:
        _run_rounds(UINT8, rounds=2)
    finally:
        telemetry.stop_profile()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    starts = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("trace.anchor", "fl.quant.ref", "task.run"):
                    starts.setdefault(e.name, []).append(e.start_ns)
    records = _by_phase(rec.records())
    (anchor_ns,), (anchor,) = starts["trace.anchor"], records["trace.anchor"]
    assert anchor.detail["time"] == anchor.t_start
    for phase in ("fl.quant.ref", "task.run"):
        on_trace = sorted((ns - anchor_ns) / 1e9 for ns in starts[phase])
        on_wall = sorted(r.t_start - anchor.t_start for r in records[phase])
        assert len(on_trace) == len(on_wall) > 0
        for a, b in zip(on_trace, on_wall):
            assert abs(a - b) < 2e-3, (phase, a, b)


def _kernel_cases():
    import jax.numpy as jnp

    from rayfed_tpu.fl import fedavg, quantize, streaming

    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    grid = (jnp.ones(1, f32), jnp.zeros(1, f32))  # scales, zps
    return {
        "fed_fold_i32": (
            fedavg.quantized_accum_kernel(8, "uint8"),
            (jnp.zeros(8, i32), jnp.ones(8, u8), i32(0), i32(1)),
        ),
        "fed_fold_f32": (
            streaming._accum_kernel(8, "float32", "bfloat16"),
            (jnp.zeros(8, f32), jnp.ones(8, jnp.bfloat16), i32(0), f32(1)),
        ),
        "fed_quant_encode": (
            quantize._quantize_kernel(8, 8, "uint8", True),
            (jnp.ones(8, f32), jnp.zeros(8, f32), *grid, jnp.zeros(8, f32)),
        ),
        "fed_quant_decode": (
            quantize._dequantize_kernel(8, 8, "uint8", "float32", True),
            (jnp.ones(8, u8), jnp.zeros(8, f32), *grid),
        ),
        "fed_finalize_f32": (
            fedavg._stripe_finalize_jit(8, "float32"),
            (jnp.zeros(8, f32), f32(2)),
        ),
        "fed_finalize_i32": (
            fedavg._quant_finalize_jit(8, 8, "float32", True),
            (jnp.zeros(8, i32), jnp.zeros(8, f32), *grid, f32(2)),
        ),
    }


@pytest.mark.parametrize("name", [
    "fed_fold_i32", "fed_fold_f32", "fed_quant_encode", "fed_quant_decode",
    "fed_finalize_f32", "fed_finalize_i32",
])
def test_kernels_are_named_in_the_trace(name):
    """A device trace names a program after its jitted function: the
    fold, codec and finalize kernels each say what ran."""
    kernel, args = _kernel_cases()[name]
    assert kernel.__name__ == name
    assert f"module @jit_{name} " in kernel.lower(*args).as_text()


# ---------------------------------------------------------------------------
# wait_sending(): every tracked send is billed when it returns
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("round_kw", [UINT8, PIPELINED],
                         ids=["streaming-uint8", "pipelined-bf16"])
def test_send_bytes_are_billed_when_wait_sending_returns(round_kw):
    out = _run_rounds(round_kw)
    sent = sum(stats["send_bytes"] for _, stats in out.values())
    received = sum(stats["receive_bytes"] for _, stats in out.values())
    assert sent == received > 0


def test_wait_sending_drains_a_ref_pushed_behind_its_sentinel():
    import threading

    from rayfed_tpu.cleanup import CleanupManager
    from rayfed_tpu.executor import LocalRef

    cm = CleanupManager()
    first, late = LocalRef(), LocalRef()
    cm.push_to_sending(first)
    waiter = threading.Thread(target=cm.wait_sending, daemon=True)
    waiter.start()
    time.sleep(0.1)  # the sentinel is queued behind ``first``
    cm.push_to_sending(late)  # lands behind the sentinel
    first.set_result(True)
    waiter.join(timeout=0.5)
    assert waiter.is_alive()  # still waiting: ``late`` is tracked too
    late.set_result(True)
    waiter.join(timeout=10)
    assert not waiter.is_alive() and not cm.check_thread_alive
