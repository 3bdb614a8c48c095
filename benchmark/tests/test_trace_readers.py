"""The readers of the round engine's own spans, each on a hand-made
context: ``driver_host_ms``, ``train_wait_ms``, ``unattributed_idle``,
and ``fold_roofline`` on a hand-made ``XLA Modules`` line."""

import os
import types

import pytest

from benchmark import harness, xplane
from rayfed_tpu.telemetry import SpanRecord

ROUNDS = [7, 8, 9]  # traced rounds, one second each from t = 100
EDGES = {r: (100.0 + (r - 7), 101.0 + (r - 7)) for r in ROUNDS}


def reader(name):
    (mod,) = [
        m for m in harness.matching_layer_metrics("any.cell")
        if m.NAME == name
    ]
    return mod


def span(phase, party, t_start, dur_s, **detail):
    return SpanRecord(
        party=party, round=None, epoch=None, phase=phase, peer=None,
        stream=None, nbytes=0, t_start=t_start, dur_s=dur_s, outcome="ok",
        detail=detail or None,
    )


def context(records, **more):
    return types.SimpleNamespace(
        traced_rounds=ROUNDS, round_edges=EDGES, recorder_records=records,
        trace={}, peaks={"hbm_bytes_per_s": 819e9}, **more,
    )


def engine_round(t, recode):
    """One round of the quantized engine as alice (coordinator) and bob
    see it, starting at ``t``."""
    rows = []
    for party in ("alice", "bob"):
        rows += [
            span("fl.pack", party, t + 0.01, 0.010, parent="driver.round"),
            span("fl.quant.ref", party, t + 0.02, 0.100,
                 parent="driver.round"),
            span("fl.quant.grid", party, t + 0.12, 0.050, side="up",
                 parent="driver.round"),
            # the trainer's own unpack and pack: party compute
            span("fl.unpack", party, t + 0.03, 0.500, parent="task.run"),
            span("fl.pack", party, t + 0.60, 0.500, parent="task.run"),
            # the uplink encode runs on the worker after the task: no parent
            span("fl.quant.encode", party, t + 0.70, 0.040),
            span("fl.quant.delta", party, t + 0.90, 0.030,
                 parent="driver.round"),
            span("fl.unpack", party, t + 0.93, 0.020, parent="driver.round"),
        ]
    rows += [
        span("fl.quant.recode", "alice", t + 0.80, recode,
             parent="driver.round"),
        # inside the recode: never counted a second time
        span("fl.quant.grid", "alice", t + 0.80, 0.04, side="down",
             parent="fl.quant.recode"),
        span("fl.quant.encode", "alice", t + 0.84, 0.03,
             parent="fl.quant.recode"),
        span("fl.quant.decode", "alice", t + 0.87, 0.01,
             parent="fl.quant.recode"),
    ]
    return rows


def test_driver_host_ms_is_the_largest_partys_top_level_engine_time():
    records = [
        row for r, recode in zip(ROUNDS, (0.08, 0.10, 0.30))
        for row in engine_round(EDGES[r][0], recode)
    ]
    # outside every traced round: ignored
    records.append(span("fl.quant.ref", "alice", 50.0, 9.0,
                        parent="driver.round"))
    # alice per round: 10 + 100 + 50 + 40 + 30 + 20 = 250 ms + the recode
    assert reader("driver_host_ms").read(context(records)) == pytest.approx(
        250.0 + 100.0
    )


def test_driver_host_ms_needs_engine_work_in_every_round():
    mod = reader("driver_host_ms")
    assert mod.read(context([])) is None
    # the lazy pipelined path: one unpack, in the last round only
    last_only = [span("fl.unpack", "alice", EDGES[9][0] + 0.5, 0.02,
                      parent="driver.dispatch")]
    assert mod.read(context(last_only)) is None
    # a trainer's own pack and unpack are not the engine's
    trainers = [
        span("fl.pack", "alice", EDGES[r][0] + 0.1, 0.5, parent="task.run")
        for r in ROUNDS
    ]
    assert mod.read(context(trainers)) is None


def test_train_wait_ms_is_the_median_wait_of_the_train_tasks():
    records = [
        span("task.wait", p, EDGES[r][0] + 0.2, wait, name="Trainer.train",
             queue_ms=1.0)
        for r, waits in zip(ROUNDS, ((0.010, 0.030), (0.020, 0.040),
                                     (0.050, 0.060)))
        for p, wait in zip(("alice", "bob"), waits)
    ]
    records += [
        span("task.wait", "alice", EDGES[8][0] + 0.3, 5.0, name="_reduce"),
        span("task.wait", "alice", 50.0, 7.0, name="Trainer.train"),
        span("task.run", "alice", EDGES[8][0] + 0.4, 0.9,
             name="Trainer.train"),
    ]
    mod = reader("train_wait_ms")
    assert mod.read(context(records)) == pytest.approx(35.0)
    assert mod.read(context([])) is None


def test_unattributed_idle_is_the_waiting_share_of_the_idle_time():
    mod = reader("unattributed_idle")
    ctx = context([])
    assert mod.read(ctx) is None  # no device trace (a CPU run)
    # The ledger's newest line of the uint8 cell (PR 23): 82%.
    ctx.trace = {
        "worst_idle_share": 0.43045, "window_s": 10.80,
        "idle_gaps": [
            ["mailbox.wait", 3.008077591], [xplane.NO_SPAN, 0.821053714],
            ["wire.frame", 0.403762713], ["wire.deliver", 0.078315994],
            ["agg.finalize", 0.064855109], ["agg.fold", 0.055607004],
        ],
    }
    assert mod.read(ctx) == pytest.approx(82.37, abs=0.01)
    ctx.trace["idle_gaps"] = [
        ["fl.quant.recode", 2.0], ["task.wait", 0.5], ["mailbox.wait", 0.5],
        ["fl.quant.delta", 1.0],
    ]
    ctx.trace.update(worst_idle_share=0.5, window_s=8.0)
    assert mod.read(ctx) == pytest.approx(25.0)
    ctx.trace.update(worst_idle_share=0.0)
    assert mod.read(ctx) is None


def modules_profile(events):
    """A profile with one chip whose ``XLA Modules`` line holds
    ``events`` and a host line holding the harness's anchor at 1 ms."""
    def line(name, rows):
        return types.SimpleNamespace(name=name, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in rows
        ])

    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line(xplane.MODULES_LINE, events), line(xplane.OPS_LINE, []),
        ]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("python3", [(xplane.ANCHOR, 1_000_000, 2_000_000)]),
        ]),
    ])


FOLD_I32 = dict(fold="jit", codes="uint8", acc="int32", chunk_elems=1 << 21)


def fold_context(monkeypatch, events, detail=FOLD_I32, window=(10.0, 11.0)):
    mod = reader("fold_roofline")
    monkeypatch.setattr(mod.xplane, "find_xplane", lambda d: "a.xplane.pb")
    monkeypatch.setattr(
        mod.xplane, "load", lambda path: modules_profile(events)
    )
    run = types.SimpleNamespace(
        profile_dir="unused", anchor_wall=10.0, profile_wall=list(window),
    )
    records = [span("agg.fold", "alice", 10.2, 0.1, **detail)] if detail else []
    ctx = context(records, run=run)
    ctx.trace = {"window_s": 1.0}  # a device trace was reduced
    return mod, ctx


def test_fold_roofline_on_a_hand_made_modules_line(monkeypatch):
    # The anchor (trace clock 1 ms) was noted at wall 10.0 s, so the
    # profiled second is 1 ms .. 1001 ms on the trace's clock.
    ms = 1_000_000
    events = [
        ("jit_fed_fold_i32(123)", 100 * ms, 40_000),
        ("jit_fed_fold_i32(123)", 200 * ms, 50_000),
        ("jit_fed_fold_i32(123)", 300 * ms, 90_000),
        ("jit_step_fn(9)", 400 * ms, 500 * ms),  # another program
        ("jit__apply(5)", 450 * ms, 10),  # the parent's name: not found
        ("jit_fed_fold_i32(123)", 2000 * ms, 10),  # after the window
    ]
    mod, ctx = fold_context(monkeypatch, events)
    # 2^21 elements x (4 + 4 + 1) bytes in the median 50 us, of 819 GB/s
    want = 100.0 * (1 << 21) * 9 / 50e-6 / 819e9
    assert mod.read(ctx) == pytest.approx(want)
    assert 0.0 < want <= 100.0
    # bf16 into float32 moves 10 bytes an element
    f32 = dict(FOLD_I32, codes="bfloat16", acc="float32")
    assert mod.bytes_per_fold(f32) == (1 << 21) * 10
    mod, ctx = fold_context(
        monkeypatch,
        [("jit_fed_fold_f32(7)", 100 * ms, 50_000)], detail=f32,
    )
    assert mod.read(ctx) == pytest.approx(want * 10 / 9)


def test_fold_roofline_is_silent_where_there_is_nothing_to_read(monkeypatch):
    ms = 1_000_000
    fold = [("jit_fed_fold_i32(123)", 100 * ms, 50_000)]
    # no fold program in the window (the parent names it jit__apply)
    mod, ctx = fold_context(monkeypatch, [("jit__apply(5)", 100 * ms, 50_000)])
    assert mod.read(ctx) is None
    # a program that records no chunk size (the parent), a host fold,
    # no device trace, no peaks
    mod, ctx = fold_context(monkeypatch, fold, detail=None)
    assert mod.read(ctx) is None
    mod, ctx = fold_context(
        monkeypatch, fold, detail=dict(FOLD_I32, fold="numpy")
    )
    assert mod.read(ctx) is None
    mod, ctx = fold_context(monkeypatch, fold)
    ctx.trace = {}
    assert mod.read(ctx) is None
    mod, ctx = fold_context(monkeypatch, fold)
    ctx.peaks = None
    assert mod.read(ctx) is None


def test_the_manifest_lists_every_reader():
    import json

    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        manifest = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("driver_host_ms", "train_wait_ms", "unattributed_idle",
                 "fold_roofline"):
        mod, entry = reader(name), manifest[name]
        assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            entry["unit"], entry["layer"], entry["moves"], entry["source"]
        )
        assert entry["workloads"]
