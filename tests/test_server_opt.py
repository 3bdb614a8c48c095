"""Packed-domain server optimization (fl.server_opt): FedAC / server
momentum as fused finalize-side kernels.

All in-process per the tier-1 budget note (toy buffers, in-memory
sinks — no party subprocesses; the fed-API e2e leg rides the EXISTING
test_streaming_agg trainer child).  What is covered here:

- kernel units against a numpy reference + the bit-exact plain-FedAvg
  degenerate configs;
- multi-controller byte agreement of the resync-replicated state;
- the quorum-cutoff subset refold feeding the step (effective Σw);
- quantized-downlink-AFTER-step parity: the post-step broadcast decoded
  on every controller equals the coordinator's full-buffer recode —
  including a cutoff round (the PR 12 gather-recode identity, one
  level later);
- the hierarchy regrouped (presummed) fold + step + downlink byte-
  identity with the flat streaming fold (the bench gate's mirror);
- checkpoint state roundtrip + the LOUD server-opt mismatch guard;
- rounds-to-target on the quadratic recurrence (FedAC < plain).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from rayfed_tpu.fl import compression as fl_comp
from rayfed_tpu.fl import fedavg
from rayfed_tpu.fl import quantize as qz
from rayfed_tpu.fl import server_opt as so
from rayfed_tpu.fl.streaming import StreamingAggregator
from rayfed_tpu.transport import wire

CE = 1 << 12


def _payload_of(tree):
    from rayfed_tpu import native

    bufs = wire.encode_payload(tree)
    return native.gather_copy(
        [
            memoryview(b) if isinstance(b, (bytes, bytearray)) else b
            for b in bufs
        ]
    )


def _setup(n=3, size=40_000, seed=1):
    rng = np.random.default_rng(seed)
    ref = rng.normal(size=(size,)).astype(np.float32)
    packeds = [
        fl_comp.pack_tree(
            {"w": jnp.asarray(ref + 0.01 * rng.normal(size=(size,))
                              .astype(np.float32))},
            jnp.float32,
        )
        for _ in range(n)
    ]
    prev_delta = 0.01 * rng.normal(size=(size,)).astype(np.float32)
    grid = qz.make_round_grid(prev_delta, chunk_elems=CE, mode="delta",
                              expand=4.0)
    return ref, packeds, grid


# ---------------------------------------------------------------------------
# Spec + kernel units
# ---------------------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        so.PackedServerOpt("adamw", (0.1,))
    with pytest.raises(ValueError, match="lr"):
        so.server_momentum(lr=0.0)
    with pytest.raises(ValueError, match="momentum"):
        so.server_momentum(momentum=1.0)
    with pytest.raises(ValueError, match="gamma"):
        so.fedac(lam=1.0, gamma=0.5)
    with pytest.raises(ValueError, match="beta"):
        so.fedac(beta=1.0)
    opt = so.fedac(1.0, 3.0, 0.5)
    assert opt.describe() == {"kind": "fedac", "hyper": [1.0, 3.0, 0.5]}
    assert opt == so.fedac(1.0, 3.0, 0.5)
    assert opt != so.fedac(1.0, 3.0, 0.25)


@pytest.mark.parametrize(
    "opt",
    [so.server_momentum(0.7, 0.6), so.fedac(0.9, 2.5, 0.4)],
    ids=["momentum", "fedac"],
)
def test_step_kernel_matches_reference(opt):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5000,)).astype(np.float32)
    avg = x - 0.01 * rng.normal(size=x.shape).astype(np.float32)
    state = opt.init(x)
    got = np.asarray(
        fedavg.server_step_kernel(opt.kind, opt.hyper)(
            jnp.asarray(x), jnp.asarray(avg), *state.bufs
        )
    )
    want, want_state = so.reference_step(
        opt, x, avg, [np.asarray(b) for b in state.bufs]
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    # Resync from the realized step reproduces the true state update.
    new_state = fedavg.server_resync_kernel(opt.kind, opt.hyper)(
        jnp.asarray(x), jnp.asarray(got), *state.bufs
    )
    np.testing.assert_allclose(
        np.asarray(new_state[0]), want_state[0], rtol=0, atol=1e-4
    )


@pytest.mark.parametrize(
    "opt",
    [so.server_momentum(1.0, 0.0), so.fedac(1.0, 1.0, 0.0)],
    ids=["momentum-degenerate", "fedac-degenerate"],
)
def test_degenerate_configs_are_plain_fedavg_bitexact(opt):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(4096,)).astype(np.float32)
    avg = x - 0.01 * rng.normal(size=x.shape).astype(np.float32)
    got = np.asarray(
        fedavg.server_step_kernel(opt.kind, opt.hyper)(
            jnp.asarray(x), jnp.asarray(avg), *opt.init(x).bufs
        )
    )
    np.testing.assert_array_equal(got, avg)


def test_step_fn_guards():
    ref, packeds, grid = _setup(1)
    opt = so.fedac(1.0, 3.0, 0.5)
    runner = so.PackedServerOptimizer(opt)
    with pytest.raises(RuntimeError, match="ensure"):
        runner.step_fn(ref)
    runner.ensure(ref)
    step = runner.step_fn(ref)
    with pytest.raises(TypeError, match="FINALIZED float"):
        step(qz.quantize_packed(packeds[0], grid, ref=ref))
    with pytest.raises(TypeError, match="PackedTree"):
        step({"w": np.ones(3)})
    short = fl_comp.pack_tree({"w": jnp.ones(7)}, jnp.float32)
    with pytest.raises(ValueError, match="elements"):
        step(short)
    out = step(packeds[0])
    assert isinstance(out, fl_comp.PackedTree)
    assert out.spec.wire_dtype == "float32"


# ---------------------------------------------------------------------------
# Multi-controller byte agreement (the ring path's whole contract)
# ---------------------------------------------------------------------------


def test_controller_replicas_byte_agree_across_rounds():
    """Three independent controller replicas stepping the same
    byte-identical broadcasts stay byte-identical in BOTH model and
    state — the invariant that makes the local step of ring rounds and
    the failover takeover of quorum rounds correct."""
    rng = np.random.default_rng(3)
    opt = so.fedac(1.0, 3.0, 0.5)
    size = 20_000
    x = rng.normal(size=(size,)).astype(np.float32)
    tmpl = fl_comp.pack_tree({"w": jnp.asarray(x)}, jnp.float32)
    controllers = [so.PackedServerOptimizer(opt) for _ in range(3)]
    cur = np.asarray(tmpl.buf).copy()
    for r in range(4):
        avg = cur - 0.01 * rng.normal(size=(size,)).astype(np.float32)
        res = fl_comp.PackedTree(
            jnp.asarray(avg), tmpl.passthrough, tmpl.spec
        )
        outs = []
        for c in controllers:
            c.ensure(cur)
            outs.append(np.asarray(c.step_fn(cur)(res).buf))
        assert all(np.array_equal(o, outs[0]) for o in outs[1:])
        for c in controllers:
            c.resync(cur, outs[0])
        states = [np.asarray(c.state.bufs[0]) for c in controllers]
        assert all(np.array_equal(s, states[0]) for s in states[1:])
        cur = outs[0]


# ---------------------------------------------------------------------------
# Quorum-cutoff subset feeds the step (effective Σw)
# ---------------------------------------------------------------------------


def test_quorum_subset_refold_feeds_step_bitexact():
    ref, packeds, grid = _setup(3)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    ws = [3, 1, 2]
    opt = so.fedac(1.0, 3.0, 0.5)
    runner = so.PackedServerOptimizer(opt)
    runner.ensure(ref)
    step = runner.step_fn(ref)

    agg = StreamingAggregator(3, weights=ws, chunk_elems=CE,
                              quant=grid, quant_ref=ref, quorum=2,
                              labels=["a", "b", "c"])
    agg.sink(1)  # source 1 never arrives
    agg.add_local(0, qts[0])
    agg.sink(2).on_complete(_payload_of(qts[2]))
    got = step(agg.result(timeout=60, deadline_s=0.4))
    assert agg.quorum_members == [0, 2]
    # The step's pseudo-gradient is the SUBSET's reweighted mean
    # (effective Σw = 3+2): one-shot subset reduce + the same kernel.
    subset = fedavg.packed_quantized_sum([qts[0], qts[2]], [3, 2], ref=ref)
    want = step(subset)
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(want.buf)
    )


# ---------------------------------------------------------------------------
# Quantized downlink AFTER the step: every controller decodes the
# coordinator's full-buffer recode (satellite of ISSUE 13)
# ---------------------------------------------------------------------------


def _decode_as_receiver(wire_tree, ref, out_dtype=np.float32):
    """Re-materialize the wire form from its serialized bytes (what a
    receiving controller holds) and decode it independently."""
    payload = _payload_of(wire_tree)
    got = wire.decode_payload(memoryview(payload), zero_copy=True)
    assert isinstance(got, qz.QuantizedPackedTree)
    return got.dequantize(
        out_dtype, ref=ref if got.gmeta.mode == "delta" else None
    )


@pytest.mark.parametrize("cutoff", [False, True], ids=["full", "cutoff"])
def test_quantized_downlink_after_step_parity(cutoff):
    """The post-step broadcast decoded on every controller == the
    coordinator's full-buffer recode of the post-step model — with and
    without a quorum cutoff feeding the step a subset refold."""
    ref, packeds, grid = _setup(3)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    ws = [3, 1, 2]
    opt = so.server_momentum(0.9, 0.5)
    runner = so.PackedServerOptimizer(opt)
    runner.ensure(ref)
    step = runner.step_fn(ref)

    if cutoff:
        agg = StreamingAggregator(3, weights=ws, chunk_elems=CE,
                                  quant=grid, quant_ref=ref, quorum=2,
                                  labels=["a", "b", "c"])
        agg.sink(1)
        agg.add_local(0, qts[0])
        agg.sink(2).on_complete(_payload_of(qts[2]))
        result = agg.result(timeout=60, deadline_s=0.4)
    else:
        agg = StreamingAggregator(3, weights=ws, chunk_elems=CE,
                                  quant=grid, quant_ref=ref)
        for i, q in enumerate(qts):
            agg.add_local(i, q)
        result = agg.result(timeout=60)

    stepped = step(result)
    wire_result, decoded, descr = qz.quantize_downlink(
        stepped, grid, ref, None
    )
    # The downlink grid is ranged by the POST-step delta (mode stays
    # "delta" against the shared starting model).
    assert descr["md"] == "delta"
    # Coordinator's return value IS the recode decode...
    np.testing.assert_array_equal(
        np.asarray(decoded.buf),
        np.asarray(
            wire_result.dequantize(np.float32, ref=ref).buf
        ),
    )
    # ...and a receiver decoding the serialized payload independently
    # lands on the identical bytes (every controller byte-agrees on the
    # post-step broadcast).
    receiver = _decode_as_receiver(wire_result, ref)
    np.testing.assert_array_equal(
        np.asarray(receiver.buf), np.asarray(decoded.buf)
    )
    # Both controllers resync to the identical state from it.
    a = so.PackedServerOptimizer(opt)
    a.ensure(ref)
    a.resync(ref, np.asarray(decoded.buf))
    b = so.PackedServerOptimizer(opt)
    b.ensure(ref)
    b.resync(ref, np.asarray(receiver.buf))
    np.testing.assert_array_equal(
        np.asarray(a.state.bufs[0]), np.asarray(b.state.bufs[0])
    )


# ---------------------------------------------------------------------------
# Hierarchy (presummed regrouped fold) + step == flat streaming + step
# ---------------------------------------------------------------------------


def test_hierarchy_regrouped_fold_step_downlink_bitexact():
    """Region partial sums folded at the root + ONE step + downlink ==
    the flat streaming fold + the SAME step + downlink, byte-exact."""
    from rayfed_tpu.fl.hierarchy import RegionSumTree, partial_sum_dtype
    from rayfed_tpu.fl.compression import PackSpec

    ref, packeds, grid = _setup(4)
    ws = [2, 1, 3, 1]
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    opt = so.fedac(1.0, 3.0, 0.5)
    runner = so.PackedServerOptimizer(opt)
    runner.ensure(ref)
    step = runner.step_fn(ref)

    # Flat: streaming integer fold over all 4 + step + downlink.
    flat = StreamingAggregator(4, weights=ws, chunk_elems=CE,
                               quant=grid, quant_ref=ref)
    for i, q in enumerate(qts):
        flat.add_local(i, q)
    flat_wire, flat_decoded, _ = qz.quantize_downlink(
        step(flat.result(timeout=60)), grid, ref, None
    )

    # Hierarchical: two regions' RAW integer partial sums fold at unit
    # weight through a presummed root aggregator, then the SAME step +
    # downlink producer.
    ps_dt = partial_sum_dtype(grid.qabs_max, sum(ws))
    regions = [(0, 1), (2, 3)]
    region_sums = []
    for members in regions:
        acc = np.zeros(grid.total_elems, np.int64)
        for i in members:
            acc += ws[i] * np.asarray(qts[i].buf).astype(np.int64)
        spec = PackSpec(qts[0].spec.entries, qts[0].spec.treedef, ps_dt)
        region_sums.append(RegionSumTree(
            acc.astype(np.dtype(ps_dt)), grid.scales, grid.zps, (),
            spec, grid.meta(),
        ))
    root = StreamingAggregator(
        2, weights=[float(ws[0] + ws[1]), float(ws[2] + ws[3])],
        chunk_elems=CE, quant=grid, quant_ref=ref, presummed=ps_dt,
        labels=["region 0", "region 1"],
    )
    for g, rs in enumerate(region_sums):
        root.add_local(g, rs)
    hier_wire, hier_decoded, _ = qz.quantize_downlink(
        step(root.result(timeout=60)), grid, ref, None
    )

    np.testing.assert_array_equal(
        np.asarray(flat_decoded.buf), np.asarray(hier_decoded.buf)
    )
    np.testing.assert_array_equal(
        np.asarray(flat_wire.buf), np.asarray(hier_wire.buf)
    )


# ---------------------------------------------------------------------------
# Checkpointing: state roundtrip + the loud mismatch guard
# ---------------------------------------------------------------------------


def test_checkpoint_state_roundtrip(tmp_path):
    from rayfed_tpu.checkpoint import FedCheckpointer

    opt = so.fedac(1.0, 3.0, 0.5)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(512,)).astype(np.float32)
    runner = so.PackedServerOptimizer(opt)
    runner.ensure(x)
    runner.resync(x, x - 0.01)  # advance once so the state is nontrivial
    ck = FedCheckpointer(str(tmp_path), "alice")
    ck.save(
        3, {"params": {"w": x}, "server_state": runner.state},
        metadata={"server_opt": opt.describe()},
    )
    target = {"params": {"w": np.zeros_like(x)},
              "server_state": opt.init(np.zeros_like(x))}
    r, snap = ck.restore(target=target)
    assert r == 3
    restored = so.PackedServerOptimizer(opt, state=snap["server_state"])
    np.testing.assert_array_equal(
        np.asarray(restored.state.bufs[0]),
        np.asarray(runner.state.bufs[0]),
    )
    assert ck.load_metadata(3)["server_opt"] == opt.describe()


def test_snapshot_server_opt_guard_matrix():
    from rayfed_tpu.fl.fedopt import server_sgd

    packed = so.fedac(1.0, 3.0, 0.5).describe()
    none = so.describe_server_opt(None)
    legacy = so.describe_server_opt(server_sgd(0.5, 0.9))
    ok = so.check_snapshot_server_opt
    # Matching stamps pass.
    ok(packed, packed)
    ok(none, none)
    ok(legacy, legacy)
    # Pre-stamp snapshots only resume stateless configs.
    ok(None, none)
    ok(None, legacy)
    with pytest.raises(ValueError, match="no server_opt stamp"):
        ok(None, packed)
    # Every cross-config restore is refused, naming both sides.
    for stored, expected in [
        (none, packed), (packed, none), (legacy, packed),
        (packed, legacy), (none, legacy), (legacy, none),
        ({"kind": "fedac", "hyper": [1.0, 3.0, 0.25]}, packed),
        ({"kind": "momentum", "hyper": [1.0, 0.9]}, packed),
    ]:
        with pytest.raises(ValueError, match="server_opt mismatch"):
            ok(stored, expected)


def test_load_state_refuses_foreign_spec():
    a = so.fedac(1.0, 3.0, 0.5)
    b = so.fedac(1.0, 2.0, 0.5)
    st = a.init(np.zeros(16, np.float32))
    with pytest.raises(ValueError, match="restored server-opt state"):
        so.PackedServerOptimizer(b, state=st)


# ---------------------------------------------------------------------------
# Rounds-to-target: the point of the whole exercise
# ---------------------------------------------------------------------------


def _rounds_to_target(opt, target_loss, max_rounds=420):
    """The quadratic FedAvg recurrence driven through the REAL kernels
    (step + resync) — 2 heterogeneous parties (zero-sum local optima
    shifts, so the SHARED optimum is the fixed point), per-coordinate
    curvature, loss = mean squared distance to the shared optimum."""
    rng = np.random.default_rng(11)
    size = 4096
    opt_point = rng.normal(size=(size,)).astype(np.float32)
    s = 0.3 * rng.normal(size=(size,)).astype(np.float32)
    shifts = [s, -s]
    curv = np.linspace(0.02, 0.12, size).astype(np.float32)
    tmpl = fl_comp.pack_tree({"w": jnp.zeros(size)}, jnp.float32)
    runner = None
    if opt is not None:
        runner = so.PackedServerOptimizer(opt)
    x = np.zeros(size, np.float32)
    for r in range(max_rounds):
        ups = [x - curv * (x - (opt_point + s)) for s in shifts]
        avg = np.mean(ups, axis=0).astype(np.float32)
        if runner is not None:
            runner.ensure(x)
            res = fl_comp.PackedTree(
                jnp.asarray(avg), tmpl.passthrough, tmpl.spec
            )
            new_x = np.asarray(runner.step_fn(x)(res).buf)
            runner.resync(x, new_x)
            x = new_x
        else:
            x = avg
        loss = float(np.mean((x - opt_point) ** 2))
        if loss <= target_loss:
            return r + 1
    return max_rounds


def test_fedac_cuts_rounds_to_target_on_quadratic():
    # Loss at x=0 is mean(opt²) ≈ 1; target three decades below it.
    base = float(np.mean(np.random.default_rng(11)
                         .normal(size=(4096,)).astype(np.float32) ** 2))
    target = 1e-3 * base
    plain = _rounds_to_target(None, target)
    accel = _rounds_to_target(so.fedac(1.0, 6.0, 0.7), target)
    assert plain < 420, plain  # plain must actually converge
    frac = accel / plain
    # The spectral analysis puts this at ~0.15; gate at the ISSUE's 0.8
    # with lots of margin so host noise can never flake it.
    assert frac <= 0.8, (plain, accel, frac)


def test_degenerate_fedac_trajectory_equals_plain_bitexact():
    """fedac(1, 1, 0) must walk EXACTLY the plain-FedAvg trajectory —
    the 'lifting the exclusion changes nothing by default' guarantee."""
    rng = np.random.default_rng(13)
    size = 2048
    tmpl = fl_comp.pack_tree({"w": jnp.zeros(size)}, jnp.float32)
    runner = so.PackedServerOptimizer(so.fedac(1.0, 1.0, 0.0))
    x_plain = rng.normal(size=(size,)).astype(np.float32)
    x_opt = x_plain.copy()
    for r in range(5):
        avg = (x_plain - 0.05 * x_plain
               + 0.001 * rng.normal(size=(size,)).astype(np.float32))
        x_plain = avg
        runner.ensure(x_opt)
        res = fl_comp.PackedTree(
            jnp.asarray(avg), tmpl.passthrough, tmpl.spec
        )
        new_x = np.asarray(runner.step_fn(x_opt)(res).buf)
        runner.resync(x_opt, new_x)
        x_opt = new_x
        np.testing.assert_array_equal(x_opt, x_plain)
