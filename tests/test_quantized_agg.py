"""Compressed-domain (shared-grid integer) aggregation — fl.quantize.

All in-process per the tier-1 budget note (toy buffers, in-memory
sinks, and two TransportManagers over loopback for the wire/delta
composition — no party subprocesses; tests/test_multirail.py is the
template).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
from rayfed_tpu.fl import compression as fl_comp
from rayfed_tpu.fl import fedavg
from rayfed_tpu.fl import quantize as qz
from rayfed_tpu.fl.streaming import StreamingAggregator, StripeAggregator
from rayfed_tpu.transport import wire
from rayfed_tpu.transport.manager import TransportManager

from .multiproc import get_free_ports


def _payload_of(tree):
    from rayfed_tpu import native

    bufs = wire.encode_payload(tree)
    return native.gather_copy(
        [
            memoryview(b) if isinstance(b, (bytes, bytearray)) else b
            for b in bufs
        ]
    )


CE = 1 << 12  # 4096-element blocks: several blocks on toy buffers


def _setup(n=3, size=40_000, seed=1):
    """Shared reference + n party trees drifted a delta-scale away."""
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
# Grid derivation + descriptor
# ---------------------------------------------------------------------------


def test_grid_derivation_deterministic_and_fingerprinted():
    buf = np.linspace(-0.01, 0.02, 10_000, dtype=np.float32)
    g1 = qz.make_round_grid(buf, chunk_elems=CE)
    g2 = qz.make_round_grid(buf.copy(), chunk_elems=CE)
    assert g1.fingerprint() == g2.fingerprint()
    assert g1 == g2
    # A range change moves the fingerprint.
    buf2 = buf.copy()
    buf2[7] += 1.0  # new block-0 max
    assert qz.make_round_grid(buf2, chunk_elems=CE).fingerprint() \
        != g1.fingerprint()
    gd = qz.grid_descriptor(g1)
    assert gd["dt"] == "uint8" and gd["md"] == "delta"
    assert gd["nb"] == g1.nblocks and gd["ce"] == CE
    qz.check_descriptor(gd, g1)  # self-check passes
    with pytest.raises(ValueError, match="grid mismatch"):
        qz.check_descriptor(dict(gd, fp=gd["fp"] ^ 1), g1)


def _numpy_round_grid(reference, chunk_elems, wire_dtype="uint8",
                      expand=1.25, min_scale=1e-12, floor_frac=0.05):
    """The derivation as it was before the statistics moved to the
    device (PR 27): pure numpy over the whole buffer, a float64 mean for
    the RMS.  Kept as the plain reference of ``make_round_grid``."""
    qmin, qmax = {"uint8": (0, 255), "int8": (-128, 127)}[wire_dtype]
    arr = np.asarray(reference).reshape(-1).astype(np.float32)
    total = arr.size
    nb = -(-total // chunk_elems)
    rms = float(np.sqrt(np.mean(np.square(arr, dtype=np.float64))))
    pad = nb * chunk_elems - total
    if pad:
        arr = np.concatenate([arr, np.full(pad, arr[-1], np.float32)])
    a2 = arr.reshape(nb, chunk_elems)
    lo, hi = a2.min(axis=1), a2.max(axis=1)
    mid = 0.5 * (hi + lo)
    half = np.maximum(
        0.5 * (hi - lo) * np.float32(expand),
        np.float32(float(floor_frac) * rms),
    )
    lo, hi = mid - half, mid + half
    scales = np.maximum(
        (hi - lo) / np.float32(qmax - qmin), np.float32(min_scale)
    ).astype(np.float32)
    return scales, (qmin - lo / scales).astype(np.float32)


def _grid_case(name):
    rng = np.random.default_rng(7)
    if name == "tail_padded":  # tail block far from 0: a zero pad would show
        return (5.0 + 0.01 * rng.normal(size=2 * CE + 777)).astype(np.float32)
    if name == "exact_multiple":
        return (0.01 * rng.normal(size=3 * CE)).astype(np.float32)
    if name == "constant_block":  # block 1 has no range: the RMS floor engages
        buf = (0.01 * rng.normal(size=3 * CE + 5)).astype(np.float32)
        buf[CE:2 * CE] = 0.25
        return buf
    if name == "all_zeros":  # no range, no RMS: min_scale
        return np.zeros(2 * CE + 3, np.float32)
    if name == "bf16":
        return jnp.asarray(
            0.01 * rng.normal(size=2 * CE + 100), jnp.bfloat16
        )
    raise AssertionError(name)


@pytest.mark.parametrize("mode", ["abs", "delta"])
@pytest.mark.parametrize(
    "case",
    ["tail_padded", "exact_multiple", "constant_block", "all_zeros", "bf16"],
)
def test_grid_from_device_statistics_matches_numpy_derivation(case, mode):
    buf = _grid_case(case)
    expand = 4.0 if mode == "delta" else 1.25
    host = qz.make_round_grid(
        np.asarray(buf), chunk_elems=CE, mode=mode, expand=expand
    )
    dev = qz.make_round_grid(
        jnp.asarray(buf), chunk_elems=CE, mode=mode, expand=expand
    )
    # One path: where the buffer lives does not reach the grid's bits.
    assert host.fingerprint() == dev.fingerprint()
    assert host.mode == mode and host.total_elems == buf.size
    # Statistics taken ahead of time (the round loop's way) give that
    # grid too, and a chunking they were not taken at is refused.
    st = qz.block_stats(jnp.asarray(buf), chunk_elems=CE)
    assert st.stats.shape == (host.nblocks, 3)
    assert qz.make_round_grid(
        st, mode=mode, expand=expand
    ).fingerprint() == host.fingerprint()
    with pytest.raises(ValueError, match="elems/block"):
        qz.make_round_grid(st, chunk_elems=2 * CE, mode=mode)

    scales, zps = _numpy_round_grid(buf, CE, expand=expand)
    # min and max are exact; the RMS comes from float32 block sums, so
    # a floored scale may differ in float32's last digits, no more.
    np.testing.assert_allclose(host.scales, scales, rtol=2e-6, atol=0)
    np.testing.assert_allclose(host.zps, zps, rtol=2e-6, atol=1e-3)
    if case == "all_zeros":
        assert np.all(host.scales == np.float32(1e-12))
    if case == "constant_block":
        assert host.scales[1] > 0 and host.scales[1] == pytest.approx(
            2 * 0.05 * float(np.sqrt(np.mean(
                np.square(np.asarray(buf, np.float64))
            ))) / 255, rel=1e-5,
        )


def test_block_stats_of_a_difference_never_materialize_it():
    """``block_stats(buf, ref)`` ranges ``buf - ref`` (the downlink
    recode, the round loop's delta) from device or host buffers alike."""
    rng = np.random.default_rng(3)
    ref = rng.normal(size=2 * CE + 9).astype(np.float32)
    buf = ref + (0.01 * rng.normal(size=ref.size)).astype(np.float32)
    want = qz.make_round_grid(buf - ref, chunk_elems=CE)
    for b, r in ((buf, ref), (jnp.asarray(buf), jnp.asarray(ref)),
                 (jnp.asarray(buf), ref)):
        st = qz.block_stats(b, r, chunk_elems=CE)
        assert isinstance(st.stats, jax.Array)
        assert qz.make_round_grid(st).fingerprint() == want.fingerprint()
    with pytest.raises(ValueError, match="reference buffer has"):
        qz.block_stats(buf, ref[:-1], chunk_elems=CE)
    with pytest.raises(ValueError, match="empty buffer"):
        qz.block_stats(np.zeros(0, np.float32))


def test_grid_floor_keeps_degenerate_blocks_usable():
    # A constant block's [min, max] range is empty; the dispersion
    # floor must keep its scale proportional to the buffer's RMS
    # instead of collapsing to the min_scale trap.
    buf = np.concatenate([
        np.zeros(CE, np.float32),                      # degenerate block
        np.full(CE, 0.01, np.float32),                 # constant block
        np.random.default_rng(0).normal(0, 0.01, CE).astype(np.float32),
    ])
    g = qz.make_round_grid(buf, chunk_elems=CE, floor_frac=0.05)
    rms = float(np.sqrt(np.mean(buf.astype(np.float64) ** 2)))
    assert g.scales[0] >= 0.05 * rms * 2 / 255 * 0.99
    assert g.scales[1] >= 0.05 * rms * 2 / 255 * 0.99


def test_weight_and_headroom_guards():
    ref, packeds, grid = _setup(2, size=5000)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    with pytest.raises(ValueError, match="integral"):
        fedavg.packed_quantized_sum(qts, [0.5, 1.5], ref=ref)
    with pytest.raises(ValueError, match="integral"):
        fedavg.packed_quantized_sum(qts, [-1, 2], ref=ref)
    # i32 widening bound: 255 * W must fit int32.
    with pytest.raises(ValueError, match="overflow"):
        fedavg.packed_quantized_sum(qts, [2**31 // 255, 5], ref=ref)
    # The aggregator applies the same guard at construction.
    with pytest.raises(ValueError, match="overflow"):
        StreamingAggregator(2, weights=[2**31 // 255, 5],
                            chunk_elems=CE, quant=grid, quant_ref=ref)


# ---------------------------------------------------------------------------
# Codec roundtrip + error feedback
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bounded_by_grid_step():
    ref, packeds, grid = _setup(1)
    qt = qz.quantize_packed(packeds[0], grid, ref=ref)
    assert qt.buf.dtype == np.uint8
    back = qt.dequantize(np.float32, ref=ref)
    err = np.abs(np.asarray(back.buf) - np.asarray(packeds[0].buf))
    # Per-block bound: half a grid step (+ float slop).
    step = np.repeat(grid.scales, CE)[: grid.total_elems]
    assert np.all(err <= 0.51 * step + 1e-7)


def test_delta_codes_need_the_reference():
    ref, packeds, grid = _setup(1)
    with pytest.raises(ValueError, match="delta"):
        qz.quantize_packed(packeds[0], grid)
    qt = qz.quantize_packed(packeds[0], grid, ref=ref)
    with pytest.raises(ValueError, match="delta"):
        qt.dequantize(np.float32)
    with pytest.raises(ValueError, match="delta"):
        fl_comp.decompress(qt)  # unpack without ref must refuse
    # abs-mode grids refuse a ref instead.
    gabs = qz.make_round_grid(np.asarray(packeds[0].buf),
                              chunk_elems=CE, mode="abs")
    with pytest.raises(ValueError, match="abs"):
        qz.quantize_packed(packeds[0], gabs, ref=ref)
    tree = fl_comp.decompress(qz.quantize_packed(packeds[0], gabs))
    assert set(tree) == {"w"}


def test_compressor_two_phase_residual():
    ref, packeds, grid = _setup(1)
    comp = qz.QuantCompressor()
    qt1 = comp.quantize(packeds[0], grid, ref=ref)
    assert comp.residual is None  # pending until commit
    comp.commit()
    resid = np.asarray(comp.residual)
    # The committed residual is exactly what the grid dropped.
    back = qt1.dequantize(np.float32, ref=ref)
    # (the kernel computes delta − deq; recomputing via the absolute
    # values re-associates the ref add, hence the small float slop)
    np.testing.assert_allclose(
        resid, np.asarray(packeds[0].buf) - np.asarray(back.buf),
        atol=1e-6,
    )
    # Rollback leaves the committed state untouched: re-quantizing
    # after an aborted round produces the identical codes.
    qt2 = comp.quantize(packeds[0], grid, ref=ref)
    comp.rollback()
    qt3 = comp.quantize(packeds[0], grid, ref=ref)
    np.testing.assert_array_equal(np.asarray(qt2.buf), np.asarray(qt3.buf))
    comp.reset()
    assert comp.residual is None


def test_ef_convergence_matches_f32_on_toy_problem():
    """Quant+EF FedAvg recurrence vs exact f32 on a quadratic: the
    compressed-domain loop must land at the same optimum (the
    acceptance criterion's 'equal converged accuracy', in-process)."""
    rng = np.random.default_rng(3)
    target = rng.normal(size=(2048,)).astype(np.float32)
    shift = [0.3 * rng.normal(size=(2048,)).astype(np.float32)
             for _ in range(2)]  # party heterogeneity
    lr = 0.3

    def local_update(x, s):
        return x - lr * (x - (target + s))  # one GD step per round

    def run(quantized: bool) -> float:
        x = np.zeros(2048, np.float32)
        comps = [qz.QuantCompressor() for _ in range(2)]
        prev_delta = None
        for _r in range(30):
            ups = [local_update(x, s) for s in shift]
            if quantized and prev_delta is not None:
                grid = qz.make_round_grid(
                    prev_delta, chunk_elems=512, mode="delta", expand=4.0
                )
                qts = []
                for c, u in zip(comps, ups):
                    qts.append(c.quantize(
                        fl_comp.pack_tree({"w": jnp.asarray(u)},
                                          jnp.float32),
                        grid, ref=x,
                    ))
                    c.commit()
                agg = np.asarray(
                    fedavg.packed_quantized_sum(qts, ref=x).buf
                )
            else:
                agg = np.mean(ups, axis=0).astype(np.float32)
            prev_delta = agg - x
            x = agg
        return float(np.mean((x - target) ** 2))

    exact, quant = run(False), run(True)
    # Both converge to the heterogeneity floor; the 8-bit path must
    # match the f32 loop closely (EF recovers what the grid drops).
    assert quant <= exact * 1.01 + 1e-6, (exact, quant)


# ---------------------------------------------------------------------------
# One-shot reduce + guards
# ---------------------------------------------------------------------------


def test_packed_quantized_sum_matches_integer_reference():
    ref, packeds, grid = _setup(3)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    ws = [3, 1, 2]
    got = fedavg.packed_quantized_sum(qts, ws, ref=ref)
    assert got.buf.dtype == np.float32
    codes = np.stack([np.asarray(q.buf, np.int64) for q in qts])
    acc = (codes * np.asarray(ws, np.int64)[:, None]).sum(0)
    nb, te = grid.nblocks, grid.total_elems
    pad = nb * CE - te
    acc_p = np.concatenate([acc, np.zeros(pad, np.int64)])
    a2 = acc_p.reshape(nb, CE).astype(np.float32)
    x = grid.scales[:, None] * (a2 - grid.zps[:, None] * np.float32(6.0))
    want = ref + x.reshape(-1)[:te] / np.float32(6.0)
    np.testing.assert_allclose(np.asarray(got.buf), want, atol=2e-6)


def test_mixed_grids_and_float_paths_rejected():
    ref, packeds, grid = _setup(2)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    other = qz.make_round_grid(
        0.02 * np.ones(grid.total_elems, np.float32),
        chunk_elems=CE, mode="delta",
    )
    alien = qz.quantize_packed(packeds[1], other, ref=ref)
    with pytest.raises(ValueError, match="different grid"):
        fedavg.packed_quantized_sum([qts[0], alien], ref=ref)
    # Integer codes must never reach the float reduce.
    with pytest.raises(ValueError, match="packed_quantized_sum"):
        fedavg.packed_weighted_sum(qts)
    # tree_average auto-routes uniform quantized trees... to the guard
    # that demands the reference, because these are delta codes.
    with pytest.raises(ValueError, match="delta"):
        fedavg.tree_average(qts)


# ---------------------------------------------------------------------------
# Streaming / stripe / quorum folds: bit-identical to the one-shot
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("weights", [None, [3, 1, 2]])
def test_streaming_integer_fold_bitexact_adversarial_order(weights):
    ref, packeds, grid = _setup(3)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    want = fedavg.packed_quantized_sum(qts, weights, ref=ref)
    agg = StreamingAggregator(3, weights=weights, chunk_elems=CE,
                              quant=grid, quant_ref=ref)
    payloads = [_payload_of(q) for q in qts]
    sinks = [agg.sink(i) for i in range(3)]
    # Adversarial arrival: source 2 completes first, 0 trickles in odd
    # increments, 1 lands whole.
    sinks[2].on_complete(payloads[2])
    mv0 = memoryview(payloads[0])
    for off in range(1 << 12, len(payloads[0]), 9999):
        sinks[0].on_bytes(mv0, off)
    sinks[0].on_complete(payloads[0])
    sinks[1].on_complete(payloads[1])
    got = agg.result(timeout=60)
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(want.buf)
    )
    assert got.buf.dtype == np.float32


def test_streaming_rejects_wrong_grid_payload_before_rescale():
    ref, packeds, grid = _setup(2)
    other = qz.make_round_grid(
        0.02 * np.ones(grid.total_elems, np.float32),
        chunk_elems=CE, mode="delta",
    )
    agg = StreamingAggregator(2, chunk_elems=CE, quant=grid,
                              quant_ref=ref)
    agg.add_local(0, qz.quantize_packed(packeds[0], grid, ref=ref))
    agg.sink(1).on_complete(
        _payload_of(qz.quantize_packed(packeds[1], other, ref=ref))
    )
    with pytest.raises(ValueError, match="different grid"):
        agg.result(timeout=60)


def test_streaming_rejects_unquantized_local_when_grid_set():
    ref, packeds, grid = _setup(1)
    agg = StreamingAggregator(1, chunk_elems=CE, quant=grid,
                              quant_ref=ref)
    agg.add_local(0, packeds[0])  # plain PackedTree: must fail loudly
    with pytest.raises(TypeError, match="QuantizedPackedTree"):
        agg.result(timeout=10)


def test_quorum_subset_refold_bitexact():
    ref, packeds, grid = _setup(3)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    ws = [3, 1, 2]
    agg = StreamingAggregator(3, weights=ws, chunk_elems=CE,
                              quant=grid, quant_ref=ref, quorum=2,
                              labels=["a", "b", "c"])
    agg.sink(1)  # source 1 never arrives
    agg.add_local(0, qts[0])
    agg.sink(2).on_complete(_payload_of(qts[2]))
    got = agg.result(timeout=60, deadline_s=0.4)
    assert agg.quorum_members == [0, 2]
    want = fedavg.packed_quantized_sum([qts[0], qts[2]], [3, 2], ref=ref)
    np.testing.assert_array_equal(
        np.asarray(got.buf), np.asarray(want.buf)
    )


def test_stripe_assembly_bitexact_vs_coordinator():
    """Each ring stripe owner's integer fold + per-row rescale (+
    reference slice) reassembles to EXACTLY the coordinator result —
    the compressed-domain half of the ring/coordinator parity."""
    ref, packeds, grid = _setup(3)
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    ws = [3, 1, 2]
    want = fedavg.packed_quantized_sum(qts, ws, ref=ref)
    nb, te = grid.nblocks, grid.total_elems
    for n_stripes in (2, 3):
        sched = fedavg.packed_stripe_schedule(nb, n_stripes)

        def compact(buf, blocks):
            return np.concatenate(
                [np.asarray(buf)[b * CE: min((b + 1) * CE, te)]
                 for b in blocks]
            )

        full = np.empty(te, np.float32)
        for blocks in sched:
            if not blocks:
                continue
            se = sum(min(CE, te - b * CE) for b in blocks)
            sa = StripeAggregator(
                3, weights=ws, chunk_elems=CE, expect_elems=se,
                quant=grid, quant_blocks=blocks,
                quant_ref=compact(ref, blocks),
            )
            sa.add_local(0, compact(qts[0].buf, blocks))
            for i in (1, 2):
                sa.sink(i).on_complete(
                    _payload_of({"data": compact(qts[i].buf, blocks)})
                )
            reduced = sa.result(timeout=60)
            off = 0
            for b in blocks:
                size = min(CE, te - b * CE)
                full[b * CE: b * CE + size] = reduced[off: off + size]
                off += size
        np.testing.assert_array_equal(full, np.asarray(want.buf))


# ---------------------------------------------------------------------------
# Wire composition: delta cache x compressed domain (two in-process
# TransportManagers over loopback — the test_multirail shape)
# ---------------------------------------------------------------------------


def _mk_manager(party, cluster_ports):
    cc = ClusterConfig(
        parties={
            p: PartyConfig.from_dict({"address": f"127.0.0.1:{port}"})
            for p, port in cluster_ports.items()
        },
        current_party=party,
    )
    return TransportManager(
        cc,
        JobConfig(
            device_put_received=False,
            zero_copy_host_arrays=True,
            cross_silo_timeout_s=20,
        ),
    )


@pytest.fixture()
def manager_pair():
    pa, pb = get_free_ports(2)
    ports = {"alice": pa, "bob": pb}
    a, b = _mk_manager("alice", ports), _mk_manager("bob", ports)
    a.start()
    b.start()
    yield a, b, ports
    a.stop()
    b.stop()


def _delta_stats(mgr):
    st = mgr.get_stats()
    return st["delta_logical_bytes"], st["delta_wire_bytes"]


def test_delta_cache_compressed_domain_composition(manager_pair):
    """Satellite: a changed-chunks-only round must fold bit-identically
    to the full-payload round, and the uint8 codes must actually ride
    the delta cache (round 2 ships less than the logical payload)."""
    a, b, _ = manager_pair
    size = wire.DELTA_CHUNK_BYTES * 3  # 3 full 4MB chunks of codes
    rng = np.random.default_rng(5)
    ref = rng.normal(size=(size,)).astype(np.float32)
    prev_delta = 0.01 * rng.normal(size=(size,)).astype(np.float32)
    grid = qz.make_round_grid(prev_delta, mode="delta", expand=4.0)

    def contribution(r):
        arr = ref.copy()
        # Round-over-round only the SECOND code chunk's values change
        # (codes are 1 byte/elem, so chunk 1 starts at element
        # DELTA_CHUNK_BYTES).
        lo = wire.DELTA_CHUNK_BYTES
        arr[lo: lo + 1000] += 1e-3 * (r + 1)
        return fl_comp.pack_tree({"w": jnp.asarray(arr)}, jnp.float32)

    def push_and_fold(r):
        qt = qz.quantize_packed(contribution(r), grid, ref=ref)
        send_ref = a.send("bob", qt, f"q{r}", "0", stream="qdelta",
                          quant_meta=qz.grid_descriptor(grid))
        agg = StreamingAggregator(1, chunk_elems=grid.chunk_elems,
                                  quant=grid, quant_ref=ref)
        b.recv_stream("alice", f"q{r}", "0", agg.sink(0))
        out = agg.result(timeout=60)
        assert send_ref.resolve(timeout=60)
        return qt, out

    qt0, out0 = push_and_fold(0)  # seeds the delta cache
    logical0, wire0 = _delta_stats(a)
    qt1, out1 = push_and_fold(1)  # only chunk 1's codes changed
    logical1, wire1 = _delta_stats(a)
    # The delta cache really engaged: round 1 shipped a proper subset.
    assert logical1 - logical0 > 0
    assert (wire1 - wire0) < (logical1 - logical0) * 0.8
    # And the delta-rebuilt fold equals folding the full payload.
    want = fedavg.packed_quantized_sum([qt1], ref=ref)
    np.testing.assert_array_equal(
        np.asarray(out1.buf), np.asarray(want.buf)
    )


def test_delta_base_desync_reseed_carries_grid(manager_pair):
    """Satellite: after the receiver loses its delta base (restart),
    the automatic full-payload re-seed must still decode as a
    QuantizedPackedTree with the grid intact."""
    a, b, ports = manager_pair
    size = wire.DELTA_CHUNK_BYTES * 2
    rng = np.random.default_rng(6)
    ref = rng.normal(size=(size,)).astype(np.float32)
    grid = qz.make_round_grid(
        0.01 * rng.normal(size=(size,)).astype(np.float32),
        mode="delta", expand=4.0,
    )
    packed = fl_comp.pack_tree({"w": jnp.asarray(ref * 1.0001)},
                               jnp.float32)
    qt = qz.quantize_packed(packed, grid, ref=ref)
    assert a.send("bob", qt, "d1", "0", stream="qs").resolve(timeout=60)
    assert b.recv("alice", "d1", "0").resolve(timeout=60) is not None

    # Receiver restarts: cached delta base gone -> the next delta send
    # answers code="delta_base" and the client re-seeds a full payload.
    b.stop()
    b2 = _mk_manager("bob", ports)
    b2.start()
    try:
        qt2 = qz.quantize_packed(
            fl_comp.pack_tree({"w": jnp.asarray(ref * 1.0002)},
                              jnp.float32),
            grid, ref=ref,
        )
        assert a.send("bob", qt2, "d2", "0", stream="qs").resolve(
            timeout=60
        )
        got = b2.recv("alice", "d2", "0").resolve(timeout=60)
        assert isinstance(got, qz.QuantizedPackedTree)
        assert got.gmeta == grid.meta()  # the grid survived the re-seed
        np.testing.assert_array_equal(
            np.asarray(got.buf), np.asarray(qt2.buf)
        )
        # ...and the re-seeded codes decode to the identical values.
        np.testing.assert_array_equal(
            np.asarray(got.dequantize(np.float32, ref=ref).buf),
            np.asarray(qt2.dequantize(np.float32, ref=ref).buf),
        )
    finally:
        b2.stop()


def test_quant_grid_metadata_key_stamped(manager_pair):
    """The grid descriptor rides frame metadata under the declared
    wire.QUANT_GRID_KEY constant (FED006/lock contract)."""
    import json

    from tool.fedlint.rules import declared_meta_keys

    keys = declared_meta_keys()
    assert keys.get("QUANT_GRID_KEY") == "qg"

    a, b, _ = manager_pair
    size = 100_000
    ref = np.linspace(-0.01, 0.01, size, dtype=np.float32)
    grid = qz.make_round_grid(ref, mode="delta", expand=4.0)
    qt = qz.quantize_packed(
        fl_comp.pack_tree({"w": jnp.asarray(ref * 1.001)}, jnp.float32),
        grid, ref=ref,
    )
    gd = qz.grid_descriptor(grid)
    assert a.send("bob", qt, "m1", "0", quant_meta=gd).resolve(timeout=60)
    # Peek the parked mailbox entry's metadata before consuming it.
    entry = b._mailbox._entries[("m1", "0")]
    meta = entry.message.metadata
    assert wire.QUANT_GRID_KEY in meta
    assert json.loads(meta[wire.QUANT_GRID_KEY]) == gd
    qz.check_descriptor(meta[wire.QUANT_GRID_KEY], grid)
    assert b.recv("alice", "m1", "0").resolve(timeout=60) is not None
