"""Bytes-on-wire budgets of the aggregation topologies.

A byte count read from ``get_stats()`` over real loopback transports is
exact and the same on every host, so it is the one "performance"
property a CPU run can certify (ROADMAP, "What counts as a speed
claim").  Each case below builds a topology out of in-process parties
or in-process managers, runs whole rounds on a toy packed tree of about
a megabyte (manifests and grid vectors are then slack, not signal) and
holds one ratio of counted bytes to its limit.  No wall clock, no
subprocesses.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

import rayfed_tpu as fed
from rayfed_tpu import inprocess
from rayfed_tpu.fl import compression as fl_comp
from rayfed_tpu.fl import fedavg
from rayfed_tpu.fl import hierarchy as H
from rayfed_tpu.fl import quantize as qz
from rayfed_tpu.fl.ring import ring_aggregate
from rayfed_tpu.fl.streaming import StreamingAggregator, streaming_aggregate
from rayfed_tpu.runtime import get_runtime

from .test_hierarchy import _Cluster

N_ELEMS = 1 << 19  # 1 MiB as bf16, the |model| unit of the budgets
MODEL_BYTES = 2 * N_ELEMS
CE = 1 << 15  # 16 blocks: every owner of a 4- or 8-stripe ring owns some
PARTIES4 = ("alice", "bob", "carol", "dave")


def _noisy(ref, seed, dtype=np.float32):
    noise = np.random.default_rng(seed).standard_normal(ref.size)
    return (ref + 1e-3 * noise.astype(np.float32)).astype(dtype)


def _packed(buf, tmpl):
    return fl_comp.PackedTree(
        buf, tmpl.passthrough,
        fl_comp.PackSpec(
            tmpl.spec.entries, tmpl.spec.treedef, np.dtype(buf.dtype).name
        ),
    )


@functools.lru_cache(maxsize=None)
def _round_inputs():
    ref = np.linspace(-0.5, 0.5, N_ELEMS, dtype=np.float32)
    tmpl = fl_comp.pack_tree({"w": jnp.asarray(ref)}, jnp.float32)
    grid = qz.make_round_grid(
        _noisy(np.zeros_like(ref), 0), mode="delta", expand=4.0,
        chunk_elems=CE,
    )
    return ref, tmpl, grid


def _coordinator_ingress_share(mode):
    """The coordinator's share of the bytes the four parties received
    in two rounds of ``mode`` ("ring" or "hub"), fresh contributions
    each round so no delta cache skips any."""
    ref, tmpl, _ = _round_inputs()

    def party_main(party):
        def contribution(i, r):
            return _packed(_noisy(ref, 100 * r + i, jnp.bfloat16), tmpl)

        produce = fed.remote(contribution)

        def one_round(r):
            objs = [
                produce.party(p).remote(i, r)
                for i, p in enumerate(PARTIES4)
            ]
            if mode == "ring":
                out = ring_aggregate(objs, stream="wb", chunk_elems=CE)
            else:
                out = streaming_aggregate(
                    objs, stream="wb", coordinator=PARTIES4[0]
                )
            np.asarray(out.buf[:8])

        transport = get_runtime().transport
        one_round(0)  # connections and compiles
        before = transport.get_stats()["receive_bytes"]
        for r in (1, 2):
            one_round(r)
        return transport.get_stats()["receive_bytes"] - before

    got = inprocess.run_parties(
        party_main, inprocess.loopback_cluster(PARTIES4), timeout=120,
        logging_level="warning",
    )
    return got[PARTIES4[0]] / sum(got.values())


def _ring_coordinator_ingress_share():
    ring = _coordinator_ingress_share("ring")
    # The same reading over the hub sits at a half whatever N is: the
    # counters are each party's own, and the ratio can tell the two.
    assert _coordinator_ingress_share("hub") > 0.45
    return ring


def _uint8_over_bf16_bytes():
    """Bytes all four managers sent in two streaming hub rounds of
    uint8 codes (shared grid up, a fresh grid down) over the same two
    rounds in packed bf16, fresh payloads each round."""
    ref, tmpl, grid = _round_inputs()
    cluster = _Cluster(PARTIES4)
    mgrs, peers = cluster.mgrs, PARTIES4[1:]

    def sent():
        return sum(m.get_stats()["send_bytes"] for m in mgrs.values())

    def resolve_all(refs):
        for r in refs:
            assert r.resolve(timeout=60)

    def bf16_round(r):
        trees = [
            _packed(_noisy(ref, 100 * r + i, jnp.bfloat16), tmpl)
            for i in range(4)
        ]
        up = [
            mgrs[p].send("alice", trees[i + 1], f"b{r}-{p}", "0")
            for i, p in enumerate(peers)
        ]
        agg = StreamingAggregator(4, chunk_elems=CE)
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"b{r}-{p}", "0", agg.sink(i + 1))
        agg.add_local(0, trees[0])
        down = mgrs["alice"].send_many(
            peers, agg.result(timeout=60), f"bd{r}", "0"
        )
        for p in peers:
            mgrs[p].recv("alice", f"bd{r}", "0").resolve(timeout=60)
        resolve_all(up + list(down.values()))

    def uint8_round(r):
        qts = [
            qz.quantize_packed(
                _packed(_noisy(ref, 100 * r + i), tmpl), grid, ref=ref
            )
            for i in range(4)
        ]
        gd = qz.grid_descriptor(grid)
        up = [
            mgrs[p].send("alice", qts[i + 1], f"q{r}-{p}", "0",
                         quant_meta=gd)
            for i, p in enumerate(peers)
        ]
        agg = StreamingAggregator(
            4, chunk_elems=CE, quant=grid, quant_ref=ref
        )
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"q{r}-{p}", "0", agg.sink(i + 1))
        agg.add_local(0, qts[0])
        result = agg.result(timeout=60)
        # Over real sockets too, the streamed fold is the one-shot sum.
        assert np.array_equal(
            np.asarray(result.buf),
            np.asarray(fedavg.packed_quantized_sum(qts, ref=ref).buf),
        )
        down_grid = qz.make_round_grid(
            np.asarray(result.buf) - ref, mode="delta", chunk_elems=CE
        )
        down = mgrs["alice"].send_many(
            peers, qz.quantize_packed(result, down_grid, ref=ref),
            f"qd{r}", "0", quant_meta=qz.grid_descriptor(down_grid),
        )
        for p in peers:
            got = mgrs[p].recv("alice", f"qd{r}", "0").resolve(timeout=60)
            got.dequantize(np.float32, ref=ref)
        resolve_all(up + list(down.values()))

    try:
        bf16_round(9)
        uint8_round(9)
        b0 = sent()
        for r in (0, 1):
            bf16_round(r)
        b1 = sent()
        for r in (0, 1):
            uint8_round(r)
        return (sent() - b1) / (b1 - b0)
    finally:
        cluster.stop()


@functools.lru_cache(maxsize=None)
def _hierarchy_traffic(n_parties, region_size, branch=None):
    """One measured ``HierarchyRound`` (uint8 codes up, int16 partial
    sums across regions, re-quantized downlink) on ``n_parties``
    in-process managers: per-party mean and maximum of received bytes
    and the root's sent bytes, each over 2 |model|, the flat-traffic
    budget of one contribution out and one broadcast in.  ``branch``
    folds the regions through interior nodes with quorum-hub leaves at
    full quorum, the deep layout's shape."""
    ref, tmpl, grid = _round_inputs()
    parties = [f"h{i:03d}" for i in range(n_parties)]
    kw = {}
    if branch is not None:
        kw = dict(branch=branch, region_quorum=region_size)
    cluster = _Cluster(parties)
    try:
        def run(r):
            contribs = {
                p: _packed(_noisy(ref, 1000 * r + i), tmpl)
                for i, p in enumerate(parties)
            }
            results, errors = cluster.run_round(
                contribs, grid, ref, region_size=region_size,
                keys=[f"wb{r}k{j}" for j in range(6)],
                quant_downlink=True, **kw,
            )
            assert not errors and len(results) == n_parties, errors
            assert len(
                {np.asarray(t.buf).tobytes() for t in results.values()}
            ) == 1, "parties disagree on the aggregate"

        def counters(name):
            return {
                p: m.get_stats()[name] for p, m in cluster.mgrs.items()
            }

        run(0)  # connections and compiles
        rx0, tx0 = counters("receive_bytes"), counters("send_bytes")
        run(1)
        rx, tx = counters("receive_bytes"), counters("send_bytes")
    finally:
        cluster.stop()
    root = H.region_layout(parties, region_size, branch=branch).root
    budget = 2.0 * MODEL_BYTES
    received = [rx[p] - rx0[p] for p in parties]
    return {
        "party_bytes": sum(received) / n_parties / budget,
        "max_ingress": max(received) / budget,
        "root_egress": (tx[root] - tx0[root]) / budget,
    }


# (gate, topology, parties, the counted ratio, limit): each ratio must
# stay at or under its limit.  The limits are the ones the retired CPU
# smoke run enforced; where a case runs fewer parties than that run
# did, the comment says what the smaller N can still tell.
CASES = [
    # The ring spreads ingress to ~1/N; the hub pins the coordinator at
    # ~0.5 of all bytes the cluster receives, whatever N.
    ("coord_bytes_in_frac", "4-party stripe ring, bf16", 4,
     _ring_coordinator_ingress_share, 0.4),
    # uint8 codes are half of bf16; grid vectors and manifests are the
    # slack between 0.5 and the limit.
    ("compressed_bytes_on_wire_frac",
     "4-party streaming hub, uint8 against bf16, both directions", 4,
     _uint8_over_bf16_bytes, 0.55),
    ("hier_party_bytes_frac_4", "2 regions x 2, region rings", 4,
     lambda: _hierarchy_traffic(4, 2)["party_bytes"], 1.25),
    ("hier_party_bytes_frac_16", "2 regions x 8, region rings", 16,
     lambda: _hierarchy_traffic(16, 8)["party_bytes"], 1.25),
    # No O(N) hub at any level: the busiest node's ingress at N=16
    # over N=4 (a flat hub's grows 5x over that span, 15 contributions
    # against 3).  N=16 is what a tier-1 case affords: N=64 is 64
    # managers and 64 party threads in one worker for several seconds.
    ("hier_ingress_flatness", "2 regions, N=16 over N=4", 16,
     lambda: (_hierarchy_traffic(16, 8)["max_ingress"]
              / _hierarchy_traffic(4, 2)["max_ingress"]), 1.6),
    # Root bytes out stay ~O(branch |model|), flat in N, under the
    # region-ring downlink.  The retired run held 8 at N=256, where a
    # coordinator's fan-out read ~32; at the N=16 a tier-1 case affords
    # that limit would pass anything, so this one sits between the
    # ring's reading (0.75) and what the same tree reads when the root
    # fans the broadcast out to its region itself (1.25).
    ("hier_root_egress_frac",
     "4 regions x 4 through branch 2, hub leaves", 16,
     lambda: _hierarchy_traffic(16, 4, branch=2)["root_egress"], 1.0),
]


@pytest.mark.parametrize(
    "name,topology,parties,measure,limit", CASES,
    ids=[case[0] for case in CASES],
)
def test_wire_budget(name, topology, parties, measure, limit):
    ratio = measure()
    assert 0 < ratio <= limit, (
        f"{name} = {ratio:.4f} over {topology} (N={parties}); "
        f"limit {limit}"
    )
