"""Recursive hierarchical aggregation: region rings/hubs + quantized
multi-level partial-sum streaming.

Every topology so far puts all N parties on ONE structure — a hub
(``fl.streaming``), a ring (``fl.ring``) or a quorum hub (``fl.quorum``)
— and benches at N ≤ 4.  At hundreds of silos the structure itself is
what breaks ("Understanding Communication Backends in Cross-Silo FL",
PAPERS.md): a hub coordinator eats O(N)·|model| ingress, and a single
N-party ring pays N-1 serial hops of latency per stripe.  Here the
sorted roster partitions **deterministically** into regions
(:func:`rayfed_tpu.transport.manager.partition_regions` — every
controller derives the same partition from the same roster epoch, no
negotiation) and the round becomes a three-hop tree over existing
bricks:

1. **Region reduce-scatter** (``fl.ring``'s chunk-striped schedule,
   region-scoped): each region runs the canonical chunk grid's stripe
   schedule over its own members; integer codes
   (:class:`~rayfed_tpu.fl.quantize.QuantGrid` — hierarchy ALWAYS runs
   in the compressed domain, see below) flow to stripe owners and fold
   into donated i32 accumulators
   (:class:`~rayfed_tpu.fl.streaming.StripeAggregator`) — but, unlike a
   flat ring round, the stripes are **not finalized**: each owner emits
   its stripe of the region's raw integer partial sum
   ``Σ_{p∈region} w_p·q_p``.

2. **Quantized cross-region streaming**: stripe owners hand their
   partial-sum stripes to the region coordinator (first live member of
   the region — :func:`~rayfed_tpu.transport.manager.roster_successor`
   semantics when the canonical first is dead), which assembles the
   region's full partial-sum buffer (a :class:`RegionSumTree`, shipped
   at the **narrowest exact integer width** —
   :func:`partial_sum_dtype`: int16 whenever ``qabs_max·W`` fits, half
   the bytes of i32) and streams it up to the ROOT coordinator, where a
   :class:`~rayfed_tpu.fl.streaming.StreamingAggregator` in
   ``presummed`` mode folds region sums at unit weight into the same
   donated i32 accumulator every flat path uses.

3. **Broadcast down the tree**: the root applies THE single fused
   rescale (:func:`~rayfed_tpu.fl.fedavg.finalize_packed_quantized`)
   once, then the aggregate travels root → region coordinators →
   members (optionally re-quantized for the wire, the shared
   :func:`~rayfed_tpu.fl.quantize.quantize_downlink` producer), with a
   commit/release pass so every controller reaches the same
   success/abort verdict (the ring's 2-pass commit, tree-shaped).

**Recursive regions (multi-level).**  The two-level shape generalizes:
leaf regions group into constant-degree interior nodes (``branch``
contiguous previous-level ids per node — :func:`region_layout` derives
the WHOLE tree from (sorted roster, region_size, branch, dead) with
zero negotiation), interior coordinators fold their children's
:class:`RegionSumTree` partial sums at unit weight through the same
donated-i32 kernel, and only the single top node's coordinator (the
root) finalizes.  Because integer folds are exact and associative, an
L-level fold == the 2-level fold == the flat fold, byte for byte, by
construction.  :func:`partial_sum_dtype` narrowing is re-derived PER
LEVEL from the level's maximum subtree roster weight, so deep levels
near the leaves ride int16 even when the root-level sums need int32.

**Per-region quorum cutoffs** (``region_quorum=``): a leaf region
switches from the stripe ring to a hub collection at its coordinator —
a quorum :class:`~rayfed_tpu.fl.streaming.StreamingAggregator`
(deadline-gated pin-members-and-refold, the same contract the flat
quorum path ships) emits the region's arrived-subset raw partial sum
instead of aborting the round.  The arrived Σw rides up the tree
inside each :class:`RegionSumTree`, and the root finalizes over the
TRUE arrived total — so a slow or partially-dead region degrades to a
subset refold (byte-identical to ``packed_quantized_sum`` over the
arrived members) and the flat fallback becomes the exception, not the
straggler path.  Interior levels stay strict: a dead region
COORDINATOR still aborts (and the next round's layout fails it over).

**Region-ring downlink** (``ring_downlink=True``, the default): the
post-finalize broadcast travels root → child coordinators (per level)
→ a relay chain inside each leaf region — the coordinator sends the
quantized result to the first participating member only, each member
forwards it to its successor on arrival and confirms with a tiny
commit token, so root egress is ~O(branch·|model|), flat in N, and no
leaf coordinator fans out O(region_size) copies.  Members excluded by
a region cutoff get a direct best-effort copy (they are not on the
chain — a straggler mid-chain would stall the relay behind the very
party the cutoff just routed around).

**Why this is byte-identical to flat.**  Integer adds are exact and
associative, so regrouping the fold as
``Σ_regions (Σ_{p∈region} w_p·q_p)`` produces bit-for-bit the
accumulator of the flat fold ``Σ_p w_p·q_p`` — and the ONE finalize is
shared — so ``hierarchy == flat streaming == packed_quantized_sum``
byte-identical BY CONSTRUCTION, whatever the arrival order at any
level.  This is also why hierarchy **requires** the compressed domain:
f32 partial sums would re-associate a non-associative fold (the same
delta-vs-abs class of lesson PR 10 measured), so an unquantized
hierarchy is a loud exclusion, never an approximate fallback.

**Why traffic stays flat in N.**  Per ordinary member: ~|codes| out
(reduce-scatter) + ~|codes| in + the broadcast — independent of N.  Per
region coordinator: the region's partial-sum gather (~2·|codes| at
int16) + one buffer up + the broadcast fan-down — independent of N for
a fixed region COUNT, and bounded by the region size otherwise.  The
root's ingress is (regions−1) partial-sum buffers — no node at any
level sees O(N) ingress (held by ``tests/test_wire_budget.py``'s
``hier_*`` cases at N ∈ {4, 16}).

**Failure story.**  Any mid-round failure poisons every key the
failing party owed (the ring's cascade, tree-shaped: errors travel up
to the root and back down), so :class:`HierarchyRoundError` raises on
EVERY controller and the driver falls back in lockstep —
``run_fedavg_rounds(mode="hierarchy")`` re-aggregates the SAME round
over the flat streaming path (classic loop) or the quorum coordinator
path (``quorum=``), where a dead region coordinator is just a dead
party: the quorum cutoff excludes it, the epoch announcement drops it,
and a dead QUORUM coordinator reaches the existing
``roster_successor`` failover arm (chaos-tested since PR 7).  The next
round re-derives the partition from the advanced roster.  For
mid-round re-runs with an explicitly agreed dead set,
:func:`region_layout` also takes ``dead=``: partition stays
roster-derived (stable), dead parties drop out of their region's
stripe ring, and each region's coordinator moves to the
``roster_successor``-derived next live member.
"""

from __future__ import annotations

import json
import logging
import time
import zlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from rayfed_tpu.fl.compression import PackedTree, PackSpec
from rayfed_tpu.fl.quantize import QuantizedPackedTree

logger = logging.getLogger(__name__)

# Version of the hierarchy region manifest ("hrm" sideband leaf) — bump
# when make_region_meta's schema changes.  Fingerprinted (with the
# schema) by tool/check_wire_format.py: region payloads are a
# cross-party contract layered on the ordinary payload manifest, like
# the ring stripe manifest.  The frame layout itself is untouched.
# v2: multi-level manifests — "lv" (tree level), "pa" (parent node id)
# and "rp" (the leaf region's path of interior node ids to the root).
HIERARCHY_VERSION = 2

# Region-ring downlink: longest relay chain one envelope travels.  The
# ring trades coordinator egress (ONE copy per chain instead of one
# per member) for serial hop latency, so an unbounded chain puts
# region_size-1 per-message costs on the round's critical path — at
# region_size=32 that relay alone regressed the N=64 round ~18%.
# Splitting the region into ceil(members/8) PARALLEL chains keeps
# coordinator egress region-size-bounded (k copies, k ≤ members/8,
# still far under fan-out's per-member copies) while capping the
# downlink critical path at 8 serial hops regardless of region size.
RING_RELAY_MAX_HOPS = 8

# Module-level round counters (the trainer's fallback path and tests
# read these — mirrors fl.ring.RING_STATS).
HIER_STATS: Dict[str, int] = {
    "rounds_completed": 0,
    "rounds_aborted": 0,
    "fallback_rounds": 0,
    # Rounds where >= 1 region completed on its arrived SUBSET (the
    # per-region quorum cutoff absorbed a straggler or corpse).
    "region_cutoffs": 0,
}

# Test-only fault injection: when set, called with (phase, party) at
# each step of the member flow ("local", "rs", "ps", "up", "down",
# "commit").  Raising simulates a failure at exactly that phase; the
# in-process chaos tests also hard-stop a virtual party's transport
# from here.  Takes the party because in-process virtual parties share
# one process (unlike fl.ring's per-process hook).
_fault_hook: Optional[Callable[[str, str], None]] = None


def _maybe_fault(phase: str, party: str) -> None:
    if _fault_hook is not None:
        _fault_hook(phase, party)


def _relay_chains(
    members: Sequence[str], max_hops: int = RING_RELAY_MAX_HOPS
) -> List[List[str]]:
    """Split a region's relay members into parallel bounded chains.

    Order-preserving contiguous split into ``ceil(len/max_hops)``
    chains of at most ``max_hops`` members each, sized as evenly as
    possible (the LONGEST chain is the downlink's critical path, so a
    33-member region becomes 7/7/7/6/6, never 8/8/8/8/1).  Every member
    appears in exactly one chain; relaying and the per-member commit
    tokens are unchanged — each envelope just carries its own chain.
    """
    if max_hops < 1:
        raise ValueError(f"max_hops must be >= 1, got {max_hops}")
    n = len(members)
    if n == 0:
        return []
    k = -(-n // max_hops)  # ceil
    base, extra = divmod(n, k)
    chains: List[List[str]] = []
    start = 0
    for i in range(k):
        size = base + (1 if i < extra else 0)
        chains.append(list(members[start:start + size]))
        start += size
    return chains


# Seq ids one hierarchy_aggregate call consumes — callers pre-allocating
# ids (the quorum driver derives string keys instead) pass exactly this
# many, in next_seq_id order: (rs, ps, up, down, commit, release).
HIER_SEQ_IDS = 6


class HierarchyRoundError(RuntimeError):
    """A hierarchy round aborted (peer death, wire failure, poisoned
    hop, partition disagreement).  The round's contributions are still
    intact on their owners — re-aggregate the SAME round over the flat
    streaming/quorum topology (``run_fedavg_rounds(mode="hierarchy")``
    does exactly that)."""


def members_fingerprint(members: Sequence[str]) -> int:
    """CRC32 over the sorted roster — what region manifests carry so
    two controllers that derived DIFFERENT partitions (a missed epoch
    advance) abort loudly instead of folding mismatched stripes."""
    return zlib.crc32("\n".join(sorted(members)).encode())


def partial_sum_dtype(qabs_max: int, total_weight: int) -> str:
    """Narrowest integer wire dtype that holds ``qabs_max · W`` exactly.

    A region partial sum ``Σ w_p·q_p`` is bounded by the ROSTER total's
    headroom bound, so int16 (half the i32 bytes) carries it exactly
    whenever ``qabs_max·W ≤ 2¹⁵−1`` — e.g. unit weights up to 128
    parties at uint8.  Every controller derives the same dtype from the
    same shared weights; the receiver's fold widens to i32 regardless.
    """
    bound = int(qabs_max) * int(total_weight)
    if bound <= 2**15 - 1:
        return "int16"
    if bound <= 2**31 - 1:
        return "int32"
    raise ValueError(
        f"integer-fold overflow: qabs_max {qabs_max} x total weight "
        f"{total_weight} = {bound} exceeds the i32 accumulator bound — "
        f"rescale the example counts"
    )


class TreeNode(NamedTuple):
    """One ACTIVE interior node of the derived tree."""

    children: tuple          # active child node ids at the level below
    coordinator: str         # == coordinator of the first active child


class HierarchyLayout(NamedTuple):
    """One round's derived tree topology (identical on every
    controller: pure function of (sorted members, region_size, branch,
    dead))."""

    regions: List[List[str]]      # full partition of the roster
    live: List[List[str]]         # per-region live members (sorted)
    coordinators: Dict[int, str]  # region index -> live coordinator
    active: List[int]             # region indices with >= 1 live member
    root: str                     # the root coordinator
    root_region: int
    # Interior levels 1..L (``levels[i]`` is level ``i+1``): active
    # node id -> TreeNode.  The LAST level always holds exactly one
    # active node, whose coordinator IS ``root``.  Node ids group the
    # FULL previous-level id range (``prev_id // branch``), so the
    # tree shape is stable under deaths — a dead subtree just drops
    # out of its parent's active children.
    levels: tuple = ()
    branch: int = 0


def region_layout(
    members: Sequence[str], region_size: int, dead: Sequence[str] = (),
    branch: Optional[int] = None,
) -> HierarchyLayout:
    """Derive the round's tree topology.

    The PARTITION derives from the roster alone (stable under a
    mid-round death — re-partitioning on health signals would move
    every stripe).  ``dead`` parties drop out of their region's stripe
    ring and fold set; a dead canonical coordinator's region fails over
    to the :func:`~rayfed_tpu.transport.manager.roster_successor`-
    derived next live member.  Above the leaf regions, every
    ``branch`` contiguous node ids group into one interior node
    (recursively, until a single top node remains); an interior node's
    coordinator is its first active child's coordinator, so the root
    is the first active region's coordinator — exactly the 2-level
    derivation when the region count fits one ``branch`` group.
    ``branch`` defaults to ``max(2, region_size)``.
    """
    from rayfed_tpu.transport.manager import (
        branch_groups, partition_regions, roster_successor,
    )

    regions = partition_regions(members, region_size)
    if branch is None:
        branch = max(2, int(region_size))
    branch = int(branch)
    if branch < 2:
        raise ValueError(
            f"branch must be >= 2 (a 1-ary interior level folds "
            f"nothing), got {branch}"
        )
    dead_set = set(dead)
    live = [[p for p in r if p not in dead_set] for r in regions]
    coordinators: Dict[int, str] = {}
    active: List[int] = []
    for g, r in enumerate(regions):
        if not live[g]:
            continue
        if r[0] in dead_set:
            succ = roster_successor(r, r[0], dead_set)
            if succ is None:  # pragma: no cover - live[g] non-empty
                continue
            coordinators[g] = succ
        else:
            coordinators[g] = r[0]
        active.append(g)
    if not active:
        raise HierarchyRoundError(
            f"no live party remains on the roster {sorted(members)} "
            f"(dead: {sorted(dead_set)})"
        )
    # Interior levels: fold the FULL id range of each level into
    # groups of ``branch`` until one node remains.  At least one
    # interior level always exists (the top node the root folds), so
    # a single-branch-group layout reproduces the 2-level shape.
    levels: List[Dict[int, TreeNode]] = []
    prev_active = list(active)
    prev_coord: Dict[int, str] = dict(coordinators)
    n_full = len(regions)
    while True:
        n_full = -(-n_full // branch)
        level = {
            nid: TreeNode(tuple(children), prev_coord[children[0]])
            for nid, children in branch_groups(prev_active, branch)
        }
        levels.append(level)
        if n_full <= 1:
            break
        prev_active = sorted(level)
        prev_coord = {nid: nd.coordinator for nid, nd in level.items()}
    root_region = active[0]
    return HierarchyLayout(
        regions, live, coordinators, active,
        coordinators[root_region], root_region,
        tuple(levels), branch,
    )


def make_region_meta(
    phase: str,
    region: int,
    n_regions: int,
    stripe: int,
    n_stripes: int,
    nblocks: int,
    total_elems: int,
    dtype: str,
    qgrid_fp: int,
    members_fp: int,
    epoch: Optional[int] = None,
    level: int = 0,
    parent: int = 0,
    path: str = "",
) -> Dict[str, Any]:
    """The ``hrm`` sideband of a hierarchy payload — single producer of
    its schema (``tool/check_wire_format.py`` fingerprints it).

    ``phase`` is ``"rs"`` (region reduce-scatter/hub codes) or ``"ps"``
    (a stripe of the region's integer partial sum).  Receivers
    cross-check every field against their independently derived
    layout, so a partition disagreement (``mf``: the roster
    fingerprint), a stale epoch (``ep``), a grid mismatch (``qg``) or
    a tree-shape disagreement (``lv``/``pa``/``rp``: the node's level,
    parent id and interior root path — v2, multi-level trees) fails
    loudly BEFORE any block folds.
    """
    return {
        "v": HIERARCHY_VERSION,
        "ph": str(phase),
        "rg": int(region),
        "nr": int(n_regions),
        "s": int(stripe),
        "n": int(n_stripes),
        "nb": int(nblocks),
        "el": int(total_elems),
        "dt": str(dtype),
        "qg": int(qgrid_fp),
        "mf": int(members_fp),
        "ep": -1 if epoch is None else int(epoch),
        "lv": int(level),
        "pa": int(parent),
        "rp": str(path),
    }


def check_region_meta(meta_json: str, want: Dict[str, Any]) -> None:
    """Validate a received ``hrm`` manifest against the locally derived
    layout; raises naming the first mismatched field."""
    hrm = json.loads(meta_json)
    if hrm.get("v", 0) > HIERARCHY_VERSION:
        raise HierarchyRoundError(
            f"region payload uses hierarchy manifest v{hrm.get('v')}; "
            f"this party understands up to v{HIERARCHY_VERSION}"
        )
    for key, expect in want.items():
        if hrm.get(key) != expect:
            raise HierarchyRoundError(
                f"region manifest mismatch: {key}={hrm.get(key)!r}, "
                f"expected {expect!r} — hierarchy peers disagree on the "
                f"round's partition/grid/epoch"
            )


class RegionSumTree(QuantizedPackedTree):
    """Wire form of a region's integer partial sum: ``Σ_{p∈region}
    w_p·q_p`` on the round's shared grid, at the narrowest exact
    integer width (:func:`partial_sum_dtype`), with the grid descriptor
    riding along (the root still verifies the fingerprint before
    folding).

    Deliberately NOT decodable on its own: a partial sum is meaningless
    before the root's single fused rescale over the WHOLE roster's
    weight — :meth:`dequantize`/:meth:`unpack` raise instead of
    silently rescaling a subtree's sum as if it were the round's.  Fold
    with a ``presummed`` :class:`~rayfed_tpu.fl.streaming.
    StreamingAggregator`, whose unit-weight integer fold reassembles
    exactly the flat accumulator.

    ``arrived_w``: the subtree's TRUE arrived integer Σw — set (and
    propagated up the tree in the pytree aux) when a per-region quorum
    cutoff excluded stragglers, so the root's finalize divides by the
    weight that actually folded.  ``None`` means the full subtree
    roster weight arrived (the all-of-n hot path carries no number).
    """

    __slots__ = ("arrived_w",)

    def __init__(self, buf, scales, zps, passthrough, spec, gmeta,
                 arrived_w: Optional[int] = None):
        super().__init__(buf, scales, zps, passthrough, spec, gmeta)
        self.arrived_w = None if arrived_w is None else int(arrived_w)

    def dequantize(self, out_dtype: Any = np.float32,
                   ref: Optional[Any] = None):
        raise HierarchyRoundError(
            "a RegionSumTree is an integer PARTIAL sum — only the root "
            "fold (StreamingAggregator(presummed=...)) may rescale it, "
            "once, over the whole roster's weight"
        )

    def unpack(self, dtype: Any = None):
        raise HierarchyRoundError(
            "a RegionSumTree cannot be unpacked — see dequantize"
        )

    def __reduce__(self):
        return (
            RegionSumTree,
            (self.buf, self.scales, self.zps, self.passthrough,
             self.spec, self.gmeta, self.arrived_w),
        )

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"RegionSumTree({self.gmeta.total_elems} partial-sum "
            f"elements on grid fp={self.gmeta.fp:#010x}"
            + ("" if self.arrived_w is None
               else f", arrived_w={self.arrived_w}") + ")"
        )


import jax  # noqa: E402  (after the numpy-only machinery, like quantize)

jax.tree_util.register_pytree_node(
    RegionSumTree,
    lambda rt: (
        (rt.buf, rt.scales, rt.zps, *rt.passthrough),
        (rt.spec, rt.gmeta, rt.arrived_w),
    ),
    lambda aux, ch: RegionSumTree(
        ch[0], ch[1], ch[2], tuple(ch[3:]), aux[0], aux[1], aux[2]
    ),
)


from rayfed_tpu.fl.streaming import StripeAggregator  # noqa: E402


class _RawStripeAggregator(StripeAggregator):
    """A region stripe owner's fold that emits the RAW i32 partial sum
    instead of a finalized stripe — the region level must NOT rescale
    (the single fused divide belongs to the root; a per-region divide
    would round twice and break hierarchical == flat byte-identity)."""

    def _finalize(self):
        # The donated accumulator holds Σ w_p·widen(q_p) on the padded
        # block grid; trim the pad, keep the exact integers.
        return np.asarray(self._acc)[: self._total_elems]


from rayfed_tpu.fl.streaming import StreamingAggregator  # noqa: E402


class _RegionHubAggregator(StreamingAggregator):
    """A leaf region's QUORUM hub fold: the coordinator collects the
    members' full code trees and emits the region's RAW i32 partial sum
    over the ARRIVED subset — the deadline-gated pin-members-and-refold
    cutoff is the base class's (the flat quorum path's contract,
    region-scoped).  No rescale happens here: the single fused divide
    belongs to the root, over the true arrived Σw the subtree reports
    up (:attr:`RegionSumTree.arrived_w`)."""

    def _finalize(self):
        members = (
            self._participating
            if self._participating is not None
            else list(range(self._n))
        )
        self._verify_quant_members(members)
        return np.asarray(self._acc)[: self._total_elems]


class _NodeAggregator(StreamingAggregator):
    """An interior node's fold of its children's :class:`RegionSumTree`
    partial sums (unit weight, strict all-of-children).  Emits the raw
    i32 subtree sum — except at the ROOT (``finalize_root=True``),
    where it applies THE single fused rescale over the subtree's TRUE
    arrived Σw (children's ``arrived_w``, falling back to their roster
    subtree weights when no cutoff happened — in which case the
    divisor is exactly the flat fold's Σw and the bytes are identical
    by construction)."""

    def __init__(self, *args, finalize_root: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self._finalize_root = bool(finalize_root)
        self.arrived_w: Optional[int] = None

    def _fold_members(self):
        return (
            self._participating
            if self._participating is not None
            else list(range(self._n))
        )

    def _finalize(self):
        members = self._fold_members()
        self._verify_quant_members(members)
        arrived = 0
        for i in members:
            s = self._streams[i]
            tree = s.local_tree if s.local_tree is not None else (
                self._tree_of(s)
            )
            arrived += (
                int(tree.arrived_w) if tree.arrived_w is not None
                else int(self._weights[i])
            )
        self.arrived_w = arrived
        if self._finalize_root:
            # The ONE fused rescale of the whole round, over what
            # actually folded.  Integer totals are exact in f32 up to
            # the headroom bound (checked at grid construction).
            self._total_w = float(arrived)
            return super()._finalize()
        return np.asarray(self._acc)[: self._total_elems]


# Stripe geometry (compaction + short-tail arithmetic) is the SAME
# cross-party contract the flat ring uses — one definition, not a copy
# that could silently diverge.
from rayfed_tpu.fl.ring import _stripe_elems, _stripe_slice  # noqa: E402


class HierarchyRound:
    """One party's data-plane walk of a hierarchical round.

    Deliberately driven through a :class:`~rayfed_tpu.transport.manager.
    TransportManager`-shaped object (``send``/``send_many``/``recv``/
    ``recv_stream_many``/``cancel_stream``) rather than the fed runtime:
    the fed wrapper (:func:`hierarchy_aggregate`), the traffic budgets
    (``tests/test_wire_budget.py``'s virtual parties) and the in-process
    chaos tests all drive EXACTLY this class, so what the tests hold is
    what the driver ships.

    ``keys`` are the round's six rendezvous ids ``(rs, ps, up, down,
    commit, release)`` — every controller passes identical ones.
    ``epoch`` stamps every frame (``wire.EPOCH_TAG_KEY``): a receiver
    whose roster advanced rejects stale-region frames loudly.
    """

    def __init__(
        self,
        transport: Any,
        *,
        party: str,
        members: Sequence[str],
        region_size: int,
        grid: Any,
        quant_ref: Optional[Any],
        keys: Sequence[Any],
        weights: Optional[Dict[str, float]] = None,
        stream: str = "hier",
        epoch: Optional[int] = None,
        round_tag: Optional[int] = None,
        backstop: Optional[float] = None,
        quant_scope: Optional[str] = None,
        allowed: Optional[Dict[str, Any]] = None,
        quant_downlink: bool = False,
        dead: Sequence[str] = (),
        timings: Optional[Dict[str, float]] = None,
        server_step: Optional[Any] = None,
        branch: Optional[int] = None,
        region_quorum: Optional[int] = None,
        region_deadline_s: Optional[float] = None,
        ring_downlink: bool = True,
    ) -> None:
        from rayfed_tpu.fl.fedavg import quant_weights
        from rayfed_tpu.fl.quantize import RoundCodec

        if grid is None:
            raise HierarchyRoundError(
                "hierarchical aggregation runs in the compressed domain "
                "ONLY: float partial sums would re-associate a "
                "non-associative fold and silently break hierarchical "
                "== flat byte-identity — pass the round's shared "
                "QuantGrid (wire_quant)"
            )
        if len(keys) != HIER_SEQ_IDS:
            raise ValueError(
                f"hierarchy rounds consume {HIER_SEQ_IDS} rendezvous "
                f"ids, got {len(keys)}"
            )
        self._t = transport
        self._me = str(party)
        self._members = sorted(members)
        if self._me not in self._members:
            raise HierarchyRoundError(
                f"{self._me!r} is not on the round roster "
                f"{self._members} — observer controllers are not "
                f"supported by hierarchy rounds"
            )
        self._dead = set(dead)
        if self._me in self._dead:
            raise HierarchyRoundError(
                f"{self._me!r} is in the round's agreed dead set"
            )
        self._lay = region_layout(
            self._members, region_size, self._dead, branch=branch,
        )
        self._grid = grid
        self._codec = RoundCodec(grid, quant_ref, quant_scope)
        self._qref = self._codec.ref
        self._keys = tuple(keys)
        self._stream = stream
        self._epoch = epoch
        self._round_tag = round_tag
        self._backstop = backstop
        self._allowed = allowed
        self._quant_scope = quant_scope
        self._quant_downlink = bool(quant_downlink)
        self._timings = timings
        # Server optimization (fl.server_opt): the state steps ONCE, at
        # the root, on the exact finalized f32 — the tree broadcast
        # below then carries the post-step model to every level.
        self._server_step = server_step
        contributors = [p for p in self._members if p not in self._dead]
        w_list = (
            None if weights is None
            else [float(weights[p]) for p in contributors]
        )
        iw, itotal = quant_weights(w_list, len(contributors))
        self._iw = dict(zip(contributors, iw))
        self._w_total = itotal
        grid.check_weight_headroom(itotal)
        lay = self._lay
        # Per-node subtree ROSTER weights (arrived <= roster, so every
        # level's wire dtype bound is safe under a region cutoff), and
        # the per-LEVEL partial-sum wire dtype: one dtype per level —
        # the max subtree weight at that level bounds every node's
        # emission, and a fold requires one uniform stream dtype.
        self._node_w: List[Dict[int, int]] = [{
            g: sum(self._iw[p] for p in lay.live[g]) for g in lay.active
        }]
        for level in lay.levels:
            below = self._node_w[-1]
            self._node_w.append({
                nid: sum(below[c] for c in nd.children)
                for nid, nd in level.items()
            })
        self._lvl_dtype = [
            partial_sum_dtype(grid.qabs_max, max(w.values()))
            for w in self._node_w[:-1]
        ] or [partial_sum_dtype(grid.qabs_max, itotal)]
        self._ps_dtype = self._lvl_dtype[0]
        self._members_fp = members_fingerprint(self._members)
        # The (level, node_id) pairs this party coordinates, ascending
        # from its leaf region.  Coordinatorship is prefix-closed: an
        # interior node's coordinator is its first active child's, so
        # the chain is a walk straight up from the leaf.
        g_mine = next(
            (j for j in lay.active if self._me in lay.live[j]), None
        )
        self._g = g_mine
        self._coordinated: List[tuple] = []
        if g_mine is not None and lay.coordinators[g_mine] == self._me:
            self._coordinated.append((0, g_mine))
            nid = g_mine
            for lv, level in enumerate(lay.levels, start=1):
                nid //= lay.branch
                if level[nid].coordinator != self._me:
                    break
                self._coordinated.append((lv, nid))
        if region_quorum is not None:
            rq = int(region_quorum)
            if rq < 1:
                raise ValueError(
                    f"region_quorum must be >= 1 (the minimum arrived "
                    f"member count per region), got {region_quorum}"
                )
            region_quorum = rq
        self._region_quorum = region_quorum
        self._region_deadline_s = (
            None if region_deadline_s is None else float(region_deadline_s)
        )
        if self._region_deadline_s is not None and region_quorum is None:
            raise ValueError(
                "region_deadline_s needs region_quorum= (the per-region "
                "minimum arrived count the deadline gates)"
            )
        self._ring_downlink = bool(ring_downlink)
        self._pending_cancels: List[tuple] = []

    # -- helpers --------------------------------------------------------------

    def _send(self, dest: str, value: Any, up: str, *, down: Any,
              stream: Optional[str] = None, quant_meta=None):
        return self._t.send(
            dest, value, up, down, stream=stream,
            round_tag=self._round_tag, epoch_tag=self._epoch,
            quant_meta=quant_meta,
        )

    def _recv(self, src: str, up: str, down: Any):
        return self._t.recv(src, up, down)

    def _coord_of(self, lv: int, nid: int) -> str:
        """Coordinator of active node ``nid`` at tree level ``lv``
        (level 0 = leaf regions)."""
        if lv == 0:
            return self._lay.coordinators[nid]
        return self._lay.levels[lv - 1][nid].coordinator

    def _node_path(self, g: int) -> str:
        """Region ``g``'s interior ancestor ids, leaf-to-root — the
        ``rp`` manifest field two peers cross-check so a tree-shape
        (branch) disagreement aborts before any block folds."""
        lay = self._lay
        nid = g
        parts: List[str] = []
        for _ in lay.levels:
            nid //= lay.branch
            parts.append(str(nid))
        return "/".join(parts)

    def _hrm(self, phase: str, g: int, stripe: int, n_stripes: int,
             nblocks: int, dtype: str) -> str:
        return json.dumps(
            make_region_meta(
                phase, g, len(self._lay.regions), stripe, n_stripes,
                nblocks, self._grid.total_elems, dtype,
                self._grid.fingerprint(), self._members_fp,
                epoch=self._epoch,
                level=0, parent=g // self._lay.branch,
                path=self._node_path(g),
            ),
            sort_keys=True,
        )

    def _hrm_want(self, phase: str, g: int, stripe: int, n_stripes: int,
                  nblocks: int, dtype: str) -> Dict[str, Any]:
        return {
            "ph": phase, "rg": g, "nr": len(self._lay.regions),
            "s": stripe, "n": n_stripes, "nb": nblocks,
            "el": self._grid.total_elems, "dt": dtype,
            "qg": self._grid.fingerprint(), "mf": self._members_fp,
            "ep": -1 if self._epoch is None else int(self._epoch),
            "lv": 0, "pa": g // self._lay.branch,
            "rp": self._node_path(g),
        }

    # -- the round ------------------------------------------------------------

    def run(self, local_value: Any) -> PackedTree:
        """Walk the round; returns the finalized aggregate (identical
        bytes on every controller) or raises
        :class:`HierarchyRoundError` on every controller."""
        t0 = time.perf_counter()
        try:
            result = self._run_inner(local_value)
        except BaseException as exc:
            self._codec.rollback()
            for up, down in self._pending_cancels:
                try:
                    self._t.cancel_stream(up, down)
                except Exception:  # pragma: no cover - best effort
                    pass
            self._poison_edges(exc)
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                # The poison still unparks the peers, but an interrupt
                # must STOP the caller unwrapped (the fl.ring contract).
                raise
            HIER_STATS["rounds_aborted"] += 1
            from rayfed_tpu import telemetry as _telemetry

            _telemetry.event(
                "hier.abort", round=self._round_tag, epoch=self._epoch,
                party=self._me,
                outcome="error", detail={"error": repr(exc)},
            )
            if isinstance(exc, HierarchyRoundError):
                raise
            raise HierarchyRoundError(
                f"hierarchy round aborted: {exc!r}"
            ) from exc
        self._codec.commit()
        HIER_STATS["rounds_completed"] += 1
        if self._timings is not None:
            self._timings["agg_s"] = time.perf_counter() - t0
            self._timings.setdefault("push_s", 0.0)
        return result

    def _run_inner(self, local_value: Any) -> PackedTree:
        from rayfed_tpu.fl import quantize as qz

        me = self._me
        lay = self._lay
        rs_id, ps_id, up_id, down_id, commit_id, release_id = self._keys
        backstop = self._backstop
        t_call0 = time.perf_counter()

        _maybe_fault("local", me)
        q = self._codec.to_wire(local_value)
        if q.passthrough:
            raise HierarchyRoundError(
                f"hierarchical aggregation covers the packed float "
                f"buffer only, but this update carries "
                f"{len(q.passthrough)} non-float (passthrough) leaf(s) "
                f"— their per-leaf reduce has no tree decomposition "
                f"yet; drop them from the update tree (loud exclusion, "
                f"never a silent partial aggregate)"
            )
        buf = np.asarray(q.buf).reshape(-1)
        g = next(
            j for j in lay.active if me in lay.live[j]
        )
        region = lay.live[g]
        m = region.index(me)
        coord = lay.coordinators[g]
        is_coord = me == coord
        is_root = me == lay.root
        from rayfed_tpu import telemetry as _telemetry

        t_mark = t_call0
        # Flight-recorder hierarchy phase boundaries, LEVEL-stamped
        # (region_rs / region_gather / up.l<k> / down.l<k> /
        # down.relay|down.fan / broadcast / commit) so trace_report can
        # attribute the critical path per tree level.  Disarmed: a bare
        # perf_counter read per phase; armed: a ring append.
        _phase_span = _telemetry.phase_spanner(
            "hier", round=self._round_tag, epoch=self._epoch,
            party=self._me,
            detail={"region": g, "coordinator": coord, "root": lay.root},
        )

        # -- 1+2. leaf phase: the region's raw integer partial sum ------
        # Classic mode stripes the fold across the region ring; quorum
        # mode collects code trees at the coordinator behind a
        # deadline-gated k-of-region cutoff.  Either way the
        # coordinator ends up holding the region's exact i32 sum.
        if self._region_quorum is None:
            ps_full, t_mark = self._leaf_stripe(
                q, buf, _phase_span, t_mark, t_call0
            )
            leaf_members = list(region)
        else:
            ps_full, leaf_members, t_mark = self._leaf_hub(
                q, _phase_span, t_mark, t_call0
            )

        # -- 3. the up walk: fold subtree sums level by level -----------
        # A coordinator climbs its prefix-closed chain of coordinated
        # nodes: at each level it folds its children's RegionSumTree
        # partial sums (unit weight -- exact + associative integer
        # adds, so ANY level count is byte-identical to the flat fold)
        # and either keeps climbing or ships the subtree sum to the
        # next coordinator.  The TOP node's coordinator is the root:
        # its fold applies the single fused rescale of the round.
        _maybe_fault("up", me)
        result = None
        ce = self._grid.chunk_elems
        n_levels = len(lay.levels)
        if is_coord:
            sub_raw = ps_full
            sub_arrived = sum(self._iw[p] for p in leaf_members)
            child_id = g
            for lv in range(1, n_levels + 1):
                nid = child_id // lay.branch
                node = lay.levels[lv - 1][nid]
                up_dt = self._lvl_dtype[lv - 1]
                sub_tree = RegionSumTree(
                    sub_raw, self._grid.scales, self._grid.zps, (),
                    PackSpec(q.spec.entries, q.spec.treedef, up_dt),
                    self._grid.meta(), arrived_w=sub_arrived,
                )
                if node.coordinator != me:
                    ref = self._send(
                        node.coordinator, sub_tree,
                        f"{up_id}.{lv}.{child_id}", down=up_id,
                        stream=f"{self._stream}/up/{lv}.{child_id}",
                        quant_meta=self._codec.descriptor,
                    )
                    if not ref.resolve(timeout=backstop):
                        raise HierarchyRoundError(
                            f"level-{lv - 1} partial sum of node "
                            f"{child_id} to {node.coordinator!r} failed"
                        )
                    t_mark = _phase_span(f"up.l{lv}", t_mark)
                    break
                children = node.children
                at_top = lv == n_levels
                node_agg = _NodeAggregator(
                    len(children),
                    weights=[
                        float(self._node_w[lv - 1][c]) for c in children
                    ],
                    allowed=self._allowed,
                    party=self._me,
                    chunk_elems=ce,
                    labels=[
                        f"level-{lv - 1} node {c}" for c in children
                    ],
                    quant=self._grid,
                    quant_ref=self._qref,
                    presummed=up_dt,
                    finalize_root=at_top,
                )
                entries = []
                for idx, c in enumerate(children):
                    if c == child_id:
                        continue
                    entries.append((
                        self._coord_of(lv - 1, c), f"{up_id}.{lv}.{c}",
                        up_id, node_agg.sink(idx),
                    ))
                    self._pending_cancels.append(
                        (f"{up_id}.{lv}.{c}", up_id)
                    )
                if entries:
                    self._t.recv_stream_many(entries)
                node_agg.add_local(children.index(child_id), sub_tree)
                folded = node_agg.result(timeout=backstop)
                sub_arrived = node_agg.arrived_w
                t_mark = _phase_span(f"up.l{lv}", t_mark)
                if at_top:
                    # ``finalize_root``: the top node's coordinator IS
                    # the round root by construction.
                    result = folded
                    break
                # Interior emission: exact i32, narrowed to the level's
                # wire dtype (bounded by its max subtree roster weight).
                sub_raw = np.asarray(folded).astype(
                    np.dtype(self._lvl_dtype[lv])
                )
                child_id = nid

        # -- 4. broadcast down the tree ---------------------------------
        _maybe_fault("down", me)
        down_descr = None
        wire_down = None
        chain: List[str] = []
        extras: List[str] = []
        if is_root:
            if self._server_step is not None:
                # The single server step of the round: exact finalized
                # f32 in, post-step model out -- the downlink recode's
                # fresh grid is therefore ranged by the POST-step
                # delta.  A failure here aborts through the standard
                # poison cascade (every controller raises
                # HierarchyRoundError and the driver falls back in
                # lockstep, re-running the SAME step from the SAME
                # state on the flat path).
                result = self._server_step(result)
            wire_down = result
            if self._quant_downlink:
                wire_down, result, down_descr = qz.quantize_downlink(
                    result, self._grid, self._qref, self._quant_scope,
                )
        elif self._coordinated:
            lvh, nidh = self._coordinated[-1]
            parent = self._coord_of(lvh + 1, nidh // lay.branch)
            value = self._recv(
                parent, f"{down_id}.c", down_id
            ).resolve(timeout=backstop)
            result = self._decode_down(value)
            wire_down = value
            if isinstance(value, QuantizedPackedTree):
                down_descr = qz.grid_descriptor(value.grid())
        if self._coordinated:
            # Interior fan-down, top level first: every child
            # coordinator of every node I coordinate -- constant
            # out-degree, so ROOT egress stays ~O(branch*|model|) flat
            # in N (the region ring below amortizes the rest).
            for lv, nid in reversed(self._coordinated[1:]):
                dests = [
                    self._coord_of(lv - 1, c)
                    for c in lay.levels[lv - 1][nid].children
                ]
                dests = [p for p in dests if p != me]
                if dests:
                    refs = self._t.send_many(
                        dests, wire_down, f"{down_id}.c", down_id,
                        stream=f"{self._stream}/down",
                        round_tag=self._round_tag,
                        epoch_tag=self._epoch,
                        quant_meta=down_descr,
                    )
                    for p, ref in refs.items():
                        if not ref.resolve(timeout=backstop):
                            raise HierarchyRoundError(
                                f"result fan-down to level-{lv - 1} "
                                f"coordinator {p!r} failed"
                            )
                    t_mark = _phase_span(f"down.l{lv}", t_mark)
            # Leaf region delivery.  Ring mode: the result relays
            # member -> member (forward-on-arrival -- the all-gather
            # relay machinery on the shared downlink codes), so the
            # coordinator sends ONE copy per chain regardless of
            # region size -- parallel chains of at most
            # RING_RELAY_MAX_HOPS members bound the serial-hop
            # latency (see the constant's comment).
            chain = [p for p in leaf_members if p != me]
            extras = [
                p for p in region if p != me and p not in leaf_members
            ]
            if self._ring_downlink:
                if chain:
                    head_refs = []
                    for sub in _relay_chains(chain):
                        env = {"chain": sub, "data": wire_down}
                        head_refs.append((sub[0], self._send(
                            sub[0], env, f"{down_id}.m", down=down_id,
                            stream=f"{self._stream}/down",
                            quant_meta=down_descr,
                        )))
                    for head, ref in head_refs:
                        if not ref.resolve(timeout=backstop):
                            raise HierarchyRoundError(
                                f"ring downlink head push to {head!r} "
                                f"failed"
                            )
                for p in extras:
                    # Best effort: a quorum-excluded member may be
                    # dead; a live straggler still gets the model.
                    if not self._send(
                        p, wire_down, f"{down_id}.m", down=down_id,
                        stream=f"{self._stream}/down",
                        quant_meta=down_descr,
                    ).resolve(timeout=backstop):
                        logger.warning(
                            "[%s] downlink to excluded member %s "
                            "failed", me, p,
                        )
            else:
                if chain:
                    refs = self._t.send_many(
                        chain, wire_down, f"{down_id}.m", down_id,
                        stream=f"{self._stream}/down",
                        round_tag=self._round_tag,
                        epoch_tag=self._epoch,
                        quant_meta=down_descr,
                    )
                    for p, ref in refs.items():
                        if not ref.resolve(timeout=backstop):
                            raise HierarchyRoundError(
                                f"result broadcast to member {p!r} "
                                f"failed"
                            )
                for p in extras:
                    if not self._send(
                        p, wire_down, f"{down_id}.m", down=down_id,
                        stream=f"{self._stream}/down",
                        quant_meta=down_descr,
                    ).resolve(timeout=backstop):
                        logger.warning(
                            "[%s] downlink to excluded member %s "
                            "failed", me, p,
                        )
            t_mark = _phase_span(
                "down.relay" if self._ring_downlink else "down.fan",
                t_mark,
            )
        else:
            value = self._recv(
                coord, f"{down_id}.m", down_id
            ).resolve(timeout=backstop)
            relay = None
            if isinstance(value, dict) and "chain" in value:
                # Region-ring envelope: forward the SAME envelope to my
                # ring successor BEFORE decoding (forward-on-arrival),
                # then confirm my hop with a tiny commit token so the
                # coordinator's commit covers the whole chain.
                relay = [str(p) for p in value["chain"]]
                inner = value["data"]
            else:
                inner = value
            if relay is not None and me in relay:
                pos = relay.index(me)
                if pos + 1 < len(relay):
                    fwd_meta = (
                        qz.grid_descriptor(inner.grid())
                        if isinstance(inner, QuantizedPackedTree)
                        else None
                    )
                    ref = self._send(
                        relay[pos + 1], value, f"{down_id}.m",
                        down=down_id, stream=f"{self._stream}/down",
                        quant_meta=fwd_meta,
                    )
                    if not ref.resolve(timeout=backstop):
                        raise HierarchyRoundError(
                            f"ring downlink relay to "
                            f"{relay[pos + 1]!r} failed"
                        )
            result = self._decode_down(inner)
            if relay is not None and me in relay:
                ref = self._send(
                    coord, {"ok": 1}, f"{commit_id}.m.{g}.{me}",
                    down=commit_id,
                )
                if not ref.resolve(timeout=backstop):
                    raise HierarchyRoundError(
                        f"relay commit token to coordinator "
                        f"{coord!r} failed"
                    )
            t_mark = _phase_span("broadcast", t_mark)

        # -- 5. commit/release: agree the round landed everywhere -------
        # Tree-shaped two-phase commit (fl.ring's token ring, L levels
        # up): every coordinator confirms its region's delivery (relay
        # commit tokens in ring mode, send acks otherwise) plus its
        # child coordinators' commits, the root collects the top
        # node's, and a release travels back down every branch -- a
        # member only RETURNS once released, so success/abort is a
        # lockstep verdict.  Like any atomic commit, a crash inside the
        # tiny release pass itself can strand waiters until the
        # backstop; the bulk phases are fully covered.
        _maybe_fault("commit", me)
        token = {"ok": 1}
        if self._coordinated:
            if self._ring_downlink:
                for p in chain:
                    self._recv(
                        p, f"{commit_id}.m.{g}.{p}", commit_id
                    ).resolve(timeout=backstop)
            for lv, nid in self._coordinated[1:]:
                for c in lay.levels[lv - 1][nid].children:
                    cc = self._coord_of(lv - 1, c)
                    if cc == me:
                        continue
                    self._recv(
                        cc, f"{commit_id}.{lv - 1}.{c}", commit_id
                    ).resolve(timeout=backstop)
            if not is_root:
                lvh, nidh = self._coordinated[-1]
                parent = self._coord_of(lvh + 1, nidh // lay.branch)
                ref = self._send(
                    parent, token, f"{commit_id}.{lvh}.{nidh}",
                    down=commit_id,
                )
                if not ref.resolve(timeout=backstop):
                    raise HierarchyRoundError(
                        f"commit token of node {nidh} (level {lvh}) "
                        f"to {parent!r} failed"
                    )
                self._recv(
                    parent, f"{release_id}.r", release_id
                ).resolve(timeout=backstop)
            rel_dests: List[str] = []
            for lv, nid in self._coordinated[1:]:
                rel_dests.extend(
                    self._coord_of(lv - 1, c)
                    for c in lay.levels[lv - 1][nid].children
                )
            rel_dests.extend(p for p in region if p != me)
            rel_dests = [
                p for p in dict.fromkeys(rel_dests) if p != me
            ]
            if rel_dests:
                refs = self._t.send_many(
                    rel_dests, token, f"{release_id}.r", release_id,
                    round_tag=self._round_tag, epoch_tag=self._epoch,
                )
                for p, ref in refs.items():
                    if not ref.resolve(timeout=backstop):
                        # Post-commit best effort: the stranded waiter
                        # aborts at its backstop (residual window).
                        logger.warning(
                            "[%s] release token to %s failed", me, p,
                        )
        else:
            self._recv(
                coord, f"{release_id}.r", release_id
            ).resolve(timeout=backstop)
        _phase_span("commit", t_mark)
        return result

    def _leaf_stripe(self, q, buf, _phase_span, t_mark, t_call0):
        """Sections 1-2, classic mode: region reduce-scatter over the
        stripe ring + partial-sum gather to the coordinator.  Returns
        ``(ps_full, t_mark)`` -- the region's raw integer sum in the
        level-0 wire dtype at the coordinator (``None`` elsewhere)."""
        from rayfed_tpu.fl.fedavg import packed_block_grid
        from rayfed_tpu.fl.fedavg import packed_stripe_schedule

        me = self._me
        lay = self._lay
        rs_id, ps_id = self._keys[0], self._keys[1]
        backstop = self._backstop
        g = self._g
        region = lay.live[g]
        m = region.index(me)
        coord = lay.coordinators[g]
        is_coord = me == coord
        ce = self._grid.chunk_elems
        total_elems = self._grid.total_elems
        nblocks = packed_block_grid(total_elems, ce)
        s_n = len(region)
        stripes = packed_stripe_schedule(nblocks, s_n)
        wire_name = self._grid.wire_dtype

        def elems(k: int) -> int:
            return _stripe_elems(stripes[k], ce, nblocks, total_elems)

        # -- 1. region reduce-scatter (codes -> stripe owners) ---------
        agg = None
        my_se = elems(m)
        if my_se:
            want = self._hrm_want("rs", g, m, s_n, nblocks, wire_name)
            agg = _RawStripeAggregator(
                s_n,
                weights=[float(self._iw[p]) for p in region],
                allowed=self._allowed,
                party=self._me,
                chunk_elems=ce,
                expect_elems=my_se,
                label=f"region {g} stripe {m}",
                meta_check=lambda v: check_region_meta(v, want),
                quant=self._grid,
                quant_blocks=stripes[m],
                # A stripe is a host buffer: its reference slice is cut
                # from host bytes, fetched here.
                quant_ref=(
                    None if self._qref is None else _stripe_slice(
                        np.asarray(self._qref), stripes[m], ce,
                        total_elems,
                    )
                ),
            )
            entries = []
            for i, p in enumerate(region):
                if i == m:
                    continue
                entries.append(
                    (p, f"{rs_id}.{g}.{i}.{m}", rs_id, agg.sink(i))
                )
                self._pending_cancels.append(
                    (f"{rs_id}.{g}.{i}.{m}", rs_id)
                )
            if entries:
                self._t.recv_stream_many(entries)

        _maybe_fault("rs", me)
        rs_refs = []
        for k, p in enumerate(region):
            if k == m or not elems(k):
                continue
            payload = {
                "data": _stripe_slice(buf, stripes[k], ce, total_elems),
                "hrm": self._hrm("rs", g, k, s_n, nblocks, wire_name),
            }
            rs_refs.append((p, f"{rs_id}.{g}.{m}.{k}", self._send(
                p, payload, f"{rs_id}.{g}.{m}.{k}", down=rs_id,
                stream=f"{self._stream}/rs",
                quant_meta=self._codec.descriptor,
            )))
        if agg is not None:
            agg.add_local(
                m, _stripe_slice(buf, stripes[m], ce, total_elems)
            )
        for p, up, ref in rs_refs:
            if not ref.resolve(timeout=backstop):
                raise HierarchyRoundError(
                    f"region reduce-scatter push {up!r} to {p!r} failed"
                )
        if self._timings is not None:
            self._timings["push_s"] = time.perf_counter() - t_call0

        raw_stripe = None
        if agg is not None:
            raw = agg.result(timeout=backstop)  # exact i32 partial sums
            # Narrowest exact width for the wire: bounded by
            # qabs_max * W_total by construction, so the cast is exact.
            raw_stripe = raw.astype(np.dtype(self._ps_dtype))

        # -- 2. partial-sum gather to the region coordinator -----------
        t_mark = _phase_span("region_rs", t_mark)
        _maybe_fault("ps", me)
        if not is_coord:
            if raw_stripe is not None:
                ref = self._send(
                    coord,
                    {
                        "data": raw_stripe,
                        "hrm": self._hrm(
                            "ps", g, m, s_n, nblocks, self._ps_dtype
                        ),
                    },
                    f"{ps_id}.{g}.{m}", down=ps_id,
                    quant_meta=self._codec.descriptor,
                )
                if not ref.resolve(timeout=backstop):
                    raise HierarchyRoundError(
                        f"partial-sum stripe {m} of region {g} to "
                        f"coordinator {coord!r} failed"
                    )
        else:
            ps_full = np.zeros(total_elems, np.dtype(self._ps_dtype))

            def scatter(stripe_arr: np.ndarray, blocks) -> None:
                off = 0
                for b in blocks:
                    size = min(ce, total_elems - b * ce)
                    ps_full[b * ce : b * ce + size] = (
                        stripe_arr[off : off + size]
                    )
                    off += size

            if raw_stripe is not None:
                scatter(raw_stripe, stripes[m])
            ps_refs = {}
            for k, p in enumerate(region):
                if k == m or not elems(k):
                    continue
                ps_refs[k] = (p, self._recv(p, f"{ps_id}.{g}.{k}", ps_id))
            for k, (p, ref) in ps_refs.items():
                value = ref.resolve(timeout=backstop)
                check_region_meta(
                    value["hrm"],
                    self._hrm_want(
                        "ps", g, k, s_n, nblocks, self._ps_dtype
                    ),
                )
                arr = np.asarray(value["data"]).reshape(-1)
                if arr.size != elems(k):
                    raise HierarchyRoundError(
                        f"partial-sum stripe {k} of region {g} carries "
                        f"{arr.size} elements, schedule says {elems(k)}"
                    )
                scatter(arr, stripes[k])

        t_mark = _phase_span("region_gather", t_mark)
        return (ps_full if is_coord else None), t_mark

    def _leaf_hub(self, q, _phase_span, t_mark, t_call0):
        """Sections 1-2, quorum mode: members stream their full code
        trees to the region coordinator, whose deadline-gated quorum
        fold (the flat quorum path's pin-members-and-refold contract,
        region-scoped) emits the ARRIVED subset's raw integer sum --
        the slow/partially-dead region contributes what landed instead
        of aborting the round.  Returns ``(ps_full, arrived_members,
        t_mark)``; non-coordinators report the full live region."""
        from rayfed_tpu import telemetry as _telemetry

        me = self._me
        lay = self._lay
        rs_id = self._keys[0]
        backstop = self._backstop
        g = self._g
        region = lay.live[g]
        m = region.index(me)
        coord = lay.coordinators[g]

        if me != coord:
            _maybe_fault("rs", me)
            ref = self._send(
                coord, q, f"{rs_id}.q.{g}.{m}", down=rs_id,
                stream=f"{self._stream}/rs",
                quant_meta=self._codec.descriptor,
            )
            if not ref.resolve(timeout=backstop):
                raise HierarchyRoundError(
                    f"code-tree push of member {m} of region {g} to "
                    f"coordinator {coord!r} failed"
                )
            if self._timings is not None:
                self._timings["push_s"] = time.perf_counter() - t_call0
            t_mark = _phase_span("region_rs", t_mark)
            _maybe_fault("ps", me)
            t_mark = _phase_span("region_gather", t_mark)
            return None, list(region), t_mark

        agg = _RegionHubAggregator(
            len(region),
            weights=[float(self._iw[p]) for p in region],
            allowed=self._allowed,
            party=self._me,
            chunk_elems=self._grid.chunk_elems,
            quorum=min(self._region_quorum, len(region)),
            labels=list(region),
            quant=self._grid,
            quant_ref=self._qref,
        )
        entries = []
        for i, p in enumerate(region):
            if i == m:
                continue
            entries.append(
                (p, f"{rs_id}.q.{g}.{i}", rs_id, agg.sink(i))
            )
            self._pending_cancels.append((f"{rs_id}.q.{g}.{i}", rs_id))
        if entries:
            self._t.recv_stream_many(entries)
        _maybe_fault("rs", me)
        agg.add_local(m, q)
        if self._timings is not None:
            self._timings["push_s"] = time.perf_counter() - t_call0
        raw = agg.result(
            timeout=backstop, deadline_s=self._region_deadline_s
        )
        t_mark = _phase_span("region_rs", t_mark)
        _maybe_fault("ps", me)
        arrived = [region[i] for i in agg.quorum_members]
        if len(arrived) < len(region):
            HIER_STATS["region_cutoffs"] += 1
            _telemetry.event(
                "hier.region_cutoff", round=self._round_tag,
                epoch=self._epoch, party=me, outcome="cutoff",
                detail={
                    "region": g,
                    "arrived": arrived,
                    "excluded": [
                        p for p in region if p not in arrived
                    ],
                },
            )
        # Narrowest exact width for the wire: bounded by qabs_max * W
        # of the FULL region roster (arrived <= roster), so the cast
        # is exact under any cutoff.
        ps_full = raw.astype(np.dtype(self._ps_dtype))
        t_mark = _phase_span("region_gather", t_mark)
        return ps_full, arrived, t_mark


    def _decode_down(self, value: Any) -> PackedTree:
        if isinstance(value, RegionSumTree):
            raise HierarchyRoundError(
                "broadcast carried a RegionSumTree — the downlink must "
                "be the FINALIZED aggregate"
            )
        if isinstance(value, QuantizedPackedTree):
            return value.dequantize(
                np.float32,
                ref=self._qref if value.gmeta.mode == "delta" else None,
            )
        if not isinstance(value, PackedTree):
            raise HierarchyRoundError(
                f"broadcast carried {type(value).__name__}, expected "
                f"the aggregated PackedTree"
            )
        return value

    def _poison_edges(self, exc: BaseException) -> None:
        """Best-effort poison of every rendezvous key this party
        produces, so peers parked on them raise within a round trip
        (the fl.ring cascade, tree-shaped: the abort travels up the
        coordinated chain and back down every branch)."""
        poison = getattr(self._t, "_send_poison", None)
        if poison is None:
            return
        lay = self._lay
        me = self._me
        rs_id, ps_id, up_id, down_id, commit_id, release_id = self._keys
        g = self._g
        if g is None:  # pragma: no cover - run() rejects dead callers
            return
        region = lay.live[g]
        m = region.index(me)
        coord = lay.coordinators[g]
        edges: List[tuple] = []
        if self._region_quorum is None:
            for k, p in enumerate(region):
                if k != m:
                    edges.append((p, f"{rs_id}.{g}.{m}.{k}", rs_id))
            if me != coord:
                edges.append((coord, f"{ps_id}.{g}.{m}", ps_id))
        elif me != coord:
            # The hub sink: a poisoned stream marks this member FAILED,
            # which lets the coordinator's quorum cut off immediately
            # instead of waiting out the deadline.
            edges.append((coord, f"{rs_id}.q.{g}.{m}", rs_id))
        if me != coord:
            if self._ring_downlink:
                # My relay commit token: the coordinator unparks (and
                # its own cascade then unparks my ring successor).
                edges.append(
                    (coord, f"{commit_id}.m.{g}.{me}", commit_id)
                )
        else:
            # Up/commit toward my parent coordinator...
            if self._coordinated and me != lay.root:
                lvh, nidh = self._coordinated[-1]
                parent = self._coord_of(lvh + 1, nidh // lay.branch)
                edges.append(
                    (parent, f"{up_id}.{lvh + 1}.{nidh}", up_id)
                )
                edges.append(
                    (parent, f"{commit_id}.{lvh}.{nidh}", commit_id)
                )
            # ...and down/release toward every child coordinator and
            # region member parked on my broadcast.
            for lv, nid in self._coordinated[1:]:
                for c in lay.levels[lv - 1][nid].children:
                    cc = self._coord_of(lv - 1, c)
                    if cc != me:
                        edges.append((cc, f"{down_id}.c", down_id))
                        edges.append(
                            (cc, f"{release_id}.r", release_id)
                        )
            for p in region:
                if p != me:
                    edges.append((p, f"{down_id}.m", down_id))
                    edges.append((p, f"{release_id}.r", release_id))
        for dest, up, down in edges:
            if dest == me:
                continue
            try:
                poison(dest, up, down, exc)
            except Exception:  # pragma: no cover - best effort
                logger.exception(
                    "[%s] failed to poison hierarchy edge (%s, %s) at "
                    "%s", me, up, down, dest,
                )



def hierarchy_aggregate(
    fed_objects: Sequence[Any],
    weights: Optional[Sequence[float]] = None,
    *,
    region_size: int,
    stream: str = "hier",
    timeout: Optional[float] = None,
    quant: Any = None,
    quant_ref: Optional[Any] = None,
    quant_scope: Optional[str] = None,
    quant_downlink: bool = False,
    seq_ids: Optional[Sequence[Any]] = None,
    round_tag: Optional[int] = None,
    epoch: Optional[int] = None,
    timings: Optional[Dict[str, float]] = None,
    dead: Sequence[str] = (),
    server_step: Optional[Any] = None,
    region_branch: Optional[int] = None,
    region_quorum: Optional[int] = None,
    region_deadline_s: Optional[float] = None,
    ring_downlink: bool = True,
) -> Any:
    """FedAvg round over the derived multi-level hierarchy (see module
    docstring).

    ``region_branch``: interior tree degree (default
    ``max(2, region_size)`` — one interior level, i.e. the classic
    2-level shape, until the region count exceeds it).
    ``region_quorum``/``region_deadline_s``: per-region quorum cutoffs
    — each leaf region contributes its deadline-gated arrived-subset
    partial sum instead of aborting the round; the root's finalize
    reweights to the true arrived Σw.  ``ring_downlink``: relay the
    broadcast member→member inside each region (default) instead of a
    coordinator fan-out.

    ``server_step`` (:mod:`rayfed_tpu.fl.server_opt`): applied ONCE, at
    the root, to the exact finalized f32 aggregate; the tree broadcast
    (and its ``quantize_downlink`` recode) carries the post-step model,
    so every controller returns the stepped bytes — byte-identical to
    the flat streaming/quorum paths applying the same step.

    Drop-in for ``streaming_aggregate``/``ring_aggregate`` when the
    contributions are PackedTrees with one contribution per party and
    the round runs in the compressed domain (``quant`` is REQUIRED —
    hierarchical float sums are a loud exclusion): every controller
    calls it at the same program point with the same arguments and
    returns the identical aggregate bytes — byte-identical to
    :func:`~rayfed_tpu.fl.fedavg.packed_quantized_sum` over the same
    contributions, and therefore to the flat quantized streaming path.

    ``region_size`` partitions the sorted roster deterministically
    (:func:`~rayfed_tpu.transport.manager.partition_regions`).
    ``seq_ids``: :data:`HIER_SEQ_IDS` pre-allocated rendezvous ids (the
    quorum driver passes round-derived string keys).  ``epoch`` stamps
    every frame (stale-region frames are rejected loudly).  Aborted
    rounds raise :class:`HierarchyRoundError` on EVERY controller so
    the driver can fall back in lockstep.  Multi-host parties: leader
    processes only (like ``streaming_aggregate``).
    """
    from rayfed_tpu.fed_object import FedObject
    from rayfed_tpu.runtime import get_runtime

    runtime = get_runtime()
    objs = list(fed_objects)
    if not objs:
        raise ValueError(
            "hierarchy_aggregate needs at least one contribution"
        )
    for obj in objs:
        if not isinstance(obj, FedObject):
            raise TypeError(
                "hierarchy_aggregate consumes FedObjects (party-owned "
                f"contributions), got {type(obj).__name__}"
            )
    owners = [obj.get_party() for obj in objs]
    if len(set(owners)) != len(owners):
        raise ValueError(
            "hierarchy_aggregate needs exactly one contribution per "
            f"party (owners: {owners}) — aggregate duplicates locally "
            f"first"
        )
    if weights is not None and len(weights) != len(objs):
        raise ValueError(
            f"{len(weights)} weights for {len(objs)} contributions"
        )
    if seq_ids is None:
        seq_ids = [runtime.next_seq_id() for _ in range(HIER_SEQ_IDS)]
    me = runtime.party
    backstop = (
        timeout if timeout is not None
        else runtime.job_config.recv_backstop_s
    )
    w_map = (
        None if weights is None
        else {p: float(w) for p, w in zip(owners, weights)}
    )
    if me not in owners:
        raise HierarchyRoundError(
            f"{me!r} contributes nothing this round — observer "
            f"controllers are not supported by hierarchy rounds (use "
            f"the flat streaming path)"
        )
    rnd = HierarchyRound(
        runtime.send_proxy,
        party=me,
        members=owners,
        region_size=region_size,
        grid=quant,
        quant_ref=quant_ref,
        keys=seq_ids,
        weights=w_map,
        stream=stream,
        epoch=epoch,
        round_tag=round_tag,
        backstop=backstop,
        quant_scope=quant_scope,
        allowed=runtime.cluster_config.serializing_allowed_list,
        quant_downlink=quant_downlink,
        dead=dead,
        timings=timings,
        server_step=server_step,
        branch=region_branch,
        region_quorum=region_quorum,
        region_deadline_s=region_deadline_s,
        ring_downlink=ring_downlink,
    )
    local_value = (
        objs[owners.index(me)].get_local_ref().resolve(timeout=backstop)
    )
    return rnd.run(local_value)
