"""Secure aggregation: pairwise-masked integer folds, sum-only reveal.

Cross-silo FL's canonical privacy primitive (Bonawitz et al., "Practical
Secure Aggregation for Privacy-Preserving Machine Learning", CCS 2017),
built as a free rider on the compressed-domain integer folds of
:mod:`rayfed_tpu.fl.quantize`:

1. **Key agreement rides the HELLO handshake**
   (:mod:`rayfed_tpu.transport.secagg`): each party publishes an
   ephemeral per-session key in the connection HELLO it already performs
   with every peer, and per-(pair, session, stream, round) mask seeds
   derive via HKDF.  Masks are *generated, never shipped* — zero payload
   bytes on the wire.

2. **Masking in the quantized integer domain**: after delta-quantization
   onto the round's shared grid, a party's contribution becomes
   ``w·q + Σ_j ±PRG(seed_pair(j))  (mod 2³²)`` — its own integer weight
   folded in, plus one pairwise keystream per active peer, added by the
   lower-named endpoint of each pair and subtracted by the higher-named
   (one fused jit, :func:`rayfed_tpu.fl.fedavg.masked_code_kernel`).
   The masked codes ship as i32 and fold through the UNCHANGED integer
   kernels (:func:`~rayfed_tpu.fl.fedavg.quantized_accum_kernel` at unit
   weight — i32 addition wraps mod 2³², is associative, and every pair
   mask appears exactly once positive and once negative), so the
   accumulator after cancellation holds exactly ``Σ w_i·q_i`` and the
   ONE fused rescale emits bytes **identical to the unmasked round's**.
   The aggregator learns only the sum; any single masked contribution is
   uniform ring noise.

3. **Quorum-dropout mask recovery** (:mod:`rayfed_tpu.fl.quorum`): the
   deadline-gated cutoff pins the member set; the coordinator's cutoff
   announcement names it, each survivor replies with its pairwise seeds
   toward the dropped parties (scoped to THAT round's seeds — the
   per-round HKDF keeps every other round dark), and the coordinator
   subtracts the orphaned masks (:func:`mask_correction`) before the
   finalize rescale.

Overflow/exactness: the masked values wrap mod 2³² BY DESIGN; after the
pair masks cancel, the residual is the true ``Σ w_i·q_i``, which the
grid's existing headroom guard (``qabs_max · W ≤ 2³¹−1``) keeps exactly
representable — the same bound the unmasked integer fold already
enforces, so masked and unmasked rounds are byte-identical, not merely
close.

This module also absorbs the seed-era :mod:`rayfed_tpu.fl.secure` demo:
its in-process fixed-point primitives (:func:`pairwise_key`,
:func:`mask_update`, :func:`unmask_sum`) live here now; the
``fl/secure.py`` shim that re-exported them is gone.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
from typing import Any, Dict, Optional, Sequence

import numpy as np

from rayfed_tpu.fl.quantize import (
    QuantGrid,
    QuantizedPackedTree,
    QuantMeta,
    RoundCodec,
)
from rayfed_tpu.fl.compression import PackSpec
from rayfed_tpu.transport.secagg import (  # noqa: F401  (re-exported API)
    HAVE_AES,
    HAVE_X25519,
    SECAGG_STATS,
    SECAGG_VERSION,
    KeyAgreement,
    SecAggError,
    hkdf_sha256,
)

logger = logging.getLogger(__name__)

# Wire dtype of masked contributions: the quantized codes widen to i32
# and live in the mod-2³² ring the masks are drawn from.  (8-bit masked
# codes cannot exist: the masked value must be uniform over the ring the
# SUM lives in, or the mask leaks through the wrap.)
MASKED_WIRE_DTYPE = "int32"


# ---------------------------------------------------------------------------
# Mask keystream (PRG)
# ---------------------------------------------------------------------------


def prg_mask(seed: bytes, n: int, scheme: Optional[str] = None) -> np.ndarray:
    """Expand a 256-bit pair seed into ``n`` uint32 mask words.

    ``scheme``: ``"aes"`` — AES-256-CTR keystream (the ``cryptography``
    optional dependency; fast and cryptographic) or ``"philox"`` — the
    numpy Philox counter PRG keyed from the seed (stdlib fallback;
    deterministic and statistically strong but NOT a cryptographic PRG —
    see ``docs/source/secure_aggregation.rst``).  Defaults to the best
    available.  Both endpoints of a pair must expand the identical
    keystream — the scheme is advertised in the HELLO suite and a
    mismatch fails loudly at seed derivation
    (:meth:`~rayfed_tpu.transport.secagg.KeyAgreement.pair_secret`).
    """
    if len(seed) < 32:
        raise SecAggError(f"prg_mask needs a 32-byte seed, got {len(seed)}")
    if scheme is None:
        scheme = "aes" if HAVE_AES else "philox"
    n = int(n)
    if scheme == "aes":
        from cryptography.hazmat.primitives.ciphers import (
            Cipher,
            algorithms,
            modes,
        )

        enc = Cipher(
            algorithms.AES(seed[:32]), modes.CTR(b"\x00" * 16)
        ).encryptor()
        stream = enc.update(b"\x00" * (4 * n))
        return np.frombuffer(stream, dtype="<u4").copy()
    if scheme == "philox":
        key = np.frombuffer(seed[:16], np.uint64)
        gen = np.random.Generator(np.random.Philox(key=key))
        return gen.integers(0, 1 << 32, size=n, dtype=np.uint32)
    raise SecAggError(f"unknown mask PRG scheme {scheme!r}")


# ---------------------------------------------------------------------------
# Per-round masking
# ---------------------------------------------------------------------------


class RoundMasker:
    """One party's mask state for ONE round attempt.

    Binds the key-agreement plane to a concrete ``(session, stream,
    round)``: derives (and caches) the pair seed toward every active
    peer, expands the party's **net mask** ``Σ_j ±PRG(seed_j)`` (sign by
    sorted-name order, so each pair's keystream appears exactly once
    positive and once negative across the parties), and answers dropout
    recovery with the seeds toward the dropped parties.  A coordinator
    failover re-attempts the round under a fresh (successor-scoped)
    stream, so a fresh masker — and fresh seeds — per attempt.

    ``weight``: this party's integral fold weight (FedAvg example
    count); the masked wire value is ``weight·q + net mask`` so unit-
    weight integer folds reproduce the weighted sum exactly (weighted
    pairwise masks could not cancel: ``w_i·m − w_j·m ≠ 0``).

    ``self_mask`` (quorum rounds): Bonawitz **double-masking** — the
    net mask additionally includes ``PRG(b)`` for a fresh private
    per-round seed ``b`` known only to this party, revealed (via the
    cutoff round trip) only if this party MADE the round's member set.
    This is what protects a deadline-excluded-but-alive straggler:
    dropout recovery necessarily reveals the survivors' pairwise seeds
    toward it — which by symmetry are its own seeds toward them — but
    its late-arriving payload still carries ``PRG(b)``, which nobody
    else ever learns, so it stays uniform ring noise to the
    coordinator.  The all-of-n streaming path runs pairwise-only
    (``self_mask=False``): it has no reveal round trip, and no seed is
    ever disclosed there.

    :meth:`prefetch` expands the net mask on a background thread — the
    keystream depends on nothing round-specific beyond the seeds, so
    generation overlaps local training / the wire instead of sitting on
    the round's critical path.
    """

    def __init__(
        self,
        keys: KeyAgreement,
        party: str,
        peers: Sequence[str],
        *,
        session: str,
        stream: str,
        round_index: int,
        weight: int = 1,
        self_mask: bool = False,
    ) -> None:
        if keys is None:
            raise SecAggError(
                "secure aggregation needs the transport's key-agreement "
                "plane (TransportManager.secagg_keys) — this transport "
                "has none"
            )
        self._keys = keys
        self.party = str(party)
        self.peers = sorted(str(p) for p in peers)
        if self.party in self.peers:
            raise SecAggError("a party cannot be its own mask peer")
        self.session = str(session)
        self.stream = str(stream)
        self.round_index = int(round_index)
        self.weight = int(weight)
        if self.weight < 0:
            raise SecAggError(
                f"masked folds need a non-negative integral weight, got "
                f"{weight!r}"
            )
        # The self-mask seed is PRIVATE randomness (never derived from
        # shared state, never equal across attempts) — a failover
        # attempt builds a fresh masker and so a fresh b.
        self._self_seed: Optional[bytes] = (
            os.urandom(32) if self_mask else None
        )
        self._seeds: Dict[str, bytes] = {}
        self._net: Optional[np.ndarray] = None
        self._net_thread: Optional[threading.Thread] = None
        self._net_err: Optional[BaseException] = None
        self._lock = threading.Lock()

    def seed_for(self, peer: str) -> bytes:
        """The (cached) pair seed toward ``peer`` for this round."""
        with self._lock:
            s = self._seeds.get(peer)
        if s is None:
            s = self._keys.pair_seed(
                peer, session=self.session, stream=self.stream,
                round_index=self.round_index,
            )
            with self._lock:
                self._seeds[peer] = s
        return s

    def _compute_net(self, n: int) -> np.ndarray:
        net = np.zeros(n, np.uint32)
        if self._self_seed is not None:
            net += prg_mask(self._self_seed, n, self._keys.prg_scheme)
        for peer in self.peers:
            ks = prg_mask(self.seed_for(peer), n, self._keys.prg_scheme)
            if self.party < peer:
                net += ks  # uint32 wraps mod 2**32 — the ring we want
            else:
                net -= ks
        return net

    def self_seed_hex(self) -> str:
        """The self-mask seed, hex — revealed ONLY by a party that made
        the member set (its contribution is in the sum, so its ``b``
        must be subtracted); an excluded party never discloses it."""
        if self._self_seed is None:
            raise SecAggError(
                "this masker carries no self-mask (self_mask=False — "
                "the all-of-n streaming path)"
            )
        return self._self_seed.hex()

    def prefetch(self, n: int) -> None:
        """Start expanding the net mask on a background thread (no-op if
        already running/done).  :meth:`net_mask` joins it."""
        with self._lock:
            if self._net is not None or self._net_thread is not None:
                return

            def _run():
                try:
                    net = self._compute_net(int(n))
                    with self._lock:
                        self._net = net
                # fedlint: disable=FED004 — transferred, not swallowed: the error re-raises from net_mask() on the round's thread
                except BaseException as e:
                    self._net_err = e

            self._net_thread = threading.Thread(
                target=_run, name="rayfed-secagg-prg", daemon=True
            )
            self._net_thread.start()

    def net_mask(self, n: int) -> np.ndarray:
        """This party's net mask for an ``n``-element code buffer
        (uint32; add it to ``weight·q`` mod 2³²)."""
        n = int(n)
        with self._lock:
            th = self._net_thread
        if th is not None:
            th.join()
            if self._net_err is not None:
                raise self._net_err
        with self._lock:
            if self._net is not None:
                if self._net.size != n:
                    raise SecAggError(
                        f"prefetched mask covers {self._net.size} "
                        f"elements, round needs {n}"
                    )
                return self._net
        net = self._compute_net(n)
        with self._lock:
            self._net = net
        return net

    def recovery_seeds(self, dropped: Sequence[str]) -> Dict[str, str]:
        """This survivor's pairwise seeds toward the dropped parties —
        the recovery reply body (hex-encoded; coordinator-only, scoped
        to THIS round's seeds)."""
        out: Dict[str, str] = {}
        for j in dropped:
            j = str(j)
            if j == self.party:
                continue
            if j not in self.peers:
                raise SecAggError(
                    f"recovery asked for seeds toward {j!r}, which was "
                    f"not a mask peer this round ({self.peers})"
                )
            out[j] = self.seed_for(j).hex()
        return out


def _seed_from_hex(hexseed: str, who: str, what: str) -> bytes:
    try:
        return bytes.fromhex(hexseed)
    except (ValueError, TypeError) as e:
        raise SecAggError(
            f"malformed {what} from {who!r}: not a hex seed ({e})"
        ) from None


def mask_correction(
    survivor_seeds: Dict[str, Dict[str, str]],
    dropped: Sequence[str],
    n: int,
    prg_scheme: Optional[str] = None,
    members: Optional[Sequence[str]] = None,
    self_seeds: Optional[Dict[str, str]] = None,
) -> np.ndarray:
    """The mask correction of a quorum round's cutoff (coordinator).

    ``survivor_seeds``: ``{survivor: {dropped party: seed hex}}`` — one
    entry per member of the pinned set (the coordinator contributes its
    own seeds without a wire hop).  The folded accumulator holds, beyond
    ``Σ_{i∈M} w_i·q_i``, the residual ``Σ_{i∈M} Σ_{j∈D} ±PRG(seed_ij)``
    (each survivor's masks toward the dropped never met their negatives)
    — this function expands exactly that residual (uint32, mod 2³²) for
    the aggregator to SUBTRACT before the finalize rescale.

    ``self_seeds``: ``{member: self-mask seed hex}`` (double-masking,
    see :class:`RoundMasker`) — each member's ``PRG(b_i)`` rides its
    folded contribution and is added to the correction here; a dropped
    party's ``b`` is neither needed (its contribution was not folded)
    nor ever revealed, which is what keeps its late payload noise.

    Raises loudly when any (survivor, dropped) pair's seed or member
    self-seed is missing, and — when ``members`` is given — when the
    survivor set does not cover the pinned member set exactly: an
    incomplete (or mis-keyed) correction would silently corrupt the
    round.
    """
    if members is not None:
        want = {str(p) for p in members}
        have = {str(p) for p in survivor_seeds}
        if have != want:
            raise SecAggError(
                f"mask recovery incomplete: seeds collected from "
                f"{sorted(have)} but the pinned member set is "
                f"{sorted(want)} — cannot finalize the round"
            )
    dropped = sorted(str(j) for j in dropped)
    corr = np.zeros(int(n), np.uint32)
    recovered = 0
    for i in sorted(survivor_seeds):
        seeds = survivor_seeds[i]
        for j in dropped:
            if j == i:
                continue
            hexseed = seeds.get(j)
            if not hexseed:
                raise SecAggError(
                    f"mask recovery incomplete: survivor {i!r} supplied "
                    f"no seed toward dropped party {j!r} — cannot "
                    f"finalize the round"
                )
            ks = prg_mask(
                _seed_from_hex(hexseed, i, f"recovery seed toward {j!r}"),
                int(n), prg_scheme,
            )
            if i < j:
                corr += ks
            else:
                corr -= ks
            recovered += 1
    if self_seeds is not None:
        for i in sorted({str(p) for p in (members or self_seeds)}):
            b = self_seeds.get(i)
            if not b:
                raise SecAggError(
                    f"mask recovery incomplete: member {i!r} supplied "
                    f"no self-mask seed — cannot finalize the round"
                )
            corr += prg_mask(
                _seed_from_hex(b, i, "self-mask seed"), int(n),
                prg_scheme,
            )
    SECAGG_STATS["recovered_seeds"] += recovered
    return corr


# ---------------------------------------------------------------------------
# Recovery control messages (cross-party contract — fingerprinted by
# tool/check_wire_format.py like the ring stripe manifest: payload-level
# schemas, no frame-layout change)
# ---------------------------------------------------------------------------


def make_recovery_request(
    members: Sequence[str], dropped: Sequence[str]
) -> Dict[str, Any]:
    """The coordinator's post-cutoff announcement to every active party:
    the pinned member set and the dropped parties whose masks need
    recovery (empty ``dr`` = nothing to recover; survivors just proceed
    to the result broadcast).  Single producer of the schema."""
    return {
        "v": SECAGG_VERSION,
        "m": sorted(str(p) for p in members),
        "dr": sorted(str(p) for p in dropped),
    }


def make_recovery_reply(
    party: str, seeds: Dict[str, str], self_seed: str
) -> Dict[str, Any]:
    """One member's cutoff reply: its pairwise seeds toward the dropped
    parties (hex; empty dict when nobody dropped) and its OWN self-mask
    seed ``b`` (revealed because this party made the member set — its
    contribution is in the sum).  Single producer of the schema."""
    return {
        "v": SECAGG_VERSION,
        "p": str(party),
        "sd": dict(seeds),
        "b": str(self_seed),
    }


def check_recovery_message(msg: Any, kind: str) -> Dict[str, Any]:
    """Validate a received recovery request/reply (version + shape);
    raises naming the problem instead of KeyError-ing mid-recovery."""
    if not isinstance(msg, dict):
        raise SecAggError(f"malformed secagg {kind}: {type(msg).__name__}")
    try:
        ver = int(msg.get("v", 0))
    except (TypeError, ValueError):
        raise SecAggError(
            f"malformed secagg {kind}: non-integer version "
            f"{msg.get('v')!r}"
        ) from None
    if ver > SECAGG_VERSION:
        raise SecAggError(
            f"secagg {kind} uses schema v{msg.get('v')}; this party "
            f"speaks up to v{SECAGG_VERSION}"
        )
    want = ("m", "dr") if kind == "request" else ("p", "sd", "b")
    for k in want:
        if k not in msg:
            raise SecAggError(f"secagg {kind} is missing field {k!r}")
    return msg


# ---------------------------------------------------------------------------
# Masked wire form + codec
# ---------------------------------------------------------------------------


class MaskedCodeTree(QuantizedPackedTree):
    """Wire form of a masked contribution: ``weight·q + net mask`` as an
    i32 buffer, with the round grid's descriptor riding along (the fold
    layer still verifies the grid fingerprint before folding).

    Deliberately NOT decodable: a masked buffer is uniform ring noise
    without the peers' contributions — :meth:`dequantize`/:meth:`unpack`
    raise instead of silently rescaling garbage.  Fold with a masked
    :class:`~rayfed_tpu.fl.streaming.StreamingAggregator`, whose unit-
    weight integer fold cancels the masks bit-exactly.
    """

    __slots__ = ()

    def dequantize(self, out_dtype: Any = np.float32,
                   ref: Optional[Any] = None):
        raise SecAggError(
            "a MaskedCodeTree is uniform ring noise on its own — only "
            "the masked FOLD (StreamingAggregator(masked=True)) can "
            "cancel the pairwise masks; there is nothing to dequantize"
        )

    def unpack(self, dtype: Any = None):
        raise SecAggError(
            "a MaskedCodeTree cannot be unpacked — see dequantize"
        )

    def __reduce__(self):
        return (
            MaskedCodeTree,
            (self.buf, self.scales, self.zps, self.passthrough,
             self.spec, self.gmeta),
        )

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"MaskedCodeTree({self.gmeta.total_elems} masked i32 codes "
            f"on grid fp={self.gmeta.fp:#010x})"
        )


import jax  # noqa: E402  (after the numpy-only machinery, like quantize)

jax.tree_util.register_pytree_node(
    MaskedCodeTree,
    lambda mt: (
        (mt.buf, mt.scales, mt.zps, *mt.passthrough),
        (mt.spec, mt.gmeta),
    ),
    lambda aux, ch: MaskedCodeTree(
        ch[0], ch[1], ch[2], tuple(ch[3:]), aux[0], aux[1]
    ),
)


class MaskedRoundCodec(RoundCodec):
    """The masked sender-side codec discipline: grid quantization (with
    the inherited fingerprint check + error-feedback two-phase commit)
    followed by the fused weight-and-mask step.  Drop-in where a
    :class:`~rayfed_tpu.fl.quantize.RoundCodec` goes — streaming and
    quorum call ``to_wire``/``commit``/``rollback`` identically."""

    __slots__ = ("masker",)

    def __init__(self, grid: Optional[QuantGrid], ref: Optional[Any],
                 scope: Optional[str], masker: RoundMasker) -> None:
        if grid is None:
            raise SecAggError(
                "secure aggregation requires the shared quantization "
                "grid (wire_quant) — masks live in the integer domain"
            )
        super().__init__(grid, ref, scope)
        self.masker = masker

    def to_wire(self, value: Any) -> MaskedCodeTree:
        if isinstance(value, MaskedCodeTree):
            raise SecAggError("contribution is already masked")
        # Overlap the keystream expansion with the quantize kernel.
        self.masker.prefetch(self.grid.total_elems)
        qt = super().to_wire(value)
        if qt.passthrough:
            # Non-float (passthrough) leaves do not live on the packed
            # buffer, so the masks cannot cover them — shipping them in
            # the clear would silently break the "uniform ring noise"
            # guarantee for exactly the leaves the caller forgot about.
            # Loud exclusion, like every other composition gap.
            raise SecAggError(
                f"secure aggregation covers the packed float buffer "
                f"only, but this update carries "
                f"{len(qt.passthrough)} non-float (passthrough) "
                f"leaf(s) that would ship UNMASKED — drop them from "
                f"the update tree (or encode them as floats) before "
                f"masking"
            )
        from rayfed_tpu.fl.fedavg import masked_code_kernel

        mask = self.masker.net_mask(self.grid.total_elems)
        buf = masked_code_kernel()(
            qt.buf, np.int32(self.masker.weight), mask
        )
        SECAGG_STATS["masked_rounds"] += 1
        spec = PackSpec(qt.spec.entries, qt.spec.treedef, MASKED_WIRE_DTYPE)
        return MaskedCodeTree(
            np.asarray(buf), qt.scales, qt.zps, qt.passthrough, spec,
            qt.gmeta,
        )


# ---------------------------------------------------------------------------
# Seed-era in-process primitives (moved from fl/secure.py, which is
# gone)
# ---------------------------------------------------------------------------

_MOD = 2**32


def pairwise_key(group_key: bytes, a: str, b: str, round_num: int) -> bytes:
    """256-bit seed for the (a, b) pair at one round — order-independent.

    The seed-era group-key derivation, kept for the in-process
    :func:`mask_update`/:func:`unmask_sum` primitives.  The transport
    rounds derive their seeds from the HELLO key agreement instead
    (:meth:`~rayfed_tpu.transport.secagg.KeyAgreement.pair_seed`).

    The full digest feeds the mask XOF: truncating to a JAX PRNGKey
    would cap the keyspace at threefry's 64 bits, which an
    honest-but-curious aggregator could brute-force offline against a
    single masked update.
    """
    lo, hi = sorted((a, b))
    lo_b, hi_b = lo.encode(), hi.encode()
    # Length-prefixed components: a '|'-delimited preimage would let
    # names containing '|' collide across pairs (('a','b|c') vs
    # ('a|b','c')), handing one pair another pair's mask seed.
    return hashlib.sha256(
        b"rayfed-secagg|%d:%s|%d:%s|%d|"
        % (len(lo_b), lo_b, len(hi_b), hi_b, round_num)
        + group_key
    ).digest()


def _encode(tree: Any, clip: float, frac_bits: int) -> Any:
    """Float pytree → uint32 fixed-point (two's-complement wrap).

    Values are clipped to ±``clip`` first: fixed-point needs a known
    range, and secure aggregation deployments clip updates anyway (the
    mask hides magnitudes only within the ring).
    """
    import jax.numpy as jnp

    scale = float(2**frac_bits)

    def enc(x):
        x = jnp.clip(x.astype(jnp.float32), -clip, clip)
        # int32 → uint32 astype is the two's-complement embedding into
        # the ring (wraps mod 2³²); clip·2^frac_bits < 2³¹ keeps the
        # int32 exact.  No int64 needed (x64 mode stays off).
        return jnp.round(x * scale).astype(jnp.int32).astype(jnp.uint32)

    return jax.tree_util.tree_map(enc, tree)


def _decode(tree: Any, frac_bits: int) -> Any:
    """uint32 fixed-point sum → float pytree.

    uint32 → int32 astype is the two's-complement read (values ≥ 2³¹
    become negative) — exact while |true sum| < 2³¹, which
    :func:`unmask_sum` guards.
    """
    import jax.numpy as jnp

    scale = float(2**frac_bits)
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.int32).astype(jnp.float32) / scale, tree
    )


def _mask_for(seed: bytes, tree: Any) -> Any:
    """One uint32 mask per element, expanded from the 256-bit pair seed.

    SHAKE-256 as the XOF (domain-separated per leaf index) keeps the
    full seed entropy — unlike JAX's threefry PRNG, whose 64-bit key
    would be the scheme's effective security level.
    """
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    masks = []
    for i, leaf in enumerate(leaves):
        stream = hashlib.shake_256(
            seed + b"|leaf|%d" % i
        ).digest(4 * leaf.size)
        masks.append(
            jnp.asarray(
                np.frombuffer(stream, dtype=np.uint32).reshape(leaf.shape)
            )
        )
    return jax.tree_util.tree_unflatten(treedef, masks)


def mask_update(
    tree: Any,
    *,
    party: str,
    parties: Sequence[str],
    round_num: int,
    group_key: bytes,
    clip: float = 8.0,
    frac_bits: int = 16,
) -> Any:
    """Fixed-point-encode ``tree`` and add this party's pairwise masks.

    The in-process (whole-tree, group-key) primitive — use
    ``run_fedavg_rounds(secure_agg=True)`` for transport rounds, where
    key agreement rides the HELLO handshake and the masks live in the
    shared-grid integer domain instead of a private fixed-point one.

    Returns a uint32 pytree safe to push: without the peers' masked
    updates it is uniformly random in the ring.  ``clip``/``frac_bits``
    must match across parties and in :func:`unmask_sum`.
    """
    if party not in parties:
        raise ValueError(f"party {party!r} not in {list(parties)!r}")
    out = _encode(tree, clip, frac_bits)
    for peer in parties:
        if peer == party:
            continue
        mask = _mask_for(pairwise_key(group_key, party, peer, round_num), out)
        sign = 1 if party < peer else -1
        out = jax.tree_util.tree_map(
            # uint32 arithmetic wraps mod 2^32 — exactly the ring we want.
            (lambda o, m: o + m) if sign > 0 else (lambda o, m: o - m),
            out,
            mask,
        )
    return out


def unmask_sum(
    masked_trees: Sequence[Any], *, frac_bits: int = 16, clip: float = 8.0
) -> Any:
    """Sum all parties' masked updates; masks cancel bit-exactly.

    Returns the float **sum** of the clipped updates (divide by the
    party count for the average).  ``clip`` bounds the representable
    sum: n·clip must stay below 2^(31−frac_bits) or the ring wraps.
    """
    import jax

    n = len(masked_trees)
    if n == 0:
        raise ValueError("unmask_sum needs at least one masked update")
    if n * clip >= float(2 ** (31 - frac_bits)):
        raise ValueError(
            f"{n} parties at clip={clip} overflow the ring at "
            f"frac_bits={frac_bits}; lower frac_bits or clip"
        )
    total = masked_trees[0]
    for t in masked_trees[1:]:
        total = jax.tree_util.tree_map(lambda a, b: a + b, total, t)
    return _decode(total, frac_bits)
