"""The feature-composition matrix (ROADMAP item 2's named test).

Table-driven, in-process, one file: every PAIR of flagship round-loop
features is classified as either COMPATIBLE — in which case
``fl.trainer.validate_round_config`` must accept the pair AND the table
names the test that verifies the composition bit-exactly —
or INCOMPATIBLE, in which case validation must raise a LOUD
``ValueError`` at ``run_fedavg_rounds`` entry.  A pair that is neither
(validation silently accepts a combination nobody verifies, or a
combination silently falls back to a different path) FAILS this test:
that is the PR 10 quantized-ring chunk-grid bug class — the config you
asked for is not the config you ran.

``validate_round_config`` is the SINGLE producer of these verdicts (the
driver calls exactly it before touching any runtime), so this test
needs no runtime, no sockets and no party subprocesses.
"""

import itertools

import pytest

from rayfed_tpu.fl import fedac, server_sgd
from rayfed_tpu.fl.trainer import validate_round_config

N_PARTIES = 4
PARTIES = {f"p{i}": None for i in range(N_PARTIES)}


def _checkpointer():
    class _Stub:  # only its presence is validated
        pass

    return _Stub()


# One canonical kwargs fragment per feature.  Fragments must be
# orthogonal: merging any two must express exactly "both features on".
FEATURES = {
    "wire_quant": dict(
        wire_quant="uint8", compress_wire=True, packed_wire=True,
        streaming_agg=True,
    ),
    "quorum": dict(
        quorum=2, round_deadline_s=5.0, compress_wire=True,
        packed_wire=True,
    ),
    "ring": dict(mode="ring", compress_wire=True, packed_wire=True),
    "hierarchy": dict(
        mode="hierarchy", region_size=2, wire_quant="uint8",
        compress_wire=True, packed_wire=True,
    ),
    "secure_agg": dict(
        secure_agg=True, wire_quant="uint8", compress_wire=True,
        packed_wire=True, streaming_agg=True,
    ),
    "server_opt": dict(
        server_opt=fedac(1.0, 3.0, 0.5), compress_wire=True,
        packed_wire=True, streaming_agg=True,
    ),
    "server_opt_legacy": dict(
        server_opt=server_sgd(0.5, 0.9),
    ),
    "overlap": dict(overlap=True, compress_wire=True, packed_wire=True),
    "checkpointer": dict(checkpointer=_checkpointer()),
    "streaming_agg": dict(
        streaming_agg=True, compress_wire=True, packed_wire=True,
    ),
    "error_feedback": dict(
        error_feedback=True, compress_wire=True, packed_wire=True,
    ),
    "sample": dict(sample=2),
    "secagg_quorum_base": None,  # placeholder (see merge rules below)
}
del FEATURES["secagg_quorum_base"]

# Merge conflicts between fragments that set the same key differently:
# mode can only take one value; streaming_agg conflicts with ring /
# hierarchy topologies (its own exclusion is part of the matrix).
def _merge(names, a: dict, b: dict):
    merged = dict(a)
    for k, v in b.items():
        if k in merged and merged[k] != v:
            if k == "wire_quant":
                merged[k] = v  # both uint8 in practice
                continue
            return None  # structurally unmergeable (e.g. two modes)
        merged[k] = v
    if (
        merged.get("mode") in ("ring", "hierarchy")
        and merged.get("streaming_agg")
        and "streaming_agg" not in names
    ):
        # streaming_agg=True is only the wire_quant/server_opt
        # fragments' default CARRIER topology; when the pair names an
        # explicit mode, that mode is the carrier — e.g. ring x
        # wire_quant means the QUANTIZED RING, not ring + streaming.
        del merged["streaming_agg"]
    return merged


# The verdict table.  Key: frozenset of the two feature names.
# Value: ("ok", "<where the composition is verified bit-exactly>") or
# ("raise", "<substring of the loud ValueError>").  Every unordered
# pair of FEATURES must appear — a missing entry fails the test, so a
# future feature cannot ship without classifying its row.
OK = "ok"
RAISE = "raise"
VERDICTS = {
    # --- wire_quant row ---------------------------------------------------
    ("wire_quant", "quorum"): (OK, "tests/test_secagg.py multiproc parity (quantized-quorum == quantized-streaming) + test_quantized_agg.py::test_quorum_subset_refold_bitexact"),
    ("wire_quant", "ring"): (OK, "tests/test_ring.py quantized-gather recode identity (PR 12)"),
    ("wire_quant", "hierarchy"): (OK, "tests/test_hierarchy.py N=4 byte-identity vs flat"),
    ("wire_quant", "secure_agg"): (OK, "tests/test_secagg.py stream_plain == stream_secure bytes"),
    ("wire_quant", "server_opt"): (OK, "tests/test_server_opt.py::test_quantized_downlink_after_step_parity"),
    ("wire_quant", "server_opt_legacy"): (RAISE, "wire_quant is incompatible with"),
    ("wire_quant", "overlap"): (OK, "tests/test_overlap.py::test_overlap_quant_and_server_opt_compositions quantized-overlap RoundCodec replay (unified staleness recurrence: the corrected contribution's delta IS the local displacement)"),
    ("wire_quant", "checkpointer"): (OK, "tests/test_quorum.py::test_quorum_checkpoint_restore_roundtrip (quantized welcomes carry the grid delta)"),
    ("wire_quant", "streaming_agg"): (OK, "tests/test_quantized_agg.py::test_streaming_integer_fold_bitexact_adversarial_order"),
    ("wire_quant", "error_feedback"): (RAISE, "wire_quant is incompatible with"),
    ("wire_quant", "sample"): (OK, "sampled quantized rounds ride the coordinator topology; tests/test_streaming_agg.py wire_quant e2e (full-set sample)"),
    # --- quorum row -------------------------------------------------------
    ("quorum", "ring"): (OK, "tests/test_quorum.py ring-mode fallback equality (quorum ring aborts re-aggregate with the cutoff)"),
    ("quorum", "hierarchy"): (OK, "tests/test_quorum.py quorum x hierarchy parity child (zero fallbacks, cross-party byte agreement)"),
    ("quorum", "secure_agg"): (OK, "tests/test_secagg.py quorum_secure == quorum_plain bytes + chaos e2e mask recovery"),
    ("quorum", "server_opt"): (OK, "tests/test_server_opt.py::test_quorum_subset_refold_feeds_step_bitexact"),
    ("quorum", "server_opt_legacy"): (RAISE, "quorum is incompatible with"),
    ("quorum", "overlap"): (RAISE, "quorum is incompatible with"),
    ("quorum", "checkpointer"): (OK, "tests/test_quorum.py::test_quorum_checkpoint_restore_roundtrip (PR 7)"),
    ("quorum", "streaming_agg"): (OK, "quorum rounds ARE the quorum-aware streaming round; tests/test_quorum.py quorum=n parity"),
    ("quorum", "error_feedback"): (RAISE, "quorum is incompatible with"),
    ("quorum", "sample"): (RAISE, "quorum is incompatible with"),
    # --- ring row ---------------------------------------------------------
    ("ring", "hierarchy"): (None, "structurally unmergeable: one mode= value"),
    ("ring", "secure_agg"): (RAISE, "mode='ring' is a loud exclusion"),
    ("ring", "server_opt"): (OK, "tests/test_server_opt.py::test_controller_replicas_byte_agree_across_rounds (every controller steps the byte-identical assembly)"),
    ("ring", "server_opt_legacy"): (OK, "legacy tree step applies after the assembled broadcast; tests/test_fl_trainer.py server_opt path"),
    ("ring", "overlap"): (OK, "tests/test_overlap.py mid-overlap ring fault -> same-round coordinator fallback equality (PR 4)"),
    ("ring", "checkpointer"): (OK, "classic-loop snapshots are topology-agnostic (params + stamped server state); tests/test_fl_trainer.py resume"),
    ("ring", "streaming_agg"): (RAISE, "mutually exclusive"),
    ("ring", "error_feedback"): (OK, "EF corrects the driver's outgoing compress, orthogonal to the ring fold; tests/test_streaming_agg.py EF-vs-control"),
    ("ring", "sample"): (RAISE, "requires full participation"),
    # --- hierarchy row ----------------------------------------------------
    ("hierarchy", "secure_agg"): (RAISE, "mutually"),
    ("hierarchy", "server_opt"): (OK, "tests/test_server_opt.py::test_hierarchy_regrouped_fold_step_downlink_bitexact"),
    ("hierarchy", "server_opt_legacy"): (RAISE, "wire_quant is incompatible with"),
    ("hierarchy", "overlap"): (RAISE, "overlap=True is incompatible with mode='hierarchy'"),
    ("hierarchy", "checkpointer"): (OK, "hierarchy rides the classic/quorum loops whose snapshots are topology-agnostic; tests/test_quorum.py restore"),
    ("hierarchy", "streaming_agg"): (RAISE, "mutually"),
    ("hierarchy", "error_feedback"): (RAISE, "wire_quant is incompatible with"),
    ("hierarchy", "sample"): (RAISE, "full participation"),
    # --- secure_agg row ---------------------------------------------------
    ("secure_agg", "server_opt"): (RAISE, "packed server_opt is incompatible with"),
    ("secure_agg", "server_opt_legacy"): (RAISE, "wire_quant is incompatible with"),
    ("secure_agg", "overlap"): (RAISE, "overlap=True is incompatible with secure_agg"),
    ("secure_agg", "checkpointer"): (OK, "secure rounds ride the quorum/streaming loops; tests/test_secagg.py trainer validation + quorum snapshot machinery"),
    ("secure_agg", "streaming_agg"): (OK, "tests/test_secagg.py stream_secure == stream_plain bytes"),
    ("secure_agg", "error_feedback"): (RAISE, "wire_quant is incompatible with"),
    ("secure_agg", "sample"): (RAISE, "mutually exclusive"),
    # --- server_opt (packed) row ------------------------------------------
    ("server_opt", "server_opt_legacy"): (None, "one server_opt= argument"),
    ("server_opt", "overlap"): (OK, "tests/test_overlap.py::test_overlap_quant_and_server_opt_compositions step/resync bit-exact replay (the step consumes the mean one-round-stale displacement)"),
    ("server_opt", "checkpointer"): (OK, "tests/test_server_opt.py::test_checkpoint_state_roundtrip + ::test_snapshot_server_opt_guard_matrix"),
    ("server_opt", "streaming_agg"): (OK, "tests/test_streaming_agg.py server_opt e2e leg + tests/test_server_opt.py downlink parity"),
    ("server_opt", "error_feedback"): (RAISE, "packed server_opt is incompatible with"),
    ("server_opt", "sample"): (RAISE, "packed server_opt is incompatible with"),
    # --- legacy server_opt row --------------------------------------------
    ("server_opt_legacy", "overlap"): (RAISE, "overlap=True is incompatible with"),
    ("server_opt_legacy", "checkpointer"): (OK, "tests/test_fl_trainer.py checkpoint resume with server state (seed-era behavior, now stamped)"),
    ("server_opt_legacy", "streaming_agg"): (OK, "legacy step applies to the f32 streaming aggregate; tests/test_fl_trainer.py"),
    ("server_opt_legacy", "error_feedback"): (OK, "both force the f32 aggregate; tests/test_fl_trainer.py EF path"),
    ("server_opt_legacy", "sample"): (OK, "legacy step consumes the sampled subset mean (seed-era behavior); tests/test_fl_trainer.py sampling"),
    # --- overlap row ------------------------------------------------------
    ("overlap", "checkpointer"): (RAISE, "overlap=True is incompatible with"),
    ("overlap", "streaming_agg"): (OK, "overlap's comms lane aggregates via streaming_aggregate; tests/test_overlap.py DGA bit-exact replay"),
    ("overlap", "error_feedback"): (RAISE, "overlap=True is incompatible with"),
    ("overlap", "sample"): (RAISE, "overlap=True is incompatible with"),
    # --- checkpointer row -------------------------------------------------
    ("checkpointer", "streaming_agg"): (OK, "classic-loop snapshot/restore is aggregation-agnostic; tests/test_fl_trainer.py resume"),
    ("checkpointer", "error_feedback"): (OK, "EF residual deliberately not snapshotted (one round of wire correction); tests/test_fl_trainer.py"),
    ("checkpointer", "sample"): (OK, "deterministic per-round draw is a pure function of (seed, round); tests/test_transport_pipeline.py sampling determinism"),
    # --- streaming_agg row ------------------------------------------------
    ("streaming_agg", "error_feedback"): (OK, "both require the packed wire; tests/test_streaming_agg.py EF-vs-control convergence"),
    ("streaming_agg", "sample"): (OK, "sampled rounds stream over the coordinator topology; tests/test_fl_trainer.py sampling"),
    # --- error_feedback row -----------------------------------------------
    ("error_feedback", "sample"): (OK, "orthogonal (driver-side residual vs participation draw); tests/test_fl_trainer.py"),
}


def _verdict(a, b):
    return VERDICTS.get((a, b)) or VERDICTS.get((b, a))


def test_every_pair_is_classified():
    """No silent gap: every unordered feature pair has a row."""
    missing = [
        (a, b)
        for a, b in itertools.combinations(sorted(FEATURES), 2)
        if _verdict(a, b) is None and _verdict(a, b) != (None,)
        and (VERDICTS.get((a, b)) or VERDICTS.get((b, a))) is None
    ]
    assert not missing, f"unclassified feature pairs: {missing}"


@pytest.mark.parametrize(
    "a,b",
    list(itertools.combinations(sorted(FEATURES), 2)),
    ids=lambda v: str(v),
)
def test_pairwise_composition(a, b):
    verdict = _verdict(a, b)
    assert verdict is not None, f"({a}, {b}) missing from VERDICTS"
    kind, detail = verdict
    merged = _merge({a, b}, FEATURES[a], FEATURES[b])
    if kind is None:
        # Structurally unmergeable (two mode= values, two server_opt=
        # arguments): there is no single config expressing the pair.
        assert merged is None or a == "server_opt" or b == "server_opt", (
            a, b, merged,
        )
        return
    assert merged is not None, (
        f"fragments for ({a}, {b}) would not merge but the table says "
        f"{kind!r}"
    )
    if kind == OK:
        # Verified composition: validation accepts it, and the table
        # names where its bit-exactness (or equivalence) is asserted.
        assert detail, f"compatible pair ({a}, {b}) names no verifier"
        cfg = validate_round_config(PARTIES, **merged)
        assert isinstance(cfg, dict)
    else:
        with pytest.raises(ValueError, match=_re_escape_frag(detail)):
            validate_round_config(PARTIES, **merged)


def _re_escape_frag(s: str) -> str:
    import re

    return re.escape(s)


def test_singletons_all_validate():
    """Each feature alone must pass validation (the matrix is about
    PAIRS; a broken singleton would poison every row)."""
    for name, frag in FEATURES.items():
        cfg = validate_round_config(PARTIES, **frag)
        assert isinstance(cfg, dict), name


def test_packed_server_opt_requires_packed_wire():
    with pytest.raises(ValueError, match="packed server_opt|requires"):
        validate_round_config(PARTIES, server_opt=fedac())


def test_quorum_ring_quant_triple_composes():
    """quorum x ring x quant (ROADMAP item 1c) — the last loud topology
    exclusion, lifted: the quorum loop derives the round grid on the
    ring's own stripe chunking (the grid chunking IS the stripe grid,
    so ring_aggregate's chunk-match guard holds) and the quorum ring
    arm passes the grid/ref/scope straight into the quantized ring
    fold.  The pairwise table cannot express a triple; this test pins
    it.  Runtime bit-exactness verifier:
    tests/test_quorum.py::test_quorum_full_participation_parity
    (quantized-ring-quorum leg: classic quantized ring == full-quorum
    quantized ring bytes on every controller, zero ring fallbacks)."""
    cfg = validate_round_config(
        PARTIES, quorum=2, round_deadline_s=5.0, mode="ring",
        wire_quant="uint8", compress_wire=True, packed_wire=True,
        ring_chunk_elems=64,
    )
    assert cfg["wire_quant"] == "uint8"


def test_overlap_quant_server_opt_triple_validates():
    """overlap x wire_quant x server_opt: the unified staleness
    recurrence composes both at once — the corrected contribution codes
    on the broadcast-anchored delta grid AND the step consumes the mean
    stale displacement; the pipelined runner drives the identical
    streaming call the synchronous quantized+stepped loop uses.
    Runtime verifier: the combined leg of
    tests/test_overlap.py::test_overlap_quant_and_server_opt_compositions."""
    cfg = validate_round_config(
        PARTIES, overlap=True, wire_quant="uint8", compress_wire=True,
        packed_wire=True, streaming_agg=True,
        server_opt=fedac(1.0, 3.0, 0.5),
    )
    assert cfg["server_opt_kind"] == "packed"
    assert cfg["wire_quant"] == "uint8"


def test_join_ticket_composes_with_server_opt():
    """join_ticket x server_opt was a loud exclusion until the object
    plane landed: welcomes now carry the server-opt spec plus a content
    handle to the replicated state, and the joiner resyncs its replica
    through the pull path.  Bit-exactness verifiers:
    tests/test_objectstore.py::test_welcome_server_opt_state_roundtrip
    (the welcome-carried state decodes byte-identical to the
    coordinator's replica) and the loud spec-mismatch guard
    tests/test_objectstore.py::test_ticket_server_opt_mismatch_is_loud
    (fl.quorum._apply_ticket_server_opt names both sides)."""
    cfg = validate_round_config(
        PARTIES, server_opt=fedac(), compress_wire=True,
        packed_wire=True, quorum=2, round_deadline_s=5.0,
        join_ticket={"round": 3},
    )
    assert cfg["server_opt_kind"] == "packed"
