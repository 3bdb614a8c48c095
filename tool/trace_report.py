#!/usr/bin/env python
"""Critical-path round reports from a merged flight-recorder timeline.

Input: the JSON of ``fed.trace_collect(...)`` (or any ``{"records":
[...]}`` / bare list of record dicts in ``telemetry.SPAN_FIELDS``
shape).  For every round tag found, the report answers the question the
raw N-party logs cannot: **which party/phase bounded the round wall**.

- *round wall*: the span of the round's record window (earliest start →
  latest end over records tagged with that round).
- *critical path*: greedy backward walk from the round's end — every
  instant is attributed to the span covering it that extends furthest
  back, so the chain is the sequence of (party, phase) segments that
  actually bounded the wall.  ``driver.round`` spans are excluded from
  the chain (they ARE the wall) but contribute synthesized
  ``driver.local`` segments from their ``local_s`` breakdown, so local
  compute competes with wire/aggregation spans for blame.  Stretches
  no span covers show up honestly as ``(untraced)``.
- *straggler*: the party whose ``driver.round`` breakdown carries the
  largest ``local_s``.
- *events*: cutoffs, failovers, handovers and chaos injections tagged
  with the round — plus untagged ones whose timestamp falls inside the
  round window (an injected partition appears next to the failover it
  caused).
- *staleness*: buffered-async rounds (fl.async_rounds) tag each model
  version as a round and stamp the decay attribution into their
  ``async.fold`` span details; the report aggregates them per version —
  staleness histogram, pushed-vs-folded weight, and the share each
  peer's contributions lost to the integer shift decay.

The driver's own measured wall (``driver.round`` duration) reconciles
with the report's window within tolerance (:func:`round_report`'s
``wall_agrees``; ``tests/test_quorum.py::test_quorum_coordinator_crash_failover``
holds it on a merged cross-party trace).

Usage::

    python -m tool.trace_report trace.json [--tolerance 0.25] [--round R]

where ``trace.json`` was written e.g. by::

    json.dump(fed.trace_collect(), open("trace.json", "w"))
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

_EPS = 1e-9

# Zero-duration record families surfaced in the per-round event list.
_EVENT_PREFIXES = ("chaos.", "quorum.", "blob.failover", "ring.abort",
                   "hier.abort", "hier.region_cutoff")


def _hier_level(phase: str) -> Optional[str]:
    """Tree-level attribution label for a ``hier.*`` phase span, or
    None for non-hierarchy phases.

    The hierarchy driver stamps the level into the span name itself
    (``hier.up.l2`` = the fold INTO level-2 interior nodes,
    ``hier.down.l1`` = the fan-down FROM level-1 coordinators), so a
    slow deep-tree round localizes to a tree level straight from the
    report — no per-party log digging.
    Leaf phases (``region_rs``/``region_gather``) map to ``leaf``; the
    in-region broadcast phases (``down.relay``/``down.fan``/
    ``broadcast``) map to ``leaf.down``; everything else (``commit``)
    keeps its own name.
    """
    if not phase.startswith("hier."):
        return None
    name = phase[len("hier."):]
    if name in ("region_rs", "region_gather"):
        return "leaf"
    if name in ("down.relay", "down.fan", "broadcast"):
        return "leaf.down"
    for stem in ("up.l", "down.l"):
        if name.startswith(stem):
            lv = name[len(stem):]
            if lv.isdigit():
                return f"l{lv}.{'up' if stem == 'up.l' else 'down'}"
    return name


def hier_level_attribution(
    chain: Sequence[Dict[str, Any]],
) -> Dict[str, float]:
    """Critical-path seconds per tree level: ``hier.*`` chain segments
    grouped by :func:`_hier_level` label, sorted by descending blame."""
    levels: Dict[str, float] = {}
    for seg in chain:
        label = _hier_level(str(seg.get("phase", "")))
        if label is not None:
            levels[label] = levels.get(label, 0.0) + float(seg["dur_s"])
    return dict(
        sorted(levels.items(), key=lambda kv: kv[1], reverse=True)
    )


def staleness_attribution(
    recs: Sequence[Dict[str, Any]],
) -> Dict[str, Any]:
    """Aggregate the buffered-async fold spans (``async.fold``) in a
    record window: how stale the folded contributions were and how much
    pushed weight the integer shift decay cost, overall and per peer.
    Empty dict when the window holds no async folds (synchronous
    rounds)."""
    hist: Dict[int, int] = {}
    w_in = 0
    w_folded = 0
    peers: Dict[str, Dict[str, Any]] = {}
    folds = 0
    for r in recs:
        if str(r.get("phase")) != "async.fold":
            continue
        d = r.get("detail") or {}
        if "staleness" not in d:
            continue
        folds += 1
        s = int(d.get("staleness") or 0)
        w = int(d.get("weight") or 0)
        we = int(d.get("w_eff") or 0)
        hist[s] = hist.get(s, 0) + 1
        w_in += w
        w_folded += we
        p = peers.setdefault(
            str(r.get("peer")),
            {"folds": 0, "staleness_sum": 0, "weight": 0, "w_eff": 0},
        )
        p["folds"] += 1
        p["staleness_sum"] += s
        p["weight"] += w
        p["w_eff"] += we
    if not folds:
        return {}
    return {
        "folds": folds,
        "staleness_hist": dict(sorted(hist.items())),
        "weight_pushed": w_in,
        "weight_folded": w_folded,
        "decayed_frac": (
            (w_in - w_folded) / w_in if w_in else 0.0
        ),
        "peers": peers,
    }


def load_records(doc: Any) -> List[Dict[str, Any]]:
    """Record dicts from a ``fed.trace_collect`` result, a
    ``{"records": [...]}`` wrapper, or a bare list."""
    if isinstance(doc, dict):
        doc = doc.get("records", [])
    if not isinstance(doc, list):
        raise ValueError(
            "expected a trace_collect result, {'records': [...]}, or a "
            "list of record dicts"
        )
    return [dict(r) for r in doc]


def _t_end(rec: Dict[str, Any]) -> float:
    return float(rec["t_start"]) + float(rec.get("dur_s") or 0.0)


def rounds_of(records: Sequence[Dict[str, Any]]) -> List[int]:
    return sorted({
        int(r["round"]) for r in records if r.get("round") is not None
    })


def round_records(
    records: Sequence[Dict[str, Any]], rnd: int,
) -> List[Dict[str, Any]]:
    """The round's tagged records, plus untagged EVENT records whose
    timestamp falls inside the tagged window (chaos wire faults and
    health events carry no round tag but belong on the round's page)."""
    tagged = [r for r in records if r.get("round") == rnd]
    if not tagged:
        return []
    t0 = min(float(r["t_start"]) for r in tagged)
    t1 = max(_t_end(r) for r in tagged)
    out = list(tagged)
    for r in records:
        if r.get("round") is not None:
            continue
        phase = str(r.get("phase", ""))
        if not phase.startswith(_EVENT_PREFIXES):
            continue
        if t0 - _EPS <= float(r["t_start"]) <= t1 + _EPS:
            out.append(r)
    out.sort(key=lambda r: float(r["t_start"]))
    return out


def _chain_spans(recs: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Candidate spans for the critical-path walk: every positive-
    duration record except ``driver.round`` (the wall itself), plus a
    synthesized ``driver.local`` span per driver record (its
    ``local_s`` breakdown), so local compute competes for blame."""
    spans: List[Dict[str, Any]] = []
    for r in recs:
        dur = float(r.get("dur_s") or 0.0)
        if dur <= 0.0:
            continue
        if str(r.get("phase")) == "driver.round":
            local_s = float((r.get("detail") or {}).get("local_s") or 0.0)
            if local_s > 0.0:
                spans.append({
                    "party": r.get("party"), "phase": "driver.local",
                    "t_start": float(r["t_start"]), "dur_s": local_s,
                })
            continue
        spans.append({
            "party": r.get("party"), "phase": str(r.get("phase")),
            "t_start": float(r["t_start"]), "dur_s": dur,
        })
    return spans


def critical_path(
    recs: Sequence[Dict[str, Any]],
) -> List[Dict[str, Any]]:
    """Greedy backward walk over the round window: attribute every
    instant to the covering span that extends furthest back.  Returns
    chronological segments ``{party, phase, dur_s}`` summing (with
    ``(untraced)`` gaps) to the round wall."""
    if not recs:
        return []
    t0 = min(float(r["t_start"]) for r in recs)
    t1 = max(_t_end(r) for r in recs)
    spans = _chain_spans(recs)
    chain: List[Dict[str, Any]] = []

    def _push(party: Optional[str], phase: str, dur: float) -> None:
        if dur <= _EPS:
            return
        last = chain[-1] if chain else None
        if last and last["party"] == party and last["phase"] == phase:
            last["dur_s"] += dur
        else:
            chain.append({"party": party, "phase": phase, "dur_s": dur})

    cursor = t1
    while cursor > t0 + _EPS:
        covering = [
            s for s in spans
            if s["t_start"] < cursor - _EPS
            and s["t_start"] + s["dur_s"] >= cursor - 1e-6
        ]
        if covering:
            seg = min(covering, key=lambda s: s["t_start"])
            _push(seg["party"], seg["phase"], cursor - seg["t_start"])
            cursor = seg["t_start"]
            continue
        below = [s for s in spans if s["t_start"] + s["dur_s"] < cursor]
        if not below:
            _push(None, "(untraced)", cursor - t0)
            break
        nxt = max(below, key=lambda s: s["t_start"] + s["dur_s"])
        _push(None, "(untraced)", cursor - (nxt["t_start"] + nxt["dur_s"]))
        cursor = nxt["t_start"] + nxt["dur_s"]
    chain.reverse()
    return chain


def round_report(
    records: Sequence[Dict[str, Any]], tolerance: float = 0.25,
) -> Dict[int, Dict[str, Any]]:
    """Per-round analysis keyed by round tag.

    Each value carries ``wall_s`` (the record window), ``driver_wall_s``
    (the slowest party's own ``driver.round`` measurement, None when no
    driver span was collected), ``wall_agrees`` (the two reconcile
    within ``tolerance``, relative), ``chain`` (critical-path
    segments), ``hier_levels`` (critical-path seconds per hierarchy
    tree level, empty for non-hierarchy rounds), ``staleness``
    (:func:`staleness_attribution` over the window — buffered-async
    versions only), ``bounded_by`` (the chain's largest segment),
    ``straggler`` (largest ``local_s``), and ``events``."""
    out: Dict[int, Dict[str, Any]] = {}
    records = list(records)
    for rnd in rounds_of(records):
        recs = round_records(records, rnd)
        if not recs:
            continue
        t0 = min(float(r["t_start"]) for r in recs)
        wall = max(_t_end(r) for r in recs) - t0
        drivers = [
            r for r in recs if str(r.get("phase")) == "driver.round"
        ]
        driver_wall = (
            max(float(r["dur_s"]) for r in drivers) if drivers else None
        )
        agrees = True
        if driver_wall is not None and wall > 0.0:
            agrees = (
                abs(wall - driver_wall) <= tolerance * max(wall, driver_wall)
            )
        chain = critical_path(recs)
        bounded = max(chain, key=lambda s: s["dur_s"]) if chain else None
        straggler = None
        local_best = 0.0
        for r in drivers:
            local_s = float((r.get("detail") or {}).get("local_s") or 0.0)
            if local_s > local_best:
                local_best, straggler = local_s, r.get("party")
        events = [
            r for r in recs
            if str(r.get("phase", "")).startswith(_EVENT_PREFIXES)
            and not float(r.get("dur_s") or 0.0)
        ]
        out[rnd] = {
            "wall_s": wall,
            "driver_wall_s": driver_wall,
            "wall_agrees": agrees,
            "chain": chain,
            "hier_levels": hier_level_attribution(chain),
            "staleness": staleness_attribution(recs),
            "bounded_by": bounded,
            "straggler": straggler,
            "straggler_local_s": local_best,
            "parties": sorted({
                str(r.get("party")) for r in recs
                if r.get("party") is not None
            }),
            "events": events,
        }
    return out


def format_report(
    records: Sequence[Dict[str, Any]], tolerance: float = 0.25,
    only_round: Optional[int] = None,
) -> str:
    rep = round_report(records, tolerance=tolerance)
    if not rep:
        return "no round-tagged records in this trace\n"
    lines: List[str] = []
    for rnd, info in sorted(rep.items()):
        if only_round is not None and rnd != only_round:
            continue
        drv = info["driver_wall_s"]
        drv_txt = (
            f"driver {drv * 1e3:.1f} ms, "
            f"{'agrees' if info['wall_agrees'] else 'DISAGREES'}"
            if drv is not None else "no driver span"
        )
        lines.append(
            f"round {rnd}  wall {info['wall_s'] * 1e3:.1f} ms ({drv_txt})"
            f"  parties={','.join(info['parties'])}"
        )
        if info["bounded_by"] is not None:
            b = info["bounded_by"]
            lines.append(
                f"  bounded by {b['party'] or '?'} · {b['phase']} "
                f"({b['dur_s'] * 1e3:.1f} ms, "
                f"{100.0 * b['dur_s'] / max(info['wall_s'], _EPS):.0f}% "
                f"of wall)"
            )
        if info["straggler"] is not None:
            lines.append(
                f"  straggler {info['straggler']} "
                f"(local {info['straggler_local_s'] * 1e3:.1f} ms)"
            )
        if info["hier_levels"]:
            lines.append(
                "  hierarchy levels: " + "  ".join(
                    f"{lbl} {dur * 1e3:.1f} ms"
                    for lbl, dur in info["hier_levels"].items()
                )
            )
        if info.get("staleness"):
            st = info["staleness"]
            lines.append(
                f"  staleness: {st['folds']} folds, hist "
                + " ".join(
                    f"s{s}x{n}"
                    for s, n in st["staleness_hist"].items()
                )
                + f", decayed {100.0 * st['decayed_frac']:.0f}% of "
                f"pushed weight"
            )
            worst = max(
                st["peers"].items(),
                key=lambda kv: kv[1]["staleness_sum"],
            )
            if worst[1]["staleness_sum"]:
                lines.append(
                    f"    stalest peer {worst[0]}: "
                    f"{worst[1]['folds']} folds, mean staleness "
                    f"{worst[1]['staleness_sum'] / worst[1]['folds']:.1f}"
                )
        for seg in info["chain"]:
            lines.append(
                f"    {seg['dur_s'] * 1e3:9.2f} ms  "
                f"{seg['party'] or '-':<12} {seg['phase']}"
            )
        for ev in info["events"]:
            detail = ev.get("detail")
            lines.append(
                f"    ! {ev.get('phase')} party={ev.get('party')} "
                f"peer={ev.get('peer')} outcome={ev.get('outcome')}"
                + (f" {json.dumps(detail, sort_keys=True)}" if detail
                   else "")
            )
    return "\n".join(lines) + "\n"


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "trace", help="JSON file: fed.trace_collect output (or a bare "
        "record list)",
    )
    ap.add_argument(
        "--tolerance", type=float, default=0.25,
        help="relative window-vs-driver wall reconciliation tolerance",
    )
    ap.add_argument(
        "--round", type=int, default=None, dest="only_round",
        help="report only this round",
    )
    args = ap.parse_args(argv)
    with open(args.trace) as f:
        records = load_records(json.load(f))
    sys.stdout.write(
        format_report(
            records, tolerance=args.tolerance, only_round=args.only_round,
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
