"""Host time the round engine itself spends packing and quantizing, per
round: the flight recorder's ``fl.pack``, ``fl.unpack`` and
``fl.quant.*`` spans that are the engine's (``detail.parent`` is not
``task.run``: a trainer's own pack and unpack are party compute) and
top-level (a span whose parent is itself ``fl.quant.*`` lies inside its
parent and is not counted twice).  A span belongs to the round whose
interval holds its start; per round the largest party's sum (the
coordinator also recodes the downlink), median over the traced rounds.
Read only where the engine does this work in every traced round: the
lazy pipelined path packs once and unpacks once in a whole call."""

import numpy as np

NAME, UNIT = "driver_host_ms", "ms"
LAYER = "pack and quantize"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def engine_spans(ctx):
    """``(round, record)`` for every top-level pack, unpack or quantize
    span of the engine that starts inside a traced round."""
    out = []
    for rec in ctx.recorder_records:
        if not (rec.phase in ("fl.pack", "fl.unpack")
                or rec.phase.startswith("fl.quant.")):
            continue
        parent = (rec.detail or {}).get("parent") or ""
        if parent == "task.run" or parent.startswith("fl.quant."):
            continue
        for r in ctx.traced_rounds:
            t0, t1 = ctx.round_edges[r]
            if t0 <= rec.t_start < t1:
                out.append((r, rec))
                break
    return out


def read(ctx):
    sums = {}
    for r, rec in engine_spans(ctx):
        key = (r, rec.party)
        sums[key] = sums.get(key, 0.0) + rec.dur_s
    per_round = [
        max((s for (r2, _), s in sums.items() if r2 == r), default=None)
        for r in ctx.traced_rounds
    ]
    if not per_round or any(v is None for v in per_round):
        return None
    return 1e3 * float(np.median(per_round))
