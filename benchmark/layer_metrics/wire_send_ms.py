"""Sum of the flight recorder's ``wire.send`` span durations per round
(all parties), median over the traced rounds.  A span is put in the
round whose interval holds its start."""

import numpy as np

NAME, UNIT = "wire_send_ms", "ms"
LAYER = "transport"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def per_round(ctx, phase):
    sums = {r: 0.0 for r in ctx.traced_rounds}
    hit = False
    for rec in ctx.recorder_records:
        if rec.phase != phase:
            continue
        for r in ctx.traced_rounds:
            t0, t1 = ctx.round_edges[r]
            if t0 <= rec.t_start < t1:
                sums[r] += rec.dur_s
                hit = True
                break
    return list(sums.values()) if hit else []


def read(ctx):
    values = per_round(ctx, "wire.send")
    return 1e3 * float(np.median(values)) if values else None
