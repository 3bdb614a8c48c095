"""What each party's compute waited for the federation, measured inside
the program: the executor's ``task.wait`` span of every ``train`` call
(``detail.name`` ends in ``train``), the time the task spent resolving
its arguments before its body ran.  On the lazy pipelined path the
argument is the aggregate on its way, so this is the wait for its
arrival; where the driver materializes every round (streaming) the
argument is already a value and the wait sits on the driver thread
instead (``exposed_fed_ms``, which stays).  Median over the party-rounds
that start inside the traced rounds."""

import numpy as np

NAME, UNIT = "train_wait_ms", "ms"
LAYER = "fed call layer and round engine"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def read(ctx):
    if not ctx.traced_rounds:
        return None
    t0 = ctx.round_edges[ctx.traced_rounds[0]][0]
    t1 = ctx.round_edges[ctx.traced_rounds[-1]][1]
    waits = [
        rec.dur_s for rec in ctx.recorder_records
        if rec.phase == "task.wait" and t0 <= rec.t_start < t1
        and str((rec.detail or {}).get("name", "")).endswith("train")
    ]
    return 1e3 * float(np.median(waits)) if waits else None
