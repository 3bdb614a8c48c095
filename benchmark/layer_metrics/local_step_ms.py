"""One local step, from the trainer's span around it, blocked at its
end.  Median over parties, steps and traced rounds."""

import numpy as np

NAME, UNIT = "local_step_ms", "ms"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "program_span"
CELLS = ["*"]


def step_seconds(ctx):
    rounds = set(ctx.traced_rounds)
    return [
        s.t_end - s.t_start for s in ctx.spans.all("step")
        if s.round in rounds
    ]


def read(ctx):
    steps = step_seconds(ctx)
    return 1e3 * float(np.median(steps)) if steps else None
