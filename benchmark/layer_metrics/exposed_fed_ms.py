"""Per round, the wall time in which no party is inside ``train``: what
the federation (wire, aggregation, the call layer) costs once local
compute is taken out.  Median over the traced rounds."""

import numpy as np

from benchmark.spans import union_length

NAME, UNIT = "exposed_fed_ms", "ms"
LAYER = "fed call layer and round engine"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def read(ctx):
    values = []
    for r in ctx.traced_rounds:
        t0, t1 = ctx.round_edges[r]
        inside = [
            (max(s.t_start, t0), min(s.t_end, t1))
            for s in ctx.spans.all("train") if s.round == r
        ]
        values.append((t1 - t0) - union_length(
            [iv for iv in inside if iv[1] > iv[0]]
        ))
    return 1e3 * float(np.median(values)) if values else None
