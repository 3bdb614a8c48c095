"""The streaming aggregator's ``agg.fold`` span per round: first byte
of a contribution to the last block received.  It ends when the bytes
have ARRIVED, not when the device finished folding them (the fold
kernels are enqueued asynchronously): it is the wire window the fold
hides under.  Its ``busy_ms`` detail is host time in the dispatches."""

import numpy as np

from benchmark.layer_metrics.wire_send_ms import per_round

NAME, UNIT = "fold_ms", "ms"
LAYER = "aggregation"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def read(ctx):
    values = per_round(ctx, "agg.fold")
    return 1e3 * float(np.median(values)) if values else None
