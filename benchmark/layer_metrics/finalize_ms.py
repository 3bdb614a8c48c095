"""The streaming aggregator's ``agg.finalize`` span per round: the one
rescale of the accumulator to the wire form; it ends after the device
finished (``block_until_ready`` on the output buffer)."""

import numpy as np

from benchmark.layer_metrics.wire_send_ms import per_round

NAME, UNIT = "finalize_ms", "ms"
LAYER = "aggregation"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def read(ctx):
    values = per_round(ctx, "agg.finalize")
    return 1e3 * float(np.median(values)) if values else None
