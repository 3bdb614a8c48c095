"""The latent expert layers' grouped matrix products against the chip's
bf16 peak.  One event is one grouped product of one chunk of sorted rows
(``grouped_matmul`` scope, the Pallas ``gmm`` kernel, forward or
transposed) of a squared-ReLU expert in the latent width: it must
multiply the rows REALLY assigned to the held experts in that chunk
(from the ``moe.counts`` records; never the chunk's padding rows) by one
``latent x d_ff`` matrix each (1,024 x 2,688 for the up product, its
transpose for the down product), ``2 x rows x latent x d_ff`` FLOPs;
those over the event's duration over the published peak, median over
events.  Computed per event, so no window edge can put more work in the
numerator than time in the denominator; it counts only FLOPs the product
needs, so it cannot read over 100% unless the count is wrong.

The rows of an event are read as ``expert_mm_roofline.py`` reads them
(its helpers, imported): one instruction runs once a layer and chunk of
its scanned group (``layers<a>-<b>`` in its ``op_name``, reversed in the
backward pass), a layer's held assignments the median over the traced
steps' records, the MTP module's expert layer (its own group) among
them."""

import numpy as np

from benchmark.layer_metrics.expert_mm_roofline import (
    event_rows,
    flops_per_event,
    route_records,
)
from benchmark.layer_metrics.moe_step_share import step_events

NAME, UNIT = "latent_expert_mm_roofline", "%"
LAYER = "expert matmul kernel"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["nemotron-3-super-120b-a12b-ep8-d11.*"]


def read(ctx):
    details = route_records(ctx)
    steps, op_names = step_events(ctx)
    if not details or not steps or ctx.peaks is None:
        return None
    held = {}  # layer -> median held assignments a step
    for layer in details[0]["layers"]:
        held[layer["layer"]] = float(np.median([
            sum(row["counts"]) for d in details for row in d["layers"]
            if row["layer"] == layer["layer"]
        ]))
    latent, d_ff = details[0].get("latent"), details[0].get("d_ff")
    if not latent or not d_ff:
        return None
    size = details[0]["chunk_rows"]
    shares = []
    for _, _, ops in steps:
        events: dict = {}  # instruction -> its events' durations, in order
        for s, e, name in ops:
            events.setdefault(name, []).append((e - s) / 1e9)
        for name, durations in events.items():
            rows = event_rows(op_names.get(name, ""), held, size)
            if rows is None or len(rows) != len(durations):
                continue
            shares += [
                flops_per_event(r, latent, d_ff) / d
                for r, d in zip(rows, durations) if r and d > 0
            ]
    if not shares:
        return None
    from benchmark.reduce import log

    log(latent_gmm_events=len(shares))
    return 100.0 * float(np.median(shares)) / ctx.peaks["bf16_flops"]
