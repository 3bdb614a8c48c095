"""The expert layers' grouped matrix product against the chip's bf16
peak.  One event is one grouped product of one chunk of sorted rows
(``grouped_matmul`` scope, the Pallas ``gmm`` kernel, forward or
transposed): it must multiply the rows REALLY assigned to the held
experts in that chunk (from the ``moe.counts`` records; never
the chunk's padding rows) by one ``d_model x d_ff`` matrix each,
``2 x rows x d_model x d_ff`` FLOPs; those over the event's duration
over the published peak, median over events.  Computed per event, so no
window edge can put more work in the numerator than time in the
denominator; it counts only FLOPs the product needs, so it cannot read
over 100% unless the count is wrong.  (The weights' bytes bound it lower
still at 512 rows an expert: PERF.md section 5.)

The rows of an event: the expert layers of a group are one scanned
body, so one instruction runs once a layer and chunk; its events within
one step are the group's layers in order (``layers1-8`` in its
``op_name``; ascending in the forward pass, descending where the
``op_name`` says ``transpose(jvp(layers...``), each layer's chunks in
order.  A layer's held assignments are the median over the traced
steps' ``moe.counts`` records (they differ by a few percent between
steps and parties; an event cannot be paired with its own step's
record, since two parties' steps interleave on the chip); a step in
which an instruction ran another number of times than those medians
give is left out for that instruction."""

import re

import numpy as np

from benchmark.layer_metrics.moe_step_share import step_events

NAME, UNIT = "expert_mm_roofline", "%"
LAYER = "expert matmul kernel"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["trinity-mini-ep8.*"]

# ".../jvp(layers1-8)/while/body/.../moe.experts/grouped_matmul/jit(gmm)/pallas_call"
KERNEL = re.compile(r"\blayers(\d+)-(\d+)\b.*grouped_matmul.*pallas_call$")
BACKWARD = "transpose(jvp(layers"


def flops_per_event(rows: int, d_model: int, d_ff: int) -> float:
    """Rows of held experts' tokens times one expert matrix each."""
    return 2.0 * rows * d_model * d_ff


def chunk_rows(total: int, chunk: int, size: int) -> int:
    """Really assigned rows in chunk ``chunk`` of ``size`` sorted rows
    when ``total`` assignments are held (they fill the chunks in
    order)."""
    return int(min(max(total - chunk * size, 0), size))


def route_records(ctx):
    """The ``moe.counts`` records' details.  The recorder is armed from
    the first traced round on, so every record is a traced step's."""
    return [
        r.detail for r in ctx.recorder_records
        if r.phase == "moe.counts" and r.detail and "layers" in r.detail
    ]


def event_rows(op_name: str, held: dict, size: int):
    """``[rows really assigned, ...]`` of the events one grouped-product
    instruction leaves in one step, in time order; None for another
    instruction.  ``held``: layer -> held assignments."""
    m = KERNEL.search(op_name)
    if not m:
        return None
    layers = [i for i in range(int(m.group(1)), int(m.group(2)) + 1)
              if i in held]
    if BACKWARD in op_name:
        layers.reverse()
    return [
        chunk_rows(int(held[i]), c, size)
        for i in layers for c in range(max(-(-int(held[i]) // size), 1))
    ]


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
    experts = ctx.family.experts
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
                flops_per_event(r, experts.d_model, experts.d_ff) / d
                for r, d in zip(rows, durations) if r and d > 0
            ]
    if not shares:
        return None
    return 100.0 * float(np.median(shares)) / ctx.peaks["bf16_flops"]
