"""How unevenly the held experts are loaded: in one expert layer of one
step, the largest held expert's token count over the mean of the held
experts' counts (1 = even; the grouped product's tiles and the chunk
loop's length follow it).  From the ``moe.counts`` records the step
keeps while the recorder is armed; per step the median over its expert
layers, then the median over the traced steps."""

import numpy as np

from benchmark.layer_metrics.expert_mm_roofline import route_records

NAME, UNIT = "moe_load_imbalance", "ratio"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "program_counter"
CELLS = ["trinity-mini-ep8.*"]


def layer_imbalance(counts) -> float:
    mean = float(np.mean(counts))
    return float(np.max(counts)) / mean if mean else float("nan")


def read(ctx):
    per_step = [
        float(np.median([layer_imbalance(row["counts"]) for row in d["layers"]]))
        for d in route_records(ctx)
    ]
    per_step = [v for v in per_step if np.isfinite(v)]
    return float(np.median(per_step)) if per_step else None
