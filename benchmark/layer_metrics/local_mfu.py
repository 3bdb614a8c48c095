"""Model FLOP/s utilization of the local step: FLOPs the model needs per
item (from shapes, by the family's function; recomputation not counted,
windowed attention counted as banded) x items per step / median step
time / the chip's published bf16 peak.  With several parties on one
chip their steps interleave, so a step's span holds a share of the
others' work: this is the utilization a party sees, not the chip's."""

import numpy as np

from benchmark.layer_metrics.local_step_ms import step_seconds

NAME, UNIT = "local_mfu", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "program_span"
CELLS = ["*"]


def read(ctx):
    steps = step_seconds(ctx)
    if not steps or ctx.peaks is None:
        return None
    fam = ctx.family
    flops = fam.flops_per_item() * fam.items_per_step
    return 100.0 * flops / float(np.median(steps)) / ctx.peaks["bf16_flops"]
