"""The share of the untraced rounds' wall time that lies beyond the
median round: ``1 - n x median / sum``.  A stall or a periodic slow
round shows here whatever its frequency, the one in ten that
``fed_items_per_s`` leaves out included; it swings from run to run with
the host (PERF.md, Stalls), which is why it is recorded without a bound
and not judged."""

import numpy as np

NAME, UNIT = "slow_round_share", "%"
LAYER = "fed call layer and round engine"
MOVES = "fed_items_per_s"
SOURCE = "host_clock"
CELLS = ["*"]


def read(ctx):
    rounds = np.asarray(ctx.plain_round_s, dtype=float)
    if rounds.size == 0:
        return None
    return 100.0 * (1.0 - rounds.size * np.median(rounds) / rounds.sum())
