"""``fl.decompress`` plus ``fl.compress(..., packed=True)`` per
party-round, from the trainer's spans around them, blocked.  Read only
where every party has a chip of its own: on a shared chip a blocked
span waits for the other party's queued step and times that (570 ms in
``lora-2p`` against 3 ms in ``lora-4p-4chip``: PERF.md, PR 23)."""

import numpy as np

NAME, UNIT = "pack_ms", "ms"
LAYER = "pack and quantize"
MOVES = "round_p50_s"
SOURCE = "program_span"
CELLS = ["*"]


def read(ctx):
    if ctx.cell.get("placement") != "one_per_chip":
        return None
    rounds = set(ctx.traced_rounds)
    per = {}
    for name in ("unpack", "pack"):
        for s in ctx.spans.all(name):
            if s.round in rounds:
                key = (s.party, s.round)
                per[key] = per.get(key, 0.0) + (s.t_end - s.t_start)
    return 1e3 * float(np.median(list(per.values()))) if per else None
