"""The ``d2h`` stage of the send-path breakdown (device-to-host fetches
of payloads about to be sent), all parties, per measured round."""

from benchmark.layer_metrics.wire_goodput import stage_ms

NAME, UNIT = "d2h_ms", "ms"
LAYER = "transport"
MOVES = "round_p50_s"
SOURCE = "program_counter"
CELLS = ["*"]


def read(ctx):
    return stage_ms(ctx, "d2h_ms") / ctx.measured_rounds
