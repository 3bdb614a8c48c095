"""Payload bytes over the ``socket`` stage time of the tcp backend's
send-path breakdown, over the measured rounds (counters, so read in the
traced run for the same window as the other transport metrics)."""

NAME, UNIT = "wire_goodput", "GB/s"
LAYER = "transport"
MOVES = "round_p50_s"
SOURCE = "program_counter"
CELLS = ["*"]


def stage_ms(ctx, stage, backend="tcp"):
    key = "send_path_breakdown_by_backend_ms"
    return sum(
        ctx.stats_final[p][key][backend][stage]
        - ctx.stats_first[p][key][backend][stage]
        for p in ctx.parties
    )


def read(ctx):
    sent = sum(
        ctx.stats_final[p]["send_bytes"] - ctx.stats_first[p]["send_bytes"]
        for p in ctx.parties
    )
    socket_ms = stage_ms(ctx, "socket_ms")
    return sent / (socket_ms / 1e3) / 1e9 if socket_ms > 0 else None
