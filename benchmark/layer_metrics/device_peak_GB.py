"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
run, in 10^9 bytes."""

NAME, UNIT = "device_peak_GB", "GB"
LAYER = "device"
MOVES = "fed_items_per_s"
SOURCE = "program_counter"
CELLS = ["*"]


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes", 0)
    return peak / 1e9 if peak else None
