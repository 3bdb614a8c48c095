"""1 - the union of device-operation intervals over the profiled window,
on the chip that was idle most (among the chips the cell uses); from
the ``.xplane.pb`` by ``benchmark/xplane.py``."""

NAME, UNIT = "device_idle", "%"
LAYER = "device"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["*"]


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace["worst_idle_share"]
