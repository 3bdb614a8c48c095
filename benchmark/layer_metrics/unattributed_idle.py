"""The share of the idlest chip's idle time that no working span
explains: what ``breakdown.idle_gaps`` gives to ``mailbox.wait`` or
``task.wait`` (a thread waited; the work was elsewhere) or to no host
span at all, over the chip's idle time in the profiled window
(``worst_idle_share x window_s``).  ``idle_gaps`` lists the ten largest
names, so a waiting name it leaves out holds less than the tenth."""

from benchmark.xplane import NO_SPAN

NAME, UNIT = "unattributed_idle", "%"
LAYER = "device"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["*"]

WAITING = ("mailbox.wait", "task.wait", NO_SPAN)


def read(ctx):
    if not ctx.trace:
        return None
    idle_s = ctx.trace["worst_idle_share"] * ctx.trace["window_s"]
    if idle_s <= 0:
        return None
    waited = sum(s for name, s in ctx.trace["idle_gaps"] if name in WAITING)
    return 100.0 * waited / idle_s
