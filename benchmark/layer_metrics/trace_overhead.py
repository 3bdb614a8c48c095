"""What the instrumentation costs when it is on: the median round of the
traced half of the measured rounds (recorder armed, spans blocked,
profiler on for a few of them) over the median round of the untraced
half of the same run, less one."""

NAME, UNIT = "trace_overhead", "%"
LAYER = "harness"
MOVES = "round_p50_s"
SOURCE = "host_clock"
CELLS = ["*"]


def read(ctx):
    return 100.0 * (ctx.traced_round_p50_s / ctx.plain_round_p50_s - 1.0)
