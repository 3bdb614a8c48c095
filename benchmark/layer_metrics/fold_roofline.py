"""The streaming fold kernel's share of its memory roofline.  One
``jit_fed_fold_*`` program (``XLA Modules`` line of the device trace)
folds one block of one contribution into the donated accumulator: it
must read the block's codes and read and write the accumulator's block,
``chunk_elems x (2 x accumulator bytes + code bytes)`` (9 bytes an
element for uint8 into int32, 10 for bf16 into float32; sizes from the
``agg.fold`` span's detail).  Those bytes over the median event's
duration over the chip's published HBM rate.  Computed per event, so no
window edge can put more work in the numerator than time in the
denominator; it counts only bytes the kernel must move, so a reading
over 100% would mean the count is wrong."""

import numpy as np

from benchmark import xplane

NAME, UNIT = "fold_roofline", "%"
LAYER = "aggregation kernel"
MOVES = "round_p50_s"
SOURCE = "device_trace"
CELLS = ["*"]

KERNEL_PREFIX = "jit_fed_fold_"


def fold_durations_ns(modules_by_plane, window=None):
    """Durations of the fold programs that start inside ``window``."""
    return [
        e - s for ops in modules_by_plane.values() for s, e, name in ops
        if name.startswith(KERNEL_PREFIX)
        and (window is None or window[0] <= s < window[1])
    ]


def bytes_per_fold(detail):
    """What one fold program must move, from ``agg.fold``'s detail."""
    import jax.numpy as jnp

    acc = jnp.dtype(detail["acc"]).itemsize
    code = jnp.dtype(detail["codes"]).itemsize
    return int(detail["chunk_elems"]) * (2 * acc + code)


def profiled_window_ns(run, profile):
    """The profiled rounds on the trace's clock (as ``reduce.py`` maps
    them: through the ``bench_anchor`` annotation)."""
    anchor = xplane.anchor_ns(profile)
    if anchor is None or run.anchor_wall is None or None in run.profile_wall:
        return None
    offset_s = anchor / 1e9 - run.anchor_wall
    return tuple(int((t + offset_s) * 1e9) for t in run.profile_wall)


def read(ctx):
    folds = [
        rec.detail for rec in ctx.recorder_records
        if rec.phase == "agg.fold" and rec.detail
        and rec.detail.get("fold") == "jit" and "chunk_elems" in rec.detail
    ]
    path = ctx.trace and xplane.find_xplane(ctx.run.profile_dir)
    if not folds or not path or ctx.peaks is None:
        return None
    profile = xplane.load(path)
    durations = fold_durations_ns(
        xplane.device_ops(profile, xplane.MODULES_LINE),
        profiled_window_ns(ctx.run, profile),
    )
    if not durations:
        return None
    seconds = float(np.median(durations)) / 1e9
    rate = bytes_per_fold(folds[-1]) / seconds
    return 100.0 * rate / ctx.peaks["hbm_bytes_per_s"]
