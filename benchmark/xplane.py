"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Kept with the benchmark so that every PR computes the same number in
the same way.  Reads the file with ``jax.profiler.ProfileData`` alone.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose line
``XLA Ops`` holds one event per device operation (name, start,
duration), and one ``/host:CPU`` plane with a line per host thread.
``bench_anchor`` is a host ``TraceAnnotation`` the harness emits right
after the trace starts, at a wall time it notes: it maps the wall clock
of host spans onto the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"  # one event per executed program (jit name)
ANCHOR = "bench_anchor"


def find_xplane(profile_dir: str):
    paths = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return paths[-1] if paths else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(profile, line_name: str = OPS_LINE) -> dict:
    """``{plane name: [(start_ns, end_ns, op name), ...]}`` sorted by
    start, for every device plane's operations line."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != line_name:
                continue
            events = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events
            ]
            if events:
                out[plane.name] = sorted(events)
    return out


def anchor_ns(profile):
    """Start of the ``bench_anchor`` host annotation, or None."""
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    return e.start_ns
    return None


def merge(intervals):
    """Sorted ``(start, end, ...)`` tuples -> merged ``[start, end]``."""
    merged = []
    for iv in intervals:
        s, e = iv[0], iv[1]
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def busy_and_gaps(ops, window=None):
    """One chip's ``(busy_ns, window_ns, gaps)``: the union of its
    operations inside ``window`` (default: first start to last end) and
    the idle intervals between them, longest first."""
    if window is None:
        window = (ops[0][0], max(e for _, e, _ in ops))
    w0, w1 = window
    merged = [
        [max(s, w0), min(e, w1)] for s, e in merge(ops)
        if e > w0 and s < w1
    ]
    busy = sum(e - s for s, e in merged)
    edges = [w0] + [t for iv in merged for t in iv] + [w1]
    gaps = [
        (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    gaps.sort(key=lambda g: g[0] - g[1])
    return busy, w1 - w0, gaps


_HLO = re.compile(r"^%?(\S+) = (\(?[a-z0-9]+\[[^\]]*\])?.*?\s([\w-]+)\(")


def short_name(text: str) -> str:
    """The trace names a device operation by its whole HLO line; keep
    the instruction's name, its opcode and its first result shape:
    ``fusion.963 fusion bf16[4,512,4096]``."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    name, shape, opcode = m.groups()
    return " ".join(x for x in (name, opcode, (shape or "").lstrip("(")) if x)


def op_totals(ops) -> dict:
    """``{short op name: self ns}`` over one chip's operations.  An
    operation that holds others (a ``while`` around a scanned layer) is
    charged only the time none of its children ran."""
    totals: dict = {}
    stack: list = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            totals[name] = totals.get(name, 0) + max(self_ns, 0)

    for s, e, name in ops:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, short_name(name), e - s])
    close(float("inf"))
    return totals


NO_SPAN = "(no host span)"


def attribute_gaps(gaps, host_spans) -> dict:
    """``{span name: idle ns}`` over ``gaps``: every nanosecond of a gap
    goes to the shortest host span that covers it (the innermost one,
    where spans nest; a worker's span before a waiter's, where threads
    overlap), and what no span covers to ``NO_SPAN``.  A span is never
    given more of a gap than it covers."""
    totals: dict = {}
    starts = np.array([s for s, _, _ in host_spans], dtype=np.int64)
    ends = np.array([e for _, e, _ in host_spans], dtype=np.int64)
    for g0, g1 in gaps:
        live = [
            (max(int(starts[i]), g0), min(int(ends[i]), g1),
             int(ends[i] - starts[i]), host_spans[i][2])
            for i in np.nonzero((starts < g1) & (ends > g0))[0]
        ]
        cuts = sorted({g0, g1, *(c[0] for c in live), *(c[1] for c in live)})
        for a, b in zip(cuts, cuts[1:]):
            over = [(c[2], c[3]) for c in live if c[0] <= a and c[1] >= b]
            name = min(over)[1] if over else NO_SPAN
            totals[name] = totals.get(name, 0) + (b - a)
    return totals


def summarize(profile, window=None, host_spans=(), top=10) -> dict:
    """Everything the harness reports from one trace.

    ``host_spans``: ``[(start_ns, end_ns, name), ...]`` on the trace's
    clock; the idle time of the idlest chip is split among the host
    spans by what each covers (``attribute_gaps``).
    """
    per_chip = {}
    for plane, ops in device_ops(profile).items():
        busy, win, gaps = busy_and_gaps(ops, window)
        per_chip[plane] = {
            "busy_ns": busy, "window_ns": win, "gaps": gaps,
            "totals": op_totals(
                [o for o in ops if window is None
                 or (o[1] > window[0] and o[0] < window[1])]
            ),
        }
    if not per_chip:
        return {}
    used = {p: c for p, c in per_chip.items() if c["busy_ns"] > 0}
    worst = min(used or per_chip, key=lambda p: per_chip[p]["busy_ns"])
    totals: dict = {}
    for c in per_chip.values():
        for name, ns in c["totals"].items():
            totals[name] = totals.get(name, 0) + ns
    n_used = max(len(used), 1)
    gap_totals = attribute_gaps(per_chip[worst]["gaps"], host_spans)
    modules: dict = {}
    for ops in device_ops(profile, MODULES_LINE).values():
        for s, e, name in ops:
            if window is None or (e > window[0] and s < window[1]):
                modules[name] = modules.get(name, 0) + (e - s)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "chips": sorted(per_chip),
        "chips_used": sorted(used),
        "busy_s": sum(c["busy_ns"] for c in used.values()) / n_used / 1e9,
        "window_s": per_chip[worst]["window_ns"] / 1e9,
        "worst_chip": worst,
        "worst_idle_share": 1.0 - (
            per_chip[worst]["busy_ns"] / max(per_chip[worst]["window_ns"], 1)
        ),
        "op_seconds": {n: ns / 1e9 for n, ns in totals.items()},
        "module_seconds": {n: ns / 1e9 for n, ns in modules.items()},
        "device_ops": [[n, ns / 1e9] for n, ns in by_time(totals)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in by_time(gap_totals)],
    }

