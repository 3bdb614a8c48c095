"""From what one run recorded to its result object: the checks that
decide ``correct``, the end-to-end metrics, and (traced run) the
per-layer metrics through the readers under ``layer_metrics/``.
"""

from __future__ import annotations

import json
import os
import sys
import types

import numpy as np

from benchmark import harness, xplane
from benchmark.peaks import peaks_for
from benchmark.reference import fedavg as ref_fedavg


def log(**fields) -> None:
    """An earlier line: never the last one of standard output."""
    print(json.dumps(fields), file=sys.stderr, flush=True)


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    ]
    return int(max(peaks))


def check_run(run, out, meshes, reference) -> dict:
    """Every condition ``correct`` stands for, by name."""
    import jax

    parties = run.parties
    first = harness.VERIFY_ROUNDS + harness.RAMP
    R = run.measured_rounds
    rec = run.records
    checks = {"reference": bool(reference["ok"])}

    # 1. verification call against the numpy FedAvg, round by round
    V = harness.VERIFY_ROUNDS
    updates = [[rec[(p, r)]["out"] for p in parties] for r in range(V)]
    received = [
        [rec[(p, r + 1)]["in"] for p in parties] for r in range(V - 1)
    ] + [[out[p]["verify_final"] for p in parties]]
    quant = 1 if run.kwargs.get("wire_quant") else None
    ok, worst = ref_fedavg.check_rounds(
        updates, received, out[parties[0]]["init"], quantized_from=quant
    )
    checks["fedavg_vs_numpy"] = ok
    log(check="fedavg_vs_numpy", ok=ok, worst=worst)

    # 2. finals byte-identical (both calls)
    bits = lambda a: a.view(np.uint32)  # compare bytes, not values
    checks["finals_identical"] = all(
        np.array_equal(bits(out[p][k]), bits(out[parties[0]][k]))
        for p in parties[1:] for k in ("verify_final", "final")
    )

    # 3. every loss finite
    losses = {
        key: [float(v) for v in row["losses"]]
        for key, row in rec.items() if key[1] != "resident"
    }
    checks["losses_finite"] = all(
        np.isfinite(v) for row in losses.values() for v in row
    )
    by_round = [
        float(np.mean([v for p in parties for v in losses[(p, r)]]))
        for r in range(first, first + R) if all((p, r) in losses for p in parties)
    ]
    log(check="losses", first=by_round[:3], last=by_round[-3:])

    # 4. placement: jax.Arrays on the platform, on the party's own chip
    platform = {d.id: d.platform for d in jax.devices()}
    placed = True
    for p in parties:
        mine = meshes and {d.id for d in meshes[p].devices.flat}
        held = [
            i for a in rec[(p, "resident")] for i in (d.id for d in a.devices())
        ]
        for r in range(first + R):
            if (p, r) in rec:
                held += rec[(p, r)]["in_devices"]
                placed &= rec[(p, r)]["in_is_jax"]
        held += out[p]["final_devices"]
        placed &= out[p]["final_is_jax"]
        placed &= all(
            isinstance(a, jax.Array) for a in rec[(p, "resident")]
        )
        placed &= all(platform[i] == run.platform for i in held)
        if mine:
            placed &= set(held) <= mine
            log(check="placement", party=p, devices=sorted(set(held)),
                own=sorted(mine))
    checks["placement"] = bool(placed)

    # 5. the bytes rode loopback TCP only
    tcp = True
    for p in parties:
        by = out[p]["stats"]["send_path_breakdown_by_backend_ms"]
        tcp &= by["tcp"]["socket_ms"] > 0
        tcp &= by["shm"]["socket_ms"] == 0 and by["uds"]["socket_ms"] == 0
    # ... and the kernel saw them: over the whole run the loopback
    # interface carried no fewer bytes than the program says it sent.
    lo0, lo1 = run.loopback_at_start, harness.loopback_rx_bytes()
    payload = sum(out[p]["stats"]["receive_bytes"] for p in parties)
    if lo0 is not None and lo1 is not None:
        tcp &= (lo1 - lo0) >= payload
        log(check="loopback", kernel_bytes=lo1 - lo0, program_bytes=payload)
    checks["tcp_only"] = bool(tcp)

    # 6. nothing compiled inside the measured call
    c0, c1 = run.compiles_at["measure_start"], run.compiles_at["measure_end"]
    checks["no_compile_in_window"] = c1[0] == c0[0]
    log(check="compiles", in_measured_call=c1[0] - c0[0],
        before=c0[0], compile_s_before=round(c0[1], 3), cache_misses=c1[2])
    return checks


def reduce_run(run, out, *, device, reference, setup_wall, recorder,
               meshes) -> dict:
    parties = run.parties
    fam = run.family
    first = harness.VERIFY_ROUNDS + harness.RAMP
    R = run.measured_rounds
    rec = run.records
    log(check="reference", **reference)

    attempted = R * len(parties)
    done = sum((p, r) in rec for p in parties for r in range(first, first + R))
    checks = check_run(run, out, meshes, reference)
    log(checks=checks)

    starts = [
        min(rec[(p, r)]["t_in"] for p in parties)
        for r in range(first, first + R)
    ]
    t_end = max(out[p]["t_end"] for p in parties)
    edges = np.array(starts + [t_end])
    durations = np.diff(edges)
    # The traced run keeps its first measured rounds untraced: both
    # halves come from one process, and their ratio is the overhead.
    n_plain = run.trace_from - first
    plain = durations[:n_plain] if run.trace else durations
    # The sustained rate leaves out the slowest tenth of the rounds: on
    # a one-chip machine, which shares its host's cores, some runs (3 of
    # the first 9, none of the next 20: PERF.md, Stalls) have a round
    # 0.6-11 s longer than its neighbours, and one such round moves an
    # untrimmed rate by 1-25%.  A slow round that comes back more often
    # than every tenth still shows; every slow round shows in the traced
    # run's ``slow_round_share`` and in this run's log line below.
    n_out = int(np.ceil(len(plain) / 10.0))
    kept = np.sort(plain)[: len(plain) - n_out]
    items = len(kept) * len(parties) * fam.local_steps * fam.items_per_step
    span_s = float(kept.sum())
    # Bytes the transport delivered (and acknowledged) in the measured
    # rounds: an exact count, see the trainer's note on where it samples.
    sent = sum(
        out[p]["stats"]["receive_bytes"] - rec[(p, first)]["received"]
        for p in parties
    )
    per_round = {
        p: sorted({
            rec[(p, r + 1)]["received"] - rec[(p, r)]["received"]
            for r in range(first, first + R - 1)
        }) for p in parties
    }
    totals = {
        k: sum(out[p]["stats"][k] for p in parties)
        for k in ("send_bytes", "receive_bytes")
    }
    e2e = {
        "fed_items_per_s": (items / span_s, "items/s"),
        "round_p50_s": (float(np.median(plain)), "s"),
        "wire_MB_per_round": (sent / R / 1e6, "MB"),
        "setup_s": (starts[0] - setup_wall, "s"),
    }
    # The highest percentile with ten samples beyond it, where there is
    # one; an earlier line, not a metric.
    tail = None
    if len(plain) >= 20:
        q = 100.0 * (1.0 - 10.0 / len(plain))
        tail = {"percentile": q, "s": float(np.percentile(plain, q))}
    log(rounds=int(R), plain_rounds=int(len(plain)),
        warm_round_s=run.warm_round_s, round_s=[round(float(d), 4) for d in durations],
        tail=tail, left_out_of_rate=[
            round(float(d), 4) for d in np.sort(plain)[len(plain) - n_out:]
        ], untrimmed_items_per_s=float(
            len(plain) * len(parties) * fam.local_steps * fam.items_per_step
            / (edges[len(plain)] - edges[0])
        ), wire_bytes_per_party_round=per_round, whole_run=totals)

    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    result = {
        "correct": bool(all(checks.values()) and done == attempted),
        "attempted": int(attempted),
        "failed": int(attempted - done),
        "device": device,
    }
    if not run.trace:
        result["metrics"] = {
            name: {"value": value, "unit": unit}
            for name, (value, unit) in e2e.items()
        }
        return result

    # -- traced run: per-layer metrics through their readers -----------
    # What a per-layer reader may read (benchmark/README.md lists it).
    ctx = types.SimpleNamespace()
    ctx.run, ctx.cell, ctx.family, ctx.parties = run, run.cell, fam, parties
    ctx.out, ctx.records = out, rec
    # Skip the round in which tracing was switched on.
    ctx.traced_rounds = list(range(run.trace_from + 1, first + R))
    ctx.round_edges = {
        r: (edges[r - first], edges[r - first + 1])
        for r in range(first, first + R)
    }
    ctx.spans = run.spans
    ctx.recorder_records = recorder.records() if recorder else []
    ctx.plain_round_s = [float(d) for d in plain]
    ctx.plain_round_p50_s = float(np.median(plain))
    ctx.traced_round_p50_s = float(np.median(
        [np.diff(ctx.round_edges[r])[0] for r in ctx.traced_rounds]
    ))
    ctx.measured_rounds = R
    ctx.stats_first = {p: rec[(p, first)]["stats"] for p in parties}
    ctx.stats_final = {p: out[p]["stats"] for p in parties}
    ctx.device = device
    ctx.peaks = peaks_for(device["kind"]) if run.platform == "tpu" else None
    ctx.trace = trace_summary(run, ctx)
    if ctx.trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {
            "device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"],
        }
    metrics = {}
    metrics_root = run.cell["root"]
    if not os.path.isdir(os.path.join(metrics_root, "layer_metrics")):
        metrics_root = harness.ROOT
    for mod in harness.matching_layer_metrics(run.cell["name"], metrics_root):
        value = mod.read(ctx)
        if value is not None and np.isfinite(value):
            metrics[mod.NAME] = {"value": float(value), "unit": mod.UNIT}
    result["metrics"] = metrics
    log(traced_e2e={k: v[0] for k, v in e2e.items()})
    return result


def host_spans_on_trace_clock(run, ctx, offset_s: float) -> list:
    """Benchmark spans and flight-recorder spans as ``(start_ns, end_ns,
    name)`` on the trace's clock.  The innermost (shortest) span wins a
    gap only by covering more of it, so ``train`` is left out: its
    parts (``unpack``, ``step``, ``pack``) tile it."""
    rows = []
    for s in run.spans.all():
        if s.name != "train":
            rows.append((s.t_start, s.t_end, f"trainer.{s.name}"))
    for r in ctx.recorder_records:
        rows.append((r.t_start, r.t_start + r.dur_s, r.phase))
    return [
        (int((a + offset_s) * 1e9), int((b + offset_s) * 1e9), name)
        for a, b, name in rows
    ]


def trace_summary(run, ctx) -> dict:
    path = run.profile_dir and xplane.find_xplane(run.profile_dir)
    if not path:
        return {}
    profile = xplane.load(path)
    anchor = xplane.anchor_ns(profile)
    if anchor is not None and run.anchor_wall is not None:
        offset_s = anchor / 1e9 - run.anchor_wall
    else:
        offset_s = 0.0  # the trace's clock is the wall clock
    w0, w1 = run.profile_wall
    window = (int((w0 + offset_s) * 1e9), int((w1 + offset_s) * 1e9))
    summary = xplane.summarize(
        profile, window=window,
        host_spans=host_spans_on_trace_clock(run, ctx, offset_s),
    )
    if summary:
        summary["profiled_rounds"] = harness.TRACE_ROUNDS
    log(trace=path, anchor_found=anchor is not None, offset_s=offset_s,
        chips=summary.get("chips"), chips_used=summary.get("chips_used"),
        busy_s=summary.get("busy_s"), window_s=summary.get("window_s"),
        modules=sorted(
            summary.get("module_seconds", {}).items(), key=lambda kv: -kv[1]
        )[:12])
    return summary
