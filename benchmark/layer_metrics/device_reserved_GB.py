"""What the fullest chip held under a running step: over the flight
recorder's ``device.memory`` records of the traced rounds (one a party
task, ``rayfed_tpu/executor.py``), the largest ``peak_bytes_in_use +
peak_bytes_reserved`` of a device, in 10^9 bytes.

``peak_bytes_in_use`` holds resident arrays (``device_peak_GB`` reads
it after the run); a running program's temporaries are under
``peak_bytes_reserved`` (PERF.md section 4, the hybrid's witness).  Both
are the process's high-water marks, so a peak that set-up's reference
check left is told from one the rounds set by the log line: the first
and the last traced record's pair, with the live ``bytes_in_use`` and
``bytes_reserved`` beside them."""

NAME, UNIT = "device_reserved_GB", "GB"
LAYER = "device"
MOVES = "fed_items_per_s"
SOURCE = "program_counter"
CELLS = ["*"]

PHASE = "device.memory"


def held(stats: dict) -> int:
    return stats.get("peak_bytes_in_use", 0) + stats.get(
        "peak_bytes_reserved", 0
    )


def fullest(record):
    """``(device id, its stats)`` of the record's fullest device."""
    return max(record.detail["devices"].items(), key=lambda kv: held(kv[1]))


def read(ctx):
    if not ctx.traced_rounds:
        return None
    t0 = ctx.round_edges[ctx.traced_rounds[0]][0]
    t1 = ctx.round_edges[ctx.traced_rounds[-1]][1]
    records = [
        rec for rec in ctx.recorder_records
        if rec.phase == PHASE and t0 <= rec.t_start < t1
        and (rec.detail or {}).get("devices")
    ]
    if not records:
        return None
    from benchmark.reduce import log

    edge = lambda rec: dict(zip(("device", "stats"), fullest(rec)))
    log(device_memory={"records": len(records), "first": edge(records[0]),
                       "last": edge(records[-1])})
    return max(held(fullest(rec)[1]) for rec in records) / 1e9
