"""Device time of the two mixers MiniCPM-SALA adds over the device time
of the step program (``jit_decoder_lora_step`` on the trace's ``XLA
Modules`` line) in the profiled rounds: the block-sparse layer's
selection (``attn.select``: compressed keys, scores, top-k, the words
and visit lists) and kernels (``attn.sparse``: forward, dQ, dK/dV), and
the linear-attention layers' scans (``attn.lightning``, and
``ssm.scan`` in a linear-attention layer's group: the scan's backward
rule is traced outside the caller's scope and names itself alone).

``step_scoped_share.py``'s account, its helpers imported: every instant
of a step charged once, to the operation that started last
(``exclusive_times``), and an instruction with no ``op_name`` of its
own named by its fusion or by its loop (``program_op_names``).

Log line: ``sala_mixer_ms`` (``select``, ``sparse``, ``lightning``: ms a
step)."""

import re

from benchmark.layer_metrics.step_scoped_share import (
    GROUP,
    exclusive_times,
    lowered_step_text,
    program_op_names,
    step_runs,
)

NAME, UNIT = "sala_mixer_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["minicpm-sala-d4.*"]

_ELEMENT = r"(?:^|[/(])(%s)(?=[/)]|$)"
PARTS = {
    "select": re.compile(_ELEMENT % r"attn\.select"),
    "sparse": re.compile(_ELEMENT % r"attn\.sparse"),
    "lightning": re.compile(_ELEMENT % r"attn\.lightning"),
}
SCAN = re.compile(_ELEMENT % r"ssm\.scan")


def lightning_groups(family) -> set:
    """The names of the scanned groups of linear-attention layers
    (``layers1-3``)."""
    cfg = getattr(family, "cfg", None)
    if cfg is None:
        return set()
    return {f"layers{a}-{b - 1}" for a, b in cfg.groups()
            if cfg.layers[a].mixer == "lightning"}


def part_of(op_name: str, groups) -> str:
    for part, pattern in PARTS.items():
        if pattern.search(op_name):
            return part
    if SCAN.search(op_name) and any(
        g in groups for g in GROUP.findall(op_name)
    ):
        return "lightning"
    return ""


def step_inputs(ctx):
    """``(runs, {instruction: op_name})`` of the step, or ``None``."""
    if hasattr(ctx, "_sala_step"):
        return ctx._sala_step
    ctx._sala_step = None
    runs = step_runs(ctx)
    if runs:
        text_of = getattr(ctx.family, "step_program_text", None)
        text = text_of() if text_of else lowered_step_text(ctx.family)
        ctx._sala_step = (runs, program_op_names(text)[0])
    return ctx._sala_step


def mixer_seconds(ctx):
    """``({part: seconds a step}, the step program's seconds a step)``,
    or ``None`` where the family has no such layer or the run no device
    trace (the readers of these mixers share it)."""
    if hasattr(ctx, "_sala_mixers"):
        return ctx._sala_mixers
    ctx._sala_mixers = None
    cfg = getattr(ctx.family, "cfg", None)
    kinds = {s.mixer for s in getattr(cfg, "layers", ())}
    found = (kinds & {"sparse", "lightning"}) and step_inputs(ctx)
    if not found:
        return None
    runs, op_names = found
    groups = lightning_groups(ctx.family)
    parts = dict.fromkeys(PARTS, 0.0)
    for _, end, ops in runs:
        for name, ns in exclusive_times(ops, end):
            part = part_of(op_names.get(name, ""), groups)
            if part:
                parts[part] += ns / 1e9 / len(runs)
    program = sum(m1 - m0 for m0, m1, _ in runs) / 1e9 / len(runs)
    ctx._sala_mixers = (parts, program)
    from benchmark.reduce import log

    log(sala_mixer_ms={k: round(v * 1e3, 3) for k, v in parts.items()},
        step_program_ms=round(program * 1e3, 3))
    return ctx._sala_mixers


def read(ctx):
    found = mixer_seconds(ctx)
    if not found or not found[1]:
        return None
    parts, program = found
    return 100.0 * sum(parts.values()) / program
