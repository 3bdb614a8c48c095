"""Device time of the multi-token-prediction module over the device time
of the step program (``jit_decoder_lora_step`` on the trace's ``XLA
Modules`` line) in the profiled rounds.

The module is every operation whose ``op_name`` holds the scope ``mtp``
(its input ``mtp.fuse``: two norms, the concatenation and ``W_eh``; its
final norm; its head-and-loss pass) or the module's own scanned group
(``layers<a>-<b>`` of ``DecoderConfig.mtp_groups()``), so that what its
layers' backward runs inside ``custom_vjp`` rules (the routed experts'
chunk loop, the fused head's scaling) is the module's wherever JAX
leaves the outer scope out.  ``step_scoped_share.py``'s account, its
helpers imported: every instant of a step charged once, to the operation
that started last (``exclusive_times``), and an instruction with no
``op_name`` of its own named by its fusion or by its loop
(``program_op_names``).

Log line: ``mtp_ms`` a step, ``mtp_passes`` (forward, second forward,
backward: ms a step), and the FR ``mtp.loss`` records of the traced
rounds (``mtp_loss_records``: how many, and the last one's terms)."""

import re

from benchmark.layer_metrics.step_scoped_share import (
    GROUP,
    PASSES,
    exclusive_times,
    pass_of,
    program_op_names,
    step_runs,
)

NAME, UNIT = "mtp_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["nemotron-3-super-120b-a12b-ep8-d11.*"]

MTP = re.compile(r"(?:^|[/(])mtp(?:\.fuse)?(?=[/)]|$)")


def mtp_groups(family) -> set:
    """The names of the MTP module's scanned groups (``layers6-6``)."""
    cfg = getattr(family, "cfg", None)
    if getattr(cfg, "mtp", None) is None:
        return set()
    return {f"layers{a}-{b - 1}" for a, b in cfg.mtp_groups()}


def is_mtp(op_name: str, groups) -> bool:
    return bool(MTP.search(op_name)) or any(
        g in groups for g in GROUP.findall(op_name)
    )


def mtp_passes(runs, op_names, groups) -> dict:
    """``{pass: seconds}`` of the module over ``runs``."""
    out = dict.fromkeys(PASSES, 0.0)
    for _, end, ops in runs:
        for name, ns in exclusive_times(ops, end):
            op_name = op_names.get(name, "")
            if is_mtp(op_name, groups):
                out[pass_of(op_name)] += ns / 1e9
    return out


def step_inputs(ctx):
    """``(runs, {instruction: op_name})`` of the step, or ``None``."""
    if hasattr(ctx, "_mtp_step"):
        return ctx._mtp_step
    ctx._mtp_step = None
    text_of = getattr(ctx.family, "step_program_text", None)
    runs = text_of and step_runs(ctx)
    if runs:
        ctx._mtp_step = (runs, program_op_names(text_of())[0])
    return ctx._mtp_step


def read(ctx):
    found = step_inputs(ctx)
    groups = mtp_groups(ctx.family)
    if not found or not groups:
        return None
    runs, op_names = found
    passes = mtp_passes(runs, op_names, groups)
    program = sum(m1 - m0 for m0, m1, _ in runs) / 1e9
    from benchmark.reduce import log

    per_step = lambda s: round(s / len(runs) * 1e3, 3)
    losses = [r.detail for r in getattr(ctx, "recorder_records", ())
              if r.phase == "mtp.loss"]
    log(mtp_ms=per_step(sum(passes.values())),
        mtp_passes={p: per_step(s) for p, s in passes.items()},
        mtp_loss_records={"count": len(losses),
                          "last": losses[-1] if losses else None})
    return 100.0 * sum(passes.values()) / program if program else None
