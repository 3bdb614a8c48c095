"""Device time under the ``attn.latent`` scope (the partial rotation and
the three flash kernels of a latent-attention layer) over the device
time of the step program (``jit_decoder_lora_step`` on the trace's
``XLA Modules`` line) in the profiled rounds.

As ``moe_step_share.py`` reads ``moe.*`` (its helpers, imported): an
operation is charged its self time, to the innermost scope its
instruction's ``op_name`` holds in the compiled step's text.  The log
line ``step_scopes`` gives every scope of the step a millisecond figure
(the expert layer's ``moe.*`` among them: no metric of this cell reads
those, PERF.md section 7)."""

import re

from benchmark.layer_metrics.moe_step_share import self_times, step_events

NAME, UNIT = "latent_attn_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["kimi-k2.7-code-ep32.*"]

SCOPE = re.compile(
    r"(moe\.(?:route|dispatch|experts|shared|combine)"
    r"|attn\.(?:latent|window|full|proj)|ffn\.dense)"
)


def scope_seconds(steps, op_names) -> dict:
    """``{scope: seconds}`` over the steps: ``attn.latent``,
    ``attn.proj`` (the five projections, the latent norms, ``W_o``),
    ``ffn.dense``, the ``moe.*`` scopes, and ``other``."""
    totals: dict = {}
    for _, _, ops in steps:
        for name, ns in self_times(ops):
            found = SCOPE.findall(op_names.get(name, ""))
            key = found[-1] if found else "other"
            totals[key] = totals.get(key, 0.0) + ns / 1e9
    return totals


def read(ctx):
    steps, op_names = step_events(ctx)
    if not steps:
        return None
    totals = scope_seconds(steps, op_names)
    program = sum(m1 - m0 for m0, m1, _ in steps) / 1e9
    from benchmark.reduce import log

    log(step_scopes={k: round(v / len(steps) * 1e3, 3)
                     for k, v in sorted(totals.items())},
        step_program_ms=program / len(steps) * 1e3, steps=len(steps))
    return 100.0 * totals.get("attn.latent", 0.0) / program if program else None
