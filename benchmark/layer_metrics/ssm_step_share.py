"""Device time under the ``ssm.*`` scopes (``ssm.proj``: a state-space
layer's two projections, its norms and its residual add; ``ssm.conv``:
the causal depthwise convolution; ``ssm.scan``: the chunked scan) over
the device time of the step program (``jit_decoder_lora_step`` on the
trace's ``XLA Modules`` line) in the profiled rounds.

As ``moe_step_share.py`` reads ``moe.*`` (its helpers, imported): an
operation is charged its self time, to the innermost scope its
instruction's ``op_name`` holds in the compiled step's text.  The log
line ``step_scopes`` gives every scope of the step a millisecond figure
(``attn.full``, ``attn.proj`` and ``ffn.dense`` among them)."""

import re

from benchmark.layer_metrics.moe_step_share import self_times, step_events

NAME, UNIT = "ssm_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["granite-4.0-h-micro-d20.*"]

SCOPE = re.compile(
    r"(ssm\.(?:proj|conv|scan)|attn\.(?:window|full|latent|proj)|ffn\.dense)"
)


def scope_seconds(steps, op_names) -> dict:
    """``{scope: seconds}`` over the steps: the three ``ssm.*`` scopes,
    ``attn.full`` (the flash kernels), ``attn.proj``, ``ffn.dense``, and
    ``other`` (embedding, final norm, head and loss, Adam)."""
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
    ssm = sum(v for k, v in totals.items() if k.startswith("ssm."))
    from benchmark.reduce import log

    log(step_scopes={k: round(v / len(steps) * 1e3, 3)
                     for k, v in sorted(totals.items())},
        step_program_ms=program / len(steps) * 1e3, steps=len(steps))
    return 100.0 * ssm / program if program else None
