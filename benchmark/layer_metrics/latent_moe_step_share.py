"""Device time under the ``moe.*`` scopes of the latent expert layers
(``moe.route``, ``moe.latent``: the projections into the routed
experts' width and back, ``moe.dispatch``, ``moe.experts``,
``moe.shared``, ``moe.combine``) over the device time of the step program
(``jit_decoder_lora_step`` on the trace's ``XLA Modules`` line) in the
profiled rounds, the MTP module's expert layer included.

As ``moe_step_share.py`` reads ``moe.*`` (its helpers, imported): an
operation is charged its self time, to the innermost scope its
instruction's ``op_name`` holds in the compiled step's text.  The log
line ``step_scopes`` gives every scope of the step a millisecond figure:
the ``moe.*`` ones, ``ssm.*``, ``attn.*``, ``mtp.fuse`` and ``mtp`` (the
module's operations under no inner scope: its final norm), ``head.loss``
and ``other``."""

import re

from benchmark.layer_metrics.moe_step_share import self_times, step_events

NAME, UNIT = "latent_moe_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["nemotron-3-super-120b-a12b-ep8-d11.*"]

# a scope as a path element, bare or wrapped by transformations; the
# innermost is the last
SCOPE = re.compile(
    r"(?:^|[/(])("
    r"moe\.(?:route|latent|dispatch|experts|shared|combine)"
    r"|ssm\.(?:proj|conv|scan)|attn\.(?:full|proj)"
    r"|mtp\.fuse|mtp|head\.loss|embed|optim\.adam"
    r")(?=[/)]|$)"
)


def scope_seconds(steps, op_names) -> dict:
    """``{scope: seconds}`` over the steps, ``other`` for what no scope
    of the list names."""
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
    moe = sum(v for k, v in totals.items() if k.startswith("moe."))
    from benchmark.reduce import log

    log(step_scopes={k: round(v / len(steps) * 1e3, 3)
                     for k, v in sorted(totals.items())},
        step_program_ms=program / len(steps) * 1e3, steps=len(steps))
    return 100.0 * moe / program if program else None
