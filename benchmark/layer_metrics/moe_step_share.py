"""Device time under the ``moe.*`` scopes (``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.shared``, ``moe.combine``) over
the device time of the step program (``jit_decoder_lora_step`` on the
trace's ``XLA Modules`` line) in the profiled rounds.

A device trace names an operation by its HLO instruction; the scope is
in the instruction's ``op_name`` in the compiled program's text, which
the family hands over (``step_program_text``).  Every operation is
charged its self time (a ``while`` around the chunk loop only what none
of its children ran), to the scope its own ``op_name`` holds."""

import re

from benchmark import xplane

NAME, UNIT = "moe_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["trinity-mini-ep8.*"]

STEP_MODULE = "jit_decoder_lora_step"
# A scope appears in an op_name bare (".../moe.experts/dot_general") or
# wrapped by the transformations it went through
# (".../transpose(jvp(moe.experts))/mul"); the innermost is the last.
SCOPE = re.compile(
    r"(moe\.(?:route|dispatch|experts|shared|combine)"
    r"|attn\.(?:window|full|proj)|ffn\.dense)"
)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*op_name=\"([^\"]*)\"", re.M
)


def instruction_op_names(program_text: str) -> dict:
    """``{instruction: op_name}`` of a compiled program's text."""
    return dict(_INSTRUCTION.findall(program_text))


def instruction_of(event_name: str) -> str:
    """The trace names an event by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def step_events(ctx):
    """``[(module start, module end, [(start, end, instruction), ...]),
    ...]``: the step program's executions inside the profiled window,
    each with the device operations it ran, in time order; and the
    step's ``{instruction: op_name}``.  ``(None, None)`` where the run
    has no device trace or the family no such program."""
    if hasattr(ctx, "_step_events"):  # both readers of the step share it
        return ctx._step_events
    ctx._step_events = found = _step_events(ctx)
    return found


def _step_events(ctx):
    text_of = getattr(ctx.family, "step_program_text", None)
    path = ctx.trace and xplane.find_xplane(ctx.run.profile_dir)
    if text_of is None or not path:
        return None, None
    from benchmark.layer_metrics.fold_roofline import profiled_window_ns

    profile = xplane.load(path)
    window = profiled_window_ns(ctx.run, profile)
    steps = []
    modules = xplane.device_ops(profile, xplane.MODULES_LINE)
    for plane, ops in xplane.device_ops(profile).items():
        mine = [
            (s, e) for s, e, name in modules.get(plane, ())
            if name.startswith(STEP_MODULE)
            and (window is None or window[0] <= s < window[1])
        ]
        i = 0
        for m0, m1 in mine:
            while i < len(ops) and ops[i][0] < m0:
                i += 1
            j = i
            while j < len(ops) and ops[j][0] < m1:
                j += 1
            steps.append((m0, m1, [
                (s, e, instruction_of(name)) for s, e, name in ops[i:j]
            ]))
            i = j
    if not steps:
        return None, None
    return steps, instruction_op_names(text_of())


def self_times(ops):
    """``[(instruction, self ns), ...]`` of nested device operations."""
    out, stack = [], []

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, ns = stack.pop()
            out.append((name, max(ns, 0)))

    for s, e, name in ops:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def scope_seconds(steps, op_names) -> dict:
    """``{scope: seconds}`` over the steps; ``moe.experts`` etc. for the
    expert layer's scopes, ``attn.window``, ``attn.full`` (the kernels
    and, on windowed layers, the rotary embedding), ``attn.proj`` (the
    projections, norms and gate around them), ``ffn.dense``, and
    ``other``."""
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
