"""Device self time of the step program under any model scope, over the
program's device time in the profiled rounds: how much of a local step
the trace can name.

The step program is ``jit_llama_lora_step`` or ``jit_decoder_lora_step``
on the trace's ``XLA Modules`` line.  A device trace names an operation
by its HLO instruction; the scopes are in the instruction's ``op_name``
in the compiled step's text (``moe_step_share.py``'s way, its helpers
imported).  An operation is charged its self time (``exclusive_times``:
the instants in which it is the last started of the operations running,
so no instant counts twice) to the innermost scope of the vocabulary its
``op_name`` holds: ``embed``, ``head.loss``,
``optim.adam`` outside the layers; inside them ``attn.proj``,
``attn.window`` / ``attn.full`` / ``attn.latent``, ``ffn.dense``,
``moe.*``, ``ssm.*``.  What has a ``layers<a>-<b>`` group and nothing
inside it (residual adds, the scan's slicing and stacking) is
``layers.glue``, which counts as named; what has no scope is ``other``.

The pass is read from what JAX writes into every ``op_name``:
``rematted_computation`` is a checkpointed layer's second forward, else
``transpose(`` is the backward pass, else the operation is forward
(optimizer and loss included; the fused head-and-loss makes ``d loss /
d x`` in its forward).  A fusion carries its root's ``op_name``: the log
line says how much time sits in fusions whose parts lie in two passes
(``pass_straddling_ms``) and in instructions with no ``op_name`` of
their own, which take their loop's (``no_own_op_name_ms``), and what
``moe_step_share.self_times`` would charge twice
(``self_time_overcount_ms``).

Log lines: ``step_scopes`` (ms a step by scope), ``step_passes``
(``{scope: {fwd, refwd, bwd}}``), ``step_unclear``.

Where a family hands over no ``step_program_text`` the step is lowered
here from shapes (``afmoe_lm.py``'s way): the compiled program is in the
compile cache.
"""

import re

from benchmark import xplane
from benchmark.layer_metrics.moe_step_share import instruction_of, self_times

NAME, UNIT = "step_scoped_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["*"]

STEP_MODULES = ("jit_llama_lora_step", "jit_decoder_lora_step")
# A scope stands in an op_name as a path element, bare
# (".../head.loss/while/...") or wrapped by the transformations it went
# through (".../transpose(jvp(ffn.dense))/mul", "jit(optim_adam)/
# optim.adam/..."); the innermost is the last.
_ELEMENT = r"(?:^|[/(])(%s)(?=[/)]|$)"
SCOPE = re.compile(_ELEMENT % (
    r"embed|head\.loss|optim\.adam"
    r"|attn\.(?:proj|window|full|latent)|ffn\.dense"
    r"|moe\.(?:route|dispatch|experts|shared|combine)"
    r"|ssm\.(?:proj|conv|scan)"
))
GROUP = re.compile(_ELEMENT % r"layers\d+-\d+")
GLUE, OTHER = "layers.glue", "other"
PASSES = ("fwd", "refwd", "bwd")

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) [^\n]*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)


def scope_of(op_name: str) -> str:
    found = SCOPE.findall(op_name)
    if found:
        return found[-1]
    return GLUE if GROUP.search(op_name) else OTHER


def pass_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "refwd"
    return "bwd" if "transpose(" in op_name else "fwd"


def program_op_names(text: str):
    """``({instruction: op_name}, inherited, straddling)`` of a compiled
    program's text, over every computation.  An instruction the compiler
    made has no ``op_name`` of its own (a layout copy, a slice of the
    scan's stack): a fusion then takes the last one inside its fused
    computation, and what still has none takes that of the instruction
    whose body it runs in (the ``while`` of its scanned group);
    ``inherited`` holds both kinds.  ``straddling``: the fusions whose
    fused computation holds instructions of more than one pass."""
    computations, entry, inside = {}, None, None
    for line in text.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            inside = computations.setdefault(start.group(2), [])
            entry = start.group(2) if start.group(1) else entry
            continue
        found = _INSTRUCTION.match(line)
        if not found or inside is None:
            continue
        own = _OP_NAME.search(line)
        called = [
            c.strip().lstrip("%") for one, many in _CALLED.findall(line)
            for c in ([one] if one else many.split(","))
        ]
        inside.append((found.group(1), own.group(1) if own else "",
                       called, " fusion(" in line))
    op_names, inherited, straddling = {}, set(), set()

    def walk(computation, context):
        for name, own, called, fusion in computations.pop(computation, ()):
            inner = [o for _, o, _, _ in computations.get(called[0], ())
                     if o] if fusion and called else []
            op_name = own or (inner[-1] if inner else context)
            if op_name:
                op_names[name] = op_name
            if not own:
                inherited.add(name)
            if len({pass_of(o) for o in inner}) > 1:
                straddling.add(name)
            for callee in () if fusion else called:
                walk(callee, op_name)

    if entry:
        walk(entry, "")
    return op_names, inherited, straddling


def lowered_step_text(family) -> str:
    """The compiled step of a family that hands over no
    ``step_program_text``, lowered from the shapes of the family's own
    parts."""
    import jax
    import jax.numpy as jnp

    tree = jax.eval_shape(family.init_global)
    opt = jax.eval_shape(family._init_opt, tree)
    base = jax.eval_shape(family._make_base, family.base_key())
    ids = jax.ShapeDtypeStruct((family.batch, family.seq), jnp.int32)
    step = getattr(family._step, "jitted", family._step)
    return step.lower(tree, opt, base, ids).compile().as_text()


def step_runs(ctx):
    """``[(module start, module end, [(start, end, instruction), ...]),
    ...]``: the step program's executions inside the profiled window
    with the device operations each ran, or ``None``."""
    path = ctx.trace and xplane.find_xplane(ctx.run.profile_dir)
    if not path:
        return None
    from benchmark.layer_metrics.fold_roofline import profiled_window_ns

    profile = xplane.load(path)
    window = profiled_window_ns(ctx.run, profile)
    runs = []
    modules = xplane.device_ops(profile, xplane.MODULES_LINE)
    for plane, ops in xplane.device_ops(profile).items():
        i = 0
        for m0, m1, name in modules.get(plane, ()):
            if not name.startswith(STEP_MODULES) or (
                window is not None and not window[0] <= m0 < window[1]
            ):
                continue
            while i < len(ops) and ops[i][0] < m0:
                i += 1
            j = i
            while j < len(ops) and ops[j][0] < m1:
                j += 1
            runs.append((m0, m1, [
                (s, e, instruction_of(op)) for s, e, op in ops[i:j]
            ]))
            i = j
    return runs or None


def exclusive_times(ops, end):
    """``[(instruction, ns), ...]``: every instant up to ``end`` goes to
    the operation that started last among those running, so the sum is
    the union of the operations' intervals whatever their nesting.
    ``moe_step_share.self_times`` gives the same where operations nest
    properly; where two overlap without nesting (a loop whose last child
    outlives it, an asynchronous copy beside a fusion) it charges the
    overlap twice, and a share of the program's time could pass 100%."""
    out, stack, cursor = [], [], 0  # stack: [end, instruction], by start

    def advance(upto):
        nonlocal cursor
        while stack and cursor < upto:
            if stack[-1][0] <= cursor:
                stack.pop()
                continue
            stop = min(stack[-1][0], upto)
            out.append((stack[-1][1], stop - cursor))
            cursor = stop
        cursor = max(cursor, upto)

    for s, e, name in ops:
        advance(min(s, end))
        stack.append([min(e, end), name])
    advance(end)
    return out


def account_of(runs, op_names, inherited=(), straddling=()) -> dict:
    """Seconds over ``runs``: ``program``, ``passes`` (``{scope: {pass:
    seconds}}``), of those seconds ``inherited`` (no ``op_name`` of
    the instruction's own) and ``straddling``, and ``overcount``: what
    ``self_times`` would have charged twice."""
    passes: dict = {}
    unclear = {"inherited": 0.0, "straddling": 0.0, "overcount": 0.0}
    for _, end, ops in runs:
        unclear["overcount"] += sum(ns for _, ns in self_times(ops)) / 1e9
        for name, ns in exclusive_times(ops, end):
            unclear["overcount"] -= ns / 1e9
            op_name = op_names.get(name, "")
            row = passes.setdefault(
                scope_of(op_name), dict.fromkeys(PASSES, 0.0)
            )
            row[pass_of(op_name)] += ns / 1e9
            if name in inherited or not op_name:
                unclear["inherited"] += ns / 1e9
            if name in straddling:
                unclear["straddling"] += ns / 1e9
    return {
        "steps": len(runs),
        "program": sum(m1 - m0 for m0, m1, _ in runs) / 1e9,
        "passes": passes, **unclear,
    }


def step_account(ctx):
    """:func:`account_of` the run's step program, logged once; ``None``
    where the run has no device trace or ran no such program (both
    readers of the account share it)."""
    if hasattr(ctx, "_step_account"):
        return ctx._step_account
    ctx._step_account = None
    runs = step_runs(ctx)
    if not runs:
        return None
    family = ctx.family
    text_of = getattr(family, "step_program_text", None)
    text = text_of() if text_of else lowered_step_text(family)
    ctx._step_account = found = account_of(runs, *program_op_names(text))
    from benchmark.reduce import log

    per_step = lambda s: round(s / found["steps"] * 1e3, 3)
    log(step_scopes={k: per_step(sum(v.values()))
                     for k, v in sorted(found["passes"].items())},
        step_passes={k: {p: per_step(s) for p, s in v.items()}
                     for k, v in sorted(found["passes"].items())},
        step_unclear={"no_own_op_name_ms": per_step(found["inherited"]),
                      "pass_straddling_ms": per_step(found["straddling"]),
                      "self_time_overcount_ms": per_step(found["overcount"])},
        step_program_ms=per_step(found["program"]), steps=found["steps"])
    return found


def read(ctx):
    found = step_account(ctx)
    if not found or not found["program"]:
        return None
    named = sum(
        sum(row.values()) for scope, row in found["passes"].items()
        if scope != OTHER
    )
    return 100.0 * named / found["program"]
