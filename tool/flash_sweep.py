#!/usr/bin/env python3
"""Time the three flash kernels alone, on the chip, at the benchmark's shapes.

    python tool/flash_sweep.py [--parent PATH] [--splits 1,2] [--out FILE]
    python tool/flash_sweep.py --lowering [--parent PATH]      # no chip needed

For each shape (the two Trinity-Mini attention kinds and Mistral's at
8,192 tokens, and the wire cells' 16 x 512) it prints milliseconds a
call of the forward, dQ and dK/dV kernels, and what `block_schedule`
says the call computes:

- ``parent``: the kernels of ``--parent`` (another checkout's
  ``rayfed_tpu/ops/flash_attention.py``, loaded by path; left out where
  the file is absent), fed K/V repeated to the query heads as that
  checkout's models fed them;
- ``split n``: this tree's kernels on the grouped K/V with `SPLIT` set to
  ``n`` (1 = straddling blocks masked whole; the tree runs `SPLIT` 2);
- ``splash``: `jax.experimental.pallas.ops.tpu.splash_attention`, the
  block-sparse kernel jax ships, as a yardstick (MQA kernel vmapped over
  the K/V heads; its dQ and dK/dV are a gradient's time less the
  forward's, since its backward is not callable alone).

A number is the best mean over ``--repeats`` batches of ``--iters``
back-to-back calls ending in ``block_until_ready``.  Needs the chip:
``--tiny`` rehearses the control flow anywhere (interpret mode, toy
shapes) and its times mean nothing.

``--lowering`` measures what every process pays at set-up BEFORE it can
ask the compile cache: seconds to trace and lower (nothing is compiled)
one forward + backward at each shape, the Mistral cells' LoRA step and
Trinity-Mini's, for a v5e described as ``tests/test_tpu_compile.py``
describes it, with the equations and matrix products in the three
kernels' jaxprs.  Each tree (this one, and ``--parent``'s) is measured
in a process of its own, on the CPU, in the same order of work.  A
kernel whose body is traced 13 times cost two cells their ``setup_s``
bound (ledger, PR 29); ``tests/test_flash_attention.py`` holds the
counts, this mode shows the seconds.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# name -> (batch, tokens, query heads, K/V heads, head width, window)
SHAPES = {
    "trinity window 2048": (1, 8192, 32, 4, 128, 2048),
    "trinity full": (1, 8192, 32, 4, 128, None),
    "mistral window 4096": (1, 8192, 32, 8, 128, 4096),
    "wire 16x512 window 4096": (16, 512, 32, 8, 128, 4096),
}
TINY = {
    "tiny window": (1, 256, 4, 2, 64, 96),
    "tiny full": (2, 128, 4, 1, 64, None),
}
# name -> (batch, tokens, heads, width without position, rotary width,
# value width): latent attention, whose key is a part a head of its own
# and ONE rotary head all query heads share.
LATENT_SHAPES = {
    "kimi latent 128+64|128": (1, 8192, 64, 128, 64, 128),
}
TINY_LATENT = {"tiny latent 16+8|16": (1, 256, 4, 16, 8, 16)}
KERNELS = ("fwd", "dq", "dkv")


def load_module(path):
    spec = importlib.util.spec_from_file_location("parent_flash_attention", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def best_ms(fn, args, iters, repeats):
    jax.block_until_ready(fn(*args))  # compiles
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def repo_kernels(fa, window, interpret, block=1024):
    """``{kernel: jitted fn}`` over (q, k, v, o, lse, do, lse_delta_b) of
    a flash_attention module's internals; an unused kernel's
    ``pallas_call`` is dead code the compiler drops."""
    kw = dict(causal=True, block_q=block, block_k=block, q_offset=0,
              kv_offset=0, interpret=interpret, window=window)

    def fwd(q, k, v, o, lse, do, ld):
        return fa._flash_forward(q, k, v, scale=q.shape[-1] ** -0.5, **kw)

    def bwd(q, k, v, o, lse, do, ld):
        return fa._flash_backward_pallas(
            q, k, v, o, lse, do, scale=q.shape[-1] ** -0.5, lse_delta_b=ld,
            **kw,
        )

    return {
        "fwd": jax.jit(fwd),
        "dq": jax.jit(lambda *a: bwd(*a)[0]),
        "dkv": jax.jit(lambda *a: bwd(*a)[1:]),
    }


def splash_times(shape, q, k, v, do, iters, repeats, interpret):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    b, t, h, kv, d, window = shape
    group = h // kv
    block = min(1024, t)
    one = (
        sm.CausalMask((t, t)) if window is None
        else sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0)
    )
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=min(512, block),
        block_q_dkv=block, block_kv_dkv=block,
        block_kv_dkv_compute=min(512, block),
        block_q_dq=block, block_kv_dq=block,
    )
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([one] * group), block_sizes=sizes,
        interpret=interpret,
    )
    attend = jax.vmap(kernel)  # over batch x K/V heads
    qs = (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
    qs = qs.reshape(b * kv, group, t, d)
    dos = do.reshape(b * kv, group, t, d)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) * dos)

    fwd = best_ms(jax.jit(attend), (qs, k, v), iters, repeats)
    with_dq = best_ms(jax.jit(jax.grad(loss, 0)), (qs, k, v), iters, repeats)
    with_dkv = best_ms(
        jax.jit(jax.grad(loss, (1, 2))), (qs, k, v), iters, repeats
    )
    return {"fwd": fwd, "dq": with_dq - fwd, "dkv": with_dkv - fwd}


def run_shape(name, shape, fa, parent, splits, splash, iters, repeats,
              interpret):
    b, t, h, kv, d, window = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b * h, t, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b * kv, t, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b * kv, t, d), jnp.bfloat16)
    do = jax.random.normal(keys[3], (b * h, t, d), jnp.bfloat16)
    group = h // kv
    k_rep, v_rep = (jnp.repeat(x, group, axis=0) for x in (k, v))
    block = fa._fit_block(t, 1024)

    rows, outputs = {}, {}

    def measure(label, module, k, v):
        fns = repo_kernels(module, window, interpret, block)
        o, lse = fns["fwd"](q, k, v, None, None, None, None)
        args = (q, k, v, o, lse, do, module._lse_delta_lanes(o, lse, do))
        rows[label] = {
            kern: best_ms(fns[kern], args, iters, repeats) for kern in KERNELS
        }
        dk, dv = fns["dkv"](*args)
        if dk.shape[0] != k_rep.shape[0] // group:  # one per query head
            dk, dv = (
                x.astype(jnp.float32).reshape(b * kv, group, t, d).sum(1)
                for x in (dk, dv)
            )
        outputs[label] = [
            np.asarray(x, np.float32) for x in (o, fns["dq"](*args), dk, dv)
        ]

    if parent is not None:
        measure("parent", parent, k_rep, v_rep)
    schedules = {}
    for n in splits:
        fa.SPLIT = n
        jax.clear_caches()  # the kernels' own jit does not key on SPLIT
        measure(f"split {n}", fa, k, v)
        schedules[f"split {n}"] = fa.block_schedule(
            t, t, block, block, n, True, window, 0, 0
        )._asdict()
    try:
        if splash:
            rows["splash"] = splash_times(
                shape, q, k, v, do, iters, repeats, interpret
            )
    except Exception as exc:  # noqa: BLE001 — a yardstick, not the subject
        rows["splash"] = {"error": repr(exc)[:200]}

    # Every variant computes the same thing: relative RMS error against
    # the first, the worst of o, dq, dk, dv (bf16 outputs: 0.2-0.4%).
    first = next(iter(outputs.values()))
    agree = {
        label: max(
            float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))
            for a, b in zip(outs, first)
        )
        for label, outs in outputs.items()
    }
    return {"shape": name, "ms": rows, "schedule": schedules,
            "rel_rms_vs_first": agree}


def run_latent(name, shape, fa, iters, repeats, interpret):
    """Both forms of the latent attention's kernels at one shape.

    ``split``: the kernels are given the score's two parts and add the
    two products in VMEM; the shared rotary key is read by its one head
    and its gradient summed over the query heads in the dK/dV kernel.
    ``plain``: k concatenated to ``[B, T, H, nope + rope]`` first (the
    rotary head copied to every head), one product over the whole
    width.  ``grad`` is a whole forward + backward of ``flash_attention``
    from the five arrays a latent layer makes, so the plain form pays
    for its concatenation and for summing the rotary key's gradient
    over the heads outside the kernels."""
    from rayfed_tpu.ops.attention import score_parts

    b, t, h, nope, rope, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    q_nope = jax.random.normal(keys[0], (b, t, h, nope), bf)
    q_pe = jax.random.normal(keys[1], (b, t, h, rope), bf)
    k_nope = jax.random.normal(keys[2], (b, t, h, nope), bf)
    k_pe = jax.random.normal(keys[3], (b, t, 1, rope), bf)
    v = jax.random.normal(keys[4], (b, t, h, dv), bf)
    do = jax.random.normal(keys[5], (b, t, h, dv), bf)
    parts = (q_nope, q_pe, k_nope, k_pe, v)
    block = fa._fit_block(t, 1024)
    scale = (nope + rope) ** -0.5
    kw = dict(scale=scale, causal=True, block_q=block, block_k=block,
              q_offset=0, kv_offset=0, interpret=interpret, window=None)
    bht = lambda x: jax.tree_util.tree_map(fa._bthd_to_bht, x)

    def qk(form, q_nope, q_pe, k_nope, k_pe, v):
        if form == "split":
            return (q_nope, q_pe), (k_nope, k_pe)
        return score_parts((q_nope, q_pe), (k_nope, k_pe), v)

    rows, outputs = {}, {}
    for form in ("split", "plain"):
        q, k = bht(qk(form, *parts))
        fns = {
            "fwd": jax.jit(lambda q, k, v, *_: fa._flash_forward(q, k, v, **kw)),
            "dq": jax.jit(lambda q, k, v, o, lse, do, ld: fa._flash_backward_pallas(
                q, k, v, o, lse, do, lse_delta_b=ld, **kw)[0]),
            "dkv": jax.jit(lambda q, k, v, o, lse, do, ld: fa._flash_backward_pallas(
                q, k, v, o, lse, do, lse_delta_b=ld, **kw)[1:]),
        }
        v_, do_ = bht(v), bht(do)
        o, lse = fns["fwd"](q, k, v_)
        args = (q, k, v_, o, lse, do_, fa._lse_delta_lanes(o, lse, do_))
        rows[form] = {
            kern: best_ms(fns[kern], args, iters, repeats) for kern in KERNELS
        }

        def loss(*parts, form=form):
            q, k = qk(form, *parts)
            out = fa.flash_attention(
                q, k, parts[4], causal=True, sm_scale=scale,
                interpret=interpret,
            )
            return jnp.sum(out.astype(jnp.float32) * do)

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        rows[form]["grad"] = best_ms(grad, parts, iters, repeats)
        outputs[form] = [np.asarray(x, np.float32) for x in grad(*parts)]
    first = outputs["split"]
    agree = {
        form: max(
            float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))
            for a, b in zip(outs, first)
        )
        for form, outs in outputs.items()
    }
    sched = fa.block_schedule(t, t, block, block, fa.SPLIT, True, None, 0, 0)
    return {"shape": name, "ms": rows, "rel_rms_vs_first": agree,
            "schedule": {form: sched._asdict() for form in rows}}


def table(results):
    lines = [
        "| shape | kernels | fwd ms | dQ ms | dK/dV ms | grid steps a head "
        "(computing nothing) | blocks unmasked | "
        "sub-tiles unmasked / masked / skipped | useful share |",
        "| --- | --- | --- | --- | --- | --- | --- | --- | --- |",
    ]
    for res in results:
        for label, ms in res["ms"].items():
            s = res["schedule"].get(label)
            steps = s and s["grid"][0] * s["grid"][1]
            if label == "parent" and "split 1" in res["schedule"]:
                # What split 1 computes, on the whole square of blocks.
                s = res["schedule"]["split 1"]
                steps = s["grid"][0] * s["grid_dkv"][0]
            cells = (
                [f"{ms[k]:.3f}" for k in KERNELS] if "error" not in ms
                else [ms["error"], "", ""]
            )
            if "grad" in ms:  # a latent form: the whole forward + backward
                cells[-1] += f" (fwd + bwd from the parts {ms['grad']:.3f})"
            idle = s and steps - s["grid"][0] * s["grid"][1] + s["steps_skipped"]
            sched = (
                [f"{steps} ({idle})",
                 str(s["blocks_unmasked"]),
                 f"{s['tiles_unmasked']} / {s['tiles_masked']} / "
                 f"{s['tiles_skipped']} of {s['sub_q']}x{s['sub_k']}",
                 f"{100 * s['useful_share']:.1f}%"] if s else ["", "", "", ""]
            )
            row = [res["shape"], label] + cells + sched
            lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines)


# --- what a process pays to trace and lower, before any compile cache ------

STEP_CELLS = (
    "mistral-7b-v0.1-d6.lora-2p",
    "mistral-7b-v0.1-d6.qlora-wire-uint8",
    "trinity-mini-ep8.lora-all-linear-2p",
    "kimi-k2.7-code-ep32.lora-all-linear-2p",
    "granite-4.0-h-micro-d20.lora-all-linear-2p",
)
TRACE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for x in value if isinstance(value, (list, tuple)) else [value]:
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def count_equations(jaxpr):
    """(equations, matrix products) in a jaxpr and all it holds."""
    n = dots = 0
    for eqn in jaxpr.eqns:
        n += 1
        dots += eqn.primitive.name == "dot_general"
        for sub in _sub_jaxprs(eqn):
            a, b = count_equations(sub)
            n, dots = n + a, dots + b
    return n, dots


def kernel_counts(jaxpr):
    """``count_equations`` of every ``pallas_call``'s kernel in a jaxpr,
    in program order (forward, dQ, dK/dV for one gradient)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(count_equations(eqn.params["jaxpr"]))
        else:
            for sub in _sub_jaxprs(eqn):
                out.extend(kernel_counts(sub))
    return out


def attention_grad(fa, shape, grouped=True):
    """(a forward + backward of ``fa.flash_attention`` compiled-mode at
    ``shape``, its abstract arguments).  ``grouped`` False repeats K/V to
    the query heads first, as a tree whose kernels want equal head counts
    had its models do."""
    b, t, h, kv, d, window = shape
    group = 1 if grouped else h // kv

    def loss(q, k, v):
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        out = fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=False
        )
        return jnp.sum(out.astype(jnp.float32))

    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, t, kv, d), jnp.bfloat16)
    return jax.grad(loss, argnums=(0, 1, 2)), (q, k, k)


def latent_attention_grad(fa, shape, split=True):
    """As :func:`attention_grad`, for a latent shape in either form."""
    from rayfed_tpu.ops.attention import score_parts

    b, t, h, nope, rope, dv = shape

    def loss(q_nope, q_pe, k_nope, k_pe, v):
        q, k = (q_nope, q_pe), (k_nope, k_pe)
        if not split:
            q, k = score_parts(q, k, v)
        out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    S = lambda heads, d: jax.ShapeDtypeStruct((b, t, heads, d), jnp.bfloat16)
    args = (S(h, nope), S(h, rope), S(h, nope), S(1, rope), S(h, dv))
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), args


def _timed(lower, events):
    """Wall seconds of ``lower()`` beside jax's own trace and lowering
    durations inside it."""
    events.clear()
    t0 = time.perf_counter()
    lower()
    took = {"wall_s": time.perf_counter() - t0, "trace_s": 0.0, "lower_s": 0.0}
    for name, seconds in events:
        took[TRACE_EVENTS[name]] += seconds
    return took


def _step_lowering(name, on_chip):
    """A function that builds ``name``'s family anew (so nothing traced
    is found again) and lowers its step from shapes (returned lowered:
    ``tests/test_tpu_compile.py`` compiles it)."""
    from benchmark import harness

    cell = harness.load_cell(name)
    family = importlib.import_module(
        "benchmark.families." + cell["config_data"]["run"]["family"]
    )

    def lower():
        fam = family.build(cell["config_data"], cell["job"], 0)
        tree = jax.eval_shape(fam.init_global)
        if hasattr(fam, "base_shapes"):
            from rayfed_tpu.models import llama

            base, init_opt = fam.base_shapes(), llama.init_adam
            step = fam._step.jitted
        else:
            key = jax.random.PRNGKey(0)
            base = jax.eval_shape(lambda: fam._llama.init_llama(key, fam.cfg))
            init_opt, step = fam._llama.init_adam, fam._step
        opt = jax.eval_shape(init_opt, tree)
        ids = jax.ShapeDtypeStruct((fam.batch, fam.seq), jnp.int32)
        return step.lower(*on_chip((tree, opt, base, ids)))

    return lower


def lowering_child(root, repeats):
    """Measure the tree at ``root`` (its ``rayfed_tpu`` and ``benchmark``
    are the ones this process imports); prints one JSON line."""
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
    )
    fa = importlib.import_module("rayfed_tpu.ops.flash_attention")
    attention = importlib.import_module("rayfed_tpu.ops.attention")
    moe = importlib.import_module("rayfed_tpu.models.moe")
    # Both ask jax.default_backend(), the CPU here: steer them to what
    # the chip runs, as the compile tests do.
    fa._interpret_default = lambda: False
    moe._grouped_impl = lambda: "megablox"
    grouped = hasattr(attention, "kv_group")

    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, seconds, **kw: (
            events.append((name, seconds)) if name in TRACE_EVENTS else None
        )
    )
    work = {}
    for name, shape in SHAPES.items():
        work["attention " + name] = lambda shape=shape: (
            lambda grad, args: jax.jit(grad).lower(*on_chip(args))
        )(*attention_grad(fa, shape, grouped))
    latent = hasattr(attention, "score_parts")  # a tree that has the kind
    for name, shape in LATENT_SHAPES.items() if latent else ():
        work["attention " + name] = lambda shape=shape: (
            lambda grad, args: jax.jit(grad).lower(*on_chip(args))
        )(*latent_attention_grad(fa, shape))
    for name in STEP_CELLS:
        if os.path.exists(os.path.join(root, "benchmark", "workloads", name + ".json")):
            work["step " + name] = _step_lowering(name, on_chip)
    report = {"kernels": {}, "first": {}, "again": {}}
    for name, shape in SHAPES.items():
        grad, args = attention_grad(fa, shape, grouped)
        report["kernels"][name] = kernel_counts(jax.make_jaxpr(grad)(*args).jaxpr)
    for name, shape in LATENT_SHAPES.items() if latent else ():
        for form in ("split", "plain"):
            grad, args = latent_attention_grad(fa, shape, form == "split")
            report["kernels"][f"{name} {form}"] = kernel_counts(
                jax.make_jaxpr(grad)(*args).jaxpr
            )
    for name, lower in work.items():
        lower()  # pays the imports
        first, again = [], []
        for _ in range(repeats):
            # What the first party's thread pays, with nothing traced
            # before it, and what the next one pays for the same program
            # built anew (jax's own caches, and the kernels' where the
            # tree keeps one, now hold what the first traced).
            jax.clear_caches()
            first.append(_timed(lower, events))
            again.append(_timed(lower, events))
        for key, runs in (("first", first), ("again", again)):
            report[key][name] = {
                k: [round(min(r[k] for r in runs), 3),
                    round(max(r[k] for r in runs), 3)]
                for k in ("wall_s", "trace_s", "lower_s")
            }
    print(json.dumps(report))


def lowering(args):
    """Run ``lowering_child`` on each tree, print the tables."""
    import subprocess

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trees = {"tree": here}
    if os.path.exists(args.parent):  # .../rayfed_tpu/ops/flash_attention.py
        trees = {
            "parent": os.path.abspath(os.path.join(args.parent, "../../..")),
            **trees,
        }
    reports = {}
    for label, root in trees.items():
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--lowering-child",
             root, "--repeats", str(args.repeats)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root, check=True,
            stdout=subprocess.PIPE, text=True,
        ).stdout
        reports[label] = json.loads(out.strip().splitlines()[-1])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(reports, f, indent=1)
    labels = list(reports)
    print("Trace + lower, wall seconds (of which jax's own lowering "
          "duration), least-most of", args.repeats, "runs; nothing compiled.")
    print("first: after jax.clear_caches(); again: the same program built "
          "anew straight after, as a process's next party builds it")
    print("| what | " + " | ".join(
        f"{label} {key}" for label in labels for key in ("first", "again")
    ) + " |")
    print("| --- |" + " --- |" * (2 * len(labels)))
    for name in reports[labels[-1]]["first"]:
        cells = []
        for label in labels:
            for key in ("first", "again"):
                s = reports[label][key].get(name)
                cells.append(
                    f"{s['wall_s'][0]:.2f}-{s['wall_s'][1]:.2f} "
                    f"({s['lower_s'][0]:.2f})" if s else "-"
                )
        print(f"| {name} | " + " | ".join(cells) + " |")
    print()
    print("Equations (matrix products) in the kernels' jaxprs: fwd / dQ / dK/dV")
    print("| shape | " + " | ".join(labels) + " |")
    print("| --- |" + " --- |" * len(labels))
    for name in reports[labels[-1]]["kernels"]:
        cells = [
            " / ".join(f"{n} ({dots})" for n, dots in reports[label]["kernels"][name])
            + f" = {sum(n for n, _ in reports[label]['kernels'][name])}"
            if name in reports[label]["kernels"] else "-"
            for label in labels
        ]
        print(f"| {name} | " + " | ".join(cells) + " |")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lowering", action="store_true")
    ap.add_argument("--lowering-child", metavar="ROOT", help=argparse.SUPPRESS)
    ap.add_argument(
        "--parent",
        default=".bench_scratch/parent/rayfed_tpu/ops/flash_attention.py",
    )
    ap.add_argument("--splits", default="1,2")
    ap.add_argument("--shapes", default="", help="substring filter")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/flash_sweep.json")
    ap.add_argument("--no-splash", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.lowering_child:
        return lowering_child(args.lowering_child, args.repeats)
    if args.lowering:
        if args.out == ap.get_default("out"):
            args.out = "chiprun_out/flash_lowering.json"
        return lowering(args)

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit(f"flash_sweep times the chip; found {device.platform} "
                 f"(--tiny rehearses the control flow)")
    fa = importlib.import_module("rayfed_tpu.ops.flash_attention")
    parent = load_module(args.parent) if os.path.exists(args.parent) else None
    shapes = TINY if args.tiny else SHAPES
    results = [
        run_shape(
            name, shape, fa, parent, [int(n) for n in args.splits.split(",")],
            not args.no_splash, args.iters, args.repeats,
            interpret=device.platform != "tpu",
        )
        for name, shape in shapes.items() if args.shapes in name
    ]
    latent = TINY_LATENT if args.tiny else LATENT_SHAPES
    results += [
        run_latent(name, shape, fa, args.iters, args.repeats,
                   interpret=device.platform != "tpu")
        for name, shape in latent.items() if args.shapes in name
    ]
    report = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "measurement": device.platform == "tpu",
        "results": results,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["device"]))
    if not report["measurement"]:
        print("REHEARSAL on", device.platform, "- the times below mean nothing")
    print(table(results))
    for res in results:
        print(res["shape"], "relative RMS error vs the first variant:",
              res["rel_rms_vs_first"])


if __name__ == "__main__":
    main()
