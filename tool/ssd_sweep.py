#!/usr/bin/env python3
"""Time the chunked-scan kernels alone, on the chip, by head block.

    python tool/ssd_sweep.py [--blocks 4,8,16,32] [--out FILE]
    python tool/ssd_sweep.py --lowering            # no chip needed

One call of ``rayfed_tpu.ops.ssd`` at the state-space hybrid cell's
shape (``[1, 8192, 64, 64]``, state 128, one group, chunks of 256, bf16
operands): milliseconds of the forward kernel and of the backward kernel
for each head block (the heads a grid step walks), their share of the
MXU's peak by ``scan_flops`` (forward once; backward twice that), and
the block ``ssd.head_block`` chooses from shapes.  The last row is the
whole ``ssd_scan`` under a layer's checkpoint, differentiated (forward,
forward again, backward, and the ``jax.numpy`` around the kernels), as
a step runs it.

A number is the best mean over ``--repeats`` batches of ``--iters``
back-to-back calls ending in ``block_until_ready``.  Needs the chip:
``--tiny`` rehearses the control flow anywhere (interpret mode, a toy
shape) and its times mean nothing.  ``--lowering`` prints the equations
and matrix products in the two kernels' jaxprs (what a process pays to
trace them; ``tests/test_granite_hybrid.py`` holds the counts).  Run by
no cell.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from rayfed_tpu.ops import ssd
from tool.flash_sweep import best_ms, kernel_counts

PEAK_FLOPS = 197e12  # bf16, one v5e chip
# batch, tokens, heads, head width, state, groups, chunk
CELL = (1, 8192, 64, 64, 128, 1, 256)
TINY = (1, 64, 4, 8, 16, 2, 16)


def scan_inputs(shape, dtype=jnp.bfloat16):
    """Inputs in the regime a mixer gives the scan (``dt`` a softplus
    about a small bias, ``A`` in [-16, -1])."""
    b, t, h, p, n, g, _ = shape
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    args = (
        jax.random.normal(k[0], (b, t, h, p), dtype),
        jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 4.0),
        -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0),
        jax.random.normal(k[3], (b, t, g, n), dtype) * 0.3,
        jax.random.normal(k[4], (b, t, g, n), dtype) * 0.3,
        jnp.ones((h,)),
    )
    return args, jax.random.normal(k[5], (b, t, h, p), dtype)


def scan_grad(shape):
    """(the gradient of a checkpointed ``ssd_scan`` in all six inputs,
    its arguments): what a remat layer's backward pass runs."""
    args, w = scan_inputs(shape)
    layer = jax.checkpoint(lambda *a: ssd.ssd_scan(*a, chunk=shape[-1]))

    def loss(*a):
        return jnp.sum(layer(*a).astype(jnp.float32) * w.astype(jnp.float32))

    return jax.grad(loss, argnums=tuple(range(6))), args


def run(shape, blocks, iters, repeats, interpret):
    b, t, h, p, n, g, chunk = shape
    (x, dt, A, B, C, D), dy = scan_inputs(shape)
    flat = lambda v: v.reshape(b, t, -1)
    rows, whole = jax.jit(ssd._token_rows, static_argnums=3)(dt, A, D, chunk)
    operands = (flat(x), rows, whole, flat(B), flat(C))
    chosen = ssd.head_block(h, g, p, n, chunk, x.dtype.itemsize, interpret)
    forward = ssd.scan_flops(b * t, h, p, n, g, chunk)
    results, first = [], None
    for hb in blocks:
        kw = dict(plan=ssd._Plan(chunk, hb, p, g, interpret))
        try:
            y, before = ssd._forward(*operands, **kw)
            fwd = best_ms(
                lambda *a: ssd._forward(*a, **kw), operands, iters, repeats
            )
            grads = ssd._backward(*operands, before, flat(dy), **kw)
            bwd = best_ms(
                lambda *a: ssd._backward(*a, **kw),
                operands + (before, flat(dy)), iters, repeats,
            )
        except Exception as exc:  # noqa: BLE001 — a block VMEM cannot hold
            results.append({"head_block": hb, "error": repr(exc)[:300]})
            continue
        outs = [jnp.asarray(v, jnp.float32) for v in (y, *grads)]
        first = first or outs
        results.append({
            "head_block": hb, "chosen": hb == chosen,
            "fwd_ms": fwd, "bwd_ms": bwd,
            "fwd_peak_share": forward / (fwd * 1e-3) / PEAK_FLOPS,
            "bwd_peak_share": 2 * forward / (bwd * 1e-3) / PEAK_FLOPS,
            "vmem_bytes": ssd.step_vmem_bytes(hb, p, n, chunk, x.dtype.itemsize),
            # every block computes the same thing: the worst relative RMS
            # distance to the first block's y, dx, d rows, d whole, dB, dC
            "rel_rms_vs_first": max(
                float(jnp.sqrt(jnp.mean((a - f) ** 2) / jnp.mean(f ** 2)))
                for a, f in zip(outs, first)
            ),
        })
    grad, args = scan_grad(shape)
    whole_call = best_ms(jax.jit(grad), args, iters, repeats)
    return {
        "shape": shape, "chosen": chosen, "blocks": results,
        "checkpointed_grad_ms": whole_call,
        "checkpointed_grad_peak_share":
            3 * forward / (whole_call * 1e-3) / PEAK_FLOPS,
    }


def table(report):
    lines = [
        "| head block | fwd ms | bwd ms | fwd share of peak | bwd share of "
        "peak | step VMEM MB |",
        "| --- | --- | --- | --- | --- | --- |",
    ]
    for r in report["blocks"]:
        if "error" in r:
            lines.append(f"| {r['head_block']} | {r['error']} | | | | |")
            continue
        mark = " (chosen)" if r["chosen"] else ""
        lines.append(
            f"| {r['head_block']}{mark} | {r['fwd_ms']:.3f} | "
            f"{r['bwd_ms']:.3f} | {100 * r['fwd_peak_share']:.1f}% | "
            f"{100 * r['bwd_peak_share']:.1f}% | "
            f"{r['vmem_bytes'] / 2 ** 20:.1f} |"
        )
    lines.append(
        f"| `ssd_scan` under a checkpoint, differentiated (block "
        f"{report['chosen']}) | {report['checkpointed_grad_ms']:.3f} in all "
        f"| | {100 * report['checkpointed_grad_peak_share']:.1f}% | | |"
    )
    return "\n".join(lines)


def lowering():
    """Equations (matrix products) in the forward and backward kernels'
    jaxprs at the cell's shape, compiled mode; nothing is compiled."""
    ssd._flash._interpret_default = lambda: False
    grad, args = scan_grad(CELL)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    counts = kernel_counts(jax.make_jaxpr(grad)(*shapes).jaxpr)
    # a checkpoint's gradient holds the forward kernel once (its second
    # run) and the backward kernel
    print(json.dumps({"kernels": ["fwd", "bwd"], "equations_products": counts}))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lowering", action="store_true")
    ap.add_argument("--blocks", default="4,8,16,32")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/ssd_sweep.json")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.lowering:
        return lowering()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit(f"ssd_sweep times the chip; found {device.platform} "
                 f"(--tiny rehearses the control flow)")
    report = run(
        TINY if args.tiny else CELL,
        [1, 2] if args.tiny else [int(n) for n in args.blocks.split(",")],
        args.iters, args.repeats, interpret=device.platform != "tpu",
    )
    report["device"] = {"platform": device.platform, "kind": device.device_kind}
    report["measurement"] = device.platform == "tpu"
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["device"]))
    if not report["measurement"]:
        print("REHEARSAL on", device.platform, "- the times below mean nothing")
    print(table(report))
    print("relative RMS distance to the first block:",
          {r["head_block"]: r.get("rel_rms_vs_first") for r in report["blocks"]})


if __name__ == "__main__":
    main()
