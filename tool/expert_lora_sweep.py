#!/usr/bin/env python3
"""Time the routed experts' LoRA bypass alone, on the chip, in both forms.

    python tool/expert_lora_sweep.py [--out FILE]
    python tool/expert_lora_sweep.py --tiny        # rehearsal, any backend

One chunk of the Nemotron cell's sorted rows (56,320 rows, of which
22,528 are held experts' and the rest padding) through one expert matrix
with its adapters, as ``models/moe.py::_expert_linear`` runs it: the up
matrix (latent 1,024 -> 2,688) and the down matrix (2,688 -> 1,024) of
64 held experts, rank-8 adapters, bf16.  For each matrix, milliseconds
of the forward call and of forward and backward together (``jax.vjp``
in ``x``, ``A`` and ``B``, as the chunk loop's backward rule runs it),
for the base grouped product alone and with the bypass in each form:
``dense`` (every row times all 64 experts' ``A`` side by side, 512
wide) and ``block`` (grouped products over blocks of 16 experts, what
``moe.lora_blocks`` picks here).  What the bypass costs is the
difference to the base row.  ``need`` is the block form's FLOPs over
the held rows at the bf16 peak.

A number is the best mean over ``--repeats`` batches of ``--iters``
back-to-back calls ending in ``block_until_ready``.  Needs the chip:
``--tiny`` rehearses the control flow anywhere (interpret mode on a CPU,
a toy shape) and its times mean nothing.  Run by no cell.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from rayfed_tpu.models import moe
from tool.flash_sweep import best_ms

PEAK_FLOPS = 197e12  # bf16, one v5e chip
# chunk rows, held rows, latent, expert width, held experts, rank
CELL = (56320, 22528, 1024, 2688, 64, 8)
TINY = (1024, 384, 128, 256, 64, 8)


def chunk(shape, d_in, d_out, seed=0):
    """Sorted rows of one chunk (held experts' rows by expert, then the
    padding rows), one expert matrix and its adapters, in bf16."""
    rows, held, _, _, g, rank = shape
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    counts = np.random.default_rng(seed).multinomial(held, np.full(g, 1 / g))
    sizes = jnp.asarray(np.append(counts, rows - held), jnp.int32)
    row_expert = jnp.asarray(np.repeat(np.arange(g + 1), np.append(
        counts, rows - held)), jnp.int32)
    bf = jnp.bfloat16
    xs = jax.random.normal(k[0], (rows, d_in), bf)
    w = (jax.random.normal(k[1], (g, d_in, d_out)) * d_in**-0.5).astype(bf)
    a = (jax.random.normal(k[2], (g, d_in, rank)) * d_in**-0.5).astype(bf)
    b = (jax.random.normal(k[3], (g, rank, d_out)) * 0.1).astype(bf)
    ct = jax.random.normal(k[4], (rows, d_out), bf)
    return (xs, a, b), (w, sizes, row_expert), ct


def form_fns(form):
    """(forward, forward and backward) of ``_expert_linear`` with the
    bypass (``base``: none), jitted, over (x, A, B, W, group sizes, row
    experts[, cotangent]): every array is an argument, none a constant
    of the program.  Which form the bypass takes is read from
    ``moe.LANES`` when they are first called."""

    def f(xs, a, b, w, sizes, row_expert):
        entry = None if form == "base" else {
            "a": a, "b": b, "scale": jnp.asarray(2.0, jnp.float32)}
        return moe._expert_linear(xs, w, sizes, row_expert, entry)

    def fb(xs, a, b, w, sizes, row_expert, ct):
        out, pull = jax.vjp(lambda *d: f(*d, w, sizes, row_expert), xs, a, b)
        return out, pull(ct)

    return jax.jit(f), jax.jit(fb)


def run(shape, iters, repeats):
    rows, held, latent, d_ff, g, rank = shape
    blocks, per_block = moe.lora_blocks(g, rank)
    report = {"shape": dict(zip(
        ("chunk_rows", "held_rows", "latent", "d_ff", "experts", "rank"),
        shape)), "blocks": blocks, "block_experts": per_block, "matrices": []}
    for name, d_in, d_out in (("up", latent, d_ff), ("down", d_ff, latent)):
        args, fixed, ct = chunk(shape, d_in, d_out)
        need = 2 * held * (d_in * per_block * rank + per_block * rank * d_out)
        row = {"matrix": name, "d_in": d_in, "d_out": d_out,
               "need_fwd_ms": need / PEAK_FLOPS * 1e3,
               "need_fwd_bwd_ms": 3 * need / PEAK_FLOPS * 1e3}
        outs = {}
        for form in ("base", "dense", "block"):
            # each form is traced under its own LANES: as wide as all
            # experts' adapters side by side gives the dense form
            lanes = moe.LANES
            if form == "dense":
                moe.LANES = g * rank
            try:
                fwd, both = form_fns(form)
                row[f"{form}_fwd_ms"] = best_ms(fwd, args + fixed, iters,
                                                repeats)
                row[f"{form}_fwd_bwd_ms"] = best_ms(
                    both, args + fixed + (ct,), iters, repeats)
                outs[form] = both(*args, *fixed, ct)
            finally:
                moe.LANES = lanes
        # the two forms compute one function: relative RMS distance of
        # the output and the three gradients, block against dense
        rel = lambda x, y: float(jnp.sqrt(
            jnp.mean((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2)
            / jnp.mean(y.astype(jnp.float32) ** 2)))
        flat = lambda o: [o[0], *o[1]]
        row["block_vs_dense_rel_rms"] = max(
            rel(x, y) for x, y in zip(flat(outs["block"]), flat(outs["dense"])))
        report["matrices"].append(row)
    return report


def table(report):
    lines = [
        "| matrix | base fwd / fwd+bwd ms | dense bypass fwd / fwd+bwd ms "
        "| block bypass fwd / fwd+bwd ms | block form's need at peak ms |",
        "| --- | --- | --- | --- | --- |",
    ]
    for r in report["matrices"]:
        cost = lambda f, p: r[f"{f}_{p}_ms"] - r[f"base_{p}_ms"]
        lines.append(
            f"| {r['matrix']} ({r['d_in']} -> {r['d_out']}) | "
            f"{r['base_fwd_ms']:.3f} / {r['base_fwd_bwd_ms']:.3f} | "
            f"{cost('dense', 'fwd'):+.3f} / {cost('dense', 'fwd_bwd'):+.3f} | "
            f"{cost('block', 'fwd'):+.3f} / {cost('block', 'fwd_bwd'):+.3f} | "
            f"{r['need_fwd_ms']:.3f} / {r['need_fwd_bwd_ms']:.3f} |"
        )
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/expert_lora_sweep.json")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit(f"expert_lora_sweep times the chip; found {device.platform} "
                 f"(--tiny rehearses the control flow)")
    if device.platform != "tpu":
        moe._grouped_impl = lambda: "megablox-interpret"
    report = run(TINY if args.tiny else CELL, args.iters, args.repeats)
    report["device"] = {"platform": device.platform, "kind": device.device_kind}
    report["measurement"] = device.platform == "tpu"
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report["device"]))
    if not report["measurement"]:
        print("REHEARSAL on", device.platform, "- the times below mean nothing")
    print(table(report))
    print("block against dense, worst relative RMS of output and gradients:",
          {r["matrix"]: r["block_vs_dense_rel_rms"] for r in report["matrices"]})


if __name__ == "__main__":
    main()
