"""The latent attention's three flash kernels against the chip's bf16
peak: the FLOPs one layer's VISIBLE (query, key) pairs need, from the
shapes alone and whatever form the kernels take (split score or one
concatenated product; never padded widths, never the pairs a masked
tile computes and throws away), over the summed median durations of the
``flash.fwd``, ``flash.dq`` and ``flash.dkv`` events inside
``attn.latent``, over the published peak.

A pair costs, per head, 2 FLOPs a multiply-add times: forward ``q . k``
over ``nope + rope`` (192) and ``p v`` over ``v_dim`` (128): 320; dQ the
score again (192), ``dO . v`` (128) and ``dS k`` (192): 512; dK/dV the
score (192), ``dO . v`` (128), ``p^T dO`` (128) and ``dS^T q`` (192): 640.
Every layer's call has the same shape, so the median over all events of
a kind is one call's time; the three medians summed are a layer's
kernels.  Only needed FLOPs are counted, so it cannot read over 100%
unless the count is wrong."""

import re

import numpy as np

from benchmark.layer_metrics.moe_step_share import step_events

NAME, UNIT = "latent_flash_roofline", "%"
LAYER = "attention kernel"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["kimi-k2.7-code-ep32.*"]

# A kernel's instruction is named by its innermost scope (``flash.fwd.20``)
# and its ``op_name`` holds the layer's: ".../attn.latent/jit(_flash_forward)/
# flash.fwd/pallas_call", in the backward pass ".../checkpoint/attn.latent/
# jit(_flash_backward_pallas)/flash.dkv/pallas_call".  The copies and tuple
# elements around a call carry the same ``op_name`` under names of their
# own (``copy.1205``) and are no kernel.
INSTRUCTION = re.compile(r"^flash\.(fwd|dq|dkv)\b")
SCOPE = re.compile(r"attn\.latent.*pallas_call$")


def visible_pairs(batch: int, tokens: int) -> int:
    """Causal, no window: query ``i`` sees keys ``0..i``."""
    return batch * tokens * (tokens + 1) // 2


def flops_per_pair(nope: int, rope: int, v_dim: int) -> dict:
    """FLOPs one visible pair of one head costs each kernel."""
    qk = nope + rope
    return {
        "fwd": 2 * (qk + v_dim),
        "dq": 2 * (qk + v_dim + qk),
        "dkv": 2 * (qk + v_dim + v_dim + qk),
    }


def layer_flops(batch, tokens, heads, nope, rope, v_dim) -> float:
    """What one layer's three kernels must compute."""
    return float(
        visible_pairs(batch, tokens) * heads
        * sum(flops_per_pair(nope, rope, v_dim).values())
    )


def kernel_of(instruction: str, op_name: str):
    """``"fwd"``, ``"dq"`` or ``"dkv"`` for a latent layer's kernel."""
    m = INSTRUCTION.match(instruction)
    return m.group(1) if m and SCOPE.search(op_name) else None


def read(ctx):
    steps, op_names = step_events(ctx)
    latent = getattr(getattr(ctx.family, "cfg", None), "latent", None)
    if not steps or ctx.peaks is None or latent is None:
        return None
    durations = {"fwd": [], "dq": [], "dkv": []}
    for _, _, ops in steps:
        for s, e, name in ops:
            kind = kernel_of(name, op_names.get(name, ""))
            if kind and e > s:
                durations[kind].append((e - s) / 1e9)
    if not all(durations.values()):
        return None
    seconds = sum(float(np.median(v)) for v in durations.values())
    fam = ctx.family
    flops = layer_flops(
        fam.batch, fam.seq, fam.cfg.num_heads, latent.nope_dim,
        latent.rope_dim, latent.v_dim,
    )
    from benchmark.reduce import log

    log(latent_flash_ms={k: round(float(np.median(v)) * 1e3, 4)
                         for k, v in durations.items()},
        events={k: len(v) for k, v in durations.items()},
        layer_gflop=flops / 1e9)
    return 100.0 * flops / seconds / ctx.peaks["bf16_flops"]
