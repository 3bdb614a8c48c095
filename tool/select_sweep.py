#!/usr/bin/env python3
"""Time InfLLM-v2's block selection alone, on the chip: the top-k by
``jax.lax.top_k`` against the threshold mask the program runs.

    python tool/select_sweep.py [--seed N] [--out FILE]
    python tool/select_sweep.py --tiny          # rehearses on the CPU

The scores are the MiniCPM-SALA cell's own (``CELL``): its base weights
and token ids from ``--seed`` as the benchmark makes them, the stream
through the sparse layer's norm, projections and ``q``/``k`` norms, then
``sparse_attention.block_scores``: ``[24, 1, 2, 1024, 384]`` float32, a
chunk of 1,024 queries by 2 K/V heads by 384 blocks, 24 chunks.  Timed,
each a jitted call over all 24 chunks:

- ``scores``: ``block_scores`` itself (the rest of ``attn.select`` but
  the words and the visit lists);
- ``top_k``: ``jax.lax.top_k(score, 64)`` a chunk in a ``lax.map``, as
  the selection ran it before it became a mask (on the chip a sort of
  each chunk's ``[1, 2, 1024, 384]``);
- ``threshold``: ``top_mask(score, 64)`` over every chunk at once, as
  ``select_mask`` runs it;
- ``threshold_by_chunk``: the same a chunk in a ``lax.map``.

It asserts that the three give the same set of blocks for every query
and K/V head, and reports the share of rows where the block index broke
a tie at the threshold, and the sorts each compiled program holds.  A
number is the best mean over ``--repeats`` batches of ``--iters``
back-to-back calls ending in ``block_until_ready``.  ``--tiny`` runs
the benchmark's toy cell of this family anywhere; its times mean
nothing.  Run by no cell.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import minicpm_sala_lm
from rayfed_tpu.models import decoder
from rayfed_tpu.ops import sparse_attention as sa
from tool.flash_sweep import best_ms

CELL = ("minicpm-sala-d4.lora-all-linear-32k-2p", harness.ROOT)
TINY = ("minicpm-sala-d4.toy-2p", os.path.join(harness.ROOT, "tests"))
HBM_BYTES_PER_S = 819e9  # one v5e chip


def cell_qk(name: str, root: str, seed: int):
    """``(q, k, sparse sizes)``: the sparse layer's queries and keys for
    the cell's sequence at ``seed`` (the base and the ids as the family's
    reference check makes them)."""
    cell = harness.load_cell(name, root)
    fam = minicpm_sala_lm.build(cell["config_data"], cell["job"], seed)
    c = fam.cfg
    assert c.stack[0].mixer == "sparse", c.stack[0]
    base = fam._make_base(fam.base_key())
    ids = jax.random.randint(
        jax.random.PRNGKey(seed + 3), (1, fam.seq), 0, c.vocab_size
    )

    @jax.jit
    def qk(base, ids):
        # the sparse layer's first sub-block up to its selection
        # (``decoder._attention_block``, no adapters)
        lp = jax.tree_util.tree_map(lambda a: a[0], base["layers"][0])
        b, t = ids.shape
        y = decoder._rms_norm(decoder.embed(base, ids, c), lp["attn_norm"],
                              c.rms_eps)
        q = decoder._linear(y, lp["wq"], None, c.dtype).reshape(
            b, t, c.num_heads, c.head_dim)
        k = decoder._linear(y, lp["wk"], None, c.dtype).reshape(
            b, t, c.num_kv_heads, c.head_dim)
        return (decoder._rms_norm(q, lp["q_norm"], c.rms_eps),
                decoder._rms_norm(k, lp["k_norm"], c.rms_eps))

    q, k = qk(base, ids)
    return q, k, c.sparse


def sorts(fn, *args):
    """The result shapes of the sorts (and ``TopK`` calls) in ``fn``'s
    compiled program."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [
        re.sub(r"\{[^}]*\}", "", line.split("=", 1)[1].split(" sort(")[0]
               .split(" custom-call(")[0]).strip()
        for line in text.splitlines()
        if " sort(" in line or 'custom_call_target="TopK"' in line
    ]


def run(cell, root, seed, iters, repeats):
    q, k, sizes = cell_qk(cell, root, seed)
    score = jax.jit(lambda q, k: sa.block_scores(q, k, sizes))(q, k)
    n = score.shape[-1]
    topk = min(sizes.topk, n)

    def by_top_k(score):
        def one(s):
            vals, idx = jax.lax.top_k(s, topk)
            return jnp.where(vals > -jnp.inf, idx, -1)

        return jax.lax.map(one, score)

    def threshold(score):
        return sa.top_mask(score, topk)

    def threshold_by_chunk(score):
        return jax.lax.map(lambda s: sa.top_mask(s, topk), score)

    forms = {"top_k": by_top_k, "threshold": threshold,
             "threshold_by_chunk": threshold_by_chunk}
    out = {form: jax.jit(fn)(score) for form, fn in forms.items()}
    want = jax.jit(lambda sel: sa.as_mask(sel, n, 1))(out["top_k"])
    same = {form: bool(jnp.array_equal(out[form][0], want))
            for form in ("threshold", "threshold_by_chunk")}
    assert all(same.values()), same
    tied = out["threshold"][1]
    passes = sa.threshold_passes(n)
    pass_bytes = score.size * 4  # one pass reads every key once
    ms = {"scores": best_ms(jax.jit(lambda q, k: sa.block_scores(q, k, sizes)),
                            (q, k), iters, repeats)}
    ms.update({form: best_ms(jax.jit(fn), (score,), iters, repeats)
               for form, fn in forms.items()})
    return {
        "cell": cell, "seed": seed, "score_shape": list(score.shape),
        "topk": topk, "same_sets": same,
        "tie_rows": float(jnp.mean(tied, dtype=jnp.float32)),
        "threshold_passes": passes,
        "ms": ms,
        # every pass reading every key from HBM, at its peak (the
        # compiler may keep some of them nearer)
        "threshold_need_ms": passes * pass_bytes / HBM_BYTES_PER_S * 1e3,
        "sorts": {form: sorts(fn, score) for form, fn in forms.items()},
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20431019)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/select_sweep.json")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit(f"select_sweep times the chip; found {device.platform} "
                 f"(--tiny rehearses the control flow)")
    report = run(*(TINY if args.tiny else CELL), args.seed, args.iters,
                 args.repeats)
    report["device"] = {"platform": device.platform, "kind": device.device_kind}
    report["measurement"] = device.platform == "tpu"
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    if not report["measurement"]:
        print("REHEARSAL on", device.platform, "- the times below mean nothing")
    print(json.dumps(report))


if __name__ == "__main__":
    main()
