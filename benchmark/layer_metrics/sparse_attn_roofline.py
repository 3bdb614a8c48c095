"""The block-sparse layers of a step against the chip's roofline: the
least time the chip could take for what their attention NEEDS, over the
device time under ``attn.select`` and ``attn.sparse`` in a step.

What is needed is counted from the published ``sparse_config`` and the
cell's length, never from the kernel's tiles (:func:`attention_flops`):
a query's pairs with the keys its selection lets it visit (up to
``topk`` blocks of ``block_size``, the keys after it left out; every
causal key where fewer blocks exist, and every causal key up to
``dense_len`` tokens), a query-key and a value product over the head
width a pair and head, forward and twice that backward; and the
selection's scores once (a query against every compressed key that ends
at or before it, a head; the choice takes no gradient).  Bytes
(:func:`attention_bytes`): ``q``, ``k``, ``v``, the output and a
cotangent each, once.  The least time is the larger of FLOPs over the
published bf16 peak and bytes over the published memory bandwidth.
The tiles' masked pairs, the union of a tile's selections and a second
forward are time and not need: they lower the share.  Only needed work
is counted, so it cannot read over 100% unless the count is wrong."""

import numpy as np

from benchmark.layer_metrics.sala_mixer_step_share import mixer_seconds

NAME, UNIT = "sparse_attn_roofline", "%"
LAYER = "attention kernel"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["minicpm-sala-d4.*"]


def visited_keys(t: int, sizes: dict):
    """``(keys, windows)`` summed over the queries of a sequence of
    ``t``: the causal keys a query visits, and the compressed keys its
    selection scores."""
    pos = np.arange(t, dtype=np.int64)
    if t <= sizes["dense_len"]:
        return float((pos + 1).sum()), 0.0
    b = sizes["block_size"]
    whole = (sizes["topk"] - 1) * b + pos % b + 1  # its own block partly
    keys = np.where(pos // b + 1 <= sizes["topk"], pos + 1, whole)
    ends = pos - sizes["kernel_size"] + 1
    windows = np.where(ends >= 0, ends // sizes["kernel_stride"] + 1, 0)
    return float(keys.sum()), float(windows.sum())


def attention_flops(t: int, sizes: dict, heads: int, head_dim: int) -> float:
    """FLOPs one layer needs for a sequence of ``t``: pairs forward and
    backward, the selection's scores forward."""
    keys, windows = visited_keys(t, sizes)
    return 12.0 * heads * head_dim * keys + 2.0 * heads * head_dim * windows


def attention_bytes(t: int, heads: int, kv_heads: int, head_dim: int,
                    itemsize: int) -> float:
    """Bytes one layer's attention moves forward and backward: ``q``,
    the output and their cotangents ``[T, H, d]``, ``k``, ``v`` and
    theirs ``[T, KV, d]``, once each."""
    return float(t) * 4 * (heads + kv_heads) * head_dim * itemsize


def least_seconds(flops, nbytes, peaks) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def read(ctx):
    found = mixer_seconds(ctx)
    fam = ctx.family
    if not found or ctx.peaks is None:
        return None
    parts, _ = found
    seconds = parts["select"] + parts["sparse"]
    layers = sum(s.mixer == "sparse" for s in fam.cfg.layers)
    if not seconds or not layers:
        return None
    c, sizes = fam.cfg, fam.config["sparse_config"]
    flops = fam.batch * attention_flops(fam.seq, sizes, c.num_heads, c.head_dim)
    nbytes = fam.batch * attention_bytes(
        fam.seq, c.num_heads, c.num_kv_heads, c.head_dim, c.dtype.itemsize
    )
    need = layers * least_seconds(flops, nbytes, ctx.peaks)
    from benchmark.reduce import log

    log(sparse_attn_ms=round(seconds * 1e3, 3), sparse_layers=layers,
        sparse_layer_gflop=flops / 1e9, sparse_layer_MB=nbytes / 1e6,
        sparse_least_ms=round(need * 1e3, 3))
    return 100.0 * need / seconds
