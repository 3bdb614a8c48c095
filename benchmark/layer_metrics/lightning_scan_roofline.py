"""The linear-attention scans of a step against the chip's roofline:
the least time the chip could take for what the scans of all
linear-attention layers need ONCE FORWARD AND ONCE BACKWARD, over the
device time under ``attn.lightning`` (and the scan's backward rule,
``ssm.scan`` in those layers' groups) in a step.

What is needed is counted from the shapes alone, in chunks of
:data:`CHUNK` whatever chunk the program uses (:func:`scan_flops`: per
head, the masked intra-chunk products ``q k^T`` and their product with
``v``, the chunk's state ``k^T v`` and the state's product with ``q``,
the state ``d x d``; 2 a multiply-add) and twice that backward; bytes
(:func:`scan_bytes`): ``q``, ``k``, ``v``, the output and a cotangent
each, once.  The least time is the larger of FLOPs over the published
bf16 peak and bytes over the published memory bandwidth.  A layer's
second forward under ``remat`` is time and not need.  Only needed work
is counted, so it cannot read over 100% unless the count is wrong."""

from benchmark.layer_metrics.sala_mixer_step_share import mixer_seconds

NAME, UNIT = "lightning_scan_roofline", "%"
LAYER = "state-space scan"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["minicpm-sala-d4.*"]

CHUNK = 256  # Lightning Attention-2's intra/inter-block split, fixed here


def scan_flops(tokens, heads, head_dim, chunk=CHUNK) -> float:
    """FORWARD FLOPs of one layer's chunked decayed linear attention
    over ``tokens`` (a head's key and value widths ``head_dim``)."""
    visible = (chunk + 1) / 2  # a token sees this much of its chunk
    return float(tokens) * heads * (
        2 * head_dim * visible  # q . k within the chunk
        + 2 * head_dim * visible  # those scores times v
        + 2 * head_dim * head_dim  # the chunk's state k^T v
        + 2 * head_dim * head_dim  # q times the carried state
    )


def scan_bytes(tokens, heads, head_dim, itemsize) -> float:
    """Bytes one layer's scan moves forward and backward: ``q``, ``k``,
    ``v`` and the output ``[T, H, d]`` and a cotangent each, once."""
    return 2.0 * tokens * 4 * heads * head_dim * itemsize


def read(ctx):
    found = mixer_seconds(ctx)
    fam = ctx.family
    if not found or ctx.peaks is None:
        return None
    seconds = found[0]["lightning"]
    layers = sum(s.mixer == "lightning" for s in fam.cfg.layers)
    if not seconds or not layers:
        return None
    c = fam.cfg
    tokens = fam.batch * fam.seq
    flops = 3 * scan_flops(tokens, c.num_heads, c.head_dim)
    nbytes = scan_bytes(tokens, c.num_heads, c.head_dim, c.dtype.itemsize)
    peaks = ctx.peaks
    need = layers * max(flops / peaks["bf16_flops"],
                        nbytes / peaks["hbm_bytes_per_s"])
    from benchmark.reduce import log

    log(lightning_scan_ms=round(seconds * 1e3, 3), lightning_layers=layers,
        lightning_layer_gflop=flops / 1e9, lightning_layer_MB=nbytes / 1e6,
        lightning_least_ms=round(need * 1e3, 3))
    return 100.0 * need / seconds
