"""The state-space scans of a step against the chip's roofline: the
least time the chip could take for what the scans of all state-space
layers need ONCE FORWARD AND ONCE BACKWARD, over the device time under
the scope ``ssm.scan`` in a step.

What is needed is counted from the shapes alone, at the PUBLISHED chunk
(``mamba_chunk_size``) whatever form or chunk implements the scan: a
layer's forward FLOPs (:func:`scan_flops`: the masked intra-chunk
product, the chunk states, states to outputs, ``C B^T``; 2 a
multiply-add) and twice that backward; a layer's bytes
(:func:`scan_bytes`): every input (``x``, ``dt``, ``B``, ``C``), the
output, and every cotangent (the output's, the four inputs'), each once.
The least time is the larger of FLOPs over the published bf16 peak and
bytes over the published memory bandwidth.  A layer's second forward
under ``remat`` is time under the scope and not need: it lowers the
share, as it lowers ``local_mfu``.
Only needed work is counted, so it cannot read over 100% unless the
count is wrong."""

from benchmark.layer_metrics.moe_step_share import step_events
from benchmark.layer_metrics.ssm_step_share import scope_seconds

NAME, UNIT = "ssm_scan_roofline", "%"
LAYER = "state-space scan"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["granite-4.0-h-micro-d20.*"]


def scan_flops(tokens, heads, head_dim, state, groups, chunk) -> float:
    """FORWARD FLOPs of one layer's chunked scan over ``tokens``."""
    visible = (chunk + 1) / 2  # a token sees this much of its chunk
    return float(tokens) * (
        2 * heads * head_dim * visible  # Y_intra
        + 2 * heads * head_dim * state  # the chunk's state
        + 2 * heads * head_dim * state  # states to outputs
        + 2 * groups * state * visible  # C . B
    )


def scan_bytes(tokens, heads, head_dim, state, groups, itemsize) -> float:
    """Bytes one layer's scan moves forward and backward: inputs and
    output, and a cotangent for each, once.  ``x`` and ``y`` [T, H, P]
    and ``B``, ``C`` [T, G, N] in the compute type, ``dt`` [T, H]
    float32."""
    call = tokens * (
        2 * heads * head_dim * itemsize + 2 * groups * state * itemsize
        + heads * 4
    )
    return 2.0 * call


def least_seconds(flops, nbytes, peaks) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def read(ctx):
    steps, op_names = step_events(ctx)
    fam = ctx.family
    ssm = getattr(getattr(fam, "cfg", None), "ssm", None)
    if not steps or ctx.peaks is None or ssm is None:
        return None
    seconds = scope_seconds(steps, op_names).get("ssm.scan", 0.0) / len(steps)
    if not seconds:
        return None
    layers = sum(s.mixer == "ssm" for s in fam.cfg.layers)
    tokens = fam.batch * fam.seq
    chunk = fam.config["mamba_chunk_size"]
    shape = (ssm.num_heads, ssm.head_dim, ssm.state, ssm.groups)
    flops = 3 * scan_flops(tokens, *shape, chunk)
    nbytes = scan_bytes(tokens, *shape, fam.cfg.dtype.itemsize)
    need = layers * least_seconds(flops, nbytes, ctx.peaks)
    from benchmark.reduce import log

    log(ssm_scan_ms=round(seconds * 1e3, 3), layers=layers,
        layer_gflop=flops / 1e9, layer_MB=nbytes / 1e6,
        bound="flops" if flops / ctx.peaks["bf16_flops"]
        >= nbytes / ctx.peaks["hbm_bytes_per_s"] else "bytes",
        least_ms=round(need * 1e3, 3))
    return 100.0 * need / seconds
