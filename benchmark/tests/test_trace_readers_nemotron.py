"""The three readers the ``nemotron_h`` cell adds (``latent_moe_step_share``,
``latent_expert_mm_roofline``, ``mtp_step_share``) on hand-made input: a
compiled step's text with the scopes the program gives its operations,
device operations with known durations, and ``moe.counts`` records
(``test_trace_readers_granite.py``'s way)."""

import json
import types

import pytest

from benchmark import harness
from benchmark.layer_metrics.moe_step_share import instruction_op_names

CELL = "nemotron-3-super-120b-a12b-ep8-d11.lora-all-linear-2p"
STEP = "jit(decoder_lora_step)"
BODY = "while/body/closed_call"

PROGRAM = f"""
HloModule jit_decoder_lora_step
ENTRY %main {{
  %fusion.1 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers0-2)/{BODY}/ssm.proj/dot_general"}}
  %fusion.2 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers0-2)/{BODY}/moe.route/dot_general"}}
  %fusion.3 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers0-2)/{BODY}/moe.latent/dot_general"}}
  %gmm.4 = bf16[8,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layers0-2)/{BODY}/while/body/moe.experts/grouped_matmul/jit(gmm)/pallas_call"}}
  %fusion.5 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers0-2)/{BODY}/moe.shared/dot_general"}}
  %fusion.6 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(mtp)/mtp.fuse/dot_general"}}
  %gmm.7 = bf16[8,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(mtp)/layers6-6/{BODY}/while/body/moe.experts/grouped_matmul/jit(gmm)/pallas_call"}}
  %fusion.8 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%g, metadata={{op_name="{STEP}/jvp(mtp)/head.loss/while/body/dot_general"}}
  %fusion.9 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%g, metadata={{op_name="{STEP}/jvp(mtp)/layers6-6/{BODY}/attn.full/jit(_flash_forward)/flash.fwd/pallas_call"}}
  ROOT %add.10 = f32[] add(%x, %y), metadata={{op_name="{STEP}/head.loss/reduce_sum"}}
}}
"""


def reader(name):
    (mod,) = [m for m in harness.matching_layer_metrics(CELL) if m.NAME == name]
    return mod


def ctx_of(ops, window=1000, records=(), peaks=None):
    return types.SimpleNamespace(
        peaks=peaks or {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12},
        family=types.SimpleNamespace(),
        recorder_records=list(records),
        _step_events=([(0, window, ops)], instruction_op_names(PROGRAM)),
    )


# 100 ns each: Mamba projections, routing, the latent pair, a grouped
# product of layer 0, the shared expert, the MTP fuse, the MTP module's
# grouped product, its head, its attention kernel; 100 outside
OPS = [(100 * i, 100 * (i + 1), name) for i, name in enumerate(
    ["fusion.1", "fusion.2", "fusion.3", "gmm.4", "fusion.5", "fusion.6",
     "gmm.7", "fusion.8", "fusion.9", "add.10"]
)]


def test_latent_moe_share_counts_every_moe_scope_the_mtp_modules_too():
    mod = reader("latent_moe_step_share")
    ctx = ctx_of(OPS)
    totals = mod.scope_seconds(*ctx._step_events)
    assert totals == pytest.approx({
        "ssm.proj": 100e-9, "moe.route": 100e-9, "moe.latent": 100e-9,
        "moe.experts": 200e-9, "moe.shared": 100e-9, "mtp.fuse": 100e-9,
        "head.loss": 200e-9, "attn.full": 100e-9,
    })
    assert mod.read(ctx) == pytest.approx(50.0)  # 500 of 1000
    empty = types.SimpleNamespace(family=None, trace={}, run=None)
    assert mod.read(empty) is None  # nothing to read: no raise


MTP_FAMILY = types.SimpleNamespace(cfg=types.SimpleNamespace(
    mtp=object(), mtp_groups=lambda: ((6, 7),),
))
BACK = f"{STEP}/transpose(jvp(mtp))/layers6-6/{BODY}"

# The module's backward: a chunk loop whose body's instructions have no
# ``op_name`` of their own, and a ``custom_vjp`` rule's product that
# holds the module's group but not its scope.
MTP_PROGRAM = PROGRAM.replace("ENTRY %main {", f"""
%chunk_body (r: f32[8]) -> f32[8] {{
  %copy.20 = f32[8]{{0}} copy(%r)
  ROOT %fusion.21 = f32[8]{{0}} fusion(%copy.20), kind=kLoop, calls=%g
}}

ENTRY %main {{
  %while.22 = f32[8]{{0}} while(%q), condition=%c, body=%chunk_body, metadata={{op_name="{BACK}/while"}}
  %fusion.23 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/transpose(jvp(layers6-6))/{BODY}/moe.experts/dot_general"}}
  %fusion.24 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/transpose(jvp(layers4-4))/{BODY}/moe.experts/dot_general"}}""")


def mtp_ctx(ops, window):
    from benchmark.layer_metrics.step_scoped_share import program_op_names

    return types.SimpleNamespace(
        family=MTP_FAMILY,
        _mtp_step=([(0, window, ops)], program_op_names(MTP_PROGRAM)[0]),
    )


def test_mtp_share_is_what_the_module_runs(capsys):
    """The module by its scope or by its group, each instant once: the
    fuse, its grouped product, its head, its attention (400 ns); the
    backward's loop with the unnamed instructions inside it (100) and
    the rule's product under the group alone (100); not layer 4's
    product nor the main head: 600 of 1,300 ns."""
    mod = reader("mtp_step_share")
    ops = OPS[:-1] + [
        (900, 1000, "while.22"), (920, 950, "copy.20"),
        (950, 980, "fusion.21"), (1000, 1100, "fusion.23"),
        (1100, 1200, "fusion.24"), (1200, 1300, "add.10"),
    ]
    ctx = mtp_ctx(ops, window=1300)
    terms = {"tokens": 8192, "main": 10.2, "mtp": 10.1, "loss_weight": 0.1,
             "total": 11.21}
    ctx.recorder_records = [
        types.SimpleNamespace(phase="moe.counts", detail={}),
        types.SimpleNamespace(phase="mtp.loss", detail=terms),
    ]
    assert mod.read(ctx) == pytest.approx(600 / 1300 * 100)
    logged = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert logged["mtp_loss_records"] == {"count": 1, "last": terms}
    passes = mod.mtp_passes(*ctx._mtp_step, mod.mtp_groups(MTP_FAMILY))
    assert passes == pytest.approx(
        {"fwd": 400e-9, "refwd": 0.0, "bwd": 200e-9}
    )
    assert mod.mtp_groups(MTP_FAMILY) == {"layers6-6"}
    assert mod.MTP.search(f"{STEP}/transpose(jvp(mtp))/layers6-6/x")
    assert not mod.MTP.search(f"{STEP}/jvp(mtpx)/y")
    assert not mod.is_mtp(f"{STEP}/jvp(layers16-6)/y", {"layers6-6"})
    assert mod.read(mtp_ctx([(0, 5, "add.10")], window=5)) == 0.0
    empty = types.SimpleNamespace(family=None, trace={}, run=None)
    assert mod.read(empty) is None
    # a family with no MTP module: nothing to read, no raise
    plain = types.SimpleNamespace(
        family=types.SimpleNamespace(cfg=types.SimpleNamespace(mtp=None)),
        _mtp_step=ctx._mtp_step,
    )
    assert mod.read(plain) is None


def _counts(latent=1024, d_ff=2688):
    detail = {
        "tokens": 8192, "top_k": 22, "chunk_rows": 56320,
        "layers": [{"layer": 0, "counts": [352] * 64},
                   {"layer": 6, "counts": [350] * 64}],
    }
    if latent:
        detail.update(latent=latent, d_ff=d_ff, shared_d_ff=5376,
                      activation="relu2")
    return types.SimpleNamespace(phase="moe.counts", detail=detail)


def test_latent_roofline_counts_the_rows_really_assigned_in_the_latent():
    """The MTP module's expert layer (index 6, a group of its own) ran its
    grouped product once with 22,400 rows held: 2 x 22,400 x 1,024 x
    2,688 FLOPs, timed at 40% of the peak."""
    mod = reader("latent_expert_mm_roofline")
    flops = 2 * 22400 * 1024 * 2688
    seconds = flops / 100e12 / 0.4  # 40% of the peak
    ns = int(seconds * 1e9)
    ops = [(0, ns, "gmm.7"), (ns, 2 * ns, "add.10")]
    ctx = ctx_of(ops, window=2 * ns, records=[_counts()])
    assert mod.read(ctx) == pytest.approx(40.0, rel=1e-3)
    # the parent writes no latent widths: nothing, no raise
    assert mod.read(ctx_of(ops, records=[_counts(latent=None)])) is None
    assert mod.read(ctx_of(ops)) is None  # no records
