"""The two readers the state-space hybrid's cell adds (``ssm_step_share``,
``ssm_scan_roofline``) on hand-made input: a compiled step's text with
the scopes the program gives its operations, and device operations with
known durations (``test_trace_readers.py``'s way)."""

import types

import pytest

from benchmark import harness
from benchmark.layer_metrics.moe_step_share import instruction_op_names

CELL = "granite-4.0-h-micro-d20.lora-all-linear-2p"

PROGRAM = """
HloModule jit_decoder_lora_step
ENTRY %main {
  %fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(decoder_lora_step)/jvp(layers0-4)/while/body/closed_call/ssm.proj/dot_general" stack_frame_id=3}
  %fusion.2 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(decoder_lora_step)/jvp(layers0-4)/while/body/closed_call/ssm.conv/mul"}
  %fusion.3 = f32[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(decoder_lora_step)/jvp(layers0-4)/while/body/closed_call/ssm.scan/exp"}
  %while.4 = f32[8,8]{1,0} while(%p), condition=%c, body=%b, metadata={op_name="jit(decoder_lora_step)/transpose(jvp(layers0-4))/while/body/closed_call/transpose(jvp(ssm.scan))/while"}
  %fusion.5 = f32[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(decoder_lora_step)/transpose(jvp(layers0-4))/while/body/closed_call/transpose(jvp(ssm.scan))/while/body/checkpoint/dot_general"}
  %flash.fwd.6 = bf16[16,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(decoder_lora_step)/jvp(layers5-5)/while/body/closed_call/attn.full/jit(_flash_forward)/flash.fwd/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%q), kind=kLoop, calls=%g, metadata={op_name="jit(decoder_lora_step)/jvp(layers5-5)/while/body/closed_call/attn.proj/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%q), kind=kLoop, calls=%g, metadata={op_name="jit(decoder_lora_step)/jvp(layers0-4)/while/body/closed_call/ffn.dense/dot_general"}
  ROOT %add.9 = f32[] add(%x, %y), metadata={op_name="jit(decoder_lora_step)/reduce_sum"}
}
"""


def reader(name):
    (mod,) = [m for m in harness.matching_layer_metrics(CELL) if m.NAME == name]
    return mod


def ctx_of(ops, window=1000, layers=("ssm", "ssm", "full"), seq=1024,
           peaks=None):
    cfg = types.SimpleNamespace(
        ssm=types.SimpleNamespace(num_heads=64, head_dim=64, state=128,
                                  groups=1),
        layers=[types.SimpleNamespace(mixer=m) for m in layers],
        dtype=types.SimpleNamespace(itemsize=2),
    )
    return types.SimpleNamespace(
        peaks=peaks or {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12},
        family=types.SimpleNamespace(
            batch=1, seq=seq, cfg=cfg, config={"mamba_chunk_size": 256},
        ),
        _step_events=([(0, window, ops)], instruction_op_names(PROGRAM)),
    )


def test_ssm_share_is_the_scopes_self_time_over_the_programs():
    mod = reader("ssm_step_share")
    # 100 ns of projections, 50 of convolution, 250 under ssm.scan (a
    # while of 200 whose child runs 150 of them, and a fusion of 50), 100
    # of flash kernel, 100 of attention projections, 300 of FFN, 100
    # outside every scope: 400 of 1000
    ops = [(0, 100, "fusion.1"), (100, 150, "fusion.2"), (150, 200, "fusion.3"),
           (200, 400, "while.4"), (220, 370, "fusion.5"),
           (400, 500, "flash.fwd.6"), (500, 600, "fusion.7"),
           (600, 900, "fusion.8"), (900, 1000, "add.9")]
    ctx = ctx_of(ops)
    totals = mod.scope_seconds(*ctx._step_events)
    assert totals == pytest.approx({
        "ssm.proj": 100e-9, "ssm.conv": 50e-9, "ssm.scan": 250e-9,
        "attn.full": 100e-9, "attn.proj": 100e-9, "ffn.dense": 300e-9,
        "other": 100e-9,
    })
    assert mod.read(ctx) == pytest.approx(40.0)
    empty = types.SimpleNamespace(family=None, trace={}, run=None)
    assert mod.read(empty) is None  # nothing to read: no raise


def test_scan_roofline_counts_what_the_scans_need_at_the_published_chunk():
    mod = reader("ssm_scan_roofline")
    shape = (64, 64, 128, 1)
    # ISSUE 35's count: 3.18 MFLOP a token forward, 9.5 with the
    # backward's twice that; 0.40 ms a layer of 8,192 tokens at 197 TF/s
    assert mod.scan_flops(1, *shape, 256) == (
        4096 * 257 + 2 * 2 * 4096 * 128 + 128 * 257
    )
    flops = 3 * mod.scan_flops(8192, *shape, 256)
    assert 78.0e9 < flops < 78.5e9
    # inputs and output once, a cotangent for each: 281 MB, 0.34 ms
    nbytes = mod.scan_bytes(8192, *shape, 2)
    assert nbytes == 2 * 8192 * (2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4)
    v5e = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert mod.least_seconds(flops, nbytes, v5e) == pytest.approx(
        flops / 197e12
    )  # the MXU bounds it
    assert 0.39e-3 < flops / 197e12 < 0.40e-3 < 0.41e-3
    assert 0.34e-3 < nbytes / 819e9 < 0.35e-3
    # two state-space layers of 1,024 tokens whose scans take four times
    # the least time of the table's peaks: 25%
    peaks = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12}
    need = 2 * mod.least_seconds(
        3 * mod.scan_flops(1024, *shape, 256),
        mod.scan_bytes(1024, *shape, 2), peaks,
    )
    total = int(need * 4 * 1e9)
    ops = [(0, total // 2, "fusion.3"), (total // 2, total, "while.4"),
           (total, total + 100, "fusion.8")]
    ctx = ctx_of(ops, window=total + 100, peaks=peaks)
    assert mod.read(ctx) == pytest.approx(25.0, rel=1e-3)
    # where bytes bound it the share is of the memory's peak
    slow = {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e11}
    assert mod.least_seconds(1e9, 1e9, slow) == pytest.approx(1e-2)
    # a program with no such scope, a family with no such layer (the
    # parent's): nothing, no raise
    bare = ctx_of([(0, 5, "add.9")])
    assert mod.read(bare) is None
    bare.family = types.SimpleNamespace(cfg=types.SimpleNamespace())
    assert mod.read(bare) is None
    bare.family = types.SimpleNamespace()
    assert mod.read(bare) is None
