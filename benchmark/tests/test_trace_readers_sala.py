"""The four readers the ``minicpm_sala`` cell adds
(``sala_mixer_step_share``, ``sparse_attn_roofline``,
``lightning_scan_roofline``, ``sparse_visit_share``) on hand-made input:
a compiled step's text with the scopes the program gives its operations,
device operations with known durations, and ``attn.select`` records
(``test_trace_readers_nemotron.py``'s way)."""

import json
import types

import pytest

from benchmark import harness
from benchmark.layer_metrics.step_scoped_share import program_op_names

CELL = "minicpm-sala-d4.lora-all-linear-32k-2p"
STEP = "jit(decoder_lora_step)"
BODY = "while/body/closed_call"

PROGRAM = f"""
HloModule jit_decoder_lora_step
ENTRY %main {{
  %fusion.1 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers0-0)/{BODY}/attn.proj/dot_general"}}
  %fusion.2 = s32[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers0-0)/{BODY}/attn.select/closed_call/top_k"}}
  %sparse.3 = bf16[8,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layers0-0)/{BODY}/attn.sparse/jit(_forward)/sparse.fwd/pallas_call"}}
  %sparse.4 = bf16[8,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(layers0-0))/{BODY}/checkpoint/attn.sparse/attn.sparse/jit(_backward)/sparse.dkv/pallas_call"}}
  %ssd.5 = bf16[8,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layers1-3)/{BODY}/attn.lightning/ssm.scan/jit(_forward)/ssd.fwd/pallas_call"}}
  %ssd.6 = bf16[8,8]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(layers1-3))/{BODY}/checkpoint/ssm.scan/jit(_backward)/ssd.bwd/pallas_call"}}
  %fusion.7 = bf16[8,8]{{1,0}} fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(layers1-3)/{BODY}/ffn.dense/dot_general"}}
  ROOT %add.8 = f32[] add(%x, %y), metadata={{op_name="{STEP}/head.loss/reduce_sum"}}
}}
"""


def reader(name):
    (mod,) = [m for m in harness.matching_layer_metrics(CELL) if m.NAME == name]
    return mod


def family():
    from benchmark.families import minicpm_sala_lm

    cell = harness.load_cell(CELL)
    return minicpm_sala_lm.build(cell["config_data"], cell["job"], 0)


def ctx_of(ops, window, fam=None, records=(), peaks=None):
    return types.SimpleNamespace(
        peaks=peaks or {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12},
        family=fam or family(),
        recorder_records=list(records),
        _sala_step=([(0, window, ops)], program_op_names(PROGRAM)[0]),
    )


def test_the_mixer_share_charges_selection_kernels_and_scans(capsys):
    """100 ns each: the projections (not a mixer), the selection, the
    sparse forward and dK/dV kernels, the scan's forward under
    ``attn.lightning`` and its backward under ``ssm.scan`` of the
    linear-attention group alone, the FFN, the head: 500 of 800 ns."""
    mod = reader("sala_mixer_step_share")
    names = ["fusion.1", "fusion.2", "sparse.3", "sparse.4", "ssd.5",
             "ssd.6", "fusion.7", "add.8"]
    ops = [(100 * i, 100 * (i + 1), n) for i, n in enumerate(names)]
    ctx = ctx_of(ops, 800)
    assert mod.read(ctx) == pytest.approx(500 / 800 * 100)
    parts, program = mod.mixer_seconds(ctx)
    assert parts == pytest.approx({"select": 100e-9, "sparse": 200e-9,
                                   "lightning": 200e-9})
    assert program == pytest.approx(800e-9)
    logged = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert set(logged["sala_mixer_ms"]) == {"select", "sparse", "lightning"}
    assert mod.lightning_groups(ctx.family) == {"layers1-3"}
    # a scan outside the linear-attention groups is not theirs
    assert mod.part_of(f"{STEP}/jvp(layers0-0)/ssm.scan/x", {"layers1-3"}) == ""
    assert mod.part_of(f"{STEP}/jvp(attn.selectx)/y", set()) == ""
    empty = types.SimpleNamespace(family=None, trace={}, run=None)
    assert mod.read(empty) is None  # nothing to read: no raise


def test_the_sparse_roofline_counts_the_published_selection_not_the_tiles():
    """A query of the cell visits ``min(t + 1, 63 x 64 + t % 64 + 1)``
    keys (64 blocks of 64, its own partly), 3,728.5 on average at
    24,576 tokens: 30.3% of dense causal attention's; the work is
    12 x 32 x 128 FLOPs a visited key and 2 x 32 x 128 a compressed key
    scored.  Timed at 40% of the peak it reads 40."""
    mod = reader("sparse_attn_roofline")
    fam = family()
    sizes = fam.config["sparse_config"]
    keys, windows = mod.visited_keys(24576, sizes)
    assert keys / 24576 == pytest.approx(3728.5)
    assert keys / (24576 * 24577 / 2) == pytest.approx(0.3034, abs=1e-4)
    assert windows / 24576 == pytest.approx(766.56, abs=0.01)
    # up to dense_len every causal key, no selection
    assert mod.visited_keys(8192, sizes) == (8192 * 8193 / 2, 0.0)
    flops = mod.attention_flops(24576, sizes, 32, 128)
    assert flops == 12 * 32 * 128 * keys + 2 * 32 * 128 * windows
    nbytes = mod.attention_bytes(24576, 32, 2, 128, 2)
    least = max(flops / 100e12, nbytes / 1e12)
    ns = int(least / 0.4 * 1e9)
    ops = [(0, ns // 2, "fusion.2"), (ns // 2, ns, "sparse.3"),
           (ns, 2 * ns, "add.8")]
    assert mod.read(ctx_of(ops, 2 * ns, fam)) == pytest.approx(40.0, rel=1e-3)
    # no sparse kernel ran: nothing to read
    assert mod.read(ctx_of([(0, 10, "add.8")], 10, fam)) is None


def test_the_lightning_roofline_counts_three_layers_at_its_own_chunk():
    mod = reader("lightning_scan_roofline")
    fam = family()
    assert mod.CHUNK == 256
    per_token = mod.scan_flops(1, 32, 128)
    assert per_token == 32 * (2 * 2 * 128 * 128.5 + 2 * 2 * 128 * 128)
    flops = 3 * mod.scan_flops(24576, 32, 128)
    nbytes = mod.scan_bytes(24576, 32, 128, 2)
    least = 3 * max(flops / 100e12, nbytes / 1e12)  # three layers
    ns = int(least / 0.25 * 1e9)
    ops = [(0, ns // 2, "ssd.5"), (ns // 2, ns, "ssd.6"), (ns, 2 * ns, "add.8")]
    assert mod.read(ctx_of(ops, 2 * ns, fam)) == pytest.approx(25.0, rel=1e-3)


def test_the_visit_share_reads_the_selection_records():
    mod = reader("sparse_visit_share")
    rec = lambda keys: types.SimpleNamespace(phase="attn.select", detail={
        "tokens": 24576, "dense_keys": 12288.5,
        "layers": [{"layer": 0, "visited_keys": keys, "blocks": 63.0}],
    })
    ctx = types.SimpleNamespace(recorder_records=[
        rec(3700.0), rec(3757.0),
        types.SimpleNamespace(phase="moe.counts", detail={}),
    ])
    assert mod.read(ctx) == pytest.approx(100 * 3728.5 / 12288.5)
    # the parent's program writes no such record: nothing, no raise
    assert mod.read(types.SimpleNamespace(recorder_records=[])) is None
