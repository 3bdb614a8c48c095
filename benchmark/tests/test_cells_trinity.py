"""The second configuration, ``trinity-mini-ep8``: its file against the
published ``config.json`` key by key (``test_cells.py`` holds every
``configs`` entry to the FIRST configuration's widths, hard-coded, so
its parametrised case for this entry fails by construction; that table
moves into data with the next ``benchmark`` PR, PERF.md section 7), the
FLOPs of its cell worked by hand, its three readers on hand-made input,
and a toy cell of the family end to end through the harness on the CPU.
"""

import json
import os
import types

import pytest

from benchmark import harness
from rayfed_tpu.telemetry import SpanRecord

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(harness.ROOT)
CELL = "trinity-mini-ep8.lora-all-linear-2p"
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

# arcee-ai/Trinity-Mini config.json, every number and switch of it.
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192,
}
RUN = {"num_hidden_layers": 9, "num_dense_layers": 1, "num_experts": 16,
       "vocab_size": 25024}


def reader(name):
    (mod,) = [m for m in harness.matching_layer_metrics(CELL) if m.NAME == name]
    return mod


def test_config_file_is_trinity_minis_but_for_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "trinity-mini-ep8")
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(RUN)
    for key, value in PUBLISHED.items():
        if key in RUN:
            assert config["reduced"][key] == {"published": value, "run": RUN[key]}
            assert config[key] == RUN[key]
        else:
            assert config[key] == value, key
    # the published list of 32 kinds, whole: three windowed, one full
    assert config["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]
    ) * 8
    # the share: an eighth of the experts and of the vocabulary, the
    # router as published; inside the model-configs guide's floors
    assert config["router_width"] == PUBLISHED["num_experts"]
    assert config["run"]["held_experts"] == list(range(16))
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_experts"] >= 8 and config["num_hidden_layers"] - 1 >= 4
    for key in ("assumed", "deployment"):
        assert config[key]


def test_the_layers_run_are_the_first_nine_published():
    from benchmark.families import afmoe_lm

    cell = harness.load_cell(CELL)
    specs = afmoe_lm.layer_specs(cell["config_data"])
    assert [(s.attention, s.ffn) for s in specs] == [
        ("window", "dense"),
        ("window", "moe"), ("window", "moe"), ("full", "moe"), ("window", "moe"),
        ("window", "moe"), ("window", "moe"), ("full", "moe"), ("window", "moe"),
    ]


def test_flops_per_token_of_the_lora_cell():
    """4 FLOPs a frozen weight and token, 6 an adapter factor, attention
    banded (window 2,048 of 8,192) or causal, routed experts at one held
    assignment a token, the head over the slice: by hand for depth 9."""
    from benchmark.families import afmoe_lm

    cell = harness.load_cell(CELL)
    fam = afmoe_lm.build(cell["config_data"], cell["job"], 0)
    d, q, kv, f, fe, v, r = 2048, 4096, 512, 6144, 1024, 25024, 8
    attn = 4 * (3 * d * q + 2 * d * kv) + 6 * r * (3 * (d + q) + 2 * (d + kv))
    window = 12 * q * (2048 * 2049 / 2 + 6144 * 2048) / 8192
    full = 12 * q * 8193 / 2
    dense = 4 * 3 * d * f + 6 * r * 3 * (d + f)
    expert = 4 * 3 * d * fe + 6 * r * 3 * (d + fe)
    moe = 4 * d * 128 + expert + (8 * 16 / 128) * expert
    want = (attn + window + dense) + 6 * (attn + window + moe) \
        + 2 * (attn + full + moe) + 4 * d * v
    assert fam.flops_per_item() == pytest.approx(want)
    assert 2.78e9 < want < 2.80e9  # ISSUE 28's 2.79 GFLOP a token
    assert fam.items_per_step == 8192


PROGRAM = """
HloModule jit_decoder_lora_step
ENTRY %main {
  %fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(decoder_lora_step)/jvp(layers1-8)/while/body/closed_call/cond/branch_1_fun/attn.window/mul" stack_frame_id=3}
  %while.2 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(decoder_lora_step)/jvp(layers1-8)/while/body/closed_call/while"}
  %gmm.3 = bf16[16,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(decoder_lora_step)/transpose(jvp(layers1-8))/while/body/closed_call/checkpoint/while/body/transpose(jvp(moe.experts))/grouped_matmul/jit(gmm)/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%q), kind=kLoop, calls=%g, metadata={op_name="jit(decoder_lora_step)/jvp(layers1-8)/while/body/closed_call/moe.route/top_k"}
  ROOT %add.5 = f32[] add(%x, %y), metadata={op_name="jit(decoder_lora_step)/reduce_sum"}
}
"""


def test_device_time_goes_to_the_scope_in_the_instructions_op_name():
    mod = reader("moe_step_share")
    names = mod.instruction_op_names(PROGRAM)
    assert names["gmm.3"].endswith("grouped_matmul/jit(gmm)/pallas_call")
    assert reader("expert_mm_roofline").KERNEL.search(names["gmm.3"]).groups() == ("1", "8")
    assert mod.instruction_of(
        "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), kind=kLoop"
    ) == "fusion.1"
    # one step: 100 ns of attention, a 300 ns while that holds 200 ns of
    # grouped product (its own 100 ns belong to no scope), 50 ns of
    # routing, 50 ns outside every scope
    ops = [(0, 100, "fusion.1"), (100, 400, "while.2"), (150, 350, "gmm.3"),
           (400, 450, "fusion.4"), (450, 500, "add.5")]
    totals = mod.scope_seconds([(0, 500, ops)], names)
    assert totals == pytest.approx({
        "attn.window": 100e-9, "moe.experts": 200e-9, "moe.route": 50e-9,
        "other": 150e-9,
    })


def route_record(counts_by_layer, tokens=8192):
    return SpanRecord(
        party="alice", round=None, epoch=None, phase="moe.counts", peer=None,
        stream=None, nbytes=0, t_start=0.0, dur_s=0.0, outcome="ok",
        detail={"tokens": tokens, "top_k": 8, "dropped": 0,
                "chunk_rows": 20480, "layers": [
            {"layer": i, "counts": c, "dropped": 0,
             "held_share": sum(c) / (tokens * 8)}
            for i, c in counts_by_layer.items()
        ]},
    )


def test_grouped_product_flops_count_assigned_rows_only():
    mod = reader("expert_mm_roofline")
    # 8,192 rows of one expert matrix each: 2 x 8192 x 2048 x 1024
    assert mod.flops_per_event(8192, 2048, 1024) == 2 * 8192 * 2048 * 1024
    # 17,000 held assignments fill chunks of 10,240 rows in order
    assert [mod.chunk_rows(17000, c, 10240) for c in range(3)] == [10240, 6760, 0]
    # One instruction serves every layer of its scanned group: its events
    # in a step are the layers in order (backward: reversed), a layer's
    # chunks in order; layer 2 here fills a chunk and 500 rows of a second.
    forward = "jit(s)/jvp(layers1-3)/while/body/moe.experts/grouped_matmul/jit(gmm)/pallas_call"
    held = {1: 9000, 2: 10740, 3: 8000}
    assert mod.event_rows(forward, held, 10240) == [9000, 10240, 500, 8000]
    assert mod.event_rows(
        forward.replace("jvp(layers1-3)", "transpose(jvp(layers1-3))"), held, 10240
    ) == [8000, 10240, 500, 9000]
    assert mod.event_rows("jit(s)/jvp(layers1-3)/moe.experts/dot_general", held, 10240) is None
    assert mod.event_rows(forward + "/jit(searchsorted)/gather", held, 10240) is None
    ctx = types.SimpleNamespace(recorder_records=[], trace={}, peaks=None,
                                family=None)
    assert mod.read(ctx) is None  # nothing to read: no raise


def test_grouped_product_events_meet_their_layers_rows():
    """One step of a group of two expert layers, one instruction forward
    and one backward: each event's FLOPs come from its own layer's held
    rows (layer 1: 8,192, layer 2: 4,096), the backward events in
    reverse; a step in which an instruction ran another number of times
    than the records give is left out."""
    mod = reader("expert_mm_roofline")
    fwd = "jit(s)/jvp(layers1-2)/while/body/moe.experts/grouped_matmul/jit(gmm)/pallas_call"
    bwd = fwd.replace("jvp(layers1-2)", "transpose(jvp(layers1-2))")
    flop = lambda rows: 2.0 * rows * 2048 * 1024
    # at 10% of the peak forward, 20% backward
    dur = lambda rows, share: int(flop(rows) / (share * 100e12) * 1e9)
    ops = [(0, dur(8192, 0.1), "gmm.1"), (10**6, 10**6 + dur(4096, 0.1), "gmm.1"),
           (2 * 10**6, 2 * 10**6 + dur(4096, 0.2), "gmm.2"),
           (3 * 10**6, 3 * 10**6 + dur(8192, 0.2), "gmm.2"),
           (4 * 10**6, 4 * 10**6 + 5, "fusion.9")]
    odd = [(0, 100, "gmm.1")]  # one event where two are due: left out
    ctx = types.SimpleNamespace(
        recorder_records=[route_record({1: [512] * 16, 2: [256] * 16})],
        peaks={"bf16_flops": 100e12},
        family=types.SimpleNamespace(
            experts=types.SimpleNamespace(d_model=2048, d_ff=1024)),
        _step_events=([(0, 5 * 10**6, ops), (0, 100, odd)],
                      {"gmm.1": fwd, "gmm.2": bwd, "fusion.9": "jit(s)/mul"}),
    )
    assert mod.read(ctx) == pytest.approx(15.0, rel=1e-3)  # median of 10, 10, 20, 20


def test_load_imbalance_is_the_largest_held_expert_over_the_mean():
    mod = reader("moe_load_imbalance")
    assert mod.layer_imbalance([512] * 16) == 1.0
    assert mod.layer_imbalance([0, 0, 1024, 1024]) == 2.0
    ctx = types.SimpleNamespace(recorder_records=[
        route_record({1: [4, 4, 4, 4], 2: [8, 4, 2, 2], 3: [16, 0, 0, 0]}),
        route_record({1: [4, 4, 4, 4], 2: [4, 4, 4, 4], 3: [4, 4, 4, 4]}),
        route_record({1: [16, 0, 0, 0], 2: [16, 0, 0, 0], 3: [16, 0, 0, 0]}),
    ])
    # per step the median over its layers: 2.0, 1.0, 4.0; then the median
    assert mod.read(ctx) == 2.0
    assert mod.read(types.SimpleNamespace(recorder_records=[])) is None


def test_per_layer_entries_name_the_new_cell():
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in ("moe_step_share", "expert_mm_roofline", "moe_load_imbalance"):
        mod = reader(name)
        assert declared[name]["workloads"] == [CELL]
        assert mod.CELLS == ["trinity-mini-ep8.*"]
        assert not any(
            m.NAME == name
            for m in harness.matching_layer_metrics("mistral-7b-v0.1-d6.lora-2p")
        )


def test_a_toy_cell_of_the_family_runs_through_the_harness(tmp_path):
    """Two in-process parties, ``fed.remote`` trainers, the streaming
    hub, the family's reference check, and (traced) the routing records
    the program writes, at toy widths on the CPU."""
    cell = harness.load_cell("trinity-mini-ep8.toy-2p", root=HERE)
    result = harness.run_cell(
        cell, seed=2**31 + 5, seconds=1.0, trace=True, platform="cpu",
        scratch=str(tmp_path),
    )
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert got["moe_load_imbalance"]["value"] >= 1.0
    assert {"local_step_ms", "fold_ms", "wire_send_ms"} <= set(got)
    # no device plane on the CPU: nothing under a device metric's name
    assert not {"moe_step_share", "expert_mm_roofline", "local_mfu"} & set(got)
