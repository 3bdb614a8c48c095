"""The third configuration, ``kimi-k2.7-code-ep32``: its file against
the catalog row's ``config`` key by key but for ``reduced``
(``test_cells.py`` holds every ``configs`` entry to the FIRST
configuration's widths, hard-coded, so its parametrised case for this
entry fails by construction, as for Trinity's; PERF.md section 7), the
FLOPs of its cell worked by hand, its two readers on hand-made input,
and a toy cell of the family end to end through the harness on the CPU.
"""

import json
import os
import types

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(harness.ROOT)
CONFIG = "kimi-k2.7-code-ep32"
CELL = CONFIG + ".lora-all-linear-2p"
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

# moonshotai/Kimi-K2.7-Code config.json as the catalog beside the
# model-configs guide gives it, every key of its `config`.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "kimi_k2", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn",
    },
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 163840,
}
RUN = {"num_hidden_layers": 5, "n_routed_experts": 12, "vocab_size": 20480}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def reader(name):
    (mod,) = [m for m in harness.matching_layer_metrics(CELL) if m.NAME == name]
    return mod


def family():
    from benchmark.families import kimi_k2_lm

    cell = harness.load_cell(CELL)
    return kimi_k2_lm.build(cell["config_data"], cell["job"], 0)


def test_config_file_is_the_catalog_rows_but_for_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
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
    if os.path.exists(CATALOG):  # the row itself, where the guide is
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        (row,) = [r for r in rows if r["name"] == "Kimi-K2.7-Code"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == entry["source"]
    # the share: 1/32 of the experts, 1/8 of the vocabulary, the router
    # as published; inside the model-configs guide's floors
    assert config["router_width"] == PUBLISHED["n_routed_experts"]
    assert config["run"]["held_experts"] == list(range(12))
    assert config["n_routed_experts"] * 32 == PUBLISHED["n_routed_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["n_routed_experts"] >= 8
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    for key in ("assumed", "deployment"):
        assert config[key]
    assert "ONE device copy" in config["assumed"]["frozen"]


def test_the_manifest_gains_one_configuration_one_cell_two_metrics():
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, layer in (("latent_attn_step_share", "local step"),
                        ("latent_flash_roofline", "attention kernel")):
        mod = reader(name)
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == mod.LAYER == layer
        assert declared[name]["moves"] == mod.MOVES == "fed_items_per_s"
        assert not any(
            m.NAME == name for m in harness.matching_layer_metrics(
                "trinity-mini-ep8.lora-all-linear-2p")
        )
    # Trinity's three readers stay pinned to its cell
    assert not {"moe_step_share", "expert_mm_roofline", "moe_load_imbalance"} & {
        m.NAME for m in harness.matching_layer_metrics(CELL)
    }


def test_every_layer_is_latent_and_the_first_is_dense():
    fam = family()
    assert [(s.attention, s.ffn) for s in fam.cfg.layers] == (
        [("latent", "dense")] + [("latent", "moe")] * 4
    )
    assert fam.cfg.groups() == ((0, 1), (1, 5))
    assert fam.experts.num_experts == 384 and len(fam.experts.held) == 12
    assert not (fam.cfg.qk_norm or fam.cfg.output_gate or fam.cfg.post_norms)
    assert abs(fam.cfg.rope_scaling.softmax_scale() - 2.00474) < 1e-5


def test_flops_per_token_of_the_lora_cell():
    """4 FLOPs a frozen weight and token, 6 an adapter factor; the
    latent kernel's pairs 6 x 64 x (192 + 128) a visible key; routed
    experts at 0.25 held assignments a token; the head over the slice:
    by hand for depth 5 (ISSUE 33: 7.63 GFLOP a token, 33% of it the
    latent kernels' pairs, 59% latent attention)."""
    fam = family()
    d, f, fe, v, r, h = 7168, 18432, 2048, 20480, 8, 64
    shapes = [(d, 1536), (1536, h * 192), (d, 576), (512, h * 256), (h * 128, d)]
    attn = sum(4 * i * o + 6 * r * (i + o) for i, o in shapes)
    pairs = 6 * h * (192 + 128) * 8193 / 2
    dense = 4 * 3 * d * f + 6 * r * 3 * (d + f)
    expert = 4 * 3 * d * fe + 6 * r * 3 * (d + fe)
    moe = 4 * d * 384 + expert + (8 * 12 / 384) * expert
    want = (attn + pairs + dense) + 4 * (attn + pairs + moe) + 4 * d * v
    assert fam.flops_per_item() == pytest.approx(want)
    assert 7.60e9 < want < 7.70e9
    assert 0.32 < 5 * pairs / want < 0.34
    assert 0.58 < 5 * (attn + pairs) / want < 0.60
    assert fam.items_per_step == 8192


PROGRAM = """
HloModule jit_decoder_lora_step
ENTRY %main {
  %fusion.1 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(decoder_lora_step)/jvp(layers1-4)/while/body/closed_call/attn.latent/mul" stack_frame_id=3}
  %flash.fwd.2 = bf16[16,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(decoder_lora_step)/jvp(layers1-4)/while/body/closed_call/attn.latent/jit(_flash_forward)/flash.fwd/pallas_call"}
  %flash.dq.3 = bf16[16,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(decoder_lora_step)/transpose(jvp(layers1-4))/while/body/closed_call/transpose(jvp(attn.latent))/jit(_flash_backward_pallas)/flash.dq/pallas_call"}
  %flash.dkv.4 = bf16[16,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(decoder_lora_step)/transpose(jvp(layers1-4))/while/body/closed_call/transpose(jvp(attn.latent))/jit(_flash_backward_pallas)/flash.dkv/pallas_call"}
  %copy.8 = bf16[16,8]{1,0} copy(%flash.dkv.4), metadata={op_name="jit(decoder_lora_step)/transpose(jvp(layers1-4))/while/body/closed_call/transpose(jvp(attn.latent))/jit(_flash_backward_pallas)/flash.dkv/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%q), kind=kLoop, calls=%g, metadata={op_name="jit(decoder_lora_step)/jvp(layers1-4)/while/body/closed_call/attn.proj/dot_general"}
  %fusion.6 = f32[8]{0} fusion(%q), kind=kLoop, calls=%g, metadata={op_name="jit(decoder_lora_step)/jvp(layers1-4)/while/body/closed_call/moe.route/top_k"}
  ROOT %add.7 = f32[] add(%x, %y), metadata={op_name="jit(decoder_lora_step)/reduce_sum"}
}
"""


def latent_ctx(ops, peak=100e12):
    from benchmark.layer_metrics.moe_step_share import instruction_op_names

    return types.SimpleNamespace(
        peaks={"bf16_flops": peak},
        family=types.SimpleNamespace(
            batch=1, seq=1024, cfg=types.SimpleNamespace(
                num_heads=4, latent=types.SimpleNamespace(
                    nope_dim=128, rope_dim=64, v_dim=128)),
        ),
        _step_events=([(0, 10**7, ops)], instruction_op_names(PROGRAM)),
    )


def test_latent_share_is_the_scopes_self_time_over_the_programs():
    mod = reader("latent_attn_step_share")
    # 100 ns of rotation and 300 of kernels under attn.latent, 400 ns of
    # projections, 100 of routing, 100 outside every scope: 400 of 1000
    ops = [(0, 100, "fusion.1"), (100, 300, "flash.fwd.2"), (300, 400, "flash.dq.3"),
           (400, 800, "fusion.5"), (800, 900, "fusion.6"), (900, 1000, "add.7")]
    ctx = latent_ctx(ops)
    ctx._step_events = ([(0, 1000, ops)], ctx._step_events[1])
    totals = mod.scope_seconds(*ctx._step_events)
    assert totals == pytest.approx({
        "attn.latent": 400e-9, "attn.proj": 400e-9, "moe.route": 100e-9,
        "other": 100e-9,
    })
    assert mod.read(ctx) == pytest.approx(40.0)
    empty = types.SimpleNamespace(family=None, trace={}, run=None)
    assert mod.read(empty) is None  # nothing to read: no raise


def test_latent_roofline_counts_visible_pairs_at_the_published_widths():
    mod = reader("latent_flash_roofline")
    assert mod.flops_per_pair(128, 64, 128) == {"fwd": 640, "dq": 1024, "dkv": 1280}
    assert mod.visible_pairs(1, 8192) == 8192 * 8193 // 2
    # visible pairs x 64 heads x 2 x (320 + 512 + 640), ISSUE 33
    assert mod.layer_flops(1, 8192, 64, 128, 64, 128) == (
        8192 * 8193 // 2 * 64 * 2 * (320 + 512 + 640)
    )
    names = {"fwd": "flash.fwd.2", "dq": "flash.dq.3", "dkv": "flash.dkv.4"}
    from benchmark.layer_metrics.moe_step_share import instruction_op_names

    op_names = instruction_op_names(PROGRAM)
    assert {k: mod.kernel_of(v, op_names[v]) for k, v in names.items()} == {
        k: k for k in names
    }
    assert mod.kernel_of("fusion.1", op_names["fusion.1"]) is None
    # a copy beside the call carries its op_name and is no kernel
    assert mod.kernel_of("copy.8", op_names["copy.8"]) is None
    assert mod.kernel_of("flash.fwd.9", "jit(s)/attn.window/flash.fwd/pallas_call") is None
    # two layers' events a kind; the kernels at 25% of a 100 TF/s peak
    flops = mod.layer_flops(1, 1024, 4, 128, 64, 128)
    each = int(flops / 3 / 25e12 * 1e9)
    ops, t = [], 0
    for _ in range(2):
        for name in names.values():
            ops.append((t, t + each, name))
            t += each + 10
    ops += [(t, t + 5, "fusion.1"), (t + 5, t + 9, "copy.8")]
    assert mod.read(latent_ctx(ops)) == pytest.approx(25.0, rel=1e-3)
    # a program with no such span or counter (the parent's): nothing, no raise
    bare = latent_ctx([(0, 5, "add.7")])
    assert mod.read(bare) is None
    bare.family = types.SimpleNamespace(cfg=types.SimpleNamespace())
    assert mod.read(bare) is None


def test_a_toy_cell_of_the_family_runs_through_the_harness(tmp_path):
    """Two in-process parties on ONE base copy, ``fed.remote`` trainers,
    the streaming hub, the family's layer-by-layer reference check, and
    (traced) the records the program writes, at toy widths on the CPU."""
    cell = harness.load_cell("kimi-k2.7-code-ep32.toy-2p", root=HERE)
    result = harness.run_cell(
        cell, seed=2**31 + 7, seconds=1.0, trace=True, platform="cpu",
        scratch=str(tmp_path),
    )
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert {"local_step_ms", "fold_ms", "wire_send_ms"} <= set(got)
    # no device plane on the CPU: nothing under a device metric's name
    assert not {"latent_attn_step_share", "latent_flash_roofline",
                "local_mfu"} & set(got)


def test_both_parties_read_one_copy_of_the_base():
    from benchmark.families import kimi_k2_lm

    cell = harness.load_cell("kimi-k2.7-code-ep32.toy-2p", root=HERE)
    fam = kimi_k2_lm.build(cell["config_data"], cell["job"], 3)
    a, b = fam.party_state(0), fam.party_state(1)
    assert a["base"] is b["base"]
    assert a["base"] is fam._make_base(fam.base_key())
    assert not (a["ids"][0] == b["ids"][0]).all()  # the data is a party's own
