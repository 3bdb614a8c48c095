"""The fourth configuration, ``granite-4.0-h-micro-d20``: its file
against the catalog row's ``config`` key by key but for ``reduced``
(``test_cells.py`` holds every ``configs`` entry to the FIRST
configuration's widths, hard-coded, so its parametrised case for this
entry fails by construction, as for the expert ones; PERF.md section 7),
the FLOPs of its cell worked by hand, and a toy cell of the family end
to end through the harness on the CPU.  The two readers are in
``test_trace_readers_granite.py``.
"""

import json
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(harness.ROOT)
CONFIG = "granite-4.0-h-micro-d20"
CELL = CONFIG + ".lora-all-linear-2p"
TOY = CONFIG + ".toy-2p"
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

# ibm-granite/granite-4.0-h-micro config.json as the catalog beside the
# model-configs guide gives it, every key of its `config`.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
RUN = {"num_hidden_layers": 20}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def family(cell=CELL, root=harness.ROOT, seed=0):
    from benchmark.families import granite_hybrid_lm

    cell = harness.load_cell(cell, root=root)
    return granite_hybrid_lm.build(cell["config_data"], cell["job"], seed)


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
        (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == entry["source"]
    # the cut: two whole periods of the published pattern, no width
    # touched; inside the model-configs guide's floors (a whole period)
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    assert kinds.count("attention") == 2 and kinds.count("mamba") == 18
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15]
    for key in ("assumed", "deployment"):
        assert config[key]
    assert "ONE device copy" in config["assumed"]["frozen"]
    for key in ("scan_buffers", "ffn", "loss", "unused", "weights"):
        assert config["assumed"][key]
    assert config["run"] == {
        "family": "granite_hybrid_lm", "compute_dtype": "bfloat16",
        "param_dtype": "bfloat16", "remat": True, "attention": "flash",
    }


def test_the_manifest_gains_one_configuration_one_cell_two_metrics():
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    assert MANIFEST["workloads"][-1]["name"] == CELL  # appended
    assert MANIFEST["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in MANIFEST["per_layer"][-2:]] == [
        "ssm_step_share", "ssm_scan_roofline",
    ]
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    mine = {m.NAME: m for m in harness.matching_layer_metrics(CELL)}
    for name, layer in (("ssm_step_share", "local step"),
                        ("ssm_scan_roofline", "state-space scan")):
        mod = mine[name]
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == mod.LAYER == layer
        assert declared[name]["moves"] == mod.MOVES == "fed_items_per_s"
        assert declared[name]["unit"] == mod.UNIT == "%"
        assert declared[name]["source"] == mod.SOURCE == "device_trace"
        for other in MANIFEST["workloads"][:-1]:
            assert name not in {
                m.NAME for m in harness.matching_layer_metrics(other["name"])
            }
    # the other configurations' readers stay pinned to their cells
    assert not {"moe_step_share", "expert_mm_roofline", "moe_load_imbalance",
                "latent_attn_step_share", "latent_flash_roofline"} & set(mine)
    # every metric with no `workloads` list is one this cell reports
    for m in MANIFEST["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine, m["name"]


def test_the_cells_layers_groups_and_multipliers():
    fam = family()
    c = fam.cfg
    assert [s.mixer for s in c.layers] == (
        ["ssm"] * 5 + ["full"] + ["ssm"] * 9 + ["full"] + ["ssm"] * 4
    )
    assert {s.ffn for s in c.layers} == {"dense"}
    assert c.groups() == ((0, 5), (5, 6), (6, 15), (15, 16), (16, 20))
    assert (c.num_heads, c.num_kv_heads, c.head_dim) == (32, 8, 64)
    assert (c.embed_scale, c.residual_scale, c.attn_scale, c.logit_scale) == (
        12.0, 0.22, 1 / 64, 1 / 8
    )
    assert c.tie_embeddings and not (c.qk_norm or c.output_gate or c.post_norms)
    m = c.ssm
    assert (m.num_heads, m.head_dim, m.state, m.groups, m.conv_width,
            m.chunk) == (64, 64, 128, 1, 4, 256)
    assert (m.d_inner, m.conv_dim, m.proj_dim) == (4096, 4352, 8512)
    assert fam.items_per_step == 8192 and fam.local_steps == 2


def test_flops_per_token_of_the_lora_cell():
    """4 FLOPs a frozen weight and token, 6 an adapter factor; the scan
    forward (1.05 + 1.05 + 1.05 + 0.03 MFLOP at the published chunk) and
    twice that backward; attention's pairs 6 x 32 x 128 a visible key;
    the head over the whole vocabulary: by hand for depth 20 (ISSUE 35:
    7.21 GFLOP a token, 79% of it state-space layers, their mixers 28%,
    the FFNs 56%)."""
    fam = family()
    d, f, v, r = 2048, 8192, 100352, 8
    ffn = 4 * 3 * d * f + 6 * r * 3 * (d + f)
    scan = 3 * (4096 * 257 + 2 * 2 * 4096 * 128 + 128 * 257)
    conv = 3 * 2 * 4 * 4352
    mamba = (4 * (d * 8512 + 4096 * d) + 6 * r * (d + 8512 + 4096 + d)
             + scan + conv)
    attn = (4 * (2 * d * d + 2 * d * 512) + 6 * r * (2 * (d + d) + 2 * (d + 512))
            + 6 * 32 * 128 * 8193 / 2)
    want = 18 * (mamba + ffn) + 2 * (attn + ffn) + 4 * d * v
    assert fam.flops_per_item() == pytest.approx(want)
    assert 7.20e9 < want < 7.22e9
    assert 0.785 < 18 * (mamba + ffn) / want < 0.795
    assert 0.275 < 18 * mamba / want < 0.285
    assert 0.555 < 20 * ffn / want < 0.565
    assert 9.5e6 < scan < 9.6e6


def test_a_toy_cell_of_the_family_runs_through_the_harness(tmp_path):
    """Two in-process parties on ONE base copy, ``fed.remote`` trainers,
    the streaming hub, the family's layer-by-layer reference check (the
    recurrence token by token against the chunked scan), and (traced)
    the records the program writes, at toy widths on the CPU."""
    cell = harness.load_cell(TOY, root=HERE)
    result = harness.run_cell(
        cell, seed=2**31 + 7, seconds=1.0, trace=True, platform="cpu",
        scratch=str(tmp_path),
    )
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert {"local_step_ms", "fold_ms", "wire_send_ms"} <= set(got)
    # no device plane on the CPU: nothing under a device metric's name
    assert not {"ssm_step_share", "ssm_scan_roofline", "local_mfu"} & set(got)


def test_the_reference_check_passes_and_its_fp8_control_fails():
    """At toy widths in float32 the system is within float32 of the
    reference; the control (fp8 operands in the reference's products)
    is refused by the logits' limit."""
    import jax.numpy as jnp

    fam = family(TOY, HERE, seed=5)
    check = fam.reference_check()
    assert check["ok"] and check["rel_rms"] < 1e-3 and check["layers"] == 4
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    assert not control["ok"] and control["rel_rms"] > control["tol"]


def test_both_parties_read_one_copy_of_the_base():
    fam = family(TOY, HERE, seed=3)
    a, b = fam.party_state(0), fam.party_state(1)
    assert a["base"] is b["base"]
    assert a["base"] is fam._make_base(fam.base_key())
    assert not (a["ids"][0] == b["ids"][0]).all()  # the data is a party's own
