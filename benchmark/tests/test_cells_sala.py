"""The sixth configuration, ``minicpm-sala-d4``: its file against the
catalog row's ``config`` key by key but for ``reduced``, its manifest
entries, the FLOPs of its cell worked by hand, and a toy cell of the
family end to end through the harness on the CPU.  The four readers are
in ``test_trace_readers_sala.py``."""

import json
import os

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(harness.ROOT)
CONFIG = "minicpm-sala-d4"
CELL = CONFIG + ".lora-all-linear-32k-2p"
TOY = CONFIG + ".toy-2p"
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

# openbmb/MiniCPM-SALA config.json as published, every key of its
# `config`.
PERIOD = ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": PERIOD + ["lightning-attn"] * 6 + ["minicpm4"] * 2
    + ["lightning-attn"] * 4 + ["minicpm4"] + ["lightning-attn"] * 6
    + ["minicpm4"] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
}
RUN = {"num_hidden_layers": 4}
READERS = ("sala_mixer_step_share", "sparse_attn_roofline",
           "lightning_scan_roofline", "sparse_visit_share")


def family(cell=CELL, root=harness.ROOT, seed=0):
    from benchmark.families import minicpm_sala_lm

    cell = harness.load_cell(cell, root=root)
    return minicpm_sala_lm.build(cell["config_data"], cell["job"], seed)


def test_config_file_is_the_catalog_rows_but_for_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(RUN)
    assert len(PUBLISHED["mixer_types"]) == 32
    assert PUBLISHED["mixer_types"].count("minicpm4") == 8
    for key, value in PUBLISHED.items():
        if key in RUN:
            assert config["reduced"][key] == {"published": value, "run": RUN[key]}
            assert config[key] == RUN[key]
        else:
            assert config[key] == value, key
    # the cut: one period, the published 1:3 ratio, no width touched
    kinds = config["mixer_types"][: config["num_hidden_layers"]]
    assert kinds == ["minicpm4"] + ["lightning-attn"] * 3
    assert config["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192,
    }
    for key in ("sparse_config", "selection", "lightning", "minicpm4",
                "block", "weights", "frozen", "loss"):
        assert config["assumed"][key], key
    assert "8 pipeline stages" in config["deployment"]
    assert config["run"]["family"] == "minicpm_sala_lm"


def test_the_manifest_gains_one_configuration_one_cell_four_metrics():
    cells = [w for w in MANIFEST["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL] and cells[0]["chips"] == 1
    assert MANIFEST["workloads"][-1]["name"] == CELL  # appended
    assert MANIFEST["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == list(READERS)
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    mine = {m.NAME: m for m in harness.matching_layer_metrics(CELL)}
    for name in READERS:
        mod = mine[name]
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == mod.LAYER
        assert declared[name]["moves"] == mod.MOVES == "fed_items_per_s"
        assert declared[name]["unit"] == mod.UNIT == "%"
        assert declared[name]["source"] == mod.SOURCE
        for other in MANIFEST["workloads"][:-1]:
            assert name not in {
                m.NAME for m in harness.matching_layer_metrics(other["name"])
            }
    # every metric with no `workloads` list is one this cell reports
    for m in MANIFEST["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine, m["name"]
    cell = harness.load_cell(CELL)
    assert cell["job"]["seq_len"] == 24576  # the recorded fallback
    assert cell["job"]["local_steps"] == 1 and cell["parties"] == 2


def test_the_cells_layers_groups_and_multipliers():
    fam = family()
    c = fam.cfg
    assert [s.mixer for s in c.layers] == ["sparse"] + ["lightning"] * 3
    assert c.groups() == ((0, 1), (1, 4))
    assert (c.num_heads, c.num_kv_heads, c.head_dim) == (32, 2, 128)
    assert c.embed_scale == 12.0 and c.logit_scale == 1 / 16
    assert c.residual_scale == pytest.approx(0.2474873734)
    assert c.qk_norm and c.output_gate and not c.post_norms
    assert not c.tie_embeddings
    assert c.lightning.depth == 32 and c.lightning.chunk == 256
    assert c.sparse.tile == 512 and c.sparse.dense_len == 8192
    assert fam.items_per_step == 24576 and fam.local_steps == 1


def test_flops_per_token_of_the_lora_cell():
    """4 FLOPs a frozen weight and token, 6 an adapter factor; the sparse
    layer at the keys its selection lets a query visit (3,728.5 on
    average) plus the scores of its selection (766.6 compressed keys);
    the scans at chunks of 256; the head over the whole vocabulary."""
    fam = family()
    d, f, v, r, h = 4096, 16384, 73448, 8, 32
    ffn = 4 * 3 * d * f + 6 * r * 3 * (d + f)
    sparse = (4 * (3 * d * d + 2 * d * 256) + 6 * r * (3 * 2 * d + 2 * (d + 256))
              + 12 * h * 128 * 3728.5 + 2 * h * 128 * 766.5631103515625)
    scan = 3 * h * (4 * 128 * 128.5 + 4 * 128 * 128)
    light = 4 * 5 * d * d + 6 * r * 5 * 2 * d + scan
    want = sparse + 3 * light + 4 * (ffn) + 4 * d * v
    assert fam.flops_per_item() == pytest.approx(want)
    assert 5.85e9 < want < 5.95e9
    assert 0.54 < 4 * ffn / want < 0.56  # the FFN over half of it


def test_a_toy_cell_of_the_family_runs_through_the_harness(tmp_path):
    """Two in-process parties on ONE base copy, ``fed.remote`` trainers,
    the streaming hub, the family's layer-by-layer reference check (the
    selection, the recurrence token by token, masked attention over
    every key), and (traced) the selection records the program writes,
    at toy widths on the CPU."""
    cell = harness.load_cell(TOY, root=HERE)
    result = harness.run_cell(
        cell, seed=2**31 + 7, seconds=1.0, trace=True, platform="cpu",
        scratch=str(tmp_path),
    )
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert {"local_step_ms", "fold_ms", "wire_send_ms"} <= set(got)
    assert 0 < got["sparse_visit_share"]["value"] < 100  # a program counter
    # no device plane on the CPU: nothing under a device metric's name
    assert not {"sala_mixer_step_share", "sparse_attn_roofline",
                "lightning_scan_roofline", "local_mfu"} & set(got)


def test_the_reference_check_passes_and_its_fp8_control_fails():
    import jax.numpy as jnp

    fam = family(TOY, HERE, seed=5)
    check = fam.reference_check()
    assert check["ok"] and check["rel_rms"] < 1e-4 and check["layers"] == 4
    assert check["selection_agreement"] == 1.0
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    assert not control["ok"] and control["rel_rms"] > control["tol"]


def test_both_parties_read_one_copy_of_the_base():
    fam = family(TOY, HERE, seed=3)
    a, b = fam.party_state(0), fam.party_state(1)
    assert a["base"] is b["base"]
    assert a["base"] is fam._make_base(fam.base_key())
    assert not (a["ids"][0] == b["ids"][0]).all()  # the data is a party's own
