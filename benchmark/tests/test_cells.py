"""Every cell, configuration and per-layer metric that BENCHMARK.json
names is there as data, loads, and agrees with its declaration."""

import importlib
import json
import os

import pytest

from benchmark import harness

REPO = os.path.dirname(harness.ROOT)
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_its_kwargs_validate(name):
    from rayfed_tpu.fl.trainer import validate_round_config

    entry = next(w for w in MANIFEST["workloads"] if w["name"] == name)
    cell = harness.load_cell(name)
    assert cell["config"] == entry["config"]
    assert cell["traffic"] == entry["traffic"]
    assert cell["chips"] == entry["chips"]
    assert name == f"{cell['config']}.{cell['traffic']}"
    parties = list(harness.PARTY_NAMES[: cell["parties"]])
    kwargs = harness.round_kwargs(cell, parties)
    validate_round_config(
        {p: object() for p in parties}, rounds=harness.RAMP + 8, **kwargs
    )
    assert "timings" not in kwargs and "on_round" not in kwargs
    if cell["chips"] == 4:
        assert cell["placement"] == "one_per_chip"
        assert kwargs["coordinator"] == parties[-1]


@pytest.mark.parametrize("kwarg", ["timings", "on_round"])
def test_a_cell_may_not_turn_the_pipelined_round_off(kwarg):
    cell = {"round_kwargs": {kwarg: []}}
    with pytest.raises(harness.BenchError):
        harness.round_kwargs(cell, ["alice", "bob"])


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_source_but_for_reduced(entry):
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    # Mistral-7B-v0.1's published config.json, width by width.
    published = {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "vocab_size": 32000, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "sliding_window": 4096, "max_position_embeddings": 32768,
        "tie_word_embeddings": False, "num_hidden_layers": 32,
    }
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["reduced"][key]["published"] == value
            assert config[key] == config["reduced"][key]["run"]
        else:
            assert config[key] == value, key
    importlib.import_module(f"benchmark.families.{config['run']['family']}")


def test_per_layer_metrics_match_their_readers():
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    readers = {m.NAME: m for m in harness.matching_layer_metrics("*")}
    every = {}
    for cell in CELLS:
        for mod in harness.matching_layer_metrics(cell):
            every[mod.NAME] = mod
    assert set(every) == set(declared), set(every) ^ set(declared)
    for name, mod in every.items():
        entry = declared[name]
        assert entry["unit"] == mod.UNIT
        assert entry["layer"] == mod.LAYER
        assert entry["moves"] == mod.MOVES and mod.MOVES in end_to_end
        assert entry["source"] == mod.SOURCE
    assert readers  # "*" patterns match any name


def test_flops_per_token_of_the_lora_cell():
    """4 FLOPs per frozen weight and token, banded attention, adapters:
    worked by hand for d6, rank 32 on q and v, T = 8,192, W = 4,096."""
    from benchmark.families import llama_lm

    cell = harness.load_cell("mistral-7b-v0.1-d6.lora-2p")
    fam = llama_lm.build(cell["config_data"], cell["job"], 0)
    d, f, v, kv = 4096, 14336, 32000, 1024
    layer = 4 * (2 * d * d + 2 * d * kv + 3 * d * f)
    layer += 6 * 32 * ((d + d) + (d + kv))
    keys = (4096 * 4097 / 2 + 4096 * 4096) / 8192
    layer += 12 * d * keys
    assert fam.flops_per_item() == pytest.approx(6 * layer + 4 * d * v)
    assert fam.items_per_step == 8192


def test_unknown_device_kind_raises():
    from benchmark.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
