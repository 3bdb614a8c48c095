"""The fifth configuration, ``nemotron-3-super-120b-a12b-ep8-d11``: its
file against the catalog row's ``config`` key by key but for
``reduced``, the manifest's new entries, the FLOPs of its cell worked by
hand, and a toy cell of the family end to end through the harness on the
CPU, with the reference check and its fp8 control.  The three readers
are in ``test_trace_readers_nemotron.py``.
"""

import json
import os

import pytest

from benchmark import harness
from rayfed_tpu.models import decoder

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(harness.ROOT)
CONFIG = "nemotron-3-super-120b-a12b-ep8-d11"
CELL = CONFIG + ".lora-all-linear-2p"
WIRE = "mistral-7b-v0.1-d6.qlora-wire-k10"
TOY = CONFIG + ".toy-2p"
# The catalog of published configurations (JSON lines), where one is given.
CATALOG = os.environ.get("MODEL_CATALOG", "")
RUN = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 64),
       "vocab_size": (131072, 16384)}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def family(cell=CELL, root=harness.ROOT, seed=0):
    from benchmark.families import nemotron_h_lm

    cell = harness.load_cell(cell, root=root)
    return nemotron_h_lm.build(cell["config_data"], cell["job"], seed)


def test_config_file_is_the_catalog_rows_but_for_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(RUN)
    for key, (published, run) in RUN.items():
        assert config["reduced"][key] == {"published": published, "run": run}
        assert config[key] == run
    if os.path.exists(CATALOG):  # the row itself, where the catalog is given
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
        assert row["source_url"] == entry["source"]
        for key, value in row["config"].items():
            if key not in RUN:
                assert config[key] == value, key
    for key in ("assumed", "deployment"):
        assert config[key]
    assert "64 v5e chips" in config["deployment"]
    assert config["run"]["held_experts"] == list(range(64))
    assert config["router_width"] == 512


def test_the_manifest_gains_one_configuration_two_cells_three_metrics():
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert names[-2:] == [CELL, WIRE]
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[CELL]["chips"] == 1 and cells[WIRE]["chips"] == 4
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 2
    assert len(names) == 9  # 25% of 9, rounded down, is 2
    assert MANIFEST["configs"][-1]["name"] == CONFIG
    new = ["latent_moe_step_share", "latent_expert_mm_roofline",
           "mtp_step_share"]
    assert [m["name"] for m in MANIFEST["per_layer"][-3:]] == new
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    mine = {m.NAME: m for m in harness.matching_layer_metrics(CELL)}
    for name in new:
        mod = mine[name]
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == mod.LAYER
        assert declared[name]["moves"] == mod.MOVES == "fed_items_per_s"
        assert declared[name]["source"] == mod.SOURCE == "device_trace"
        for other in names:
            if other != CELL:
                assert name not in {
                    m.NAME for m in harness.matching_layer_metrics(other)
                }
    for m in MANIFEST["per_layer"]:
        if "workloads" not in m:
            assert m["name"] in mine, m["name"]
    wire = harness.load_cell(WIRE)
    assert wire["job"]["local_steps"] == 10 and wire["parties"] == 4
    assert wire["placement"] == "one_per_chip" and wire["coordinator"] == "last"


def test_the_cells_layers_groups_and_widths():
    fam = family()
    c, e, m = fam.cfg, fam.experts, fam.cfg.ssm
    assert fam.pattern == "MEMEMEM*EME" and fam.mtp_pattern == "*E"
    assert [(s.mixer, s.ffn) for s in c.stack] == [
        ("ssm", "moe")] * 3 + [("ssm", "none"), ("full", "moe"),
                               ("ssm", "moe"), ("full", "moe")]
    assert c.groups() == ((0, 3), (3, 4), (4, 5), (5, 6))
    assert c.mtp_groups() == ((6, 7),)
    assert (c.num_heads, c.num_kv_heads, c.head_dim) == (32, 2, 128)
    assert not (c.qk_norm or c.output_gate or c.post_norms or c.tie_embeddings)
    assert (m.num_heads, m.head_dim, m.state, m.groups, m.chunk) == (
        128, 64, 128, 8, 128)
    assert (m.d_inner, m.conv_dim, m.proj_dim) == (8192, 10240, 18560)
    assert (e.num_experts, len(e.held), e.top_k, e.d_ff, e.latent,
            e.shared_width, e.activation, e.route_scale) == (
        512, 64, 22, 2688, 1024, 5376, "relu2", 5.0)
    assert decoder.MTP_LOSS_WEIGHT == 0.1
    assert fam.items_per_step == 8192 and fam.local_steps == 2


def test_flops_per_token_of_the_lora_cell():
    """4 FLOPs a frozen weight and token, 6 an adapter factor; the scan
    forward at the published chunk of 128 and twice that backward;
    attention's pairs 6 x 32 x 2 x 128 a visible key; a routed expert's
    two latent matrices at 2.75 assignments a token; two heads over the
    slice: by hand.  2,572 MFLOP a token forward; the frozen weights'
    backward makes it twice that, the pairs' and the scan's three times:
    5.33 GFLOP a token, 43.7 TFLOP a step of 8,192 tokens."""
    fam = family()
    r, d, v = 8, 4096, 16384
    lin = lambda i, o: 4 * i * o + 6 * r * (i + o)
    scan = 3 * (2 * 8192 * 64.5 + 2 * 2 * 8192 * 128 + 2 * 8 * 128 * 64.5)
    mamba = lin(d, 18560) + lin(8192, d) + scan + 3 * 2 * 4 * 10240
    attn = (lin(d, 4096) + 2 * lin(d, 256) + lin(4096, d)
            + 6 * 32 * 2 * 128 * 8193 / 2)
    moe = (4 * d * 512 + lin(d, 1024) + lin(1024, d) + lin(d, 5376)
           + lin(5376, d) + 2.75 * (lin(1024, 2688) + lin(2688, 1024)))
    want = (5 * mamba + 2 * attn + 6 * moe + 2 * 4 * d * v + lin(2 * d, d))
    assert fam.flops_per_item() == pytest.approx(want)
    assert 5.32e9 < want < 5.34e9


def test_a_toy_cell_of_the_family_runs_through_the_harness(tmp_path):
    """Two in-process parties on ONE base copy, the streaming hub, the
    family's block-by-block reference check with both losses, and
    (traced) the records the program writes, at toy widths on the CPU."""
    cell = harness.load_cell(TOY, root=HERE)
    result = harness.run_cell(
        cell, seed=2**31 + 7, seconds=1.0, trace=True, platform="cpu",
        scratch=str(tmp_path),
    )
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert {"local_step_ms", "fold_ms", "wire_send_ms"} <= set(got)
    # no device plane on the CPU: nothing under a device metric's name
    assert not {"latent_moe_step_share", "latent_expert_mm_roofline",
                "mtp_step_share", "local_mfu"} & set(got)


def test_the_reference_check_passes_and_its_fp8_control_fails():
    """At toy widths in float32 the system is within float32 of the
    reference in the logits, both losses and every layer's selection;
    the control (fp8 operands in the reference's products) is refused."""
    import jax.numpy as jnp

    fam = family(TOY, HERE, seed=5)
    check = fam.reference_check()
    assert check["ok"] and check["rel_rms"] < 1e-3 and check["blocks"] == 13
    assert check["routing_exact_share"] == 1.0
    assert len(check["routing_by_layer"]) == 6
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    assert not control["ok"] and control["rel_rms"] > control["tol"]


def test_both_parties_read_one_copy_of_the_base():
    fam = family(TOY, HERE, seed=3)
    a, b = fam.party_state(0), fam.party_state(1)
    assert a["base"] is b["base"]
    assert a["base"] is fam._make_base(fam.base_key())
    assert not (a["ids"][0] == b["ids"][0]).all()  # the data is a party's own
    # every expert layer's selection bias balanced, the MTP module's too
    assert float(abs(a["base"]["mtp"]["layers"][0]["moe"]["router_bias"]).max()) > 0
