"""The harness's functions run whole cells end to end at a toy size on
the CPU, from the toy configuration kept here; the device check is
replaced by the test (``platform="cpu"``).  The command line has no such
switch and fails without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
E2E = {"fed_items_per_s", "round_p50_s", "wire_MB_per_round", "setup_s"}


def run_toy(name, trace, tmp_path, root=HERE, seconds=1.0):
    cell = harness.load_cell(name, root=root)
    return harness.run_cell(
        cell, seed=5, seconds=seconds, trace=trace, platform="cpu",
        scratch=str(tmp_path),
    )


@pytest.mark.parametrize(
    "name", ["toy-lm.lora-2p", "toy-lm.lora-2p-uint8"]
)
def test_untraced_run_reports_the_end_to_end_metrics(name, tmp_path):
    from rayfed_tpu import telemetry

    result = run_toy(name, False, tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 * harness.MIN_ROUNDS
    assert set(result["metrics"]) == E2E
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert not telemetry.armed()  # the untraced run arms no recorder
    assert "breakdown" not in result
    json.dumps(result)


def test_wire_count_is_exact(tmp_path):
    """2 parties, hub: one uplink and one downlink of the packed
    adapters a round, the same bytes every round and every run."""
    a = run_toy("toy-lm.lora-2p", False, tmp_path)
    b = run_toy("toy-lm.lora-2p", False, tmp_path, seconds=0.5)
    assert (a["metrics"]["wire_MB_per_round"]["value"]
            == b["metrics"]["wire_MB_per_round"]["value"])


def test_traced_run_reports_per_layer_metrics(tmp_path):
    result = run_toy("toy-lm.lora-2p-uint8", True, tmp_path)
    assert result["correct"] is True
    got = set(result["metrics"])
    assert {"exposed_fed_ms", "local_step_ms", "wire_send_ms",
            "wire_goodput", "d2h_ms", "fold_ms", "finalize_ms",
            "slow_round_share", "trace_overhead"} <= got
    # Parties that share a device: a blocked span around the pack would
    # time the other party's queued step, so it is not reported.
    assert "pack_ms" not in got
    assert not got & E2E
    # A CPU trace has no device plane: nothing from the device is
    # reported under a device metric's name.
    assert not {"device_idle", "local_mfu"} & got
    assert "busy_s" not in result["device"]


def test_four_parties_each_on_its_own_device(tmp_path):
    result = run_toy("toy-lm.lora-4p-4chip", False, tmp_path)
    assert result["correct"] is True  # placement is one of its checks
    assert result["device"]["count"] == 4


def test_a_new_cell_is_only_a_new_file(tmp_path):
    """A later PR adds a cell by adding files: drop a new cell (and a
    new per-layer reader) into a copy and run it, editing nothing."""
    root = tmp_path / "bench"
    shutil.copytree(os.path.join(HERE, "configs"), root / "configs")
    shutil.copytree(os.path.join(HERE, "workloads"), root / "workloads")
    shutil.copytree(
        os.path.join(harness.ROOT, "layer_metrics"), root / "layer_metrics",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    with open(root / "workloads" / "toy-lm.lora-2p.json") as f:
        cell = json.load(f)
    cell["traffic"] = "lora-3p-k1"
    cell["parties"], cell["job"]["local_steps"] = 3, 1
    with open(root / "workloads" / "toy-lm.lora-3p-k1.json", "w") as f:
        json.dump(cell, f)
    (root / "layer_metrics" / "rounds_traced.py").write_text(
        'NAME, UNIT = "rounds_traced", "rounds"\n'
        'LAYER, MOVES, SOURCE = "harness", "round_p50_s", "program_counter"\n'
        'CELLS = ["*.lora-3p-*"]\n'
        "def read(ctx):\n    return len(ctx.traced_rounds)\n"
    )
    result = run_toy("toy-lm.lora-3p-k1", True, tmp_path, root=str(root))
    assert result["correct"] is True
    assert result["attempted"] % 3 == 0
    assert result["metrics"]["rounds_traced"]["value"] >= 3


def test_command_line_refuses_the_cpu(tmp_path):
    """``JAX_PLATFORMS=cpu python benchmark/run.py ...`` exits non-zero
    and prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "run.py"),
         "--workload", "mistral-7b-v0.1-d6.lora-2p", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "never measures on another platform" in proc.stderr
