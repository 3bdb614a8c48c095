"""The three readers of the whole local step (``step_scoped_share``,
``refwd_step_share``, ``device_reserved_GB``) on hand-made input: a
compiled step's text with the three passes, a nested ``while`` and
instructions the compiler left without a name, device operations with
known durations, and a window of ``device.memory`` records
(``test_trace_readers_granite.py``'s way)."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark.layer_metrics import step_scoped_share as account
from rayfed_tpu.telemetry import SpanRecord

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "jit(llama_lora_step)"
BODY = f"{STEP}/jvp(layers0-5)/while/body/closed_call"
BACK = f"{STEP}/transpose(jvp(layers0-5))/while/body/closed_call/checkpoint"

PROGRAM = f"""
HloModule jit_llama_lora_step, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

%fused_computation.1 (param_0: bf16[8,8]) -> bf16[8,8] {{
  %param_0 = bf16[8,8]{{1,0}} parameter(0)
  %mul.1 = bf16[8,8]{{1,0}} multiply(%param_0, %param_0), metadata={{op_name="{BACK}/rematted_computation/ffn.dense/mul"}}
  ROOT %add.1 = bf16[8,8]{{1,0}} add(%mul.1, %param_0), metadata={{op_name="{BACK}/ffn.dense/add_any"}}
}}

%fused_computation.2 (param_0.1: bf16[8,8]) -> bf16[8,8] {{
  %param_0.1 = bf16[8,8]{{1,0}} parameter(0)
  %convert.2 = bf16[8,8]{{1,0}} convert(%param_0.1), metadata={{op_name="{BODY}/attn.proj/convert_element_type"}}
  ROOT %copy.2 = bf16[8,8]{{0,1}} copy(%convert.2)
}}

%fused_computation.3 (param_0.2: bf16[8,8]) -> bf16[8,8] {{
  %param_0.2 = bf16[8,8]{{1,0}} parameter(0)
  ROOT %copy.3 = bf16[8,8]{{0,1}} copy(%param_0.2)
}}

%forward_body (p: (s32[], bf16[8,8])) -> (s32[], bf16[8,8]) {{
  %p = (s32[], bf16[8,8]{{1,0}}) parameter(0)
  %fusion.10 = bf16[8,8]{{1,0}} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %flash.fwd.11 = bf16[8,8]{{1,0}} custom-call(%x), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/attn.window/jit(_flash_forward)/flash.fwd/pallas_call" stack_frame_id=4}}
  %fusion.12 = bf16[8,8]{{1,0}} fusion(%x), kind=kOutput, calls=%f, metadata={{op_name="{BODY}/ffn.dense/dot_general"}}
  ROOT %fusion.13 = bf16[8,8]{{0,1}} fusion(%x), kind=kLoop, calls=%fused_computation.3
}}

%backward_body (q: (s32[], bf16[8,8])) -> (s32[], bf16[8,8]) {{
  %q = (s32[], bf16[8,8]{{1,0}}) parameter(0)
  %fusion.20 = bf16[8,8]{{1,0}} fusion(%y), kind=kOutput, calls=%f, metadata={{op_name="{BACK}/rematted_computation/attn.proj/dot_general"}}
  %fusion.21 = bf16[8,8]{{1,0}} fusion(%y), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{BACK}/ffn.dense/add_any"}}
  %flash.dq.22 = bf16[8,8]{{1,0}} custom-call(%y), custom_call_target="tpu_custom_call", metadata={{op_name="{BACK}/attn.window/jit(_flash_backward_pallas)/flash.dq/pallas_call"}}
  ROOT %fusion.23 = bf16[8,8]{{1,0}} fusion(%y), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/transpose(jvp(layers0-5))/while/body/dynamic_update_slice"}}
}}

%chunk_body (r: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %r = (s32[], f32[8]{{0}}) parameter(0)
  ROOT %fusion.30 = f32[8]{{0}} fusion(%z), kind=kOutput, calls=%f, metadata={{op_name="{STEP}/jvp(head.loss)/head.loss/while/body/closed_call/dot_general"}}
}}

ENTRY %main.99 (arg: f32[8]) -> f32[8] {{
  %arg = f32[8]{{0}} parameter(0), metadata={{op_name="ids"}}
  %fusion.1 = bf16[8,8]{{1,0}} fusion(%arg), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(jit(embed))/embed/gather"}}
  %while.2 = (s32[], bf16[8,8]{{1,0}}) while(%t), condition=%c, body=%forward_body, metadata={{op_name="{STEP}/jvp(layers0-5)/while"}}
  %while.3 = (s32[], f32[8]{{0}}) while(%u), condition=%c, body=%chunk_body, metadata={{op_name="{STEP}/jvp(head.loss)/head.loss/while"}}
  %fusion.4 = f32[8]{{0}} fusion(%arg), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/transpose(jvp(head.loss))/head.loss/mul"}}
  %while.5 = (s32[], bf16[8,8]{{1,0}}) while(%v), condition=%c, body=%backward_body, metadata={{op_name="{STEP}/transpose(jvp(layers0-5))/while"}}
  %fusion.6 = f32[8]{{0}} fusion(%arg), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jit(optim_adam)/optim.adam/sqrt"}}
  %copy.7 = f32[8]{{0}} copy(%arg)
  ROOT %fusion.8 = f32[8]{{0}} fusion(%arg), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp()/iota"}}
}}
"""

# One execution of 2,000 ns.  Inside the loops the children leave their
# ``while`` 10 ns of its own each time.
OPS = [
    (0, 50, "fusion.1"),            # embed, fwd
    (50, 560, "while.2"),           # 10 ns of glue, fwd
    (50, 150, "fusion.10"),         # attn.proj fwd: named by its inside
    (150, 350, "flash.fwd.11"),     # attn.window fwd
    (350, 500, "fusion.12"),        # ffn.dense fwd
    (500, 550, "fusion.13"),        # the loop's: layers.glue, fwd
    (560, 770, "while.3"),          # head.loss fwd, 10 of its own
    (570, 770, "fusion.30"),        # head.loss fwd
    (770, 790, "fusion.4"),         # head.loss bwd
    (790, 1800, "while.5"),         # 10 ns of glue, bwd
    (790, 1090, "fusion.20"),       # attn.proj refwd
    (1090, 1390, "fusion.21"),      # ffn.dense bwd; straddles two passes
    (1390, 1690, "flash.dq.22"),    # attn.window bwd
    (1690, 1790, "fusion.23"),      # layers.glue, bwd
    (1800, 1900, "fusion.6"),       # optim.adam
    (1900, 1940, "copy.7"),         # no name, no loop: other
    (1940, 1960, "fusion.8"),       # a name outside every scope: other
]


def reader(name, cell="any.cell"):
    (mod,) = [m for m in harness.matching_layer_metrics(cell) if m.NAME == name]
    return mod


def test_the_account_names_scope_and_pass_of_every_operation(capsys):
    op_names, inherited, straddling = account.program_op_names(PROGRAM)
    assert op_names["fusion.10"].endswith("attn.proj/convert_element_type")
    assert op_names["fusion.13"] == f"{STEP}/jvp(layers0-5)/while"
    assert "copy.7" not in op_names
    assert inherited == {"fusion.10", "fusion.13", "copy.7", "p", "q", "r"}
    assert straddling == {"fusion.21"}
    found = account.account_of(
        [(0, 2000, OPS), (3000, 5000, [(s + 3000, e + 3000, n)
                                      for s, e, n in OPS])],
        op_names, inherited, straddling,
    )
    assert found["steps"] == 2 and found["program"] == pytest.approx(4e-6)
    per_step = {
        scope: {p: round(s / 2 * 1e9) for p, s in row.items()}
        for scope, row in found["passes"].items()
    }
    assert per_step == {
        "embed": {"fwd": 50, "refwd": 0, "bwd": 0},
        "layers.glue": {"fwd": 60, "refwd": 0, "bwd": 110},
        "attn.proj": {"fwd": 100, "refwd": 300, "bwd": 0},
        "attn.window": {"fwd": 200, "refwd": 0, "bwd": 300},
        "ffn.dense": {"fwd": 150, "refwd": 0, "bwd": 300},
        "head.loss": {"fwd": 210, "refwd": 0, "bwd": 20},
        "optim.adam": {"fwd": 100, "refwd": 0, "bwd": 0},
        "other": {"fwd": 60, "refwd": 0, "bwd": 0},
    }
    assert found["inherited"] / 2 == pytest.approx(190e-9)  # 100 + 50 + 40
    assert found["straddling"] / 2 == pytest.approx(300e-9)
    assert found["overcount"] == pytest.approx(0.0, abs=1e-15)

    ctx = types.SimpleNamespace(_step_account=found)
    # 1,900 of 2,000 ns named; 300 of 2,000 the second forward
    assert reader("step_scoped_share").read(ctx) == pytest.approx(95.0)
    assert reader("refwd_step_share").read(ctx) == pytest.approx(15.0)
    # every cell has the three readers
    for cell in ("mistral-7b-v0.1-d6.lora-4p-4chip",
                 "granite-4.0-h-micro-d20.lora-all-linear-2p"):
        names = {m.NAME for m in harness.matching_layer_metrics(cell)}
        assert {"step_scoped_share", "refwd_step_share",
                "device_reserved_GB"} <= names
    # nothing to read (the parent's program, a CPU run): None, no raise
    empty = types.SimpleNamespace(family=None, trace={}, run=None)
    assert reader("step_scoped_share").read(empty) is None
    assert reader("refwd_step_share").read(empty) is None


def test_no_instant_is_charged_twice_where_operations_overlap():
    total = lambda rows: {n: sum(v for m, v in rows if m == n)
                          for n, _ in rows}
    nested = [(0, 100, "P"), (10, 50, "A"), (20, 30, "a"), (60, 70, "B")]
    assert total(account.exclusive_times(nested, 100)) == {
        "P": 50, "A": 30, "a": 10, "B": 10,
    }
    # B starts inside A and outlives it; C outlives the loop and the
    # program: 100 ns in all, where nested self times would sum to 150
    ragged = [(0, 100, "P"), (10, 50, "A"), (40, 80, "B"), (90, 120, "C")]
    assert total(account.exclusive_times(ragged, 100)) == {
        "P": 20, "A": 30, "B": 40, "C": 10,
    }
    found = account.account_of([(0, 100, ragged)], {
        "P": f"{STEP}/jvp(layers0-5)/while", "A": f"{BODY}/ffn.dense/mul",
        "B": f"{BODY}/attn.proj/mul",
    })
    assert found["overcount"] == pytest.approx(50e-9)
    ctx = types.SimpleNamespace(_step_account=found)
    assert reader("step_scoped_share").read(ctx) == pytest.approx(90.0)


def test_scope_and_pass_rules():
    scope, pass_ = account.scope_of, account.pass_of
    nested = (f"{STEP}/transpose(jvp(layers5-5))/while/body/closed_call/"
              "checkpoint/attn.full/jit(_flash_backward_pallas)/flash.dkv/"
              "pallas_call")
    assert (scope(nested), pass_(nested)) == ("attn.full", "bwd")
    wrapped = f"{STEP}/transpose(jvp(ffn.dense))/mul"
    assert (scope(wrapped), pass_(wrapped)) == ("ffn.dense", "bwd")
    again = (f"{STEP}/transpose(jvp(layers0-4))/while/body/closed_call/"
             "checkpoint/rematted_computation/ssm.scan/ssd.fwd/pallas_call")
    assert (scope(again), pass_(again)) == ("ssm.scan", "refwd")
    assert scope(f"{STEP}/jit(optim_adam)/optim.adam/sqrt") == "optim.adam"
    assert scope(f"{STEP}/jvp(layers0-8)/while/body/add") == "layers.glue"
    # a parameter's name is no scope, nor is a scope's name inside a word
    assert scope("base_params['embed']") == "other"
    assert scope(f"{STEP}/jvp()/embedding_sum") == "other"
    assert pass_("") == "fwd" and scope("") == "other"


def memory(party, t, in_use, peak, reserved, peak_reserved, device="0"):
    return SpanRecord(
        party=party, round=None, epoch=None, phase="device.memory",
        peer=None, stream=None, nbytes=in_use, t_start=t, dur_s=0.0,
        outcome="ok", detail={"name": "Trainer.train", "devices": {device: {
            "bytes_in_use": in_use, "peak_bytes_in_use": peak,
            "bytes_reserved": reserved, "peak_bytes_reserved": peak_reserved,
            "bytes_limit": 16_909_336_064,
        }}},
    )


def test_device_reserved_is_the_fullest_device_over_the_traced_rounds(capsys):
    mod = reader("device_reserved_GB")
    rounds = [7, 8, 9]
    edges = {r: (100.0 + (r - 7), 101.0 + (r - 7)) for r in rounds}
    gb = 10**9
    records = [
        memory("alice", 50.0, 9 * gb, 12 * gb, 9 * gb, 9 * gb),  # before
        memory("alice", 100.5, 3 * gb, 4 * gb, 0, 5 * gb),
        memory("bob", 101.5, 3 * gb, 4 * gb, 5 * gb, 5 * gb + 400, "1"),
        memory("alice", 102.5, 3 * gb, 4 * gb + 100, 0, 5 * gb),
        memory("bob", 103.5, 9 * gb, 12 * gb, 9 * gb, 9 * gb),  # after
    ]
    ctx = types.SimpleNamespace(
        traced_rounds=rounds, round_edges=edges, recorder_records=records,
    )
    assert mod.read(ctx) == pytest.approx(9.0000004)
    logged = [json.loads(line) for line in capsys.readouterr().err.splitlines()]
    (line,) = [row["device_memory"] for row in logged if "device_memory" in row]
    assert line["records"] == 3
    assert line["first"]["stats"]["peak_bytes_reserved"] == 5 * gb
    assert line["last"]["stats"]["peak_bytes_in_use"] == 4 * gb + 100
    # the parent's program writes no such record; the CPU reports nothing
    ctx.recorder_records = [r for r in records if r.t_start < 60]
    assert mod.read(ctx) is None
    ctx.traced_rounds = []
    assert mod.read(ctx) is None


def test_a_family_with_no_program_text_has_its_step_lowered_from_shapes():
    from benchmark.families import llama_lm

    cell = harness.load_cell("toy-lm.lora-2p", root=HERE)
    family = llama_lm.build(cell["config_data"], cell["job"], 5)
    assert not hasattr(family, "step_program_text")
    text = account.lowered_step_text(family)
    assert text.startswith("HloModule jit_llama_lora_step")
    op_names, _, _ = account.program_op_names(text)
    scopes = {account.scope_of(o) for o in op_names.values()}
    assert {"embed", "attn.proj", "attn.window", "ffn.dense", "head.loss",
            "optim.adam", "layers.glue"} <= scopes
    passes = {account.pass_of(o) for o in op_names.values()}
    assert passes == {"fwd", "refwd", "bwd"}
