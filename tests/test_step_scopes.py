"""The scope vocabulary of the two LoRA step builders
(``llama.make_lora_train_step``, ``decoder.make_lora_train_step``), read
from the LOWERED step (``jitted.lower(...).as_text(debug_info=True)``),
never the compiled one: tier-1 shares one compile cache, the cache's key
leaves locations out, and a step that differs from an older one by
scopes alone would be handed the older executable's names
(``llama.step_part``, which also holds the remedy one case checks).

In the lowered text an operation's location is its name stack RELATIVE
to its function; the stack of the function is on its call sites.
``lowered_op_names`` joins the two, as XLA does when it inlines."""

import contextlib
import re

import pytest

import jax
import jax.numpy as jnp

# the vocabulary a device trace's reader charges an operation to
from benchmark.layer_metrics.step_scoped_share import SCOPE
from rayfed_tpu import telemetry
from rayfed_tpu.models import decoder, llama, lora
from rayfed_tpu.ops.flash_attention import flash_attention
from tests import test_granite_hybrid, test_kimi_k2

_ALIAS = re.compile(r'^#loc(\d+) = loc\("((?:[^"\\]|\\.)*)"\(', re.M)
_FUNC = re.compile(r"^\s*func\.func \w+ @([\w.\-]+)\(")
_OP = re.compile(
    r"= (?:\"?(stablehlo\.[\w.]+)\"?|(?:func\.)?call @([\w.\-]+))"
    r".* loc\(#loc(\d+)\)$"
)


def lowered_op_names(text: str, every_call: bool = False) -> list:
    """``[(operation, full name, its line), ...]`` of a lowered module
    printed with debug info: every ``stablehlo`` operation under every
    name stack its function is called with (``every_call``: once for
    each chain of calls that reaches it, so that a function called twice
    under one name counts twice)."""
    alias = dict(_ALIAS.findall(text))
    ops, callers, inside = [], {}, None
    for line in text.splitlines():
        start = _FUNC.match(line)
        if start:
            inside = start.group(1)
            continue
        found = _OP.search(line)
        if not found or inside is None:
            continue
        op, callee, loc = found.groups()
        local = alias.get(loc, "")
        if callee:
            callers.setdefault(callee, []).append((inside, local))
        else:
            ops.append((inside, op, local, line))
    join = lambda a, b: f"{a}/{b}" if a and b else a or b
    stacks = {"main": {""}}

    def stacks_of(func):
        if func not in stacks:
            stacks[func] = [
                join(outer, local) for caller, local in callers.get(func, ())
                for outer in stacks_of(caller)
            ]
            if not every_call:
                stacks[func] = set(stacks[func])
        return stacks[func]

    return [
        (op, join(outer, local), line) for func, op, local, line in ops
        for outer in stacks_of(func)
    ]


def _mistral():
    cfg = llama.llama_tiny(sliding_window=16, remat=True, dtype=jnp.bfloat16)
    base = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = test_kimi_k2._trained(lora.init_lora(
        jax.random.PRNGKey(1), base, lora.LoraConfig(rank=2, alpha=4.0)
    ), 2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, 256)
    step = llama.make_lora_train_step(cfg, attn_fn=flash_attention)
    return step, (adapters, llama.init_adam(adapters), base, ids), cfg


def _decoder(module, **kw):
    def build():
        cfg, base, adapters, ids = module.make(
            cfg=module.toy_config(dtype=jnp.bfloat16, remat=True, **kw)
        )
        step = decoder.make_lora_train_step(cfg, attn_fn=flash_attention)
        return step.jitted, (adapters, llama.init_adam(adapters), base, ids), cfg

    return build


STEPS = {
    "mistral": _mistral,
    "hybrid": _decoder(test_granite_hybrid),
    "kimi": _decoder(test_kimi_k2),
}
FFN_WIDTH = {"mistral": 128, "hybrid": test_granite_hybrid.FFN}


@pytest.fixture(scope="module")
def lowered():
    """``{model: [(operation, full name, line), ...]}``, lowered once."""
    made = {}

    def of(model):
        if model not in made:
            step, args, _ = STEPS[model]()
            made[model] = lowered_op_names(
                step.lower(*args).as_text(debug_info=True)
            )
        return made[model]

    return of


@pytest.mark.parametrize("model", list(STEPS))
def test_every_product_of_the_lowered_step_carries_a_scope(lowered, model):
    products = [
        (op, name) for op, name, _ in lowered(model)
        if op in ("stablehlo.dot_general", "stablehlo.convolution")
    ]
    assert len(products) > 20
    bare = [name for _, name in products if not SCOPE.search(name)]
    assert not bare, bare[:5]
    found = {SCOPE.findall(name)[-1] for _, name in products}
    assert {"attn.proj", "ffn.dense", "head.loss"} <= found
    # elementwise work has its names too: the lookup and the optimizer
    names = [name for _, name, _ in lowered(model)]
    for scope, part in (("embed", "jit(embed)"),
                        ("optim.adam", "jit(optim_adam)")):
        mine = [n for n in names if SCOPE.findall(n)[-1:] == [scope]]
        assert mine and all(part in n for n in mine), scope
    step = "llama_lora_step" if model == "mistral" else "decoder_lora_step"
    assert all(n.startswith(f"jit({step})") for n in names if SCOPE.search(n))


@pytest.mark.parametrize("model", ["mistral", "hybrid"])
def test_head_and_loss_are_named_in_both_passes(lowered, model):
    mine = [name for _, name, _ in lowered(model) if "head.loss" in name]
    forward = [n for n in mine if "transpose(" not in n]
    backward = [n for n in mine if "transpose(" in n]
    # the chunk loop's two products (logits, d loss / d x) are forward
    assert sum(n.endswith("while/body/closed_call/dot_general")
               for n in forward) == 2
    assert backward and not any("rematted_computation" in n for n in mine)


@pytest.mark.parametrize("model", ["mistral", "hybrid"])
def test_the_second_forward_runs_the_gate_and_no_kernel_nor_up_product(
    lowered, model
):
    again = [
        (op, name, line) for op, name, line in lowered(model)
        if "rematted_computation" in name
    ]
    assert again and all("transpose(" in name for _, name, _ in again)
    scopes = {SCOPE.findall(name)[-1] for _, name, _ in again
              if SCOPE.search(name)}
    assert "ffn.dense" in scopes and "attn.proj" in scopes
    assert not any("flash.fwd" in name for _, name, _ in again)  # PR 32
    # PR 34: of the FFN's two wide products the second forward runs the
    # gate alone (adapters on the FFN add their own rank-wide pairs)
    wide = f"x{FFN_WIDTH[model]}xbf16>"
    wide_products = lambda rows: sum(
        op == "stablehlo.dot_general" and SCOPE.findall(name)[-1] == "ffn.dense"
        and line.split("->")[-1].split(" loc(")[0].strip().endswith(wide)
        and "x2xbf16>, " not in line.split(" : ")[-1]
        for op, name, line in rows
    )
    first = [
        row for row in lowered(model)
        if "transpose(" not in row[1] and "rematted" not in row[1]
    ]
    # one traced FFN a scanned group
    groups = 1 if model == "mistral" else len(
        test_granite_hybrid.toy_config().groups()
    )
    assert wide_products(first) == 2 * groups
    assert wide_products(again) == 1 * groups


def _set_parts(patch, step_part):
    """Build the steps' named parts another way."""
    patch.setattr(llama, "step_part", step_part)  # adam_part's
    for module in (llama, decoder):
        patch.setattr(module, "embed_part", step_part(
            llama.EMBED_SCOPE, llama._embed_lookup,
            static_argnames=("dtype", "scale"),
        ))


@contextlib.contextmanager
def _without_scopes(monkeypatch):
    """The builders with this vocabulary taken out: no named scope, no
    named part."""
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        _set_parts(patch, lambda scope, fn, **jit_kw: fn)
        yield


@pytest.mark.parametrize("model", ["mistral", "hybrid"])
def test_the_scopes_change_no_bit_of_the_step(monkeypatch, model):
    step, args, _ = STEPS[model]()
    got = step(*args)
    with _without_scopes(monkeypatch):
        bare, bare_args, _ = STEPS[model]()
        text = bare.lower(*bare_args).as_text(debug_info=True)
        assert "head.loss" not in text and "optim_adam" not in text
        want = bare(*bare_args)
    assert float(got[2]).hex() == float(want[2]).hex()
    for a, b in zip(jax.tree_util.tree_leaves(got[0]),
                    jax.tree_util.tree_leaves(want[0])):
        assert (a == b).all()


def test_the_decoders_step_never_hashes_to_a_step_without_its_parts(
    monkeypatch
):
    """The compile cache keys a program on its text without locations.
    Scopes alone leave that text as it was (the trap); the named parts
    do not (the remedy)."""
    step, args, _ = STEPS["hybrid"]()
    with_parts = step.lower(*args).as_text()
    with monkeypatch.context() as patch:
        # the same scopes, as scopes alone
        def scope_only(scope, fn, **jit_kw):
            def part(*a, **kw):
                with jax.named_scope(scope):
                    return fn(*a, **kw)
            return part

        _set_parts(patch, scope_only)
        scoped, scoped_args, _ = STEPS["hybrid"]()
        lowered_scoped = scoped.lower(*scoped_args)
    with _without_scopes(monkeypatch):
        bare, bare_args, _ = STEPS["hybrid"]()
        without = bare.lower(*bare_args).as_text()
    assert "optim.adam" in lowered_scoped.as_text(debug_info=True)
    assert lowered_scoped.as_text() == without  # what the cache would key
    assert with_parts != without
    for symbol in ("@embed(", "@optim_adam("):
        assert symbol in with_parts and symbol not in without


# -- the counter: FR ``device.memory`` ---------------------------------


class _Device:
    def __init__(self, id_, stats):
        self.id, self.stats, self.calls = id_, stats, 0

    def memory_stats(self):
        self.calls += 1
        return self.stats


def _run_tasks(devices, n=3):
    import types

    from rayfed_tpu import executor, runtime

    bound = types.SimpleNamespace(
        party="alice", local_devices=lambda: devices
    )
    pool = executor.TaskExecutor(
        max_workers=1, bind_runtime_fn=lambda: setattr(
            runtime._tls, "runtime", bound
        )
    )
    try:
        for i in range(n):
            assert pool.submit(lambda x: x + 1, (i,), {}, name="train") \
                .resolve(timeout=30) == i + 1
    finally:
        pool.shutdown()


STATS = {"bytes_in_use": 3, "peak_bytes_in_use": 5, "bytes_reserved": 7,
         "peak_bytes_reserved": 11, "bytes_limit": 13, "num_allocs": 17}


@pytest.mark.parametrize("case", ["armed", "cpu", "disarmed"])
def test_device_memory_is_one_record_a_task_while_armed(case):
    devices = [_Device(0, dict(STATS)),
               _Device(1, dict(STATS, bytes_in_use=4, peak_bytes_reserved=1))]
    if case == "cpu":  # the CPU's memory_stats() is None
        devices = [_Device(0, None)]
    recorder = None if case == "disarmed" else telemetry.install()
    try:
        _run_tasks(devices)
    finally:
        telemetry.uninstall()
    if case == "disarmed":
        assert all(d.calls == 0 for d in devices)
        return
    records = [r for r in recorder.records() if r.phase == "device.memory"]
    runs = [r for r in recorder.records() if r.phase == "task.run"]
    assert len(runs) == 3 and all(d.calls == 3 for d in devices)
    if case == "cpu":
        assert records == []
        return
    assert len(records) == 3
    for rec in records:
        assert rec.party == "alice" and rec.detail["name"] == "train"
        assert rec.nbytes == 4  # the largest bytes_in_use
        assert set(rec.detail["devices"]) == {"0", "1"}
        for stats in rec.detail["devices"].values():
            assert tuple(stats) == telemetry.DEVICE_MEMORY_FIELDS
        assert rec.detail["devices"]["0"] == {
            k: STATS[k] for k in telemetry.DEVICE_MEMORY_FIELDS
        }
    # the record survives the wire encoding trace_collect uses
    assert telemetry.decode_records(
        telemetry.encode_records(records)
    )[0].detail == records[0].detail
