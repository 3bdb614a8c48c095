"""Compile the ``nemotron_h`` cell's LoRA step for a DESCRIBED v5e (no
chip attached), as ``test_tpu_compile_granite.py`` does for the hybrid
cell: the chunked scan at 128 heads x 64 in 8 groups, state 128, 64
chunks of 128; the grouped products of 64 held experts in the latent
width; the flash kernels at 32 x 128-wide heads on 2 K/V heads; the fused
head-and-loss twice (the main head and the MTP module's on the same
16,384 rows).  The readers' scopes are in the program's text, the step
holds five scanned bodies (four groups and the MTP module's), and what
is resident (one copy of the base, two parties' adapters, Adam state and
ids) plus ONE running step's temporaries fit the chip by XLA's count.
The topology is described inside a fixture, never at import; keep chip
compiles of this family in this one file."""

import importlib

import pytest

from benchmark import harness

CELL = "nemotron-3-super-120b-a12b-ep8-d11.lora-all-linear-2p"


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_nemotron_lora_step_compiles_and_one_step_fits_beside_two_parties(
    one_chip, no_compile_cache, monkeypatch
):
    import time

    import jax
    import jax.numpy as jnp

    from benchmark.families import nemotron_h_lm
    from benchmark.layer_metrics.moe_step_share import instruction_op_names
    from benchmark.layer_metrics.step_scoped_share import SCOPE
    from rayfed_tpu.models import llama

    flash_attention = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # It asks jax.default_backend(), which is the CPU here, and would
    # take the interpreter: steer it (the scan's kernels read it too).
    monkeypatch.setattr(flash_attention, "_interpret_default", lambda: False)
    moe = importlib.import_module("rayfed_tpu.models.moe")
    monkeypatch.setattr(moe, "_grouped_impl", lambda: "megablox")

    cell = harness.load_cell(CELL)
    fam = nemotron_h_lm.build(cell["config_data"], cell["job"], 0)
    base = fam.base_shapes()
    adapters = jax.eval_shape(fam.init_global)
    opt = jax.eval_shape(llama.init_adam, adapters)
    ids = jax.ShapeDtypeStruct((fam.batch, fam.seq), jnp.int32)
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree,
    )
    lowered = fam._step.jitted.lower(
        put(adapters), put(opt), put(base), put(ids)
    )
    t0 = time.time()
    compiled = lowered.compile()
    print("compile s", round(time.time() - t0, 1))
    hlo = compiled.as_text()
    op_names = instruction_op_names(hlo).values()
    scopes = set()
    for op in op_names:
        scopes.update(SCOPE.findall(op))
    assert {"ssm.proj", "ssm.conv", "ssm.scan", "attn.full", "attn.proj",
            "moe.route", "moe.dispatch", "moe.experts", "moe.shared",
            "moe.combine", "head.loss", "optim.adam"} <= scopes
    assert any("moe.latent" in op for op in op_names)
    assert any("mtp.fuse" in op for op in op_names)
    assert any("(mtp)/layers6-6/" in op for op in op_names)
    # two attention layers (the block at 7 and the MTP module's), each
    # ONE forward kernel (the checkpoint saves its output and statistics)
    forwards = [
        line for line in hlo.splitlines()
        if "custom-call(" in line and "flash.fwd" in line
    ]
    assert len(forwards) == 2
    mem = compiled.memory_analysis()
    print(CELL, mem)
    base_gb = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(base)
    ) / 1e9
    temp_gb = mem.temp_size_in_bytes / 1e9
    held_gb = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
    ) / 1e9 - base_gb
    print("base GB", round(base_gb, 3), "a party holds GB", round(held_gb, 3),
          "a running step's temporaries GB", round(temp_gb, 3))
    assert 6.45 < base_gb < 6.47  # the cut's 3,228.4 M parameters in bf16
    # XLA's count of ONE program; whether both parties' steps hold their
    # temporaries at once is the chip's to say (PERF.md section 4).
    assert base_gb + 2 * held_gb + temp_gb < 16.9
