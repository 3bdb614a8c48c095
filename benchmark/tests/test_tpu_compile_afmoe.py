"""Compile the expert-decoder LoRA step of the real cell for a DESCRIBED
v5e (no chip attached), as ``test_tpu_compile.py`` does for the dense
decoder: the grouped product and the flash kernel are in the program,
and two parties' steps fit one chip.  The topology is described inside
a fixture, never at import; keep chip compiles of this family in this
one file (another file may land on another worker, which cannot load
the TPU library a second time)."""

import importlib

import pytest

from benchmark import harness

CELL = "trinity-mini-ep8.lora-all-linear-2p"


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


def test_expert_lora_step_compiles_and_two_parties_fit_one_chip(
    one_chip, no_compile_cache, monkeypatch
):
    import jax
    import jax.numpy as jnp

    from benchmark.families import afmoe_lm
    from rayfed_tpu.models import llama, moe

    flash_attention = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # Both ask jax.default_backend(), which is the CPU here, and would
    # take their CPU branch (interpreter, ragged_dot): steer them.
    monkeypatch.setattr(flash_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(moe, "_grouped_impl", lambda: "megablox")

    cell = harness.load_cell(CELL)
    fam = afmoe_lm.build(cell["config_data"], cell["job"], 0)
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
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    import time

    t0 = time.time()
    compiled = lowered.compile()
    print("compile s", round(time.time() - t0, 1))
    hlo = compiled.as_text()
    # The 8 expert layers are one scanned body: three grouped products
    # in the forward pass' chunk loop; in the backward pass' three
    # recomputed by the checkpoint, and in its chunk loop three run
    # again and three transposed; the frozen experts' weight-gradient
    # product (tgmm) is not in the program.  Both flash kernels (window
    # and full) are in the body, under its cond.
    calls = [l for l in hlo.splitlines() if "custom-call(" in l and "gmm" in l]
    print(len(calls), "grouped-product calls")
    assert len(calls) >= 12
    assert "tgmm" not in hlo
    assert "attn.window" in hlo and "attn.full" in hlo
    mem = compiled.memory_analysis()
    print(CELL, mem)
    per_party = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
    )
    print("per party GB", per_party / 1e9)
    assert 2 * per_party / 1e9 < 16.9
