"""Compile the latent-attention LoRA step of the real cell for a
DESCRIBED v5e (no chip attached), as ``test_tpu_compile_afmoe.py`` does
for Trinity's: the flash kernels with a two-part score and the grouped
product at K = 7,168 are in the program, a scanned body runs ONE forward
kernel, and the temporaries XLA reports leave room for two parties on
one copy of the base.  The topology is described inside a fixture,
never at import; keep chip compiles of this family in this one file."""

import importlib

import pytest

from benchmark import harness

CELL = "kimi-k2.7-code-ep32.lora-all-linear-2p"


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


def test_latent_lora_step_compiles_and_two_parties_fit_beside_one_base(
    one_chip, no_compile_cache, monkeypatch
):
    import time

    import jax
    import jax.numpy as jnp

    from benchmark.families import kimi_k2_lm
    from benchmark.layer_metrics.moe_step_share import instruction_op_names
    from rayfed_tpu.models import llama, moe

    flash_attention = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # Both ask jax.default_backend(), which is the CPU here, and would
    # take their CPU branch (interpreter, ragged_dot): steer them.
    monkeypatch.setattr(flash_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(moe, "_grouped_impl", lambda: "megablox")

    cell = harness.load_cell(CELL)
    fam = kimi_k2_lm.build(cell["config_data"], cell["job"], 0)
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
    assert "tgmm" not in hlo  # the frozen experts' weight gradient
    assert "attn.latent" in hlo and "attn.window" not in hlo
    # The readers' view: every kernel by the scope in its op_name.  Two
    # scanned bodies (the dense layer, the four expert layers), each
    # with ONE forward kernel (the checkpoint saves its output and row
    # statistics), one dQ and one dK/dV.
    from benchmark.layer_metrics import latent_flash_roofline as reader

    kinds = [
        reader.kernel_of(name, op)
        for name, op in instruction_op_names(hlo).items()
    ]
    print({k: kinds.count(k) for k in ("fwd", "dq", "dkv")})
    assert [kinds.count(k) for k in ("fwd", "dq", "dkv")] == [2, 2, 2]
    mem = compiled.memory_analysis()
    print(CELL, mem)
    base_gb = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(base)
    ) / 1e9
    party_gb = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
    ) / 1e9 - base_gb
    print("base GB", round(base_gb, 3), "a party's own GB", round(party_gb, 3),
          "of it temporaries", round(mem.temp_size_in_bytes / 1e9, 3))
    assert 6.9 < base_gb < 7.1  # the cut's 6.99 GB
    # XLA's analysis of ONE program, an upper bound on what a step holds
    # (5.74 GB of temporaries); the chip's peak with both parties' steps
    # in flight read 10.83 GB (my chip run, PR 33): the process's
    # high-water mark is not where both programs' temporaries are whole.
    assert base_gb + party_gb < 16.0
