"""Compile the Mistral LoRA step of the real cells for a DESCRIBED v5e
(no chip attached): what the chip's compiler would refuse costs no chip
time.  The topology is described inside a fixture, never at import."""

import pytest

from benchmark import harness


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
    """A described-topology compile is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name, parties_on_chip, limit_gb", [
    ("mistral-7b-v0.1-d6.lora-2p", 2, 16.9),
    ("mistral-7b-v0.1-d6.qlora-wire-bf16-stream", 2, 16.9),
])
def test_lora_step_compiles_and_two_parties_fit_one_chip(
    name, parties_on_chip, limit_gb, one_chip, no_compile_cache, monkeypatch
):
    import jax
    import jax.numpy as jnp

    from benchmark.families import llama_lm
    import importlib

    flash_attention = importlib.import_module("rayfed_tpu.ops.flash_attention")

    # The kernel asks jax.default_backend(), which is the CPU here, and
    # would take its interpret branch: steer it in the test.
    monkeypatch.setattr(flash_attention, "_interpret_default", lambda: False)

    cell = harness.load_cell(name)
    fam = llama_lm.build(cell["config_data"], cell["job"], 0)
    key = jax.random.PRNGKey(0)
    base = jax.eval_shape(lambda: fam._llama.init_llama(key, fam.cfg))
    adapters = jax.eval_shape(
        lambda b: fam._lora.init_lora(key, b, fam.lcfg), base
    )
    opt = jax.eval_shape(fam._llama.init_adam, adapters)
    ids = jax.ShapeDtypeStruct((fam.batch, fam.seq), jnp.int32)
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree,
    )
    lowered = fam._step.lower(put(adapters), put(opt), put(base), put(ids))
    assert "tpu_custom_call" in lowered.as_text()  # the flash kernel
    mem = lowered.compile().memory_analysis()
    print(name, mem)
    per_party = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
    )
    # Every party on the chip holds its own base and may have its step
    # in flight at the same time.
    assert parties_on_chip * per_party / 1e9 < limit_gb
