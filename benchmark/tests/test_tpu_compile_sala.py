"""Compile MiniCPM-SALA's LoRA step of the real cell for a DESCRIBED v5e
(no chip attached), as ``test_tpu_compile_granite.py`` does for the
state-space cell: the block-sparse kernels at 32 x 128 heads on 2 K/V
heads in tiles of 512 over 24,576 tokens, the selection, the scan with
one group a head at 32 heads of 128, the fused head-and-loss on the
73,448-row head; the readers' scopes are in the program's text, the
checkpoint runs neither the selection nor the sparse forward kernel
again, and what is resident plus ONE running step fits the chip by
XLA's count.  The topology is described inside a fixture, never at
import; keep chip compiles of this family in this one file."""

import importlib

import pytest

from benchmark import harness

CELL = "minicpm-sala-d4.lora-all-linear-32k-2p"


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


def test_sala_lora_step_compiles_and_one_step_fits_beside_two_parties(
    one_chip, no_compile_cache, monkeypatch
):
    import time

    import jax
    import jax.numpy as jnp

    from benchmark.families import minicpm_sala_lm
    from benchmark.layer_metrics.sala_mixer_step_share import PARTS
    from benchmark.layer_metrics.step_scoped_share import program_op_names
    from rayfed_tpu.models import llama

    flash_attention = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # Every kernel asks it through this module: steer them all off the
    # interpreter (jax.default_backend() is the CPU here).
    monkeypatch.setattr(flash_attention, "_interpret_default", lambda: False)

    cell = harness.load_cell(CELL)
    fam = minicpm_sala_lm.build(cell["config_data"], cell["job"], 0)
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
    op_names = program_op_names(hlo)[0].values()
    for part, pattern in PARTS.items():
        assert any(pattern.search(op) for op in op_names), part
    # the selection and the sparse forward run once: the checkpoint
    # keeps their arrays; the scan's forward runs again (its output is
    # not kept), once a step in the scanned body
    assert not any("rematted_computation" in op and "attn.select" in op
                   for op in op_names)
    kernels = lambda name: [
        line for line in hlo.splitlines()
        if "custom-call(" in line and name in line
    ]
    (forward,) = kernels("sparse.fwd")
    assert "rematted_computation" not in forward
    assert len(kernels("sparse.dq")) == len(kernels("sparse.dkv")) == 1
    assert len(kernels("ssd.fwd")) == 2 and len(kernels("ssd.bwd")) == 1
    mem = compiled.memory_analysis()
    print(CELL, mem)
    base_gb = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(base)
    ) / 1e9
    held_gb = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
    ) / 1e9 - base_gb
    print("base GB", round(base_gb, 3), "a party holds GB", round(held_gb, 3),
          "temporaries GB", round(mem.temp_size_in_bytes / 1e9, 3))
    assert 3.42 < base_gb < 3.43  # 1,711M parameters in bf16
    assert held_gb < 0.1  # adapters, Adam state and ids
