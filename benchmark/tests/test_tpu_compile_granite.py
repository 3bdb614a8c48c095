"""Compile the state-space hybrid's LoRA step of the real cell for a
DESCRIBED v5e (no chip attached), as ``test_tpu_compile_kimi.py`` does
for the latent cell: the chunked scan at 64 heads x 64, state 128, 32
chunks of 256, the flash kernels at 32 x 64-wide heads on 8 K/V heads,
the fused head-and-loss on the tied 100,352-row embedding; the readers'
scopes are in the program's text, no transposed copy of the embedding
is made, and what is resident (one copy of the base, two parties'
adapters, Adam state and ids) plus ONE running step's temporaries fit
the chip by XLA's count.  The topology is described inside a fixture,
never at import; keep chip compiles of this family in this one file."""

import importlib
import re

import pytest

from benchmark import harness

CELL = "granite-4.0-h-micro-d20.lora-all-linear-2p"


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


def test_hybrid_lora_step_compiles_and_one_step_fits_beside_two_parties(
    one_chip, no_compile_cache, monkeypatch
):
    import time

    import jax
    import jax.numpy as jnp

    from benchmark.families import granite_hybrid_lm
    from benchmark.layer_metrics.moe_step_share import instruction_op_names
    from benchmark.layer_metrics.ssm_step_share import SCOPE
    from rayfed_tpu.models import llama

    flash_attention = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # It asks jax.default_backend(), which is the CPU here, and would
    # take the interpreter: steer it.
    monkeypatch.setattr(flash_attention, "_interpret_default", lambda: False)

    cell = harness.load_cell(CELL)
    fam = granite_hybrid_lm.build(cell["config_data"], cell["job"], 0)
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
    # The readers' view: every scope of the step is in some op_name.
    scopes = set()
    for op in instruction_op_names(hlo).values():
        scopes.update(SCOPE.findall(op))
    assert scopes == {"ssm.proj", "ssm.conv", "ssm.scan", "attn.full",
                      "attn.proj", "ffn.dense"}
    # five scanned groups, the attention layers' two each with ONE
    # forward kernel (the checkpoint saves its output and statistics)
    forwards = [
        line for line in hlo.splitlines()
        if "custom-call(" in line and "flash.fwd" in line
    ]
    assert len(forwards) == 2
    for line in forwards:
        assert "transpose(" not in line and "rematted_computation" not in line
    # The tied head reads the embedding as it lies: nothing of its size
    # is copied or transposed a step, and no logits array of all tokens
    # is made (chunks of 256 rows at 100,352 columns).
    for line in hlo.splitlines():
        if re.search(r"= bf16\[(100352,2048|2048,100352)\]\S* (copy|transpose)\(", line):
            raise AssertionError(line[:200])
    products = re.findall(r"= (\w+\[[\d,]+\])\S* (?:convolution|dot)\(", hlo)
    assert products.count("f32[256,100352]") == 1
    for shape in set(re.findall(r"f32\[([\d,]+)\]", hlo)):
        dims = {int(d) for d in shape.split(",")}
        assert not (100352 in dims and dims & {8191, 8192}), shape
    mem = compiled.memory_analysis()
    print(CELL, mem)
    base_gb = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(base)
    ) / 1e9
    temp_gb = mem.temp_size_in_bytes / 1e9
    # a party's own arguments (adapters, Adam state, ids) and outputs
    held_gb = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
    ) / 1e9 - base_gb
    print("base GB", round(base_gb, 3), "a party holds GB", round(held_gb, 3),
          "a running step's temporaries GB", round(temp_gb, 3))
    assert 3.39 < base_gb < 3.41  # the cut's 3.40 GB
    # XLA's count of ONE program: 7.35 GB of temporaries (3.36 of them
    # the 20 layers' kept inputs and up products, all 64 heads of a scan
    # at once).  What the compiler can show is that one running step
    # fits beside everything resident; whether both parties' steps hold
    # their temporaries at once is the chip's to say (PERF.md section 4).
    assert base_gb + 2 * held_gb + temp_gb < 16.9
    assert temp_gb < 7.5
