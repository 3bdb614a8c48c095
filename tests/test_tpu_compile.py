"""Compile the main path's device programs for the real chip — without one.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is DESCRIBED (``v5e:2x2``), not attached: what Mosaic or XLA
would refuse on the chip (a mis-tiled Pallas block, too much VMEM, a
program that does not fit or cannot be partitioned) is refused here, at
no chip time.  Nothing runs, so these say nothing about results or
speed — ``chip_smoke.py`` is the run.  Skipped where the topology cannot
be described.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@functools.lru_cache(maxsize=None)
def _topology():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next run would warn
    and recompile): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _on_chip(tree, sharding=None):
    """Shapes of ``tree`` placed on the described chip 0 (or ``sharding``)."""
    sharding = sharding or SingleDeviceSharding(_topology().devices[0])
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _flash_fwd_bwd(t, d, heads=2, kv_heads=None, **kw):
    from rayfed_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=False, **kw)
            .astype(jnp.float32) ** 2
        )

    q = jax.ShapeDtypeStruct((1, t, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, t, kv_heads or heads, d), jnp.bfloat16)
    q, k, v = _on_chip((q, k, k))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v)


def _latent_flash_fwd_bwd(split, t=8192, heads=64, nope=128, rope=64, dv=128):
    """The latent shape: a score of two parts (the rotary one on ONE key
    head), values of their own width; ``split`` False concatenates the
    key first (one 192-wide product)."""
    from rayfed_tpu.ops.attention import score_parts
    from rayfed_tpu.ops.flash_attention import flash_attention

    def loss(q_nope, q_pe, k_nope, k_pe, v):
        q, k = (q_nope, q_pe), (k_nope, k_pe)
        if not split:
            q, k = score_parts(q, k, v)
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=False)
            .astype(jnp.float32) ** 2
        )

    shape = lambda h, d: jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16)
    args = _on_chip((shape(heads, nope), shape(heads, rope), shape(heads, nope),
                     shape(1, rope), shape(heads, dv)))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args)


def _resnet18_bundle():
    from rayfed_tpu.fl import compress
    from rayfed_tpu.models import resnet

    cfg = resnet.resnet18(num_classes=10)
    bundle = jax.eval_shape(
        lambda: compress(
            resnet.init_resnet(jax.random.PRNGKey(0), cfg), packed=True
        )
    )
    return cfg, bundle


def _resnet18_fed_step():
    from rayfed_tpu.models import resnet

    cfg, bundle = _resnet18_bundle()
    x = jax.ShapeDtypeStruct((32, 32, 32, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((32,), jnp.int32)
    return resnet.make_fed_train_step(cfg, lr=0.05).lower(
        *_on_chip((bundle, x, y))
    )


def _resnet18_grid():
    from rayfed_tpu.fl.fedavg import packed_block_grid
    from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS

    _, bundle = _resnet18_bundle()
    total = int(bundle.buf.size)
    return total, DEFAULT_CHUNK_ELEMS, packed_block_grid(total)


def _quantized_accum():
    from rayfed_tpu.fl.fedavg import quantized_accum_kernel

    _, chunk, nblocks = _resnet18_grid()
    acc = jax.ShapeDtypeStruct((nblocks * chunk,), jnp.int32)
    codes = jax.ShapeDtypeStruct((chunk,), jnp.uint8)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return quantized_accum_kernel(chunk, "uint8").lower(
        *_on_chip((acc, codes, scalar, scalar))
    )


def _quantized_finalize():
    from rayfed_tpu.fl.fedavg import _quant_finalize_jit

    total, chunk, nblocks = _resnet18_grid()
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    args = (
        jax.ShapeDtypeStruct((nblocks * chunk,), jnp.int32),
        f32((total,)), f32((nblocks,)), f32((nblocks,)), f32(()),
    )
    # finalize_packed_quantized's program for a delta-coded f32 round.
    return _quant_finalize_jit(chunk, total, "float32", True).lower(
        *_on_chip(args)
    )


def _quant_stats(total=None):
    from rayfed_tpu.fl.quantize import _stats_kernel

    # The delta's statistics, as the round loop and the downlink recode
    # take them: at ResNet-18's size (a padded tail block), or at the
    # benchmark's r=64 adapters (15 whole blocks, 126 MB as float32).
    chunk = _resnet18_grid()[1]
    total = total or _resnet18_grid()[0]
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    return _stats_kernel(chunk, total, True).lower(
        *_on_chip((f32((total,)), f32((total,))))
    )


def _mesh_fedavg_step():
    from examples import mesh_fedavg

    mesh = Mesh(np.asarray(_topology().devices).reshape(4), ("fsdp",))
    w = jax.ShapeDtypeStruct(
        (mesh_fedavg.ROWS, mesh_fedavg.COLS), jnp.float32
    )
    params = _on_chip({"w": w}, NamedSharding(mesh, P("fsdp", None)))
    return mesh_fedavg.make_train_step(1.0).lower(params)


# name -> (lowering, must the program contain a compiled Pallas kernel)
CASES = {
    # The chip_smoke.py Llama widths: T=2048, 128-wide heads.
    "flash_t2048_d128": (lambda: _flash_fwd_bwd(2048, 128), True),
    "flash_t4096_d64": (lambda: _flash_fwd_bwd(4096, 64), True),
    "flash_t2048_d128_window1024": (
        lambda: _flash_fwd_bwd(2048, 128, window=1024), True,
    ),
    # Grouped K/V read by KV head, as the benchmark's two models run it
    # (Trinity-Mini: 32/4 heads, window 2,048; Mistral: 32/8, 4,096).
    "flash_t8192_d128_h32_kv4_window2048": (
        lambda: _flash_fwd_bwd(8192, 128, heads=32, kv_heads=4, window=2048),
        True,
    ),
    "flash_t8192_d128_h32_kv8_window4096": (
        lambda: _flash_fwd_bwd(8192, 128, heads=32, kv_heads=8, window=4096),
        True,
    ),
    # Latent attention at the published widths, 1024 x 1024 blocks: the
    # split score (128 + 64 wide parts, one shared rotary key head, the
    # form the decoder runs) and the concatenated key, values 128 wide.
    "flash_t8192_latent_128+64_v128_split": (
        lambda: _latent_flash_fwd_bwd(True), True,
    ),
    "flash_t8192_latent_192_v128_plain": (
        lambda: _latent_flash_fwd_bwd(False), True,
    ),
    "resnet18_fed_train_step": (_resnet18_fed_step, False),
    "quantized_accum_kernel_resnet18": (_quantized_accum, False),
    "finalize_packed_quantized_resnet18": (_quantized_finalize, False),
    "mesh_fedavg_step_4chip_mesh": (_mesh_fedavg_step, False),
    "quant_stats_resnet18": (_quant_stats, False),
    "quant_stats_qlora_r64": (lambda: _quant_stats(15 << 21), False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(case):
    lower, wants_kernel = CASES[case]
    _topology()  # skip before any work where it cannot be described
    lowered = lower()
    compiled = lowered.compile()  # raises what the chip's compiler raises
    assert ("tpu_custom_call" in compiled.as_text()) == wants_kernel
    if case.startswith("quant_stats"):
        # Only the statistics leave the kernel (three floats a block, in
        # one tile), and it keeps no model-sized temporary: the
        # difference is never materialized.
        memory = compiled.memory_analysis()
        assert memory.output_size_in_bytes <= 4096
        assert memory.temp_size_in_bytes < 1 << 20
    if case.startswith("mesh_fedavg"):
        # Each of the four chips holds its quarter of the 8 MiB leaf.
        out_bytes = compiled.memory_analysis().output_size_in_bytes
        assert out_bytes == 2048 * 1024 * 4 // 4


# cell -> `flash.fwd` kernels in its compiled step: one a scanned body
# (Trinity-Mini: layer 0's, and both kinds under the expert group's
# ``cond``), all in the forward pass.
STEP_FLASH_FORWARDS = {
    "mistral-7b-v0.1-d6.lora-2p": 1,
    "trinity-mini-ep8.lora-all-linear-2p": 3,
}
# cell -> (the output of a product as wide as its dense FFN, how many
# the compiled step holds, the vocabulary, a bound on XLA's GB a party).
# Mistral's scanned layer: gate and up forward, the gate again in the
# second forward, and `dh` (the up product is saved: five when the
# second forward ran it too).  Trinity's dense layer 0 adapts its FFN,
# so each is a pair (the base's and the adapter's ``(x a) b``): ten
# before.  The bound is what this tree reads and a fiftieth of a GB:
# 7.684 for `.lora-2p` (7.584 at the parent of PR 38; `layer.mid`'s six
# stacked `bf16[8192,4096]` are 0.403 GB, of which XLA's sum shows
# 0.100) and 7.091 for Trinity's (6.689; nine `[8192,2048]` are 0.302,
# the sum shows 0.402).  Both stay under what the step took before PR 34
# fused the logits away (7.824, 7.266).
STEP_FFN_PRODUCTS = {
    "mistral-7b-v0.1-d6.lora-2p": ("bf16[8192,14336]", 4, 32000, 7.70),
    "trinity-mini-ep8.lora-all-linear-2p": ("bf16[8192,6144]", 8, 25024, 7.11),
}
# cell -> {(a product's output, the scope it is under): (how many the
# compiled step holds, how many of them in a checkpointed layer's second
# forward)}: the products the size of the mixer's output projection and
# of a Mamba layer's `W_in` since the policy keeps `layer.mid` and
# `ssm.in` (PR 38); in brackets the parent's.  `.lora-2p`: `wo`'s was the
# one hidden-width product the second forward ran (q, k and v come out
# shaped by heads) [7, 1].  The hybrid's: `W_out` and its adapter's wide
# product in each of three Mamba bodies [18, 6] and `W_in`'s pair [12, 6]
# are gone from the second forward; an attention body still makes `q`
# and its adapter's wide product again (32 x 64-wide heads are the
# stream's width), `wo`'s pair no more [24, 6].  Trinity's: unchanged, by
# the block and not the policy: its `post_attn_norm` reads `wo`'s
# product, so the norm's backward needs it and the second forward makes
# it (`layer.mid` spares that cell the norm and the add alone).
STEP_MIXER_PRODUCTS = {
    "mistral-7b-v0.1-d6.lora-2p": {("bf16[8192,4096]", "attn.proj"): (6, 0)},
    "trinity-mini-ep8.lora-all-linear-2p": {
        ("bf16[8192,2048]", "attn.proj"): (16, 4),
    },
    "granite-4.0-h-micro-d20.lora-all-linear-2p": {
        ("bf16[8192,2048]", "ssm.proj"): (12, 0),
        ("bf16[8192,8512]", "ssm.proj"): (6, 0),
        ("bf16[8192,2048]", "attn.proj"): (20, 2),
    },
}
_PRODUCT = r"= (\w+\[[\d,]+\])\S* (?:convolution|dot)\("


def _mixer_products(text):
    """``{(output, innermost mixer scope): [all, in the second forward]}``
    of the products in a compiled step's text."""
    import re

    found = {}
    for line in text.splitlines():
        product = re.search(_PRODUCT, line)
        scopes = re.findall(r"(?:attn|ssm)\.proj", line)
        if product and scopes:
            n = found.setdefault((product.group(1), scopes[-1]), [0, 0])
            n[0] += 1
            n[1] += "rematted_computation" in line
    return found


@pytest.mark.parametrize("cell", list(STEP_FLASH_FORWARDS))
def test_step_runs_the_flash_forward_once_and_two_parties_fit(cell, monkeypatch):
    """The benchmark's LoRA step at the cell's shapes: the checkpointed
    layers save the flash kernel's output and row statistics and the
    FFN's up product (``llama.REMAT_SAVED``), so the backward pass'
    recompute holds no forward kernel and one FFN-width product less;
    they save the stream between the sub-blocks, so it holds no output
    projection either (where no norm reads that product); the fused
    head-and-loss leaves no float32 array of tokens x vocabulary; and
    two parties' steps still fit one chip, in no more memory than the
    kept arrays add."""
    import importlib
    import re

    from rayfed_tpu.models import moe
    from tool.flash_sweep import _step_lowering

    flash = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # Both ask jax.default_backend(), the CPU here: steer them to what
    # the chip runs.
    monkeypatch.setattr(flash, "_interpret_default", lambda: False)
    monkeypatch.setattr(moe, "_grouped_impl", lambda: "megablox")
    _topology()
    compiled = _step_lowering(cell, _on_chip)().compile()
    text = compiled.as_text()
    forwards = [
        line for line in text.splitlines()
        if "custom-call(" in line and "flash.fwd" in line
    ]
    assert len(forwards) == STEP_FLASH_FORWARDS[cell]
    for line in forwards:  # none in the backward pass or its recompute
        assert "transpose(" not in line and "rematted_computation" not in line
    ffn_out, ffn_products, vocab, parent_gb = STEP_FFN_PRODUCTS[cell]
    products = re.findall(_PRODUCT, text)
    assert products.count(ffn_out) == ffn_products
    mixer = _mixer_products(text)
    for product, counts in STEP_MIXER_PRODUCTS[cell].items():
        assert tuple(mixer[product]) == counts, product
    # the logits are made once, a chunk of 1,024 rows at a time (their
    # gradient's product is the head's only other)
    assert products.count(f"f32[1024,{vocab}]") == 1
    for shape in set(re.findall(r"f32\[([\d,]+)\]", text)):
        dims = {int(d) for d in shape.split(",")}
        # the 8,192 tokens (or the 8,191 that have a target) x vocabulary
        assert not (vocab in dims and dims & {8191, 8192}), shape
    memory = compiled.memory_analysis()
    party = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes
    )
    # XLA's analysis, an upper bound (its own buffer report reads 0.7 GB
    # a party less for `lora-2p`), not the chip's peak: `device_peak_GB`
    # read 12.59 and 6.62 GB with both parties' steps in flight (PR 32).
    print(f"{cell}: {party / 1e9:.3f} GB a party, {2 * party / 1e9:.3f} two")
    assert 2 * party / 1e9 < 16.9
    # no more than the names' stacks added (STEP_FFN_PRODUCTS)
    assert party / 1e9 <= parent_gb


def test_state_space_hybrid_step_compiles_and_one_step_fits_beside_two_parties(
    monkeypatch,
):
    """The fourth configuration's cell (18 Mamba-2 layers, 2 attention
    layers without positions, a head tied to 100,352 rows of embedding)
    at its shapes: the chunked scan's kernels (``ops/ssd.py``: in each of
    the three scanned bodies that hold Mamba layers the forward kernel
    twice, once again under the checkpoint, and the backward kernel
    once, all under ``ssm.scan``; no ``[.., 256, 256]`` float32 array is
    left in the program), the convolution
    and both projections are in the program under their scopes, the two
    attention groups run ONE forward kernel each at 32 x 64-wide heads
    on 8 K/V heads, nothing of the embedding's size is copied for the
    tied head, and what is resident (ONE copy of the base, two parties'
    adapters, Adam state and ids) plus ONE running step's temporaries
    fit the chip by XLA's count.  Whether two steps' temporaries are
    ever whole at once the compiler cannot say: their sum is printed,
    and the chip's runs are PERF.md's."""
    import importlib
    import re

    from tool.flash_sweep import _step_lowering

    flash = importlib.import_module("rayfed_tpu.ops.flash_attention")
    # the one patch steers the flash kernels and the scan's
    monkeypatch.setattr(flash, "_interpret_default", lambda: False)
    _topology()
    cell = "granite-4.0-h-micro-d20.lora-all-linear-2p"
    compiled = _step_lowering(cell, _on_chip)().compile()
    text = compiled.as_text()
    for scope in ("ssm.proj", "ssm.conv", "ssm.scan", "attn.full",
                  "attn.proj", "ffn.dense"):
        assert scope in text, scope
    calls = [line for line in text.splitlines() if "custom-call(" in line]
    scans = {
        kernel: [line for line in calls if f"ssd.{kernel}" in line]
        for kernel in ("fwd", "bwd")
    }
    # groups 0-4, 6-14 and 16-19: a body's forward, its second forward
    # under the checkpoint (the carried states are its only residual:
    # no kernel's output is kept across it), its backward
    assert (len(scans["fwd"]), len(scans["bwd"])) == (3 * 2, 3)
    for line in scans["fwd"] + scans["bwd"]:
        assert "ssm.scan" in line  # the readers' scope, backward too
    # the masked decay and its kin (537 MB each a layer before the
    # kernel) are VMEM's: nothing chunk x chunk in float32 is HBM's
    for shape in set(re.findall(r"f32\[([\d,]+)\]", text)):
        assert not shape.endswith("256,256"), shape
    forwards = [
        line for line in text.splitlines()
        if "custom-call(" in line and "flash.fwd" in line
    ]
    assert len(forwards) == 2
    assert not re.search(
        r"= bf16\[(100352,2048|2048,100352)\]\S* (copy|transpose)\(", text
    )
    products = re.findall(_PRODUCT, text)
    mixer = _mixer_products(text)
    for product, counts in STEP_MIXER_PRODUCTS[cell].items():
        assert tuple(mixer[product]) == counts, product
    # the FFN's width: gate and up forward, the gate again in the second
    # forward, `dh`, each with its adapter's, in each of five scanned
    # bodies; the up product is kept and not run again
    assert products.count("bf16[8192,8192]") == 5 * 8
    assert products.count("f32[256,100352]") == 1  # the logits, a chunk
    memory = compiled.memory_analysis()
    base = 1_698_459_520 * 2 - 18 * 3 * 64 * 2  # bf16, the scan's buffers float32
    assert abs(base / 1e9 - 3.397) < 0.001
    # a party's own arguments (adapters, Adam state, ids) and outputs
    held = memory.argument_size_in_bytes - base + memory.output_size_in_bytes
    temp = memory.temp_size_in_bytes
    print(f"{cell}: base {base / 1e9:.3f} GB, a party holds {held / 1e9:.3f} "
          f"GB, a running step's temporaries {temp / 1e9:.3f} GB; resident "
          f"and ONE step {(base + 2 * held + temp) / 1e9:.3f} GB, and TWO "
          f"{(base + 2 * held + 2 * temp) / 1e9:.3f} GB")
    assert (base + 2 * held + temp) / 1e9 < 16.9
    # 13.11 GB by `temp_size_in_bytes` since the policy keeps `layer.mid`
    # (20 stacked `bf16[8192,2048]`, 0.67 GB) and `ssm.in` (18
    # `bf16[8192,8512]`, 2.51): 6.29 more than the parent's 6.83
    # (`layer.mid` alone +1.40, `ssm.in` alone +5.21).  That counter is
    # NOT what the program reserves: the compiler's buffer assignment
    # (`XLA_FLAGS=--xla_dump_to`, its memory-usage report) holds each kept
    # stack ONCE, at one offset of one preallocated temporary of 7.67 GiB
    # = 8.236 GB (the parent's 4.33 GiB = 4.649 GB), and those are the
    # chip's `peak_bytes_reserved` to 2 MB in both (8.238, 4.649:
    # PERF.md section 5); no copy of a stack and no change of layout is
    # in the program.  `peak_memory_in_bytes` less the arguments (8.31,
    # 4.68) follows the assignment to 0.1 GB, so the bound above, kept on
    # the counter it had (ISSUE 38), reads 16.874 where the assignment
    # gives 12.0: ROADMAP Queue 1 asks to re-base it.
    assert temp / 1e9 < 13.2
    assert memory.peak_memory_in_bytes / 1e9 < 11.9


def test_the_lora_bypass_by_block_adds_no_kernel_the_readers_charge(
    monkeypatch,
):
    """A Nemotron-shaped expert layer (64 held squared-ReLU experts in a
    latent, rank-8 adapters: the bypass in four blocks of 16) lowered for
    the chip and differentiated: ``expert_mm_roofline`` and
    ``latent_expert_mm_roofline`` charge every kernel under
    ``grouped_matmul`` with a base product's FLOPs, so those are the
    base products' alone, as many as without adapters; the bypass's own
    kernels (a second forward's two products a matrix, a backward's two
    and two ``tgmm``) sit under ``moe.experts`` beside them, and the
    chip's compiler takes them."""
    from rayfed_tpu.models import moe
    from tests.test_step_scopes import lowered_op_names

    monkeypatch.setattr(moe, "_grouped_impl", lambda: "megablox")
    held, rank = 64, 8
    cfg = moe.ExpertShareConfig(
        num_experts=128, held=tuple(range(held)), top_k=4, d_model=256,
        d_ff=256, shared_d_ff=256, latent=128, activation="relu2",
    )
    assert moe.lora_blocks(held, rank) == (4, 16)
    params = jax.eval_shape(
        lambda: moe.init_expert_share(jax.random.PRNGKey(0), cfg,
                                      jnp.bfloat16)
    )
    adapters = {"experts": {
        name: {
            "a": jax.ShapeDtypeStruct((held, w.shape[1], rank), jnp.float32),
            "b": jax.ShapeDtypeStruct((held, rank, w.shape[2]), jnp.float32),
            "scale": jax.ShapeDtypeStruct((), jnp.float32),
        } for name, w in params["experts"].items()
    }}

    def grads(params, x, adapters):
        def loss(x, adapters):
            out, _ = moe.apply_expert_share(params, x, cfg, lora=adapters)
            return jnp.sum(out.astype(jnp.float32))

        return jax.grad(loss, argnums=(0, 1))(x, adapters)

    x = jax.ShapeDtypeStruct((512, cfg.d_model), jnp.bfloat16)
    kernels = {}
    for name, lora in (("without", {}), ("with", adapters)):
        lowered = jax.jit(grads).lower(*_on_chip((params, x, lora)))
        kernels[name] = [
            full for op, full, line in lowered_op_names(
                lowered.as_text(debug_info=True), every_call=True
            ) if op == "stablehlo.custom_call" and "tpu_custom_call" in line
        ]
        if name == "with":
            lowered.compile()
    base = lambda names: sorted(n for n in names if "grouped_matmul" in n)
    # a second forward's up and down product, their transposes, and the
    # frozen base's `tgmm`s, which the backward rule discards
    assert len(base(kernels["without"])) == 6
    assert base(kernels["with"]) == base(kernels["without"])
    bypass = [n for n in kernels["with"] if "grouped_matmul" not in n]
    assert len(bypass) == 12 and all("moe.experts" in n for n in bypass)
