"""The decoder's latent-attention kind (``rayfed_tpu.models.decoder``,
the ``kimi_k2`` / DeepSeek-V3 block) against the plain reference
(``benchmark/reference/kimi_k2.py``), at toy widths on the CPU with the
published ratios: the rotary part half the part without positions, a
value width that differs from the query-key width, ONE rotary key head
for all query heads.

The reference is the benchmark's (the cell's ``correct`` is decided by
the same functions at the published widths on the chip), so a change to
either side is caught here first.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import kimi_k2 as ref
from rayfed_tpu.models import decoder, llama, lora, moe
from rayfed_tpu.ops.attention import dot_product_attention
from rayfed_tpu.ops.flash_attention import flash_attention

# Toy widths: a dense layer and two expert layers, all latent (two
# groups); query-key width 16 + 8 = 24, values 12 wide.
D, HEADS, Q_RANK, KV_RANK, NOPE, ROPE, VDIM = 32, 4, 24, 16, 16, 8, 12
E, HELD, TOPK, T, VOCAB = 16, (0, 1, 2, 3), 4, 32, 64
SPECS = (decoder.LayerSpec("latent", "dense"),) + (
    decoder.LayerSpec("latent", "moe"),
) * 2
# The published scaling group, with an original length the toy sequence
# passes, so that the blended frequencies matter here.
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
THETA = 50000.0


def scaling(yarn=YARN):
    return llama.YarnScaling(
        factor=yarn["factor"],
        original_max_position=yarn["original_max_position_embeddings"],
        beta_fast=yarn["beta_fast"], beta_slow=yarn["beta_slow"],
        mscale=yarn["mscale"], mscale_all_dim=yarn["mscale_all_dim"],
    )


def toy_config(dtype=jnp.float32, held=HELD, **kw):
    experts = moe.ExpertShareConfig(
        num_experts=E, held=held, top_k=TOPK, d_model=D, d_ff=16,
        route_scale=2.827,
    )
    return decoder.DecoderConfig(
        layers=SPECS, vocab_size=VOCAB, hidden_size=D, num_heads=HEADS,
        intermediate_size=48, rope_theta=THETA, rope_scaling=scaling(),
        latent=decoder.LatentConfig(Q_RANK, KV_RANK, NOPE, ROPE, VDIM),
        qk_norm=False, output_gate=False, post_norms=False,
        experts=experts, dtype=dtype, param_dtype=jnp.float32, **kw,
    )


def ref_kwargs(cfg, **kw):
    return dict(
        num_dense_layers=1, num_heads=HEADS, kv_rank=KV_RANK, nope_dim=NOPE,
        rope_dim=ROPE, v_dim=VDIM, rope_theta=THETA, yarn=YARN,
        rms_eps=cfg.rms_eps, held=cfg.experts.held, top_k=TOPK,
        route_scale=cfg.experts.route_scale, block=T, **kw,
    )


def make(seed=0, cfg=None):
    """(config, base, adapters with a non-zero B, ids)."""
    cfg = cfg or toy_config()
    base = decoder.init_decoder(jax.random.PRNGKey(seed), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(seed + 1), base,
        lora.LoraConfig(rank=2, alpha=4.0, targets=decoder.ALL_LINEAR),
    )
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (1, T), 0, VOCAB)
    return cfg, base, _trained(adapters, seed + 2), ids


def _trained(adapters, seed):
    """B starts at zero, where A has no gradient: give every B a value."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 200))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or path[-1].key != "b"
        else 0.05 * jax.random.normal(next(keys), x.shape),
        adapters,
    )


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def test_a_latent_layer_has_its_five_matrices_and_two_latent_norms():
    cfg, base, adapters, _ = make()
    assert cfg.groups() == ((0, 1), (1, 3))
    lp = base["layers"][1]
    assert lp["wq_a"].shape == (2, D, Q_RANK)
    assert lp["wq_b"].shape == (2, Q_RANK, HEADS * (NOPE + ROPE))
    assert lp["wkv_a"].shape == (2, D, KV_RANK + ROPE)  # one rotary key head
    assert lp["wkv_b"].shape == (2, KV_RANK, HEADS * (NOPE + VDIM))
    assert lp["wo"].shape == (2, HEADS * VDIM, D)
    assert lp["q_a_norm"].shape == (2, Q_RANK)
    assert lp["kv_a_norm"].shape == (2, KV_RANK)
    # none of the optional parts: head norms, gate, norms after sub-blocks
    assert not {"wq", "wz", "q_norm", "post_attn_norm", "post_mlp_norm"} & set(lp)
    got = sorted(adapters["layers"]["1"])
    assert got == ["moe", "wkv_a", "wkv_b", "wo", "wq_a", "wq_b"]
    # a layer of another kind has parameters of its own: a group of its own
    # (the refusal to mix latent layers with others became this rule, PR 35)
    mixed = dataclasses.replace(
        cfg, layers=SPECS + (decoder.LayerSpec("full", "moe"),)
    )
    assert mixed.groups() == ((0, 1), (1, 3), (3, 4))
    with pytest.raises(ValueError, match="needs config.latent"):
        dataclasses.replace(cfg, latent=None)


def test_adapters_at_the_published_shapes_count_what_the_cell_states():
    """Rank 8 on the published shapes, by hand (ISSUE 33): attention
    500,224 a layer, the dense FFN 614,400, the shared expert 221,184,
    twelve held experts 2,654,208; depth 5 is one dense and four expert
    layers."""
    experts = moe.ExpertShareConfig(
        num_experts=384, held=tuple(range(12)), d_model=7168, d_ff=2048,
    )
    cfg = decoder.DecoderConfig(
        layers=(decoder.LayerSpec("latent", "dense"),)
        + (decoder.LayerSpec("latent", "moe"),) * 4,
        vocab_size=20480, hidden_size=7168, num_heads=64,
        intermediate_size=18432, latent=decoder.LatentConfig(),
        qk_norm=False, output_gate=False, post_norms=False, experts=experts,
    )
    base = jax.eval_shape(
        lambda: decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    )
    adapters = jax.eval_shape(
        lambda b: lora.init_lora(
            jax.random.PRNGKey(0), b,
            lora.LoraConfig(targets=decoder.ALL_LINEAR),
        ), base,
    )
    assert lora.num_lora_params(adapters) == 14_617_088
    assert (500_224 + 614_400) + 4 * (500_224 + 221_184 + 2_654_208) == 14_617_088
    frozen = sum(x.size for x in jax.tree_util.tree_leaves(base))
    assert round(frozen / 1e6) == 3497  # the cut's 3,497 M parameters


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("attn", ["dense", "flash"])
def test_float32_system_matches_the_reference(attn, remat):
    """Logits, loss and the gradient of every adapter leaf, through the
    dense attention (the CPU path) and the flash kernels (interpreter),
    with and without the checkpointed scan.  Both sides are float32 and
    differ in the order of their sums: 1e-4 relative is a hundred
    float32 roundings, and every mistake in the mathematics is of order
    one (the omissions below)."""
    cfg, base, adapters, ids = make(cfg=toy_config(remat=remat))
    attn_fn = {"dense": dot_product_attention, "flash": flash_attention}[attn]
    kw = ref_kwargs(cfg)

    def sys_loss(a):
        logits, aux = decoder.apply_decoder(
            base, ids, cfg, lora=a, attn_fn=attn_fn
        )
        return llama.lm_loss(logits[:, :-1], ids[:, 1:]), (logits, aux)

    (loss, (logits, aux)), grads = jax.jit(jax.value_and_grad(
        sys_loss, has_aux=True
    ))(adapters)
    plain_base = decoder.unstack(base, cfg)
    plain_adapters = decoder.unstack(adapters, cfg)
    want_logits, infos = ref.forward(
        plain_base, ids[0], lora=plain_adapters, **kw
    )
    want_loss, want_grads = ref.lora_gradients(
        plain_base, plain_adapters, ids[0], remat=remat, **kw
    )
    assert rel_rms(logits[0], want_logits) < 1e-4
    assert abs(float(loss) - float(want_loss)) < 1e-4 * float(want_loss)
    for i in (1, 2):
        np.testing.assert_array_equal(
            np.sort(aux[i]["selected"], -1), np.sort(infos[i]["selected"], -1)
        )
        np.testing.assert_array_equal(aux[i]["counts"], infos[i]["counts"])
    flat_got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) > 60
    for (path, got), want in zip(flat_got, flat_want):
        if path[-1].key == "scale":
            continue  # the system holds it constant (stop_gradient)
        assert float(jnp.abs(want).max()) > 0, path
        assert rel_rms(got, want) < 2e-4, path


@pytest.mark.parametrize("piece", ref.PIECES)
def test_the_comparison_notices_every_piece_of_the_mathematics(piece):
    """The reference with one piece left out is another function: the
    logits move by far more than the 1e-4 the agreement is held to."""
    cfg, base, adapters, ids = make()
    logits, _ = decoder.apply_decoder(base, ids, cfg, lora=adapters)
    got, _ = ref.forward(
        decoder.unstack(base, cfg), ids[0],
        lora=decoder.unstack(adapters, cfg), omit=(piece,), **ref_kwargs(cfg),
    )
    assert rel_rms(logits[0], got) > 1e-3, piece


def test_yarn_tables_at_the_published_numbers():
    """``rope_tables`` with the configuration's scaling entry against
    the closed form (ISSUE 33): theta 50000, 64 rotary dims, factor 64
    over 4,096 positions, beta 32 / 1."""
    s = llama.YarnScaling(factor=64, original_max_position=4096,
                          beta_fast=32, beta_slow=1, mscale=1,
                          mscale_all_dim=1)
    d = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(50000))
    low, high = s.blend(64, 50000.0)
    assert (low, high) == (math.floor(d(32)), math.ceil(d(1))) == (8, 20)
    freqs = np.asarray(llama.rope_frequencies(64, 50000.0, s))
    plain = 50000.0 ** (-np.arange(32) * 2 / 64)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(
        freqs, plain / 64 * ramp + plain * (1 - ramp), rtol=1e-6
    )
    assert freqs[0] == 1.0  # turns fastest: kept
    np.testing.assert_allclose(freqs[31], plain[31] / 64, rtol=1e-6)
    # the tables carry m(mscale) / m(mscale_all_dim) = 1, the scores
    # 192^-0.5 x m(1)^2
    assert s.table_scale() == 1.0
    assert abs(s.magnitude(1) - 1.41589) < 1e-5
    assert abs(s.softmax_scale() - 2.00474) < 1e-5
    cos, sin = llama.rope_tables(jnp.arange(8), 64, 50000.0, s)
    np.testing.assert_allclose(cos, np.cos(np.arange(8)[:, None] * freqs),
                               atol=1e-6)
    # the reference's own arithmetic gives the same frequencies
    yarn = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096}
    assert ref.yarn_blend(64, 50000.0, yarn) == (low, high)
    np.testing.assert_allclose(ref.inv_freq(64, 50000.0, yarn), freqs, rtol=1e-6)
    # and no scaling entry gives the tables every other model had, bit for bit
    for a, b in zip(llama.rope_tables(jnp.arange(8), 64, 1e4),
                    llama.rope_tables(jnp.arange(8), 64, 1e4, None)):
        np.testing.assert_array_equal(a, b)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of four of sixteen experts: what each share's routed
    experts give (its layer's output less the shared expert's, which
    every chip computes alike) summed, plus the shared expert once, is
    the uncut reference's expert layer, in program and reference alike."""
    cfg, base, _, ids = make()
    p = jax.tree_util.tree_map(lambda x: x[0], base["layers"][1]["moe"])
    m = jax.random.normal(jax.random.PRNGKey(9), (T, D))
    keys = jax.random.split(jax.random.PRNGKey(10), 3)
    every = {  # all sixteen experts' weights, which the shares divide
        n: jax.random.normal(k, (E,) + p["experts"][n].shape[1:]) * 0.2
        for n, k in zip(("w_gate", "w_up", "w_down"), keys)
    }
    kw = dict(top_k=TOPK, route_scale=cfg.experts.route_scale)
    whole, _ = ref.expert_layer(
        m, dict(p, experts=every), held=tuple(range(E)), **kw
    )
    shared, _ = ref.expert_layer(  # a share that holds no selected expert
        m, dict(p, experts={n: w[:1] * 0 for n, w in every.items()}),
        held=(0,), **kw,
    )
    total = shared
    for first in range(0, E, 4):
        held = tuple(range(first, first + 4))
        mine = {n: w[first:first + 4] for n, w in every.items()}
        part, _ = moe.apply_expert_share(
            dict(p, experts=mine), m,
            dataclasses.replace(cfg.experts, held=held),
        )
        want, _ = ref.expert_layer(m, dict(p, experts=mine), held=held, **kw)
        assert rel_rms(part, want) < 1e-5
        total = total + (part - shared)
    assert rel_rms(total, whole) < 1e-5


# Loss (as float.hex) and a digest of every adapter gradient's bytes at a
# fixed seed on the CPU: the kernels' bodies, the decoder's block and
# `rope_tables` were generalised under both models (PR 33), and
# neither's program may change.  Two builds are pinned, as in
# `test_parent_bits.py`, which says why: `default`, what ships (XLA may
# hand a fusion's consumer the float32 value a bf16 result was rounded
# from, so which values are rounded follows the compiler's fusions;
# re-recorded from PR 38's tree, whose checkpoint keeps the stream
# between a layer's sub-blocks: the loss moved by 1e-4 on these toys
# from the bits of PR 33's parent, 441dd9c), and `strict`, compiled
# without excess precision, where the parent of PR 38 (0616888) and its
# tree compute the same bits.  The strict build also runs XLA:CPU's
# contractions on one thread (``xla_cpu_multi_thread_eigen`` off): a
# multi-threaded contraction splits its sums by the host's thread pool,
# and Mistral's strict digest read on one host (c9088b17e1d01ccd) was
# not what another computed from the same tree, or from its parent
# (870a5ec1deb0434a).  Single-threaded, 0616888 (before the stream was
# kept) and this tree still agree on all five strict pins here and in
# `test_parent_bits.py`.  Single-threaded, Mistral's strict LOSS still
# read 0x1.8bfd5a on one host and 0x1.8bfd5c on another (its gradients
# agreed): with platform-dependent math on, XLA:CPU lowers ``log`` and
# ``exp`` to what the host's math library picks for the CPU it runs
# on.  Off (``xla_cpu_enable_platform_dependent_math``), XLA emits its
# own approximations; capping the ISA (``xla_cpu_max_isa``: SSE4_2,
# AVX2, AVX512) moved no bit of any strict pin either way.  The strict
# digests were re-recorded with it off from the tree at 493da76.
STRICT = {"compiler_options": {"xla_allow_excess_precision": False,
                               "xla_cpu_multi_thread_eigen": False,
                               "xla_cpu_enable_platform_dependent_math": False}}
BUILDS = {"default": {}, "strict": STRICT}
BITS = {
    "default": {
        "trinity": ("0x1.352ba00000000p+2", "57f7abd6dbb9216a"),
        "mistral": ("0x1.8bfc1e0000000p+2", "ec0bc06699df0c28"),
    },
    "strict": {
        "trinity": ("0x1.35605a0000000p+2", "75f2fb6ea71edec6"),
        "mistral": ("0x1.8bfd5c0000000p+2", "30b6df790c0a98ea"),
    },
}


def _bits(loss, grads):
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(grads):
        h.update(np.asarray(leaf).tobytes())
    return float(loss).hex(), h.hexdigest()[:16]


@pytest.mark.parametrize("build", list(BITS))
@pytest.mark.parametrize("model", ["trinity", "mistral"])
def test_the_models_that_were_there_compute_the_parents_bits(model, build):
    """Trinity's toy decoder (window and full attention under a ``cond``,
    expert layers, the checkpointed scan) and Mistral's toy (a sliding
    window on grouped K/V), bf16 through the flash kernels.  The layers
    under ``lm_loss`` of the logits compute the parent's bits; the LoRA
    step's own loss (``lora_loss``, head and loss fused since PR 34)
    sums the mean in another order and rounds ``d loss / d x`` to bf16
    a chunk, so it is held to those bits within a tolerance: the loss
    1e-6 relative, every leaf's gradient 3% of its norm (read: 1e-7,
    and 1.7% / 1.0% at most, 0.6% / 0.9% in the median)."""
    if model == "trinity":
        experts = moe.ExpertShareConfig(
            num_experts=8, held=(0, 1, 2, 3), top_k=3, d_model=32, d_ff=16,
            route_scale=2.826,
        )
        cfg = decoder.DecoderConfig(
            layers=(decoder.LayerSpec("window", "dense"),
                    decoder.LayerSpec("window", "moe"),
                    decoder.LayerSpec("full", "moe")),
            vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, intermediate_size=48, sliding_window=8,
            embed_scale=32 ** 0.5, experts=experts, dtype=jnp.bfloat16,
            param_dtype=jnp.float32, remat=True,
        )
        base = decoder.init_decoder(jax.random.PRNGKey(0), cfg)
        adapters = _trained(lora.init_lora(
            jax.random.PRNGKey(1), base, lora.LoraConfig(
                rank=2, alpha=4.0,
                targets=(r"/w[qkvoz]$", r"/w_(gate|up|down)$"),
            )), 2)
        ids = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 0, 64)

        def loss_fn(a):
            logits, _ = decoder.apply_decoder(base, ids, cfg, lora=a,
                                              attn_fn=flash_attention)
            return llama.lm_loss(logits[:, :-1], ids[:, 1:])

        def step_loss_fn(a):
            return decoder.lora_loss(a, base, ids, cfg,
                                     attn_fn=flash_attention)[0]
    else:
        cfg = llama.llama_tiny(sliding_window=16, remat=True, dtype=jnp.bfloat16)
        base = llama.init_llama(jax.random.PRNGKey(0), cfg)
        adapters = _trained(lora.init_lora(
            jax.random.PRNGKey(1), base, lora.LoraConfig(rank=2, alpha=4.0)
        ), 2)
        ids = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, 256)

        def loss_fn(a):
            logits = llama.apply_llama(base, ids, cfg, lora=a,
                                       attn_fn=flash_attention)
            return llama.lm_loss(logits[:, :-1], ids[:, 1:])

        def step_loss_fn(a):
            return llama.lora_loss(a, base, ids, cfg, attn_fn=flash_attention)

    loss, grads = jax.jit(
        jax.value_and_grad(loss_fn), **BUILDS[build]
    )(adapters)
    assert _bits(loss, grads) == BITS[build][model]
    step_loss, step_grads = jax.jit(
        jax.value_and_grad(step_loss_fn), **BUILDS[build]
    )(adapters)
    np.testing.assert_allclose(float(step_loss), float(loss), rtol=1e-6)
    for want, got in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(step_grads)):
        want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
        assert np.linalg.norm(got - want) <= 0.03 * np.linalg.norm(want)


def test_the_grouped_products_contraction_tile_divides_the_shape():
    """Trinity's products keep the tiles they had (K = 2,048 and 1,024
    are their own); K = 7,168 is walked in its largest lane-aligned
    divisor under 2,048, not in 2,048s whose last is masked."""
    tile = moe._contraction_tile
    assert (tile(2048, 2048), tile(1024, 2048), tile(64, 2048)) == (2048, 1024, 64)
    assert tile(7168, 2048) == 1792 and 7168 % 1792 == 0 and 1792 % 128 == 0
    assert tile(5000, 2048) == 2048  # no aligned divisor: as before
