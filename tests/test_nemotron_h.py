"""The ``nemotron_h`` block (NVIDIA Nemotron-3 Super) through the decoder:
Mamba-2 layers with several groups of ``B``/``C`` and a gated norm a
group (``models/mamba2.py``), latent expert layers with squared-ReLU
experts and a shared expert of its own width (``moe.apply_expert_share``
with ``latent`` and ``activation="relu2"``), layers with no FFN (a
single-part block), attention without positions, and the
multi-token-prediction module (``DecoderConfig.mtp``), against the plain
reference (``benchmark/reference/nemotron_h.py``: block by block as
published, the recurrence token by token), at toy widths on the CPU with
the published ratios: ``d_inner`` twice the hidden size, eight groups,
the latent a quarter of the hidden size, the shared expert twice a
routed one's width, top-k of a wide router, the first eleven blocks of
the published pattern.

The reference is the benchmark's (the cell's ``correct`` is decided by
the same functions at the published widths on the chip), so a change to
either side is caught here first.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import granite_hybrid as granite_ref
from benchmark.reference import nemotron_h as ref
from rayfed_tpu import telemetry
from rayfed_tpu.models import decoder, llama, lora, mamba2, moe
from rayfed_tpu.ops.attention import dot_product_attention
from rayfed_tpu.ops.flash_attention import flash_attention
from rayfed_tpu.ops.ssd import ssd_scan
from tests.test_kimi_k2 import _trained, rel_rms

PATTERN = "MEMEMEM*EME"  # the published pattern's first eleven blocks
MTP_PATTERN = "*E"
D, T, VOCAB = 32, 32, 64
HEADS, KV, DH = 4, 2, 8  # attention
SSM = mamba2.SsmConfig(num_heads=16, head_dim=4, state=8, groups=8,
                       conv_width=4, chunk=8)
E, HELD, TOPK = 32, (0, 1, 2, 3), 6
EXPERTS = moe.ExpertShareConfig(
    num_experts=E, held=HELD, top_k=TOPK, d_model=D, d_ff=12,
    route_scale=5.0, shared_d_ff=24, latent=8, activation="relu2",
)


def layer_specs(pattern):
    from benchmark.families.nemotron_h_lm import layer_specs

    return layer_specs(pattern)


def toy_config(dtype=jnp.float32, **kw):
    assert SSM.d_inner == 2 * D
    return decoder.DecoderConfig(
        layers=layer_specs(PATTERN), vocab_size=VOCAB, hidden_size=D,
        num_heads=HEADS, num_kv_heads=KV, head_dim=DH, ssm=SSM,
        experts=EXPERTS, qk_norm=False, output_gate=False, post_norms=False,
        mtp=decoder.MtpConfig(layers=layer_specs(MTP_PATTERN)),
        dtype=dtype, param_dtype=jnp.float32, **kw,
    )


def ref_kwargs(cfg, **kw):
    m, e = cfg.ssm, cfg.experts
    return dict(
        pattern=PATTERN, mtp_pattern=MTP_PATTERN, rms_eps=cfg.rms_eps,
        ssm=dict(heads=m.num_heads, head_dim=m.head_dim, state=m.state,
                 groups=m.groups, conv_width=m.conv_width),
        attn=dict(num_heads=HEADS, num_kv_heads=KV, attn_head_dim=DH),
        moe=dict(held=e.held, top_k=e.top_k, route_scale=e.route_scale),
        mtp_loss_weight=decoder.MTP_LOSS_WEIGHT, **kw,
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


def plain(tree, cfg):
    return decoder.unstack(tree, cfg)


# -- the configuration: the pairing, groups, parameters -----------------


PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME"
)


def test_single_part_blocks_pair_into_four_groups():
    """A mixer block and the E block after it are one layer; M before *
    is a layer with no FFN; eleven blocks are six layers in four scanned
    groups and the MTP module one more; the whole published pattern has
    40 M, 40 E and 8 attention blocks, every eleven of them one of each
    five and one."""
    cfg = toy_config()
    spec = decoder.LayerSpec
    assert cfg.layers == (
        spec("ssm", "moe"), spec("ssm", "moe"), spec("ssm", "moe"),
        spec("ssm", "none"), spec("full", "moe"), spec("ssm", "moe"),
    )
    assert cfg.groups() == ((0, 3), (3, 4), (4, 5), (5, 6))
    assert cfg.mtp_groups() == ((6, 7),) and cfg.stack[6] == spec("full", "moe")
    assert len(PUBLISHED_PATTERN) == 88
    for kind, n in (("M", 40), ("E", 40), ("*", 8)):
        assert PUBLISHED_PATTERN.count(kind) == n
    for stage in range(8):
        part = PUBLISHED_PATTERN[11 * stage: 11 * (stage + 1)]
        assert (part.count("M"), part.count("E"), part.count("*")) == (5, 5, 1)
    with pytest.raises(ValueError, match="follows no mixer"):
        layer_specs("EM")
    with pytest.raises(ValueError, match="dense, moe, none"):
        spec("ssm", "experts")
    # a layer with no FFN has no FFN weights and no norm before one
    _, base, adapters, _ = make()
    none = base["layers"][1]
    assert "mlp_norm" not in none and "moe" not in none and "w_up" not in none
    assert none["w_in"].shape == (1, D, SSM.proj_dim)
    mo = base["layers"][0]["moe"]
    assert sorted(mo) == ["experts", "router", "router_bias", "shared",
                          "w_lat_in", "w_lat_out"]
    assert sorted(mo["experts"]) == sorted(mo["shared"]) == ["w_down", "w_up"]
    assert mo["experts"]["w_up"].shape == (3, len(HELD), 8, 12)
    assert mo["shared"]["w_up"].shape == (3, D, 24)
    assert mo["w_lat_in"].shape == (3, D, 8)
    mtp = base["mtp"]
    assert mtp["w_eh"].shape == (2 * D, D)
    assert sorted(mtp) == ["enorm", "final_norm", "hnorm", "layers", "w_eh"]
    assert sorted(adapters["mtp"]) == ["layers", "w_eh"]
    assert sorted(adapters["layers"]["0"]["moe"]) == [
        "experts", "shared", "w_lat_in", "w_lat_out",
    ]
    assert sorted(adapters["layers"]["1"]) == ["w_in", "w_out"]


def test_adapters_at_the_published_shapes_count_what_the_cell_states():
    """Rank 8 on the published shapes, by hand: a Mamba block's
    two matrices, an attention block's four, an expert block's latent
    pair, shared expert and 64 held experts' two each, the MTP module's
    W_eh and its two blocks: 26,104,832; the frozen cut 3,228.4 M
    parameters (6.46 GB of bf16)."""
    cfg = dataclasses.replace(
        toy_config(), vocab_size=16384, hidden_size=4096, num_heads=32,
        num_kv_heads=2, head_dim=128,
        ssm=mamba2.SsmConfig(num_heads=128, head_dim=64, state=128,
                             groups=8, conv_width=4, chunk=128),
        experts=moe.ExpertShareConfig(
            num_experts=512, held=tuple(range(64)), top_k=22, d_model=4096,
            d_ff=2688, route_scale=5.0, shared_d_ff=5376, latent=1024,
            activation="relu2",
        ),
    )
    base = jax.eval_shape(
        lambda: decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    )
    targets = ("/w[qkvo]$", "/w_(in|out)$", "/w_(up|down)$",
               "/w_lat_(in|out)$", "/w_eh$")  # the cell's
    for t in (targets, decoder.ALL_LINEAR):
        adapters = jax.eval_shape(
            lambda b: lora.init_lora(
                jax.random.PRNGKey(0), b, lora.LoraConfig(targets=t)
            ), base,
        )
        assert lora.num_lora_params(adapters) == 26_104_832
    r = 8
    mamba = r * (4096 + 18560) + r * (8192 + 4096)
    attn = r * (4096 + 4096) + 2 * r * (4096 + 256) + r * (4096 + 4096)
    experts = (2 * r * (4096 + 1024) + 2 * r * (4096 + 5376)
               + 64 * 2 * r * (1024 + 2688))
    assert 5 * mamba + 2 * attn + 6 * experts + r * (8192 + 4096) == 26_104_832
    assert base["layers"][0]["w_in"].shape == (3, 4096, 18560)
    frozen = sum(x.size for x in jax.tree_util.tree_leaves(base))
    assert 3_228_300_000 < frozen < 3_228_500_000


# -- each part alone -----------------------------------------------------


def test_the_gated_norm_is_normed_a_group_at_a_time():
    """Eight groups of the last dim, each normed alone, against numpy;
    one group is the granite norm's bits as they were."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(k[0], (2, 5, 64))
    z = jax.random.normal(k[1], (2, 5, 64))
    w = 1.0 + 0.1 * jax.random.normal(k[2], (64,))
    u = np.asarray(y, np.float64) * np.asarray(jax.nn.silu(z), np.float64)
    parts = u.reshape(2, 5, 8, 8)
    want = parts / np.sqrt(np.mean(parts ** 2, -1, keepdims=True) + 1e-5)
    want = want.reshape(2, 5, 64) * np.asarray(w, np.float64)
    got = mamba2.gated_norm(y, z, w, 1e-5, 8)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    whole = u / np.sqrt(np.mean(u ** 2, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        mamba2.gated_norm(y, z, w, 1e-5), whole * np.asarray(w), rtol=2e-5,
        atol=2e-6,
    )
    assert rel_rms(got, mamba2.gated_norm(y, z, w, 1e-5)) > 1e-2


def test_the_scan_with_eight_groups_and_chunks_of_128_is_the_recurrence():
    """The published grouping (a group's ``B`` and ``C`` shared by its
    heads) and chunk, two chunks long: forward and all six gradients
    against the token-by-token recurrence, float32."""
    t, h, p, g, n = 256, 16, 4, 8, 8
    k = jax.random.split(jax.random.PRNGKey(1), 7)
    args = (
        jax.random.normal(k[0], (1, t, h, p)),
        jax.nn.softplus(jax.random.normal(k[1], (1, t, h)) - 1.0),
        -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
        jax.random.normal(k[3], (1, t, g, n)),
        jax.random.normal(k[4], (1, t, g, n)),
        jax.random.normal(k[5], (h,)),
    )
    w = jax.random.normal(k[6], (1, t, h, p))

    def recurrence(x, dt, a, b, c, d):
        rep = lambda v: jnp.repeat(v[0], h // g, axis=1)
        return granite_ref.recurrence(x[0], dt[0], a, rep(b), rep(c), d)[None]

    scan = lambda *a: ssd_scan(*a, chunk=128)
    assert rel_rms(scan(*args), recurrence(*args)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(scan(*a) * w), argnums=range(6))(*args)
    want = jax.grad(
        lambda *a: jnp.sum(recurrence(*a) * w), argnums=range(6)
    )(*args)
    for name, g_, r in zip("x dt A B C D".split(), got, want):
        assert float(jnp.abs(r).max()) > 0, name
        assert rel_rms(g_, r) < 1e-4, name


def _expert_case(seed=4):
    cfg, base, adapters, _ = make(seed)
    p = jax.tree_util.tree_map(lambda x: x[0], base["layers"][0]["moe"])
    ll = jax.tree_util.tree_map(
        lambda x: x[0] if jnp.ndim(x) else x, adapters["layers"]["0"]["moe"]
    )
    m = jax.random.normal(jax.random.PRNGKey(seed + 9), (T, D))
    return cfg, p, ll, m


def test_the_latent_relu2_experts_and_their_backward_match_the_reference():
    """One expert layer alone: the output and, through the routed path's
    hand-written backward (two matrices an expert), the gradients of the
    input and of every adapter of the latent pair, the shared expert and
    the held experts, against ``jax.grad`` of the reference."""
    cfg, p, ll, m = _expert_case()
    e = cfg.experts
    kw = dict(held=e.held, top_k=e.top_k, route_scale=e.route_scale)

    def system(m, ll):
        out, aux = moe.apply_expert_share(p, m, e, lora=ll)
        return out, aux

    def reference(m, ll):
        return ref.experts(m, p, ll, **kw)

    (got, aux), (want, info) = system(m, ll), reference(m, ll)
    assert rel_rms(got, want) < 1e-5
    np.testing.assert_array_equal(
        np.sort(aux["selected"], -1), np.sort(info["selected"], -1)
    )
    assert int(aux["held_assignments"]) == int(aux["counts"].sum()) > 0
    np.testing.assert_array_equal(aux["counts"], info["counts"])
    w = jax.random.normal(jax.random.PRNGKey(11), got.shape)
    loss = lambda f: lambda m, ll: jnp.sum(f(m, ll)[0] * w)
    g_sys = jax.grad(loss(system), argnums=(0, 1))(m, ll)
    g_ref = jax.grad(loss(reference), argnums=(0, 1))(m, ll)
    assert rel_rms(g_sys[0], g_ref[0]) < 1e-5
    flat_sys = jax.tree_util.tree_leaves_with_path(g_sys[1])
    flat_ref = jax.tree_util.tree_leaves(g_ref[1])
    assert len(flat_sys) == len(flat_ref) == 6 * 3  # a, b, scale
    for (path, got), want in zip(flat_sys, flat_ref):
        if path[-1].key == "scale":
            continue
        assert float(jnp.abs(want).max()) > 0, path
        assert rel_rms(got, want) < 1e-5, path


def test_the_latent_projections_are_on_the_routed_path_only():
    """The latent pair changes the routed part alone: with ``W_lat_in``
    and ``W_lat_out`` the identity and the zero padding of a latent as
    wide as the stream, the layer is the one that runs its experts at
    full width; the router and the shared expert read the stream as it
    is (a router on the latent, or a shared expert in it, is another
    function)."""
    cfg, p, _, m = _expert_case()
    e = dataclasses.replace(cfg.experts, latent=None)
    wide = moe.init_expert_share(jax.random.PRNGKey(3), e)
    # experts at full width whose first `latent` rows/columns are the
    # latent ones, the rest zero
    lat = cfg.experts.latent
    pad_in = lambda w: jnp.pad(w, [(0, 0), (0, D - lat), (0, 0)])
    pad_out = lambda w: jnp.pad(w, [(0, 0), (0, 0), (0, D - lat)])
    full = dict(
        router=p["router"], router_bias=p["router_bias"], shared=p["shared"],
        experts={"w_up": pad_in(p["experts"]["w_up"]),
                 "w_down": pad_out(p["experts"]["w_down"])},
    )
    assert sorted(wide) == sorted(full)
    eye_in = jnp.eye(D, lat)
    latent = dict(p, w_lat_in=eye_in, w_lat_out=eye_in.T)
    want, _ = moe.apply_expert_share(full, m, e)
    got, _ = moe.apply_expert_share(latent, m, cfg.experts)
    assert rel_rms(got, want) < 1e-6
    # the reference's own latent against the system's
    kw = dict(held=HELD, top_k=TOPK, route_scale=5.0)
    ref_got, _ = ref.experts(m, p, {}, **kw)
    sys_got, _ = moe.apply_expert_share(p, m, cfg.experts)
    assert rel_rms(sys_got, ref_got) < 1e-5
    dropped, _ = ref.experts(m, p, {}, omit=("latent",), **kw)
    assert rel_rms(sys_got, dropped) > 1e-2


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of four of 32 experts (the cell's 8-way division):
    what each share's routed experts give (its layer's output less the
    shared expert's, which every chip computes alike) summed, plus the
    shared expert once, is the uncut reference's expert layer, in program
    and reference alike."""
    cfg, p, _, m = _expert_case()
    keys = jax.random.split(jax.random.PRNGKey(10), 2)
    every = {  # all 32 experts' weights, which the shares divide
        n: jax.random.normal(k, (E,) + p["experts"][n].shape[1:]) * 0.3
        for n, k in zip(("w_up", "w_down"), keys)
    }
    kw = dict(top_k=TOPK, route_scale=5.0)
    whole, _ = ref.experts(m, dict(p, experts=every), {},
                           held=tuple(range(E)), **kw)
    shared, _ = ref.experts(  # a share whose one expert adds nothing
        m, dict(p, experts={n: w[:1] * 0 for n, w in every.items()}), {},
        held=(0,), **kw,
    )
    total = shared
    for first in range(0, E, E // 8):
        held = tuple(range(first, first + E // 8))
        mine = {n: w[first:first + E // 8] for n, w in every.items()}
        part, _ = moe.apply_expert_share(
            dict(p, experts=mine), m,
            dataclasses.replace(cfg.experts, held=held),
        )
        want, _ = ref.experts(m, dict(p, experts=mine), {}, held=held, **kw)
        assert rel_rms(part, want) < 1e-5
        total = total + (part - shared)
    assert rel_rms(total, whole) < 1e-5


def test_the_mtp_loss_targets_the_token_after_next():
    """The fused head-and-loss with ``shift=2`` is the mean cross entropy
    of position ``i`` against ``ids[i + 2]`` over the first ``T - 2``
    positions; ``shift=1`` is the next-token loss as it was."""
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k[0], (2, T, D))
    head = jax.random.normal(k[1], (D, VOCAB)) * D ** -0.5
    ids = jax.random.randint(k[2], (2, T), 0, VOCAB)
    logits = jnp.einsum("btd,dv->btv", x, head)
    for shift in (1, 2):
        want = llama.lm_loss(logits[:, :-shift], ids[:, shift:])
        got = llama.frozen_head_loss(x, head, ids, shift=shift)
        assert abs(float(got) - float(want)) < 1e-5 * float(want)
    assert abs(float(llama.frozen_head_loss(x, head, ids, shift=2))
               - float(llama.lm_loss(logits[:, :-1], ids[:, 1:]))) > 1e-3
    # and the reference's, one sequence
    fin = jnp.ones((D,))
    for shift in (1, 2):
        normed = llama._rms_norm(x[0], fin, 1e-5)
        want = llama.lm_loss(normed[None, :-shift] @ head, ids[:1, shift:])
        got = ref.head_loss(x[0], fin, head, ids[0], shift=shift,
                            rms_eps=1e-5)
        assert abs(float(got) - float(want)) < 1e-5 * float(want)


# -- the whole stage against the reference ------------------------------


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("attn", ["dense", "flash"])
def test_float32_system_matches_the_reference(attn, remat):
    """The main logits, both losses (the step's fused heads) and the
    gradient of every adapter leaf, the MTP module's included, through
    the dense attention (the CPU path) and the flash kernels
    (interpreter), with and without the checkpointed scan.  Both sides
    are float32 and differ in the order of their sums: 1e-4 relative,
    and every mistake in the mathematics is of order one (the pieces
    below)."""
    cfg, base, adapters, ids = make(cfg=toy_config(remat=remat))
    attn_fn = {"dense": dot_product_attention, "flash": flash_attention}[attn]
    kw = ref_kwargs(cfg, remat=remat)

    def sys_loss(a):
        loss, aux, terms = decoder.lora_loss_terms(
            a, base, ids, cfg, attn_fn=attn_fn
        )
        return loss, (terms, aux)

    (loss, ((main, mtp), aux)), grads = jax.jit(
        jax.value_and_grad(sys_loss, has_aux=True)
    )(adapters)
    logits, _ = decoder.apply_decoder(base, ids, cfg, lora=adapters,
                                      attn_fn=attn_fn, last=5)
    want, (want_main, want_mtp), want_logits, infos = ref.run(
        plain(base, cfg), ids[0], lora=plain(adapters, cfg), last=5, **kw
    )
    assert rel_rms(logits[0], want_logits) < 1e-4
    for got, ref_ in ((loss, want), (main, want_main), (mtp, want_mtp)):
        assert abs(float(got) - float(ref_)) < 1e-4 * float(ref_)
    assert float(loss) == pytest.approx(float(main) + 0.1 * float(mtp))
    assert sorted(aux) == [0, 1, 2, 4, 5, 6] == [
        i for i, s in enumerate(cfg.stack) if s.ffn == "moe"
    ]
    for i, info in zip(sorted(aux), infos):
        np.testing.assert_array_equal(
            np.sort(aux[i]["selected"], -1), np.sort(info["selected"], -1)
        )
    _, want_grads = ref.lora_gradients(
        plain(base, cfg), plain(adapters, cfg), ids[0], **kw
    )
    flat_got = jax.tree_util.tree_leaves_with_path(plain(grads, cfg))
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) > 80
    for (path, got), want in zip(flat_got, flat_want):
        if path[-1].key == "scale":
            continue  # the system holds it constant (stop_gradient)
        assert float(jnp.abs(want).max()) > 0, path
        assert rel_rms(got, want) < 2e-4, path


@pytest.mark.parametrize("piece", ref.PIECES)
def test_the_comparison_notices_every_piece_of_the_mathematics(piece):
    """The reference with one piece left out or broken (the gated norm
    over all channels, ``relu`` unsquared, the latent pair dropped, the
    shared expert, the routing scale or bias, the MTP targets or its
    input one token off) is another function: the main logits or the
    MTP loss move by far more than the 1e-4 the agreement is held to."""
    cfg, base, adapters, ids = make()
    total, (main, mtp), logits, _ = ref.run(
        plain(base, cfg), ids[0], lora=plain(adapters, cfg), last=T,
        **ref_kwargs(cfg),
    )
    _, (b_main, b_mtp), broken, _ = ref.run(
        plain(base, cfg), ids[0], lora=plain(adapters, cfg), last=T,
        omit=(piece,), **ref_kwargs(cfg),
    )
    moved = max(rel_rms(broken, logits),
                abs(float(b_mtp) - float(mtp)) / float(mtp))
    assert moved > 1e-3, piece


def test_bf16_step_runs_finite_near_the_reference():
    """The cell's dtypes at toy widths: bf16 compute, the checkpointed
    groups, the flash kernels, the MTP module; the total loss near the
    float32 reference's and the adapters' gradients within bf16 of its
    (the MTP module's among them)."""
    cfg, base, adapters, ids = make(
        cfg=toy_config(dtype=jnp.bfloat16, remat=True)
    )
    step = decoder.make_lora_train_step(cfg, attn_fn=flash_attention)
    new, _, loss, counts = step(adapters, llama.init_adam(adapters), base, ids)
    assert counts.shape == (6, len(HELD) + 1)
    want, grads_want = ref.lora_gradients(
        plain(base, cfg), plain(adapters, cfg), ids[0], **ref_kwargs(cfg)
    )
    assert abs(float(loss) - float(want)) < 2e-2 * float(want)
    _, grads = jax.jit(jax.value_and_grad(
        lambda a: decoder.lora_loss(a, base, ids, cfg,
                                    attn_fn=flash_attention)[0]
    ))(adapters)
    off = norm = 0.0
    for got, want in zip(jax.tree_util.tree_leaves(plain(grads, cfg)),
                         jax.tree_util.tree_leaves(grads_want)):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        off, norm = off + np.sum((got - want) ** 2), norm + np.sum(want ** 2)
    assert off <= 0.1 ** 2 * norm
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), new, adapters
    )
    assert all(np.isfinite(v) for v in jax.tree_util.tree_leaves(moved))
    assert moved["mtp"]["w_eh"]["b"] > 0
    assert moved["layers"]["0"]["moe"]["w_lat_in"]["b"] > 0


def test_the_step_writes_its_records_while_armed():
    """While the recorder is armed: ``remat.saved`` prices what every
    group keeps (no ``ffn.up`` or ``layer.mid`` where a layer has no
    FFN; the shared expert's up product at its own width; the MTP
    module's group), ``moe.counts`` carries the latent, the widths, the
    activation and the form of the experts' LoRA bypass, and one
    ``mtp.loss`` record a step gives both terms and the weight;
    disarmed, nothing is kept."""
    cfg, base, adapters, ids = make(cfg=toy_config(remat=True))
    step = decoder.make_lora_train_step(cfg)
    opt = llama.init_adam(adapters)
    step(adapters, opt, base, ids)
    step.flush_routing()
    rec = telemetry.install(capacity=256)
    try:
        # a new step: its trace emits `remat.saved` while armed
        step = decoder.make_lora_train_step(cfg)
        _, _, loss, _ = step(adapters, opt, base, ids)
        step.flush_routing()
        records = {r.phase: r for r in rec.records()}
        mtp = [r for r in rec.records() if r.phase == "mtp.loss"]
    finally:
        telemetry.uninstall()
    saved = records["remat.saved"].detail
    assert saved["layers"] == {"layers0-2": 3, "layers3-3": 1,
                               "layers4-4": 1, "layers5-5": 1,
                               "layers6-6": 1}
    elem = T * 4
    mid = {"layer.mid": elem * D}
    routed = dict(mid, **{"ffn.up": elem * 24, "moe.selected": T * TOPK * 4})
    assert saved["bytes_per_layer"] == {
        "layers0-2": dict(routed, **{"ssm.in": elem * SSM.proj_dim}),
        "layers3-3": {"ssm.in": elem * SSM.proj_dim},
        "layers4-4": routed,
        "layers5-5": dict(routed, **{"ssm.in": elem * SSM.proj_dim}),
        "layers6-6": routed,
    }
    counts = records["moe.counts"].detail
    assert (counts["latent"], counts["d_ff"], counts["shared_d_ff"],
            counts["activation"]) == (8, 12, 24, "relu2")
    # four held experts of rank 2 fill no MXU: one block, the dense form
    assert (counts["lora_blocks"], counts["lora_block_experts"]) == (1, 64)
    assert [row["layer"] for row in counts["layers"]] == [0, 1, 2, 4, 5, 6]
    (record,) = mtp
    detail = record.detail
    assert detail["loss_weight"] == 0.1 and detail["tokens"] == T
    assert detail["total"] == pytest.approx(float(loss), rel=1e-6)
    assert detail["total"] == pytest.approx(
        detail["main"] + 0.1 * detail["mtp"]
    )


def test_a_model_without_mtp_or_latent_keeps_its_records():
    """Trinity's kind of expert layer writes ``moe.counts`` without the
    new widths and no ``mtp.loss``; its step's outputs are as before, and
    its experts' LoRA bypass is the dense form (two held experts of rank
    8 are 16 of the MXU's 128 columns)."""
    experts = moe.ExpertShareConfig(num_experts=8, held=(0, 1), top_k=2,
                                    d_model=D, d_ff=16)
    cfg = decoder.DecoderConfig(
        layers=(decoder.LayerSpec("full", "moe"),), vocab_size=VOCAB,
        hidden_size=D, num_heads=HEADS, num_kv_heads=KV, head_dim=DH,
        experts=experts, param_dtype=jnp.float32, dtype=jnp.float32,
    )
    base = decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    adapters = lora.init_lora(jax.random.PRNGKey(1), base,
                              lora.LoraConfig(targets=decoder.ALL_LINEAR))
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, T), 0, VOCAB)
    rec = telemetry.install(capacity=64)
    try:
        step = decoder.make_lora_train_step(cfg)
        out = step(adapters, llama.init_adam(adapters), base, ids)
        step.flush_routing()
        phases = [r.phase for r in rec.records()]
        (counts,) = [r.detail for r in rec.records() if r.phase == "moe.counts"]
    finally:
        telemetry.uninstall()
    assert len(out) == 4 and "mtp.loss" not in phases
    assert not {"latent", "shared_d_ff", "activation"} & set(counts)
    assert (counts["lora_blocks"], counts["lora_block_experts"]) == (1, 16)
    assert "w_gate" in base["layers"][0]["moe"]["experts"]
    assert "w_lat_in" not in base["layers"][0]["moe"]
