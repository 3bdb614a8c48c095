"""The ``minicpm_sala`` block at toy widths on the CPU: InfLLM-v2
block-sparse attention (``LayerSpec("sparse")``, ``ops/sparse_attention.py``)
and decayed linear attention (``LayerSpec("lightning")``, ``ops/ssd.py``
with one group a head) against the plain reference
(``benchmark/reference/minicpm_sala.py``): logits, loss and every
adapter's gradient; the sparse layer at a length it attends densely; the
sparse kernels against masked attention over random selections and, given
every causal block, against the flash kernel; the scan with one group a
head against the recurrence; the system's selection against the
reference's.

The toy keeps the published pattern's first period (``minicpm4``, three
``lightning-attn``) and the InfLLM-v2 sizes scaled down so that its 96
tokens lie beyond ``dense_len`` (compressed keys of 8 at a stride of 4,
blocks of 8, 5 of 12 blocks a query, 1 init block, 8 local tokens,
``dense_len`` 32, tiles of 16)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import minicpm_sala as ref
from rayfed_tpu import telemetry
from rayfed_tpu.models import decoder, llama, lora
from rayfed_tpu.ops import sparse_attention as sa
from rayfed_tpu.ops import ssd
from rayfed_tpu.ops.attention import dot_product_attention
from rayfed_tpu.ops.flash_attention import flash_attention
from tests.test_kimi_k2 import _trained, rel_rms

D, T, VOCAB, FFN = 32, 96, 64, 64
HEADS, KV, DH = 4, 2, 8
SPARSE = sa.SparseConfig(kernel_size=8, kernel_stride=4, block_size=8, topk=5,
                         init_blocks=1, window_size=8, dense_len=32, tile=16,
                         select_chunk=32)
LIGHTNING = decoder.LightningConfig(depth=32, chunk=16)
KINDS = ("minicpm4", "lightning-attn", "lightning-attn", "lightning-attn")
MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
MULTIPLIERS = dict(embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
                   logit_scale=1 / 16)


def toy_config(dtype=jnp.float32, **kw):
    return decoder.DecoderConfig(
        layers=tuple(decoder.LayerSpec(MIXERS[k]) for k in KINDS),
        vocab_size=VOCAB, hidden_size=D, num_heads=HEADS, num_kv_heads=KV,
        head_dim=DH, intermediate_size=FFN, rms_eps=1e-6, sparse=SPARSE,
        lightning=LIGHTNING, qk_norm=True, output_gate=True,
        post_norms=False, dtype=dtype, param_dtype=jnp.float32,
        **MULTIPLIERS, **kw,
    )


def ref_kwargs(cfg, **kw):
    s = cfg.sparse
    return dict(
        mixer_types=KINDS, scale_emb=cfg.embed_scale,
        residual_scale=cfg.residual_scale, rms_eps=cfg.rms_eps,
        logit_scale=cfg.logit_scale,
        attn=dict(num_heads=HEADS, num_kv_heads=KV, head_dim=DH),
        sparse=dict(kernel_size=s.kernel_size, kernel_stride=s.kernel_stride,
                    block_size=s.block_size, topk=s.topk,
                    init_blocks=s.init_blocks, window_size=s.window_size,
                    dense_len=s.dense_len),
        lightning=dict(depth=LIGHTNING.depth, rope_theta=cfg.rope_theta), **kw,
    )


def make(seed=0, cfg=None, t=T):
    """(config, base, adapters with a non-zero B, ids)."""
    cfg = cfg or toy_config()
    base = decoder.init_decoder(jax.random.PRNGKey(seed), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(seed + 1), base,
        lora.LoraConfig(rank=2, alpha=4.0, targets=decoder.ALL_LINEAR),
    )
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (1, t), 0, VOCAB)
    return cfg, base, _trained(adapters, seed + 2), ids


# -- the configuration ---------------------------------------------------


def test_two_groups_and_the_parameters_of_each_kind():
    cfg, base, _, _ = make()
    assert cfg.groups() == ((0, 1), (1, 4))
    sparse_layer, lightning = base["layers"]
    assert sparse_layer["wk"].shape == (1, D, KV * DH)  # grouped K/V
    assert lightning["wk"].shape == (3, D, HEADS * DH)  # a head each
    assert lightning["o_norm"].shape == (3, HEADS * DH)
    assert "o_norm" not in sparse_layer and "decay" not in sparse_layer
    # MiniMax-01's slopes times the depth factor of the published index
    want = np.stack([ref.decays(i, HEADS, 32) for i in (1, 2, 3)])
    np.testing.assert_allclose(np.exp(-np.asarray(lightning["decay"])), want,
                               rtol=1e-6)
    with pytest.raises(ValueError, match="sparse"):
        dataclasses.replace(cfg, sparse=None)
    with pytest.raises(ValueError, match="power of two"):
        decoder.LightningConfig().decay_rates(0, 6)


def test_adapters_at_the_published_shapes_count_what_the_cell_states():
    """Rank 8 on every linear matrix: 757,760 in the sparse layer,
    819,200 in each linear-attention layer, 3,215,360 in all."""
    cfg = dataclasses.replace(
        toy_config(), vocab_size=73448, hidden_size=4096, num_heads=32,
        num_kv_heads=2, head_dim=128, intermediate_size=16384,
        sparse=sa.SparseConfig(), lightning=decoder.LightningConfig(),
    )
    shapes = jax.eval_shape(lambda: decoder.init_decoder(jax.random.PRNGKey(0), cfg))
    adapters = jax.eval_shape(lambda: lora.init_lora(
        jax.random.PRNGKey(1), shapes, lora.LoraConfig(
            rank=8, alpha=16.0, targets=(r"/w[qkvoz]$", r"/w_(gate|up|down)$")
        )))
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree)
                             if x.ndim)
    assert count(adapters["layers"]["0"]) == 757_760
    assert count(adapters["layers"]["1"]) == 3 * 819_200
    assert count(adapters) == 3_215_360
    assert count(shapes) == 1_711_129_696


# -- the pieces ------------------------------------------------------------


def _random_selection(key, t, kv, block, topk):
    """Random causal blocks a query, its own block always among them
    (the local window's): ``[1, KV, T, topk]``, -1 where fewer exist."""
    nblocks = t // block
    score = jax.random.uniform(key, (1, kv, t, nblocks))
    own = jnp.arange(t)[:, None] // block
    blocks = jnp.arange(nblocks)[None, :]
    score = jnp.where(blocks == own, 2.0, score)
    score = jnp.where(blocks <= own, score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, topk)
    return jnp.where(vals > -jnp.inf, idx, -1).astype(jnp.int32)


def _qkv(seed, t, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (1, t, HEADS, DH), dtype),
            jax.random.normal(k[1], (1, t, KV, DH), dtype),
            jax.random.normal(k[2], (1, t, KV, DH), dtype),
            jax.random.normal(k[3], (1, t, HEADS, DH)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_sparse_kernels_are_masked_attention_over_random_selections(seed):
    """Forward and the three gradients of the interpreted kernels against
    the reference's attention over every key masked to each query's own
    blocks, over selections that differ from query to query within a
    tile."""
    q, k, v, w = _qkv(seed, T)
    sel = _random_selection(jax.random.PRNGKey(seed + 9), T, KV, 8, 4)
    arrays = sa.selection_arrays(sel, T, SPARSE)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    kernel = lambda q, k, v: sa.sparse_attention(q, k, v, arrays, SPARSE)
    masked = lambda q, k, v: ref.sparse_attention(
        q[0], k[0], v[0], sel[0], block_size=8)[None]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(q, k, v), masked(q, k, v),
                                   atol=2e-6)
        got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(masked), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert rel_rms(g, r) < 1e-6


def test_every_causal_block_selected_is_the_flash_kernel():
    q, k, v, w = _qkv(5, T)
    nblocks = T // 8
    every = jnp.broadcast_to(
        jnp.arange(nblocks, dtype=jnp.int32), (1, KV, T, nblocks)
    )
    every = jnp.where(every <= (jnp.arange(T) // 8)[:, None], every, -1)
    config = dataclasses.replace(SPARSE, topk=nblocks)
    arrays = sa.selection_arrays(every, T, config)
    assert int(arrays[1][2][0]) == sa.causal_pairs(T // 16)  # every pair
    kernel = lambda q, k, v: jnp.sum(
        sa.sparse_attention(q, k, v, arrays, config) * w)
    flash = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=16, block_k=16) * w)
    np.testing.assert_allclose(
        sa.sparse_attention(q, k, v, arrays, config),
        flash_attention(q, k, v, causal=True), atol=2e-6,
    )
    for g, r in zip(jax.grad(kernel, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(flash, argnums=(0, 1, 2))(q, k, v)):
        assert rel_rms(g, r) < 1e-6


def test_the_tile_is_cut_to_a_shorter_sequence():
    """The kernel's tile is the configuration's, or the whole sequence
    where that is shorter; a tile of no whole blocks, of more blocks
    than a word holds, or a sequence of no whole tiles is refused."""
    assert sa.SparseConfig().tile_for(24576) == 512
    assert sa.SparseConfig().tile_for(256) == 256
    assert SPARSE.tile_for(T) == 16
    for config, t in ((sa.SparseConfig(), 24576 + 64),
                      (dataclasses.replace(SPARSE, tile=12), T),
                      (sa.SparseConfig(block_size=16), 1024)):
        with pytest.raises(ValueError, match="whole"):
            config.tile_for(t)


def test_the_visit_lists_walk_each_tiles_union_in_order():
    """Every (query tile, key tile) pair some token of the query tile
    selected a block of, by query tile then key tile (and the other way
    round for dK/dV), padded with the last pair; a word's bit per
    selected block of its key tile."""
    sel = jnp.full((1, 1, 48, 2), -1, jnp.int32)
    sel = sel.at[0, 0, :, 0].set(jnp.arange(48) // 8)  # its own block
    sel = sel.at[0, 0, 40, 1].set(1)  # tile 2 reaches back to tile 0
    config = dataclasses.replace(SPARSE, topk=2)
    words = sa.block_words(sel, 48, config)
    assert words.shape == (1, 1, 48, 3)
    assert float(words[0, 0, 40, 0]) == 2.0  # block 1 = bit 1 of tile 0
    assert float(words[0, 0, 40, 2]) == 2.0  # its own block 5 = bit 1
    (fq, fk, count), (tq, tk) = sa.visit_lists(words)
    assert int(count[0]) == 4
    assert fq[0].tolist() == [0, 1, 2, 2, 2, 2]
    assert fk[0].tolist() == [0, 1, 0, 2, 2, 2]
    assert tk[0].tolist() == [0, 0, 1, 2, 2, 2]
    assert tq[0].tolist() == [0, 2, 1, 2, 2, 2]


def _top_k_set(score, k):
    """``[..., N]`` bool: the blocks ``jax.lax.top_k`` returns with a
    value above ``-inf``, the set the threshold's mask is held to."""
    vals, idx = jax.lax.top_k(score, k)
    hit = (idx[..., None] == jnp.arange(score.shape[-1])) & (
        vals > -jnp.inf)[..., None]
    return hit.any(-2)


def _scores(case, rows=256, n=40):
    """Block scores ``[rows, n]`` of one kind: ``top_k``'s tie rule and
    the edges of the selection (forced and non-causal blocks, rows with
    fewer causal blocks than ``k``)."""
    rng = np.random.default_rng(sum(map(ord, case)))
    s = rng.uniform(size=(rows, n)).astype(np.float32)
    if case == "levels":  # a few values: ties at the threshold
        s = np.round(s * 4) / 4
    elif case == "equal":
        s = np.full_like(s, 0.25)
    elif case == "forced":  # +inf forced, -inf after the query's block
        s[rng.uniform(size=s.shape) < 0.2] = np.inf
        s[rng.uniform(size=s.shape) < 0.4] = -np.inf
    elif case == "short":  # most rows with fewer than k blocks above -inf
        s = np.round(s * 2) / 2
        causal = rng.integers(0, n + 1, size=(rows, 1))
        s = np.where(np.arange(n) < causal, s, -np.inf).astype(np.float32)
    elif case == "signs":  # negatives, both zeros, infinities
        s = rng.choice(np.float32([-np.inf, -2.5, -1.0, -0.0, 0.0, 1e-30,
                                   1.0, 3.0, np.inf]), size=s.shape)
    return jnp.asarray(s)


@pytest.mark.parametrize(
    "case", ["uniform", "levels", "equal", "forced", "short", "signs"])
@pytest.mark.parametrize("k", [1, 7, 39, 40])
def test_the_top_k_mask_is_top_ks_set(case, k):
    """The threshold's mask is the set ``jax.lax.top_k`` returns, block
    for block, and ``tied`` marks the rows where a block equal to the
    threshold was left out."""
    score = _scores(case)
    mask, tied = jax.jit(sa.top_mask, static_argnums=1)(score, k)
    np.testing.assert_array_equal(mask, _top_k_set(score, k))
    key = np.asarray(score)
    kth = -np.sort(-key, axis=-1)[:, k - 1]  # the k-th largest score
    selected = np.asarray(mask)
    left_out_tie = ((key == kth[:, None]) & ~selected).any(-1) & (
        kth > -np.inf)
    np.testing.assert_array_equal(tied, left_out_tie)
    if case == "equal" and k < score.shape[-1]:
        assert np.asarray(tied).all()


def _top_k_selection(q, k, config):
    """``[B, KV, T, topk]``: the selection by ``jax.lax.top_k`` over
    :func:`sa.block_scores` (-1 where fewer causal blocks exist)."""
    score = sa.block_scores(q, k, config)
    vals, idx = jax.lax.top_k(score, min(config.topk, score.shape[-1]))
    sel = jnp.where(vals > -jnp.inf, idx, -1)
    b, kv, t = k.shape[0], k.shape[2], q.shape[1]
    return sel.transpose(1, 2, 0, 3, 4).reshape(b, kv, -1, sel.shape[-1])[
        :, :, :t]


def _words_from_indices(sel, t, config):
    """The words from block indices by a compare and a sum over the
    choices: the reference the words from a mask are held to."""
    tile = config.tile_for(t)
    per_tile = tile // config.block_size
    tiles = jnp.arange(t // tile, dtype=jnp.int32)
    of = jnp.where(sel >= 0, sel // per_tile, -1)[..., None]
    bit = jnp.left_shift(1, jnp.maximum(sel, 0) % per_tile)[..., None]
    return jnp.sum(jnp.where(of == tiles, bit, 0), axis=-2).astype(jnp.float32)


@pytest.mark.parametrize("case", [
    "zeros", "random", "more-topk-than-blocks", "chunk-not-whole"])
def test_the_selection_is_top_ks_and_its_words_the_same_bits(case):
    """``select_mask`` and ``select_blocks`` choose the sets that
    ``jax.lax.top_k`` does over the same scores (on zero queries and
    keys every score ties), and the kernels' words and visit lists made
    from the mask equal, bit for bit, those made from the indices."""
    config = {
        "more-topk-than-blocks": dataclasses.replace(SPARSE, topk=20),
        "chunk-not-whole": dataclasses.replace(SPARSE, select_chunk=36),
    }.get(case, SPARSE)
    q, k, _, _ = _qkv(11, T)
    if case == "zeros":
        q, k = jnp.zeros_like(q), jnp.zeros_like(k)
    want = _top_k_selection(q, k, config)
    mask, tied = sa.select_mask(q, k, config)
    assert mask.shape == (1, KV, T, T // 8) and tied.shape == (1, KV, T)
    np.testing.assert_array_equal(mask, sa.as_mask(want, T, 8))
    got = sa.select_blocks(q, k, config)
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
    last = np.where(got < 0, T, got)  # ascending, the -1s after
    np.testing.assert_array_equal(last, np.sort(last, -1))
    if case == "zeros":
        assert np.asarray(tied).any()
    words = sa.block_words(mask, T, config)
    np.testing.assert_array_equal(words, _words_from_indices(want, T, config))
    from_mask = sa.selection_arrays(mask, T, config)
    from_indices = sa.selection_arrays(want, T, config)
    for a, b in zip(jax.tree_util.tree_leaves(from_mask),
                    jax.tree_util.tree_leaves(from_indices)):
        np.testing.assert_array_equal(a, b)


def test_the_sparse_layer_sorts_no_blocks():
    """Compiled, the toy sparse layer's forward ranks no row of block
    scores: no sort over the blocks and no ``TopK``; the visit lists'
    argsort over the 6 x 6 tile pairs stays."""
    cfg, base, _, _ = make()
    lp = jax.tree_util.tree_map(lambda a: a[0], base["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, D))
    hlo = jax.jit(lambda x, lp: decoder.apply_block(
        x, lp, cfg, ffn="dense", mixer="sparse", attn_fn=flash_attention
    )).lower(x, lp).compile().as_text()
    sorts = [line.split(" sort(")[0] for line in hlo.splitlines()
             if " sort(" in line]
    nblocks, pairs = T // SPARSE.block_size, (T // SPARSE.tile_for(T)) ** 2
    assert sorts and all(f",{pairs}]" in out for out in sorts), sorts
    assert not any(f",{nblocks}]" in out for out in sorts)
    assert "TopK" not in hlo


@pytest.mark.parametrize("decay", [0.3, 0.0])
def test_the_scan_with_a_group_a_head_is_the_recurrence(decay):
    """``ssd_scan`` as the linear attention calls it (``dt = 1``, ``A``
    constant, ``B = k``, ``C = q``, ``D = 0``, one group a head) against
    the reference's token-by-token recurrence, forward and the three
    gradients; a decay rate of 0 (``lambda = 1``) carries the state whole."""
    t, h, p = 40, 4, 8
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    q, kk, v, w = (jax.random.normal(k[i], (1, t, h, p)) for i in range(4))
    rates = jnp.asarray([decay, 2 * decay, 0.01, decay], jnp.float32)

    def scan(q, k, v):
        return ssd.ssd_scan(v, jnp.ones((1, t, h)), -rates, k, q,
                            jnp.zeros((h,)), chunk=16)

    def recurrence(q, k, v):
        return ref.recurrence(q[0], k[0], v[0], jnp.exp(-rates))[None]

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(scan(q, kk, v), recurrence(q, kk, v),
                                   rtol=1e-5, atol=1e-4)
        loss = lambda fn: lambda *a: jnp.sum(fn(*a) * w)
        got = jax.grad(loss(scan), argnums=(0, 1, 2))(q, kk, v)
        want = jax.grad(loss(recurrence), argnums=(0, 1, 2))(q, kk, v)
    for g, r in zip(got, want):
        assert rel_rms(g, r) < 1e-5


def test_a_block_of_one_head_tiles_where_a_group_is_one_head(monkeypatch):
    """Compiled, one group a head takes blocks of one head (``l`` read
    from the token rows), and its lanes of ``x`` must fill 128."""
    assert ssd.head_block(32, 32, 128, 128, 256, 2, False) == 1
    with pytest.raises(ValueError, match="128-lane"):
        ssd.head_block(32, 32, 64, 128, 256, 2, False)
    monkeypatch.setattr(ssd._flash, "_interpret_default", lambda: False)
    args = (jnp.zeros((1, 256, 4, 64), jnp.bfloat16), jnp.ones((1, 256, 4)),
            -jnp.ones((4,)), jnp.zeros((1, 256, 4, 128), jnp.bfloat16),
            jnp.zeros((1, 256, 4, 128), jnp.bfloat16), jnp.zeros((4,)))
    with pytest.raises(ValueError, match="of 128 with one group a head"):
        jax.eval_shape(lambda *a: ssd.ssd_scan(*a, chunk=256), *args)


# -- the whole model against the reference ---------------------------------


def test_up_to_dense_len_the_sparse_layer_is_causal_attention():
    """At ``T <= dense_len`` the sparse layer is plain causal attention
    without positions: the same layer as a ``full`` one, bit for bit,
    and no selection is reported."""
    cfg, base, _, _ = make()
    lp = jax.tree_util.tree_map(lambda a: a[0], base["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, D))
    got, aux = decoder.apply_block(x, lp, cfg, ffn="dense", mixer="sparse",
                                   attn_fn=flash_attention)
    want, _ = decoder.apply_block(x, lp, cfg, ffn="dense", mixer="full",
                                  attn_fn=flash_attention)
    assert aux is None
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("remat", [False, True])
def test_float32_system_matches_the_reference(remat):
    """Logits, the step's loss and the gradient of every adapter leaf,
    float32 on both sides: 1e-4 relative (the chunked scan and the
    tiled kernels sum in another order than the reference's token by
    token recurrence and its masked attention over every key); every
    mistake in the mathematics is of order one (below).  The system's
    selection is the reference's, choice for choice."""
    cfg, base, adapters, ids = make(cfg=toy_config(remat=remat))
    kw = ref_kwargs(cfg)
    logits, aux = decoder.apply_decoder(base, ids, cfg, lora=adapters,
                                        attn_fn=flash_attention)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda a: decoder.lora_loss(a, base, ids, cfg,
                                    attn_fn=flash_attention)[0]
    ))(adapters)
    plain_base = decoder.unstack(base, cfg)
    plain_adapters = decoder.unstack(adapters, cfg)
    with jax.default_matmul_precision("highest"):
        want_logits, chosen = ref.forward(plain_base, ids[0],
                                          lora=plain_adapters, **kw)
        want_loss, want_grads = ref.lora_gradients(
            plain_base, plain_adapters, ids[0], **kw
        )
    assert rel_rms(logits[0], want_logits) < 1e-4
    assert abs(float(loss) - float(want_loss)) < 1e-4 * float(want_loss)
    assert ref.selection_agreement(aux[0]["selected"][0], chosen[0]) == 1.0
    # the same sets: the system lists a query's blocks in ascending order
    np.testing.assert_array_equal(np.sort(aux[0]["selected"][0], -1),
                                  np.sort(chosen[0], -1))
    flat_got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) == 4 * 8 * 3
    for (path, got), want in zip(flat_got, flat_want):
        if path[-1].key == "scale":
            continue  # the system holds it constant (stop_gradient)
        assert float(jnp.abs(want).max()) > 0, path
        assert rel_rms(got, want) < 1e-4, path


@pytest.mark.parametrize("piece", ref.PIECES)
def test_the_comparison_notices_every_piece_of_the_mathematics(piece):
    """The reference with one piece left out or broken is another
    function: the logits move by far more than the 1e-4 the agreement is
    held to, or (a change to the selection alone) the selection differs."""
    cfg, base, adapters, ids = make()
    logits, aux = decoder.apply_decoder(base, ids, cfg, lora=adapters,
                                        attn_fn=flash_attention)
    with jax.default_matmul_precision("highest"):
        got, chosen = ref.forward(
            decoder.unstack(base, cfg), ids[0],
            lora=decoder.unstack(adapters, cfg), omit=(piece,),
            **ref_kwargs(cfg),
        )
    agree = ref.selection_agreement(aux[0]["selected"][0], chosen[0])
    assert rel_rms(logits[0], got) > 1e-3 or agree < 0.99, piece


def test_bf16_step_runs_and_records_the_selection(monkeypatch):
    """The cell's dtypes at toy widths: bf16 through the checkpointed
    groups, the step's loss near the float32 reference's; armed, the
    step writes one ``attn.select`` record a call (the keys and blocks
    a query visited) and the ``remat.saved`` record prices the
    selection's arrays; no expert layer, so no routing counts."""
    cfg, base, adapters, ids = make(cfg=toy_config(jnp.bfloat16, remat=True))
    step = decoder.make_lora_train_step(cfg, attn_fn=flash_attention)
    rec = telemetry.install(capacity=256)
    try:
        new, _, loss, counts = step(adapters, llama.init_adam(adapters),
                                    base, ids)
        step.flush_routing()
        records = rec.records()
    finally:
        telemetry.uninstall()
    assert counts is None
    with jax.default_matmul_precision("highest"):
        want = ref.loss(decoder.unstack(base, cfg), ids[0],
                        lora=decoder.unstack(adapters, cfg), **ref_kwargs(cfg))
    assert abs(float(loss) - float(want)) < 2e-2 * float(want)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(new))
    (select,) = [r for r in records if r.phase == "attn.select"]
    (layer,) = select.detail["layers"]
    assert layer["layer"] == 0 and 0 < layer["blocks"] <= SPARSE.topk
    visited, blocks = sa.visit_stats(
        sa.select_blocks(jnp.zeros((1, T, HEADS, DH)), jnp.zeros((1, T, KV, DH)),
                         SPARSE), SPARSE.block_size)
    assert 0 < layer["visited_keys"] < select.detail["dense_keys"] == (T + 1) / 2
    # every query tile walks itself at least; 21 causal pairs of 6 tiles
    assert 6 <= layer["pairs"] <= select.detail["causal_pairs"] == 21
    assert float(blocks) == pytest.approx(layer["blocks"])  # forced + causal
    (saved,) = [r for r in records if r.phase == "remat.saved"]
    assert saved.detail["bytes_per_layer"]["layers0-0"]["attn.selected"] == (
        sa.selection_bytes(1, T, KV, SPARSE)
    )
    assert "attn.selected" not in saved.detail["bytes_per_layer"]["layers1-3"]
    assert any(r.phase == "attn.sparse" for r in records)


def test_the_select_record_counts_the_tie_break_and_the_short_rows():
    """Zero queries in the sparse layer (its ``q_norm`` zeroed) make
    every causal block's score the same: the block index decides the
    rows with more causal blocks than ``topk``, and the armed step's
    ``attn.select`` record says so (``tie_rows`` above 0); the first 32
    of the 96 queries have fewer than 5 causal blocks of 8 tokens
    (``short_rows`` a third); 32 passes find a threshold and 4 more the
    tie among 12 blocks."""
    cfg, base, adapters, ids = make()
    layers = [dict(base["layers"][0],
                   q_norm=jnp.zeros_like(base["layers"][0]["q_norm"])),
              *base["layers"][1:]]
    base = dict(base, layers=layers)
    step = decoder.make_lora_train_step(cfg, attn_fn=flash_attention)
    rec = telemetry.install(capacity=64)
    try:
        step(adapters, llama.init_adam(adapters), base, ids)
        step.flush_routing()
        records = rec.records()
    finally:
        telemetry.uninstall()
    (select,) = [r for r in records if r.phase == "attn.select"]
    assert select.detail["threshold_passes"] == 32 + 4
    (layer,) = select.detail["layers"]
    assert 0 < layer["tie_rows"] <= 1 - layer["short_rows"]
    assert layer["short_rows"] == pytest.approx(32 / 96)


def test_a_dense_attention_fn_gives_the_dense_branch_and_the_kernels_the_rest():
    """``attn_fn`` reaches the sparse layer only where it attends densely;
    the float32 model with dense attention there is the flash one's."""
    cfg, base, adapters, _ = make()
    ids = jax.random.randint(jax.random.PRNGKey(9), (1, 32), 0, VOCAB)
    a, _ = decoder.apply_decoder(base, ids, cfg, lora=adapters,
                                 attn_fn=dot_product_attention)
    b, _ = decoder.apply_decoder(base, ids, cfg, lora=adapters,
                                 attn_fn=flash_attention)
    assert rel_rms(a, b) < 1e-5
