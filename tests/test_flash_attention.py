"""Pallas flash attention vs dense reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu.ops import dot_product_attention
from rayfed_tpu.ops.flash_attention import flash_attention


def _qkv(key, b=2, t=64, h=2, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, t, h, d), dtype),
        jax.random.normal(kk, (b, t, h, d), dtype),
        jax.random.normal(kv, (b, t, h, d), dtype),
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    expected = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


def test_flash_single_block():
    q, k, v = _qkv(jax.random.PRNGKey(1), t=32)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    expected = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients(causal):
    q, k, v = _qkv(jax.random.PRNGKey(2), t=32, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=8, block_k=8) ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)


def test_flash_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(3), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    expected = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), expected.astype(np.float32), atol=3e-2, rtol=3e-2
    )


def test_flash_jit_and_shape_check():
    q, k, v = _qkv(jax.random.PRNGKey(4), t=48)
    jitted = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16)
    )
    out = jitted(q, k, v)
    assert out.shape == q.shape
    # Non-dividing block sizes auto-shrink to a divisor instead of
    # raising (T=48 with block 13 → largest fitting block).
    out2 = flash_attention(q, k, v, block_q=13, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out2), np.asarray(out), atol=2e-5, rtol=2e-5
    )


def test_flash_block_autofit_nonmultiple_t():
    """T=1280 is a multiple of 128 but not of the 1024 default blocks —
    must run (shrunken block), not raise (round-2 regression guard)."""
    q, k, v = _qkv(jax.random.PRNGKey(11), t=1280, h=1)
    out = flash_attention(q, k, v, causal=True)
    expected = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), atol=2e-2, rtol=2e-2
    )


def test_flash_offsets_match_dense():
    q, k, v = _qkv(jax.random.PRNGKey(5), t=16)
    out = flash_attention(
        q, k, v, causal=True, q_offset=16, kv_offset=0, block_q=8, block_k=8
    )
    expected = dot_product_attention(q, k, v, causal=True, q_offset=16, kv_offset=0)
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)
    # Fully-future kv block: all rows masked -> zeros, not NaN.
    out2 = flash_attention(
        q, k, v, causal=True, q_offset=0, kv_offset=100, block_q=8, block_k=8
    )
    np.testing.assert_allclose(out2, np.zeros_like(out2))


def test_flash_rejects_dense_mask():
    q, k, v = _qkv(jax.random.PRNGKey(6), t=16)
    with pytest.raises(ValueError, match="mask"):
        flash_attention(q, k, v, mask=jnp.ones((1, 1, 16, 16), bool))


def test_flash_offset_gradients():
    q, k, v = _qkv(jax.random.PRNGKey(7), t=16, d=8)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, q_offset=16, block_q=8, block_k=8
            ) ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(
            dot_product_attention(q, k, v, causal=True, q_offset=16) ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)


def test_flash_partially_masked_rows():
    """kv block overlaps some q rows but not others: fully-masked rows must
    be exactly zero (fwd) with zero grads (bwd), not mean-of-V / sum-of-dO."""
    q, k, v = _qkv(jax.random.PRNGKey(8), t=16, h=1, d=8)
    # q rows 0..7 see no keys (kv starts at global pos 8); rows 8..15 do.
    out = flash_attention(
        q, k, v, causal=True, q_offset=0, kv_offset=8, block_q=16, block_k=16
    )
    expected = dot_product_attention(q, k, v, causal=True, q_offset=0, kv_offset=8)
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[:, :8], np.zeros_like(out[:, :8]), atol=1e-6)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, q_offset=0, kv_offset=8,
                block_q=16, block_k=16,
            ) ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(
            dot_product_attention(q, k, v, causal=True, q_offset=0, kv_offset=8) ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)
        assert not np.any(np.isnan(gf))


def _band_mask(t, window):
    q_pos = np.arange(t)[:, None]
    k_pos = np.arange(t)[None, :]
    return jnp.asarray((q_pos >= k_pos) & (q_pos - k_pos < window))


@pytest.mark.parametrize("window", [1, 8, 24])
def test_flash_window_matches_dense(window):
    """Sliding-window attention equals dense attention under the same
    band mask — including the window=1 (self-only) edge."""
    q, k, v = _qkv(jax.random.PRNGKey(7))
    t = q.shape[1]
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=16, block_k=16
    )
    expected = dot_product_attention(
        q, k, v, mask=_band_mask(t, window)[None, None]
    )
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


def test_flash_window_gradients():
    q, k, v = _qkv(jax.random.PRNGKey(8), t=48)
    t, window = q.shape[1], 12

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, window=window, block_q=16, block_k=16
            )
            ** 2
        )

    def loss_dense(q, k, v):
        return jnp.sum(
            dot_product_attention(q, k, v, mask=_band_mask(t, window)[None, None])
            ** 2
        )

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)


def test_flash_window_validation():
    q, k, v = _qkv(jax.random.PRNGKey(9), t=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)


# --- grouped K/V, the band walk, sub-tiled edges, the schedule, the size budget ---

import functools
import importlib

from rayfed_tpu import telemetry

fa = importlib.import_module("rayfed_tpu.ops.flash_attention")


def _grouped_qkv(key, t_q, t_k, h, kv, d=8, b=1):
    kq, kk, kv_ = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, t_q, h, d), jnp.float32),
        jax.random.normal(kk, (b, t_k, kv, d), jnp.float32),
        jax.random.normal(kv_, (b, t_k, kv, d), jnp.float32),
    )


# name -> (t_q, t_k, block, keywords).  Blocks of 16 are cut into
# sub-tiles of 8 (SPLIT 2), so the windows below are smaller than a
# sub-tile, equal to one, not a multiple of one, and larger than the
# sequence; 96 tokens shrink a block of 64 to 48 (sub-tiles of 24), 48
# tokens shrink a block of 32 to 24 (which no aligned cut divides).
GROUPED_CASES = {
    "no_window": (64, 64, 16, {}),
    "window_below_sub_tile": (64, 64, 16, dict(window=3)),
    "window_is_sub_tile": (64, 64, 16, dict(window=8)),
    "window_not_a_multiple": (64, 64, 16, dict(window=13)),
    "window_beyond_sequence": (64, 64, 16, dict(window=1000)),
    "unequal_offsets": (64, 64, 16, dict(window=13, q_offset=24, kv_offset=5)),
    "keys_partly_future": (32, 32, 16, dict(q_offset=3, kv_offset=10)),
    "t_q_less_than_t_k": (32, 64, 16, dict(window=20, q_offset=32)),
    "fit_block_shrunk_96": (96, 96, 64, dict(window=30)),
    "fit_block_shrunk_48": (48, 48, 32, dict(window=7)),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
@pytest.mark.parametrize("group", [1, 4, 8])
def test_flash_grouped_kv_matches_dense_on_repeated(group, case):
    """Forward and all three gradients on grouped K/V ``[B, T, KV, D]``
    equal dense attention on K/V explicitly repeated to the query heads
    (so dK/dV are the group's sums), across the band's edge cases."""
    t_q, t_k, block, kw = GROUPED_CASES[case]
    q, k, v = _grouped_qkv(jax.random.PRNGKey(group), t_q, t_k, 8, 8 // group)

    def flash(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block, **kw
        )

    def dense(q, k, v):
        rep = lambda x: jnp.repeat(x, group, axis=2)
        return dot_product_attention(q, rep(k), rep(v), causal=True, **kw)

    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=1e-5, rtol=1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(99), q.shape)
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * weight)
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for gf, gd, like in zip(g_flash, g_dense, (q, k, v)):
        assert gf.shape == like.shape
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)


@pytest.fixture()
def split(request, monkeypatch):
    """``fa.SPLIT`` set for one test.  The kernels' own ``jax.jit`` keys on
    their static arguments, not on the constant: drop what it holds."""
    monkeypatch.setattr(fa, "SPLIT", request.param)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


@pytest.mark.parametrize("split", [1, 2, 4], indirect=True)
def test_flash_every_split_gives_the_same(split):
    """The cut changes which pairs sit in a masked tile, never a result:
    blocks of 32 whole, in sub-tiles of 16, and of 8."""
    q, k, v = _grouped_qkv(jax.random.PRNGKey(5), 64, 64, 4, 2)
    kw = dict(causal=True, window=21, q_offset=7)

    def loss(f, **blocks):
        return lambda q, k, v: jnp.sum(f(q, k, v, **kw, **blocks) ** 2)

    got = jax.value_and_grad(
        loss(fa.flash_attention, block_q=32, block_k=32), argnums=(0, 1, 2)
    )(q, k, v)
    want = jax.value_and_grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_flash_grouped_kv_validation():
    q, k, v = _grouped_qkv(jax.random.PRNGKey(0), 16, 16, 4, 3)
    with pytest.raises(ValueError, match="divide"):
        fa.flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="agree"):
        fa.flash_attention(q, k[:, :, :2], v[:, :, :1], causal=True)
    with pytest.raises(ValueError, match="divide"):
        dot_product_attention(q, k, v, causal=True)


def _visible_pairs(t_q, t_k, causal, window, q_offset, kv_offset):
    """Brute force: the boolean matrix of visible (query, key) pairs."""
    q = q_offset + np.arange(t_q)[:, None]
    k = kv_offset + np.arange(t_k)[None, :]
    if not causal:
        return np.ones((t_q, t_k), bool)
    seen = q >= k
    return seen if window is None else seen & (q - k < window)


# (t_q, t_k, block_q, block_k, sub, causal, window, q_offset, kv_offset)
SCHEDULE_CASES = {
    "trinity_2x2": (8192, 8192, 1024, 1024, 2, True, 2048, 0, 0),
    "trinity_4x4": (8192, 8192, 2048, 2048, 4, True, 2048, 0, 0),
    "trinity_unsplit": (8192, 8192, 1024, 1024, 1, True, 2048, 0, 0),
    "mistral_2x2": (8192, 8192, 1024, 1024, 2, True, 4096, 0, 0),
    "full_causal": (4096, 4096, 1024, 1024, 2, True, None, 0, 0),
    "one_block_512": (512, 512, 512, 512, 2, True, 4096, 0, 0),
    "odd_window_offsets": (768, 1280, 128, 160, 2, True, 333, 700, 130),
    "narrow_band": (1024, 1024, 128, 128, 4, True, 50, 0, 0),
    "unequal_blocks": (2048, 1024, 1024, 64, 2, True, 600, 0, 1000),
    "all_future": (512, 512, 64, 64, 2, True, None, 0, 4096),
    "not_causal": (512, 1024, 256, 512, 2, False, None, 0, 0),
}


@pytest.mark.parametrize("case", list(SCHEDULE_CASES))
def test_block_schedule_against_brute_force(case):
    """``pairs_visible`` is the brute-force count; every visible pair
    lies in exactly one visited rectangle; an unmasked rectangle holds
    only visible pairs, a masked one both kinds (or it would have been
    unmasked or skipped); the counts add up to the grid."""
    args = SCHEDULE_CASES[case]
    t_q, t_k, block_q, block_k, sub, causal, window, q_off, kv_off = args
    seen = _visible_pairs(t_q, t_k, causal, window, q_off, kv_off)
    s = fa.block_schedule(*args)
    assert s.pairs_visible == int(seen.sum())
    covered = np.zeros_like(seen, np.int32)
    computed = 0
    for q0, rows, k0, cols, masked in fa.schedule_visits(*args):
        part = seen[q0:q0 + rows, k0:k0 + cols]
        assert part.any()
        assert part.all() != masked
        covered[q0:q0 + rows, k0:k0 + cols] += 1
        computed += rows * cols
    assert covered.max(initial=0) <= 1 and (covered[seen] == 1).all()
    assert s.pairs_computed == computed
    assert s.useful_share == pytest.approx(int(seen.sum()) / max(computed, 1))
    per_block = (block_q // s.sub_q) * (block_k // s.sub_k)
    straddling = (s.tiles_skipped + s.tiles_unmasked + s.tiles_masked) // per_block
    assert s.steps_skipped + s.blocks_unmasked + straddling == s.grid[0] * s.grid[1]


@pytest.mark.parametrize("case", list(SCHEDULE_CASES))
def test_the_grids_walk_every_block_with_a_visible_pair(case):
    """Both bands (the kv blocks a q block walks; the q blocks a kv
    block walks, for dK/dV): every block that holds a visible pair is
    walked exactly once, within the axis, and the block held in VMEM
    changes only inside the run (no DMA for a step that computes
    nothing)."""
    t_q, t_k, block_q, block_k, _, causal, window, q_off, kv_off = (
        SCHEDULE_CASES[case]
    )
    seen = _visible_pairs(t_q, t_k, causal, window, q_off, kv_off)
    blocks = seen.reshape(t_q // block_q, block_q, t_k // block_k, block_k)
    active = blocks.any(axis=(1, 3))  # [q block, kv block]
    bands = fa._bands(t_q, t_k, block_q, block_k, causal, window, q_off, kv_off)
    for band, table in zip(bands, (active, active.T)):
        assert band.steps <= table.shape[1]
        for x, row in enumerate(table):
            walked = [band.block(x, s) for s in range(band.steps)]
            assert walked == list(range(walked[0], walked[0] + band.steps))
            assert 0 <= walked[0] and walked[-1] < table.shape[1]
            assert set(np.flatnonzero(row)) <= set(walked)
            held = [band.fetch(x, s) for s in range(band.steps)]
            assert all(0 <= h < table.shape[1] for h in held)
            for s, (w, h) in enumerate(zip(walked, held)):
                if row[w]:
                    assert h == w
            if row.any():
                assert set(held) == set(np.flatnonzero(row))
            else:
                assert len(set(held)) == 1
        if table.any():  # no narrower grid would do
            assert band.steps == max(
                np.flatnonzero(r)[-1] - np.flatnonzero(r)[0] + 1
                for r in table if r.any()
            )


def test_block_schedule_at_the_benchmark_shape():
    """T = 8,192, window 2,048, blocks of 1,024: 14,681,088 visible
    pairs.  Whole straddling blocks (the parent's schedule) compute 21
    blocks = 22.0M pairs, 67% useful; cut 2 x 2, 70 sub-tiles of 512 =
    18.35M, 80%."""
    args = (8192, 8192, 1024, 1024)
    whole = fa.block_schedule(*args, 1, True, 2048, 0, 0)
    assert whole.pairs_visible == 14_681_088
    assert (whole.blocks_unmasked, whole.tiles_masked) == (7, 14)
    assert whole.pairs_computed == 21 * 1024 * 1024 == 22_020_096
    assert round(100 * whole.useful_share) == 67
    cut = fa.block_schedule(*args, 2, True, 2048, 0, 0)
    assert (cut.sub_q, cut.sub_k) == (512, 512)
    # The grid is the band, not the square: 8 x 3 steps a head where the
    # parent walked 8 x 8 and skipped 43 of them (each still a DMA).
    assert (cut.grid, cut.grid_dkv, cut.steps_skipped) == ((8, 3), (8, 3), 3)
    assert 4 * cut.blocks_unmasked + cut.tiles_unmasked + cut.tiles_masked == 70
    assert (cut.tiles_unmasked, cut.tiles_masked, cut.tiles_skipped) == (14, 28, 14)
    assert cut.pairs_computed == 70 * 512 * 512 == 18_350_080
    assert round(100 * cut.useful_share) == 80


def test_fit_split_keeps_sub_tiles_aligned():
    assert [fa._fit_split(b, 2) for b in (1024, 2048, 512, 256, 640)] == [2, 2, 1, 1, 1]
    assert [fa._fit_split(b, 4) for b in (1024, 2048, 1536, 768)] == [2, 4, 3, 1]
    assert [fa._fit_split(b, 2) for b in (16, 24, 48, 8, 1)] == [2, 1, 2, 1, 1]


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_kernels_visit_exactly_the_schedule(kernel):
    """A NaN planted in one sub-tile's keys (queries, for dK/dV) reaches
    exactly the rows of the rectangles ``schedule_visits`` lists over it
    — a masked tile multiplies it by zero, which is NaN; a skipped tile
    never reads it.  Interpret mode."""
    t, block, sub, window, d = 64, 32, 16, 24, 8
    kw = dict(scale=d ** -0.5, causal=True, block_q=block, block_k=block,
              q_offset=0, kv_offset=0, interpret=True, window=window)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v, do = (jax.random.normal(key, (1, t, d)) for key in keys)
    o, lse = fa._flash_forward(q, k, v, **kw)
    visits = fa.schedule_visits(t, t, block, block, fa.SPLIT, True, window, 0, 0)
    assert {(v_[1], v_[3]) for v_ in visits} == {(sub, sub)}  # no whole block
    skipped = 0
    for start in range(0, t, sub):
        poison = lambda x: x.at[:, start:start + sub].set(jnp.nan)
        if kernel == "fwd":
            got, _ = fa._flash_forward(q, k, poison(v), **kw)
        elif kernel == "dq":
            got = fa._flash_backward_pallas(q, poison(k), v, o, lse, do, **kw)[0]
        else:
            got = fa._flash_backward_pallas(poison(q), k, v, o, lse, do, **kw)[1]
        want = np.zeros(t, bool)
        for q0, rows, k0, cols, _ in visits:
            if kernel == "dkv" and q0 == start:
                want[k0:k0 + cols] = True
            if kernel != "dkv" and k0 == start:
                want[q0:q0 + rows] = True
        np.testing.assert_array_equal(np.isnan(got[0]).any(-1), want)
        skipped += int((~want).sum())
    assert skipped > 0  # some sub-tile over each strip really is skipped


def test_attn_schedule_record_when_armed():
    """Armed while a kernel is traced, the flight recorder gets one
    ``attn.schedule`` record carrying ``block_schedule``'s result."""
    q, k, v = _grouped_qkv(jax.random.PRNGKey(1), 64, 64, 4, 2)
    call = lambda: fa.flash_attention(
        q, k, v, causal=True, window=24, block_q=32, block_k=32
    )
    call()  # disarmed: nothing recorded, nothing raised
    rec = telemetry.install(party="alice")
    try:
        call()
        got = [r for r in rec.records() if r.phase == "attn.schedule"]
    finally:
        telemetry.uninstall()
    assert len(got) == 1  # the forward's; a gradient adds the backward's
    want = fa.block_schedule(64, 64, 32, 32, fa.SPLIT, True, 24, 0, 0)
    detail = got[0].detail
    assert detail["useful_share"] == want.useful_share
    assert detail["tiles_masked"] == want.tiles_masked
    assert tuple(detail["grid"]) == want.grid == (2, 2)
    assert (detail["heads"], detail["kv_heads"], detail["window"]) == (4, 2, 24)


# --- what a process pays to trace the kernels: the size budget -------------

# (query heads, K/V heads, window) of the three 8,192-token attention
# shapes the benchmark runs: Trinity-Mini's two kinds and Mistral's.
BENCHMARK_SHAPES = {
    "trinity_window_2048": (32, 4, 2048),
    "trinity_full": (32, 4, None),
    "mistral_window_4096": (32, 8, 4096),
}
# Equations in the forward, dQ and dK/dV kernels' jaxprs: twice what the
# kernels had with two copies of the tile body (145 / 98 / 113 at the
# parent of PR 30).  Thirteen copies (700 / 498 / 581, 26 / 39 / 52
# products) cost two cells their set-up bound (ledger, PR 29).
MAX_EQUATIONS = (290, 196, 226)
# Matrix products: 2 / 3 / 4 a tile body, at most four bodies a kernel
# (whole block; sub-tile unmasked, cut by the diagonal, cut by the band).
MAX_PRODUCTS = (8, 12, 16)


# The latent shape (64 heads, 128 + 64 wide score parts, one shared rotary
# key head, values 128 wide) in both forms: equations / products in the
# forward, dQ and dK/dV kernels as `tool/flash_sweep.py --lowering` read
# them (PR 33), with a few equations of room.  The split form's tile body
# holds one more product a score part in each kernel; the plain form
# (k concatenated first) is the full-attention program of the other
# shapes at another width.
LATENT_SHAPE = (1, 8192, 64, 128, 64, 128)
LATENT_MAX = {
    "split": ((190, 152, 188), (9, 15, 18)),
    "plain": ((172, 118, 144), (6, 9, 12)),
}


@pytest.mark.parametrize("form", list(LATENT_MAX))
def test_latent_kernel_programs_stay_within_their_size_budget(form):
    from tool.flash_sweep import kernel_counts, latent_attention_grad

    grad, args = latent_attention_grad(fa, LATENT_SHAPE, form == "split")
    counts = kernel_counts(jax.make_jaxpr(grad)(*args).jaxpr)
    assert len(counts) == 3  # forward, dQ, dK/dV
    most, most_products = LATENT_MAX[form]
    assert [n <= m for (n, _), m in zip(counts, most)] == [True] * 3, counts
    assert [d for _, d in counts] == list(most_products)


@pytest.mark.parametrize("shape", list(BENCHMARK_SHAPES))
def test_kernel_programs_stay_within_the_size_budget(shape):
    """Tracing and lowering the kernels is host work every party's thread
    does before it can ask the compile cache: its size is held here, as
    numbers, at the shapes the benchmark runs (nothing is compiled)."""
    from tool.flash_sweep import attention_grad, kernel_counts

    heads, kv_heads, window = BENCHMARK_SHAPES[shape]
    grad, args = attention_grad(fa, (1, 8192, heads, kv_heads, 128, window))
    counts = kernel_counts(jax.make_jaxpr(grad)(*args).jaxpr)
    assert len(counts) == 3  # forward, dQ, dK/dV
    for (equations, products), most, most_products in zip(
        counts, MAX_EQUATIONS, MAX_PRODUCTS
    ):
        assert equations <= most
        assert products <= most_products
    assert sum(n for n, _ in counts) <= 2 * 356


def test_a_process_traces_each_kernel_once(monkeypatch):
    """The wrappers of the three ``pallas_call``s are jitted on their
    static arguments: a second program that holds the same call (another
    party's step, the forward a checkpoint recomputes, the other branch
    of a ``cond``) finds the kernels' jaxprs, and another window does not."""
    traced = []
    for name in ("_flash_fwd_kernel", "_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"):
        def counting(*args, _kernel=getattr(fa, name), _name=name, **kw):
            traced.append(_name)
            return _kernel(*args, **kw)

        monkeypatch.setattr(fa, name, counting)
    q, k, v = _grouped_qkv(jax.random.PRNGKey(2), 32, 32, 4, 2)

    def program(window):
        def loss(q, k, v):
            out = fa.flash_attention(
                q, k, v, causal=True, window=window, block_q=16, block_k=16
            )
            return jnp.sum(out ** 2)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    jax.clear_caches()
    first = program(9)(q, k, v)
    # Once each, though a gradient traces the forward twice (the primal
    # function, then the rule that keeps the residuals).
    assert sorted(traced) == [
        "_flash_bwd_dkv_kernel", "_flash_bwd_dq_kernel", "_flash_fwd_kernel"
    ]
    again = program(9)(q, k, v)  # a new program, the same kernels
    assert len(traced) == 3
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    program(10)(q, k, v)
    assert len(traced) == 6
    jax.clear_caches()  # the counting kernels leave with the test


# --- a checkpointed layer keeps the kernel's output and row statistics -----


def _forward_kernels(jaxpr, inside=False):
    """How many forward ``pallas_call``s a jaxpr and all it holds run:
    those inside the kernel's jitted wrapper, ``_flash_forward`` (a scan
    may leave an empty equation of that name where it hoisted nothing)."""
    from tool.flash_sweep import _sub_jaxprs

    return sum(
        (inside and eqn.primitive.name == "pallas_call")
        + sum(
            _forward_kernels(
                sub, inside or eqn.params.get("name") == "_flash_forward"
            )
            for sub in _sub_jaxprs(eqn)
        )
        for eqn in jaxpr.eqns
    )


def _llama_case(**kw):
    from rayfed_tpu.models import llama

    cfg = llama.llama_tiny(remat=True, **kw)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab_size)

    def loss(p):
        logits = llama.apply_llama(p, ids, cfg, attn_fn=flash_attention)
        return llama.lm_loss(logits[:, :-1], ids[:, 1:])

    return loss, params, 1  # one scanned body, one kernel


def _decoder_case(*kinds):
    from rayfed_tpu.models import decoder, llama

    cfg = decoder.DecoderConfig(
        layers=tuple(decoder.LayerSpec(kind, "dense") for kind in kinds),
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=8, intermediate_size=48, sliding_window=8,
        dtype=jnp.float32, remat=True,
    )
    params = decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, cfg.vocab_size)

    def loss(p):
        logits, _ = decoder.apply_decoder(p, ids, cfg, attn_fn=flash_attention)
        return llama.lm_loss(logits[:, :-1], ids[:, 1:])

    # One group, one scanned body: a kernel a kind (both under the cond).
    return loss, params, len(set(kinds))


REMAT_CASES = {
    "llama": _llama_case,
    "llama_dots": functools.partial(_llama_case, remat_policy="dots"),
    "decoder_one_kind": functools.partial(_decoder_case, "window", "window"),
    "decoder_kinds_under_cond": functools.partial(_decoder_case, "window", "full"),
}


@pytest.mark.parametrize("case", list(REMAT_CASES))
def test_checkpointed_layer_does_not_run_the_forward_kernel_again(
    case, monkeypatch
):
    """``remat`` saves the two residuals only the kernel can make
    (``RESIDUAL_NAMES``, named in the ``custom_vjp``'s forward rule), so
    the gradient's program holds the forward kernel as often as the
    forward pass alone, not twice; with nothing saved it holds it twice,
    and the gradients are the same to the last bit (the same kernel on
    the same values, kept in place of made again).  Interpret mode."""
    from rayfed_tpu.models import decoder, llama

    loss, params, kernels = REMAT_CASES[case]()
    assert _forward_kernels(jax.make_jaxpr(loss)(params).jaxpr) == kernels
    grad = jax.grad(loss)
    assert _forward_kernels(jax.make_jaxpr(grad)(params).jaxpr) == kernels
    with jax.disable_jit():  # op by op: no fusion to differ by an ulp
        got = grad(params)

    nothing = jax.checkpoint_policies.nothing_saveable
    monkeypatch.setattr(llama, "REMAT_SAVED", nothing)
    monkeypatch.setattr(decoder, "REMAT_SAVED", nothing)
    grad = jax.grad(REMAT_CASES[case]()[0])  # jax keeps what it traced
    assert _forward_kernels(jax.make_jaxpr(grad)(params).jaxpr) == 2 * kernels
    with jax.disable_jit():
        want = grad(params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    ):
        assert np.any(np.asarray(a)), path
        np.testing.assert_array_equal(a, b, err_msg=str(path))


def test_attn_schedule_record_carries_the_residual_bytes():
    """``residual_bytes`` is what a checkpoint that saves
    ``RESIDUAL_NAMES`` keeps for the call: the output in its dtype and a
    float32 statistic a (batch, head, query)."""
    b, t, h, kv, d = 2, 64, 4, 2, 8
    q, k, v = (
        x.astype(jnp.bfloat16)
        for x in _grouped_qkv(jax.random.PRNGKey(1), t, t, h, kv, d=d, b=b)
    )
    rec = telemetry.install(party="alice")
    try:
        fa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        (record,) = [r for r in rec.records() if r.phase == "attn.schedule"]
    finally:
        telemetry.uninstall()
    assert record.detail["residual_bytes"] == b * t * h * d * 2 + b * h * t * 4


def _llama_lora_step():
    from rayfed_tpu.models import llama, lora

    cfg = llama.llama_tiny(remat=True, dtype=jnp.bfloat16)
    base = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = lora.init_lora(jax.random.PRNGKey(1), base, lora.LoraConfig(rank=2))
    step = llama.make_lora_train_step(cfg, attn_fn=flash_attention)
    b, t, f = 2, 24, cfg.intermediate_size
    shape = (b, t, cfg.vocab_size)
    return step, (adapters, llama.init_adam(adapters), base), shape, {
        "layers": {"layers0-1": 2},
        "bytes_per_layer": {"layers0-1": {
            "ffn.up": b * t * f * 2, "layer.mid": b * t * cfg.hidden_size * 2,
        }},
    }


def _decoder_lora_step():
    from rayfed_tpu.models import decoder, llama, lora, moe

    experts = moe.ExpertShareConfig(
        num_experts=8, held=(0, 1, 2, 3), top_k=3, d_model=32, d_ff=16,
    )
    cfg = decoder.DecoderConfig(
        layers=(decoder.LayerSpec("window", "dense"),
                decoder.LayerSpec("window", "moe"),
                decoder.LayerSpec("full", "moe")),
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=8, intermediate_size=48, sliding_window=8, experts=experts,
        dtype=jnp.float32, remat=True,
    )
    base = decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(1), base,
        lora.LoraConfig(rank=2, targets=decoder.ALL_LINEAR),
    )
    step = decoder.make_lora_train_step(cfg, attn_fn=flash_attention).jitted
    b, t = 1, 24
    return step, (adapters, llama.init_adam(adapters), base), (b, t, 64), {
        # the stream between the sub-blocks in every layer; a dense FFN's
        # up product; a shared expert's, and the selection
        "layers": {"layers0-0": 1, "layers1-2": 2},
        "bytes_per_layer": {
            "layers0-0": {
                "layer.mid": b * t * 32 * 4, "ffn.up": b * t * 48 * 4,
            },
            "layers1-2": {
                "layer.mid": b * t * 32 * 4, "ffn.up": b * t * 16 * 4,
                "moe.selected": b * t * 3 * 4,
            },
        },
    }


@pytest.mark.parametrize("model", ["llama", "decoder"])
def test_remat_saved_record_when_armed(model, monkeypatch):
    """Armed while a LoRA step is traced, the flight recorder gets one
    ``remat.saved`` record: the policy's names, what a layer of each
    scanned group keeps under those the models give, and how the fused
    head-and-loss is chunked; disarmed, none."""
    from rayfed_tpu.models import llama

    make = {"llama": _llama_lora_step, "decoder": _decoder_lora_step}[model]
    step, args, (b, t, vocab), want = make()
    # chunks of 16 rows: 48 rows are three, 24 rows two (the last padded)
    monkeypatch.setattr(llama, "HEAD_CHUNK_BYTES", 4 * 16 * vocab)
    ids = jnp.zeros((b, t), jnp.int32)
    step.lower(*args, ids)  # disarmed: nothing recorded, nothing raised
    rec = telemetry.install(party="alice")
    try:
        make()[0].lower(*args, ids)  # a step built anew is traced anew
        (record,) = [r for r in rec.records() if r.phase == "remat.saved"]
    finally:
        telemetry.uninstall()
    detail = record.detail
    assert detail["names"] == list(llama.REMAT_SAVED_NAMES)
    assert {"ffn.up", "flash.out", "layer.mid"} <= set(detail["names"])
    assert "ffn.gate" not in detail["names"]
    assert detail["head_chunk_rows"] == 16
    assert detail["head_chunks"] == -(-b * t // 16)
    assert detail["logits_bytes_avoided"] == b * t * vocab * 4
    for key, value in want.items():
        assert detail[key] == value, key


# --- a score in parts, and values of a width of their own -----------------


def _latent_parts(key, b=2, t=64, h=4, nope=16, rope=8, dv=12):
    """The published ratios at toy widths: the rotary part half the part
    without positions, ONE rotary key head, a value width that is
    neither."""
    ks = jax.random.split(key, 6)
    shapes = [(h, nope), (h, rope), (h, nope), (1, rope), (h, dv), (h, dv)]
    return [jax.random.normal(k, (b, t, *s)) for k, s in zip(ks, shapes)]


@pytest.mark.parametrize("window", [None, 24])
def test_flash_split_score_matches_dense_on_the_concatenated_key(window):
    """The kernels given the score's two parts (the rotary key read by
    its one head, its gradient summed over the query heads in the dK/dV
    kernel) against ``dot_product_attention`` on the concatenated key:
    forward and all five gradients."""
    from rayfed_tpu.ops.attention import score_parts

    *parts, w = _latent_parts(jax.random.PRNGKey(0))
    kw = dict(causal=True, window=window, sm_scale=0.3)

    def split(q_nope, q_pe, k_nope, k_pe, v):
        out = fa.flash_attention(
            (q_nope, q_pe), (k_nope, k_pe), v, block_q=16, block_k=32, **kw
        )
        return jnp.sum(out * w), out

    def dense(q_nope, q_pe, k_nope, k_pe, v):
        q, k = score_parts((q_nope, q_pe), (k_nope, k_pe), v)
        assert q.shape[-1] == k.shape[-1] == 24 and k.shape[2] == 4
        out = dot_product_attention(q, k, v, **kw)
        return jnp.sum(out * w), out

    argnums = (0, 1, 2, 3, 4)
    (_, got), got_grads = jax.value_and_grad(split, argnums, has_aux=True)(*parts)
    (_, want), want_grads = jax.value_and_grad(dense, argnums, has_aux=True)(*parts)
    assert got.shape == (2, 64, 4, 12)  # the VALUE width
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for part, g, wg in zip(parts, got_grads, want_grads):
        assert g.shape == part.shape  # k_pe's: one head
        np.testing.assert_allclose(g, wg, rtol=2e-4, atol=2e-4)
    # the dense path takes the parts as they are, too
    np.testing.assert_allclose(
        dot_product_attention(tuple(parts[:2]), tuple(parts[2:4]), parts[4], **kw),
        want, rtol=1e-6, atol=1e-6,
    )


def test_flash_value_width_differs_from_the_query_key_width():
    """One product over the whole query-key width, V not padded to it."""
    from rayfed_tpu.ops.attention import score_parts

    *parts, w = _latent_parts(jax.random.PRNGKey(1))
    q, k = score_parts(tuple(parts[:2]), tuple(parts[2:4]), parts[4])
    v = parts[4]

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, **kw) * w)

    got = jax.grad(loss(fa.flash_attention, block_q=32, block_k=16), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(dot_product_attention), (0, 1, 2))(q, k, v)
    for g, wg in zip(got, want):
        np.testing.assert_allclose(g, wg, rtol=2e-4, atol=2e-4)


def test_score_parts_validation():
    q_nope, q_pe, k_nope, k_pe, v, _ = _latent_parts(jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="do not pair"):
        fa.flash_attention((q_nope, q_pe), (k_nope, k_pe[..., :4]), v)
    with pytest.raises(ValueError, match="query parts"):
        fa.flash_attention((q_nope, q_pe), (k_nope,), v)
    with pytest.raises(ValueError, match="must divide"):
        fa.flash_attention(
            (q_nope, q_pe), (k_nope, jnp.repeat(k_pe, 3, axis=2)), v
        )


def test_attn_schedule_record_carries_the_widths_of_a_latent_call():
    """The record of a call with a two-part score: the parts' widths and
    K heads, the value width, and ``residual_bytes`` from the OUTPUT's
    shape (the value width; the query-key width would over-count by half
    at 192 against 128)."""
    b, t, h = 2, 64, 4
    q_nope, q_pe, k_nope, k_pe, v, _ = (
        x.astype(jnp.bfloat16) for x in _latent_parts(jax.random.PRNGKey(3))
    )
    rec = telemetry.install(party="alice")
    try:
        fa.flash_attention((q_nope, q_pe), (k_nope, k_pe), v, causal=True,
                           block_q=32, block_k=32)
        (record,) = [r for r in rec.records() if r.phase == "attn.schedule"]
    finally:
        telemetry.uninstall()
    d = record.detail
    assert (d["qk_widths"], d["part_kv_heads"], d["v_width"]) == ([16, 8], [4, 1], 12)
    assert d["residual_bytes"] == b * t * h * 12 * 2 + b * h * t * 4
    assert (d["heads"], d["kv_heads"]) == (4, 4)
