"""MoE layer: routing correctness + expert-parallel sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu.models import moe
from rayfed_tpu.parallel import create_mesh
from rayfed_tpu.parallel.sharding import shard_params_by_rules


def test_moe_forward_shapes_and_grad():
    cfg = moe.MoeConfig(num_experts=4, top_k=2, d_model=16, d_ff=32)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    out, aux = moe.apply_moe(params, x, cfg, return_aux=True)
    assert out.shape == x.shape
    assert float(aux["aux_loss"]) > 0
    assert 0.0 <= float(aux["dropped_fraction"]) <= 1.0

    def loss(p):
        y, a = moe.apply_moe(p, x, cfg, return_aux=True)
        return jnp.sum(y**2) + a["aux_loss"]

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.all(np.isfinite(leaf))
    # Gate must receive gradient (routing is trained).
    assert float(jnp.sum(jnp.abs(g["gate"]))) > 0


def test_moe_top1_equals_dense_expert_when_single_expert():
    """With E=1, k=1 and ample capacity, MoE == plain FFN (gate prob 1)."""
    cfg = moe.MoeConfig(
        num_experts=1, top_k=1, capacity_factor=2.0, d_model=8, d_ff=16
    )
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 8))
    out = moe.apply_moe(params, x, cfg)
    dense = (
        jax.nn.gelu(x @ params["w_in"][0]) @ params["w_out"][0]
    )
    np.testing.assert_allclose(out, dense, atol=1e-5, rtol=1e-5)


def test_moe_capacity_drops_overflow():
    """Tiny capacity must drop tokens (dropped_fraction > 0), not crash."""
    cfg = moe.MoeConfig(
        num_experts=2, top_k=1, capacity_factor=0.25, d_model=8, d_ff=16
    )
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8))
    out, aux = moe.apply_moe(params, x, cfg, return_aux=True)
    assert float(aux["dropped_fraction"]) > 0
    assert np.all(np.isfinite(np.asarray(out)))


def test_moe_scatter_matches_einsum_dispatch():
    """The default scatter dispatch agrees exactly with the GShard-style
    one-hot einsum reference, including under drops and in gradients."""
    for cf in (1.25, 0.25):  # ample capacity and forced overflow
        cfg = moe.MoeConfig(
            num_experts=4, top_k=2, capacity_factor=cf, d_model=16, d_ff=32
        )
        params = moe.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
        out_s = moe.apply_moe(params, x, cfg, dispatch="scatter")
        out_e = moe.apply_moe(params, x, cfg, dispatch="einsum")
        np.testing.assert_allclose(out_s, out_e, atol=1e-5, rtol=1e-5)

        def loss(p, mode):
            return jnp.sum(moe.apply_moe(p, x, cfg, dispatch=mode) ** 2)

        g_s = jax.grad(lambda p: loss(p, "scatter"))(params)
        g_e = jax.grad(lambda p: loss(p, "einsum"))(params)
        for ls, le in zip(
            jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_e)
        ):
            np.testing.assert_allclose(ls, le, atol=1e-4, rtol=1e-4)


def test_moe_einsum_guard_at_scale():
    """The einsum path refuses mask shapes in the tens-of-GB regime."""
    import pytest

    cfg = moe.MoeConfig(num_experts=64, top_k=2, d_model=8, d_ff=16)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((8, 8192, 8))
    with pytest.raises(ValueError, match="scatter"):
        # eval_shape: trace only — no 34GB allocation on the test host.
        jax.eval_shape(
            lambda p, x: moe.apply_moe(p, x, cfg, dispatch="einsum"), params, x
        )


def test_moe_expert_parallel_sharding():
    """Experts shard over ep; jitted apply under the mesh matches single-dev."""
    mesh = create_mesh({"ep": 4, "tp": 2})
    cfg = moe.MoeConfig(num_experts=8, top_k=2, d_model=16, d_ff=32)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg)
    shardings = shard_params_by_rules(mesh, params, moe.PARTITION_RULES)
    assert "ep" in str(shardings["w_in"].spec)
    sharded = jax.device_put(params, shardings)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    expected = moe.apply_moe(params, x, cfg)
    with jax.sharding.set_mesh(mesh):
        out = jax.jit(lambda p, x: moe.apply_moe(p, x, cfg))(sharded, x)
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


# -- the routed experts' LoRA bypass: its form follows from shapes -------


@pytest.mark.parametrize("g, rank, blocks, per_block", [
    (16, 8, 1, 16),  # Trinity's share: the dense form at the MXU's width
    (12, 8, 1, 16),  # Kimi's
    (64, 2, 1, 64),  # the toys' rank
    (64, 8, 4, 16),  # Nemotron's: four blocks of 16
    (20, 8, 2, 16),  # the last block filled with 12 zero experts
])
def test_the_bypass_form_follows_from_the_experts_and_the_rank(
    g, rank, blocks, per_block
):
    assert moe.lora_blocks(g, rank) == (blocks, per_block)


def _bypass_case(g, rank=8, d_in=128, d_out=256, rows=384, seed=0):
    """Sorted rows of ``g`` experts (``d_in`` -> ``d_out``) and padding
    rows after them, as a chunk of ``moe._routed`` holds them: experts
    3 and 40 (where held) and the whole block 16-31 get no row.  Returns
    (arguments of ``_expert_linear``'s differentiable part, the unreached
    experts)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    counts = np.array(jax.random.randint(k[0], (g,), 1, 9))
    unreached = [e for e in (3, 40) + tuple(range(16, 32)) if e < g]
    counts[unreached] = 0
    padding = rows - int(counts.sum())
    assert padding > 0
    sizes = jnp.asarray(np.append(counts, padding), jnp.int32)
    row_expert = jnp.asarray(np.repeat(np.arange(g + 1), np.asarray(sizes)),
                             jnp.int32)
    xs = jax.random.normal(k[1], (rows, d_in))
    w = jax.random.normal(k[2], (g, d_in, d_out)) * d_in**-0.5
    a = jax.random.normal(k[3], (g, d_in, rank)) * d_in**-0.5
    b = jax.random.normal(k[4], (g, rank, d_out)) * 0.3
    cot = jax.random.normal(k[5], (rows, d_out))
    return (xs, a, b), (w, sizes, row_expert, cot), unreached


def _bypass_and_grads(args, fixed, dtype):
    """The output and the gradients of ``x``, ``A`` and ``B`` through
    ``_expert_linear``, computed in ``dtype``, returned in float32."""
    w, sizes, row_expert, cot = fixed

    def f(xs, a, b):
        entry = {"a": a, "b": b, "scale": jnp.asarray(2.0)}
        return moe._expert_linear(xs.astype(dtype), w.astype(dtype), sizes,
                                  row_expert, entry)

    out, pull = jax.vjp(f, *args)
    grads = pull(cot.astype(out.dtype))
    return [jnp.asarray(v, jnp.float32) for v in (out, *grads)]


@pytest.mark.parametrize("g", [64, 20])
def test_the_bypass_by_block_is_the_dense_form(grouped, g, monkeypatch):
    """Nemotron's 64 experts of rank 8 (four blocks) and 20 (two, with
    zero experts filling the second): the output and the gradients of
    ``x``, ``A`` and ``B`` are the dense form's, which a wider MXU would
    pick for the same call; tight in float32, and in bf16 no further
    from float32 than the dense form's own bf16.  An expert no row
    reached, and a whole block no row reached, get a gradient of
    exactly zero; padding rows get none."""
    args, fixed, unreached = _bypass_case(g)
    assert moe.lora_blocks(g, 8)[0] > 1
    block = {dt: _bypass_and_grads(args, fixed, dt)
             for dt in (jnp.float32, jnp.bfloat16)}
    monkeypatch.setattr(moe, "LANES", g * 8)  # one block: the dense form
    assert moe.lora_blocks(g, 8) == (1, g)
    dense = {dt: _bypass_and_grads(args, fixed, dt)
             for dt in (jnp.float32, jnp.bfloat16)}
    rel = lambda got, want: float(
        jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want**2))
    )
    names = ("out", "dx", "dA", "dB")
    for name, got, want in zip(names, block[jnp.float32], dense[jnp.float32]):
        assert rel(got, want) < 1e-5, name
    for name, got, want, f32 in zip(names, block[jnp.bfloat16],
                                    dense[jnp.bfloat16], dense[jnp.float32]):
        # the dense form's own bf16 error, and the block form's
        assert rel(got, f32) <= 1.1 * rel(want, f32) + 1e-4, name
    _, dx, da, db = block[jnp.float32]
    padding = int(fixed[1][-1])
    assert float(jnp.abs(dx[-padding:]).max()) == 0.0
    for e in unreached:
        assert float(jnp.abs(da[e]).max()) == 0.0, e
        assert float(jnp.abs(db[e]).max()) == 0.0, e
    reached = [e for e in range(g) if e not in unreached]
    assert all(float(jnp.abs(da[e]).max()) > 0 for e in reached)
