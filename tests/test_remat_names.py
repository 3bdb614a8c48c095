"""What a checkpointed layer's second forward stops running once the
policy keeps the stream between its two sub-blocks (``layer.mid``) and a
state-space layer's input projection (``ssm.in``): ``llama.REMAT_SAVED``,
PR 38.  Toy models of every kind of layer the repo has, on the CPU: a
``llama.py`` step, a decoder with window and full attention under a
``cond`` and expert layers, latent attention, and a hybrid with Mamba
layers.

Read from the gradient's JAXPR, where JAX marks what a checkpoint makes
again (``rematted_computation`` on the name stack of those equations and
of no other): a product is told by its operands' shapes, so the toy
widths are chosen to keep the output projection's apart from its
neighbours' where the model allows it (``llama.py``'s ``wq`` and ``wo``
are both hidden x hidden: counted together there).
"""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rayfed_tpu.models import decoder, llama, lora, moe
from rayfed_tpu.ops.flash_attention import flash_attention
from tests import test_granite_hybrid, test_kimi_k2
from tool.flash_sweep import _sub_jaxprs

NEW_NAMES = (llama.LAYER_MID_NAME, llama.SSM_IN_NAME)


def rematted_products(jaxpr, inside=False):
    """``Counter{(lhs shape, rhs shape): n}`` of the ``dot_general``s a
    jaxpr and all it holds run under ``rematted_computation``, forward
    products alone (``x @ w`` contracts the right operand's FIRST dim; a
    backward pass's ``g @ w.T`` its second)."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        again = inside or "rematted_computation" in str(
            eqn.source_info.name_stack
        )
        if again and eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            (_, contract_rhs), _ = eqn.params["dimension_numbers"]
            if tuple(contract_rhs) == (0,) and len(rhs) == 2:
                found[lhs, rhs] += 1
        for sub in _sub_jaxprs(eqn):
            found += rematted_products(sub, again)
    return found


def _llama(remat=True):
    cfg = llama.llama_tiny(sliding_window=16, remat=remat)
    base = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = test_kimi_k2._trained(lora.init_lora(
        jax.random.PRNGKey(1), base, lora.LoraConfig(
            rank=2, alpha=4.0, targets=(r"w[qkvo]$", r"w_(gate|up|down)$"),
        )), 2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, 256)
    return adapters, lambda a: llama.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )


def _window_full_experts(remat=True, post_norms=False):
    """Trinity's block at toy widths: head norms, an output gate, window
    and full attention in one scanned group, expert layers; 4 x 12-wide
    heads on a stream of 32, so that ``wo`` [48, 32] is no other
    matrix's shape."""
    experts = moe.ExpertShareConfig(
        num_experts=8, held=(0, 1, 2, 3), top_k=3, d_model=32, d_ff=16,
        route_scale=2.826,
    )
    cfg = decoder.DecoderConfig(
        layers=(decoder.LayerSpec("window", "dense"),
                decoder.LayerSpec("window", "moe"),
                decoder.LayerSpec("full", "moe")),
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=12, intermediate_size=40, sliding_window=8,
        post_norms=post_norms, experts=experts, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=remat,
    )
    base = decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    adapters = test_kimi_k2._trained(lora.init_lora(
        jax.random.PRNGKey(1), base,
        lora.LoraConfig(rank=2, alpha=4.0, targets=decoder.ALL_LINEAR),
    ), 2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 0, 64)
    return adapters, lambda a: decoder.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )[0]


def _of_module(module):
    def build(remat=True):
        cfg, base, adapters, ids = module.make(
            cfg=module.toy_config(remat=remat)
        )
        return adapters, lambda a: decoder.lora_loss(
            a, base, ids, cfg, attn_fn=flash_attention
        )[0]

    return build


MODELS = {
    "llama": _llama,
    "window_full_experts": _window_full_experts,
    "latent": _of_module(test_kimi_k2),
    "hybrid": _of_module(test_granite_hybrid),
}

# model -> {(lhs, rhs) of a forward product: (how many the second
# forward runs with neither name kept, with both)}; `R` the adapters'
# rank: ``(x a) b`` is the adapter's WIDE product.
R = 2
DROPPED = {
    # wq and wo are both [64, 64]: the pair, then q's alone
    "llama": {
        ((2, 24, 64), (64, 64)): (2, 1),
        ((2, 24, R), (R, 64)): (2, 1),
    },
    # two scanned groups (the dense layer; the two expert layers)
    "window_full_experts": {
        ((1, 24, 48), (48, 32)): (2, 0),
        ((1, 24, R), (R, 32)): (2, 0),
    },
    # W_o [heads x value width, hidden] in both groups
    "latent": {
        ((1, 32, 48), (48, 32)): (2, 0),
        ((1, 32, R), (R, 32)): (2, 0),
    },
    # two groups of Mamba layers and one attention layer between them:
    # W_in [32, 168] and W_out [64, 32] a Mamba group; the attention
    # layer's wq and wo are both [32, 32]; an adapter's [R, 32] is
    # W_out's twice, wo's and wq's
    "hybrid": {
        ((1, 32, 32), (32, 168)): (2, 0),
        ((1, 32, R), (R, 168)): (2, 0),
        ((1, 32, 64), (64, 32)): (2, 0),
        ((1, 32, 32), (32, 32)): (2, 1),
        ((1, 32, R), (R, 32)): (4, 1),
    },
}


def _keep(monkeypatch, names):
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    monkeypatch.setattr(llama, "REMAT_SAVED", policy)
    monkeypatch.setattr(decoder, "REMAT_SAVED", policy)


def _second_forward(model, **kw):
    adapters, loss = MODELS[model](**kw)  # jax keeps what it traced
    return rematted_products(jax.make_jaxpr(jax.grad(loss))(adapters).jaxpr)


@pytest.mark.parametrize("model", list(MODELS))
def test_second_forward_runs_no_output_projection_nor_w_in(model, monkeypatch):
    """With ``layer.mid`` kept the second forward starts the FFN half
    from the kept stream: the output projection's base product and its
    adapter's wide one feed nothing there and JAX drops them; with
    ``ssm.in`` kept a Mamba layer's ``W_in`` and its adapter's wide
    product go too.  What the backward pass reads of those projections
    (the input, the adapter's ``x a``) is still made."""
    assert set(NEW_NAMES) <= set(llama.REMAT_SAVED_NAMES)
    got = _second_forward(model)
    before = [n for n in llama.REMAT_SAVED_NAMES if n not in NEW_NAMES]
    _keep(monkeypatch, before)
    was = _second_forward(model)
    for product, (neither, both) in DROPPED[model].items():
        assert (was[product], got[product]) == (neither, both), product
    # nothing else moved: the rest of the layer still runs again
    changed = {p for p in was | got if was[p] != got[p]}
    assert changed == set(DROPPED[model])
    # the narrow ``x a`` of every adapted matrix is among what stays
    assert any(rhs[1] == R for _, rhs in got)


@pytest.mark.parametrize("name", NEW_NAMES)
def test_each_name_alone_in_a_hybrid(name, monkeypatch):
    """The two names are independent: ``layer.mid`` alone leaves
    ``W_in`` in the second forward, ``ssm.in`` alone leaves ``W_out``
    and ``wo``."""
    before = [n for n in llama.REMAT_SAVED_NAMES if n not in NEW_NAMES]
    _keep(monkeypatch, before + [name])
    got = _second_forward("hybrid")
    w_in, w_out = ((1, 32, 32), (32, 168)), ((1, 32, 64), (64, 32))
    q_and_o = ((1, 32, 32), (32, 32))
    want = {
        llama.LAYER_MID_NAME: (2, 0, 1), llama.SSM_IN_NAME: (0, 2, 2),
    }[name]
    assert (got[w_in], got[w_out], got[q_and_o]) == want


def test_a_norm_on_the_projections_output_keeps_the_product():
    """Where the block puts a norm on the mixer's output
    (``post_norms``), that norm's backward reads the output projection's
    product, so the second forward still makes it: ``layer.mid`` saves
    only the add there."""
    got = _second_forward("window_full_experts", post_norms=True)
    assert got[(1, 24, 48), (48, 32)] == 2  # a scanned group each


@pytest.mark.parametrize("model", list(MODELS))
def test_adapter_gradients_with_remat_equal_those_without(model):
    """float32 all through: a kept array in place of one made again
    changes no value, so the checkpointed step's loss and every adapter
    leaf's gradient are the plain step's to float32 tolerance."""
    adapters, loss = MODELS[model](remat=True)
    got_loss, got = jax.jit(jax.value_and_grad(loss))(adapters)
    adapters, loss = MODELS[model](remat=False)
    want_loss, want = jax.jit(jax.value_and_grad(loss))(adapters)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got),
        jax.tree_util.tree_leaves(want),
    ):
        if path[-1].key == "scale":
            continue
        assert np.any(np.asarray(b)), path
        scale = float(np.abs(np.asarray(b)).max())
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-5 * scale, err_msg=str(path)
        )
