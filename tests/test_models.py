"""Model family smoke + correctness tests (CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu.models import bert, llama, logistic, lora, resnet
from rayfed_tpu.ops.attention import dot_product_attention
from rayfed_tpu.ops.flash_attention import flash_attention
from rayfed_tpu.parallel import create_mesh
from rayfed_tpu.parallel.sharding import ShardingStrategy, shard_params_by_rules


def test_logistic_learns_separable():
    key = jax.random.PRNGKey(0)
    n, d = 256, 8
    w_true = jax.random.normal(key, (d,))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    y = (x @ w_true > 0).astype(jnp.int32)
    params = logistic.init_logistic(key, d, 2)
    step = logistic.make_train_step(logistic.apply_logistic, lr=0.5)
    for _ in range(60):
        params, loss = step(params, x, y)
    acc = logistic.accuracy(logistic.apply_logistic(params, x), y)
    assert acc > 0.97, float(acc)


def test_mlp_shapes_and_loss_decreases():
    key = jax.random.PRNGKey(0)
    params = logistic.init_mlp(key, 16, (32,), 4)
    x = jax.random.normal(key, (64, 16))
    y = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, 4)
    step = logistic.make_train_step(logistic.apply_mlp, lr=0.1)
    _, loss0 = step(params, x, y)
    params = logistic.init_mlp(key, 16, (32,), 4)
    for _ in range(30):
        params, loss = step(params, x, y)
    assert float(loss) < float(loss0)


def test_resnet18_forward_and_train_step():
    cfg = resnet.ResNetConfig(stage_sizes=(1, 1), width=8, num_classes=10)
    params, state = resnet.init_resnet(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    logits, _ = resnet.apply_resnet(params, state, x, cfg, train=False)
    assert logits.shape == (4, 10)

    y = jnp.array([0, 1, 2, 3])
    opt = resnet.init_opt_state(params)
    step = resnet.make_train_step(cfg, lr=0.01)
    losses = []
    for _ in range(5):
        params, state, opt, loss = step(params, state, opt, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # BN state actually updated
    assert float(jnp.sum(jnp.abs(state["stem"]["mean"]))) > 0


def test_resnet_fed_train_step_matches_unfused():
    # The fused wire-dtype round (cast+opt-init+step+cast in ONE jit)
    # must match the explicit decompress -> init_opt -> step -> compress
    # chain it replaces in the FedAvg trainers.
    from rayfed_tpu.fl import compress, decompress

    cfg = resnet.ResNetConfig(stage_sizes=(1, 1), width=8, num_classes=10)
    params, state = resnet.init_resnet(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3])
    wire = compress((params, state))

    fed_step = resnet.make_fed_train_step(cfg, lr=0.01)
    fused_wire, fused_loss = fed_step(wire, x, y)

    p2, s2 = decompress(wire)
    step = resnet.make_train_step(cfg, lr=0.01)
    p2, s2, _opt, loss = step(p2, s2, resnet.init_opt_state(p2), x, y)
    expected_wire = compress((p2, s2))

    assert float(fused_loss) == pytest.approx(float(loss), rel=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(fused_wire),
        jax.tree_util.tree_leaves(expected_wire),
    ):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
            atol=1e-2, rtol=1e-2,
        )

    # local_steps > 1 runs the whole multi-step round in one call.
    fed_step2 = resnet.make_fed_train_step(cfg, lr=0.01, local_steps=2)
    w2, l2 = fed_step2(wire, x, y)
    assert float(l2) != pytest.approx(float(fused_loss))


def test_resnet_partition_rules_apply():
    mesh = create_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    cfg = resnet.ResNetConfig(stage_sizes=(1,), width=8)
    params, _ = resnet.init_resnet(jax.random.PRNGKey(0), cfg)
    shardings = shard_params_by_rules(mesh, params, resnet.PARTITION_RULES)
    stem = shardings["stem"]["conv"]
    assert "fsdp" in str(stem.spec)


def test_bert_split_equals_full():
    cfg = bert.BertConfig(
        vocab_size=100, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, max_position=64, num_classes=3,
    )
    params = bert.init_bert(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 100)
    full = bert.apply_bert(params, ids, cfg)
    assert full.shape == (2, 3)

    enc_params, head_params = bert.split_params(params)
    hidden = bert.apply_encoder(enc_params, ids, cfg)
    pooled = bert.apply_pooler(enc_params, hidden)
    split_logits = bert.apply_head(head_params, pooled)
    np.testing.assert_allclose(full, split_logits, atol=1e-6)
    assert "head" not in enc_params


def test_bert_attention_mask():
    cfg = bert.BertConfig(
        vocab_size=50, hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, max_position=32,
    )
    params = bert.init_bert(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 50)
    mask = jnp.array([[1, 1, 1, 1, 0, 0, 0, 0]])
    out = bert.apply_encoder(params, ids, cfg, attention_mask=mask)
    # Changing masked-out tokens must not change unmasked outputs.
    ids2 = ids.at[0, 5].set((ids[0, 5] + 7) % 50)
    out2 = bert.apply_encoder(params, ids2, cfg, attention_mask=mask)
    np.testing.assert_allclose(out[:, :4], out2[:, :4], atol=1e-5)


def test_llama_forward_shapes_and_causality():
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = llama.apply_llama(params, ids, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32

    # Causality: changing a later token must not affect earlier logits.
    ids2 = ids.at[:, 10].set((ids[:, 10] + 1) % cfg.vocab_size)
    logits2 = llama.apply_llama(params, ids2, cfg)
    np.testing.assert_allclose(logits[:, :10], logits2[:, :10], atol=1e-5)
    assert not np.allclose(logits[:, 10:], logits2[:, 10:])


def test_llama_flash_attention_matches_dense():
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab_size)
    dense = llama.apply_llama(params, ids, cfg)
    flash = llama.apply_llama(
        params, ids, cfg,
        attn_fn=lambda q, k, v, **kw: flash_attention(
            q, k, v, block_q=16, block_k=16, **kw
        ),
    )
    np.testing.assert_allclose(dense, flash, atol=1e-4, rtol=1e-4)


def _repeated_then_dense(q, k, v, **kw):
    """What the models did before the kernels read K/V by KV head:
    repeat to the query heads, then attend."""
    reps = q.shape[2] // k.shape[2]
    return dot_product_attention(
        q, jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2), **kw
    )


@pytest.mark.parametrize("attn", ["dense", "flash"])
@pytest.mark.parametrize("window", [None, 8])
def test_llama_unrepeated_kv_matches_repeated(attn, window):
    """GQA (4 query heads on 2 K/V heads): logits and adapter gradients
    with K/V handed to the ``attn_fn`` unrepeated equal those of the
    explicit repeat, dense and through the flash kernel."""
    cfg = llama.llama_tiny(sliding_window=window)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(2), params, lora.LoraConfig(rank=4, targets=(r"w[qkv]$",))
    )
    adapters = jax.tree_util.tree_map(
        lambda x: x + 0.02 if x.ndim > 2 else x, adapters
    )
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    attn_fn = dot_product_attention if attn == "dense" else (
        lambda q, k, v, **kw: flash_attention(q, k, v, block_q=16, block_k=16, **kw)
    )

    def loss(adapters, attn_fn):
        logits = llama.apply_llama(params, ids, cfg, lora=adapters, attn_fn=attn_fn)
        return llama.lm_loss(logits[:, :-1], ids[:, 1:]), logits

    (l_got, got), g_got = jax.value_and_grad(loss, has_aux=True)(adapters, attn_fn)
    (l_want, want), g_want = jax.value_and_grad(loss, has_aux=True)(
        adapters, _repeated_then_dense
    )
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_got), jax.tree_util.tree_leaves(g_want)
    ):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4, err_msg=str(path))


def test_llama_ring_sp_matches_dense():
    """Long-context path: the model forward under a 4-way sequence-
    parallel mesh (flash-inner ring attention) equals the dense forward —
    ring/Ulysses plug straight into ``attn_fn`` (kwarg-compatible)."""
    from rayfed_tpu.ops import make_ring_attention

    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    dense = llama.apply_llama(params, ids, cfg)
    mesh = create_mesh({"sp": 4}, devices=jax.devices()[:4])
    ring = make_ring_attention(mesh, "sp", causal=True, use_flash=True)
    out = jax.jit(
        lambda p, i: llama.apply_llama(p, i, cfg, attn_fn=ring)
    )(params, ids)
    np.testing.assert_allclose(dense, out, atol=2e-4, rtol=2e-4)
    # Conflicting build-time/call-time settings are rejected, not ignored.
    non_causal = make_ring_attention(mesh, "sp", causal=False)
    with pytest.raises(ValueError, match="conflicts"):
        llama.apply_llama(params, ids, cfg, attn_fn=non_causal)


def test_llama_lora_train_decreases_loss():
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    lcfg = lora.LoraConfig(rank=4, targets=(r"w[qv]$",))
    adapters = lora.init_lora(jax.random.PRNGKey(2), params, lcfg)
    assert set(adapters["layers"]) == {"wq", "wv"}
    assert adapters["layers"]["wq"]["a"].shape == (
        cfg.num_layers, cfg.hidden_size, 4,
    )

    ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg.vocab_size)
    step = llama.make_lora_train_step(cfg, lr=1e-2)
    opt = llama.init_adam(adapters)
    losses = []
    for _ in range(10):
        adapters, opt, loss = step(adapters, opt, params, ids)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # Scale must remain untouched by the optimizer.
    np.testing.assert_allclose(
        adapters["layers"]["wq"]["scale"], lcfg.scaling, atol=1e-7
    )


# (B, T, rows a chunk): one sequence and several; a row count the chunk
# divides, one it does not (the last chunk is padded), one chunk for all.
HEAD_LOSS_CASES = [(1, 32, 8), (3, 16, 8), (3, 20, 16), (2, 9, 64)]


@pytest.mark.parametrize("head", ["float", "int8"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("batch,seq,chunk", HEAD_LOSS_CASES)
def test_frozen_head_loss_is_lm_loss_of_the_head(
    batch, seq, chunk, dtype, head, monkeypatch
):
    """The LoRA steps' fused, chunked head-and-loss against
    ``lm_loss(_lm_head(x)[:, :-1], ids[:, 1:])``: the value (the mean
    sums in another order) and ``d/dx`` (its product's operand rounded
    to the compute dtype, as the chip's transposed product rounds it)."""
    cfg = llama.llama_tiny(dtype=jnp.dtype(dtype))
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    if head == "int8":
        params = llama.quantize_llama_base(params)
    monkeypatch.setattr(llama, "HEAD_CHUNK_BYTES", 4 * cfg.vocab_size * chunk)
    rows = batch * seq
    assert llama.head_chunk_rows(rows, cfg.vocab_size) == min(chunk, rows)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    x = jax.random.normal(
        jax.random.PRNGKey(2), (batch, seq, cfg.hidden_size), cfg.dtype
    )

    def plain(x):
        logits = llama._lm_head(x, params, cfg)
        return llama.lm_loss(logits[:, :-1], ids[:, 1:])

    def fused(x):
        y = llama._rms_norm(x, params["final_norm"], cfg.rms_eps)
        head, out_scale = llama._head_matrix(params, cfg)
        return llama.frozen_head_loss(y, head, ids, out_scale)

    want, d_want = jax.value_and_grad(plain)(x)
    got, d_got = jax.value_and_grad(fused)(x)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # without a gradient (no d/dx in the chunks) the same number
    np.testing.assert_allclose(fused(x), got, rtol=1e-6)
    assert d_got.dtype == d_want.dtype == cfg.dtype
    d_want, d_got = (np.asarray(d, np.float32) for d in (d_want, d_got))
    # float32: the order of sums alone; bf16: one rounding (2^-8) of an
    # operand and one of the result, against the largest entry.
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6
    assert np.abs(d_got - d_want).max() <= tol * np.abs(d_want).max()


def test_lora_step_runs_the_head_product_twice():
    """A LoRA step's gradient holds the vocabulary product twice (the
    logits, and the input gradient the fused loss makes beside them):
    chunking it under a plain ``jax.checkpoint`` would make it three."""
    from tool.flash_sweep import _sub_jaxprs

    cfg = llama.llama_tiny(remat=True)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(1), params, lora.LoraConfig(rank=2)
    )
    ids = jnp.zeros((2, 12), jnp.int32)
    v = cfg.vocab_size

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in _sub_jaxprs(eqn):
                yield from walk(sub)

    grad = jax.grad(lambda a: llama.lora_loss(a, params, ids, cfg))
    eqns = list(walk(jax.make_jaxpr(grad)(adapters).jaxpr))
    head_products = [
        e for e in eqns if e.primitive.name == "dot_general"
        and any(v in x.aval.shape for x in e.invars)
    ]
    assert len(head_products) == 2


def test_lora_merge_matches_bypass():
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    lcfg = lora.LoraConfig(rank=2, targets=(r"w[qv]$",), init_scale=0.1)
    adapters = lora.init_lora(jax.random.PRNGKey(1), params, lcfg)
    # Give B nonzero values so the delta is nontrivial.
    adapters = jax.tree_util.tree_map(
        lambda x: x + 0.05 if x.ndim >= 2 else x, adapters
    )
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab_size)
    bypass = llama.apply_llama(params, ids, cfg, lora=adapters)
    merged = lora.merge_lora(params, adapters)
    folded = llama.apply_llama(merged, ids, cfg)
    np.testing.assert_allclose(bypass, folded, atol=1e-4, rtol=1e-4)


def test_llama_partition_rules():
    mesh = create_mesh({"fsdp": 2, "tp": 4})
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    shardings = shard_params_by_rules(mesh, params, llama.PARTITION_RULES)
    assert "tp" in str(shardings["layers"]["wq"].spec)
    assert "fsdp" in str(shardings["embed"].spec)
    strategy = ShardingStrategy(mesh=mesh, param_rules=llama.PARTITION_RULES)
    sharded = strategy.shard_params(params)
    ids = jnp.zeros((2, 8), jnp.int32)
    logits = jax.jit(lambda p, i: llama.apply_llama(p, i, cfg))(sharded, ids)
    assert logits.shape == (2, 8, cfg.vocab_size)


def test_llama_remat():
    cfg = llama.llama_tiny(remat=True)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)

    def loss(p):
        return llama.lm_loss(llama.apply_llama(p, ids, cfg)[:, :-1], ids[:, 1:])

    g = jax.grad(loss)(params)
    assert jnp.all(jnp.isfinite(g["embed"]))


def test_llama_remat_dots_policy():
    """The selective ('dots') policy must differentiate like full remat
    and match its gradients (coverage for the bench's TPU config)."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 256)

    def grad_for(policy):
        cfg = llama.llama_tiny(remat=True, remat_policy=policy)
        params = llama.init_llama(jax.random.PRNGKey(0), cfg)

        def loss(p):
            return llama.lm_loss(
                llama.apply_llama(p, ids, cfg)[:, :-1], ids[:, 1:]
            )

        return jax.grad(loss)(params)

    g_full = grad_for(None)
    g_dots = grad_for("dots")
    for a, b in zip(
        jax.tree_util.tree_leaves(g_full), jax.tree_util.tree_leaves(g_dots)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_llama_kv_cache_decode_matches_full_forward():
    """Token-at-a-time decode through the static-shape KV cache must
    reproduce the full causal forward's logits at every position."""
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    ref = llama.apply_llama(params, ids, cfg)

    cache = llama.init_kv_cache(cfg, 2, 12)
    step = llama.make_decode_step(cfg)
    outs = []
    for t in range(12):
        cache, logits = step(params, cache, ids[:, t], t)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_llama_sliding_window_forward_and_decode():
    """config.sliding_window applies uniformly: the training forward
    (dense and flash attn_fn agree) and the KV-cache decode step produce
    identical logits, and differ from the unwindowed model."""
    cfg_full = llama.llama_tiny()
    cfg = llama.llama_tiny(sliding_window=4)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg_full)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)

    ref = llama.apply_llama(params, ids, cfg)
    via_flash = llama.apply_llama(
        params, ids, cfg,
        attn_fn=lambda q, k, v, **kw: flash_attention(
            q, k, v, block_q=8, block_k=8, **kw
        ),
    )
    np.testing.assert_allclose(
        np.asarray(via_flash), np.asarray(ref), atol=2e-4, rtol=2e-4
    )

    cache = llama.init_kv_cache(cfg, 2, 12)
    step = llama.make_decode_step(cfg)
    outs = []
    for t in range(12):
        cache, logits = step(params, cache, ids[:, t], t)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(ref), rtol=2e-4, atol=2e-4
    )

    # The window genuinely restricts attention (t=11 sees only 8..11).
    full = llama.apply_llama(params, ids, cfg_full)
    assert not np.allclose(np.asarray(ref[:, -1]), np.asarray(full[:, -1]))


@pytest.mark.parametrize("kv_quant", [False, True])
def test_llama_rolling_cache_matches_linear(kv_quant):
    """The O(W) ring-buffer decode reproduces the linear sliding-window
    decode exactly — prefill, conversion, and many overwrite cycles —
    composing with the int8 cache (scales roll with their planes)."""
    cfg = llama.llama_tiny(sliding_window=4, kv_quant=kv_quant)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    t0, n_new = 6, 10
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, t0), 0, cfg.vocab_size)
    max_len = t0 + n_new

    cache_lin, logits_lin = llama.prefill(params, cfg, ids, max_len)
    step_lin = llama.make_decode_step(cfg)

    cache_roll = llama.roll_kv_cache(cache_lin, cfg, t0)
    assert cache_roll["k"].shape[2] == 4  # O(W) memory
    step_roll = llama.make_decode_step(cfg, rolling=True)

    logits_roll = logits_lin
    tok = jnp.argmax(logits_lin, axis=-1).astype(ids.dtype)
    for i in range(n_new):
        cache_lin, logits_lin = step_lin(params, cache_lin, tok, t0 + i)
        cache_roll, logits_roll = step_roll(params, cache_roll, tok, t0 + i)
        np.testing.assert_allclose(
            np.asarray(logits_roll), np.asarray(logits_lin),
            rtol=2e-4, atol=2e-4,
        )
        tok = jnp.argmax(logits_lin, axis=-1).astype(ids.dtype)


@pytest.mark.parametrize("kv_quant", [False, True])
def test_llama_rolling_cache_deep_wraparound(kv_quant):
    """pos ≫ window: 100 decoded tokens over a 16-slot ring (6+ full
    overwrite cycles) match the linear sliding-window decode at EVERY
    step, on bf16 and int8 KV alike — ring-buffer index bugs live at
    large pos where (pos − i) mod W has cycled many times, not at the
    first wrap."""
    cfg = llama.llama_tiny(sliding_window=16, kv_quant=kv_quant)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    t0, n_new = 7, 100
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, t0), 0, cfg.vocab_size)
    max_len = t0 + n_new

    cache_lin, logits_lin = llama.prefill(params, cfg, ids, max_len)
    step_lin = llama.make_decode_step(cfg)
    cache_roll = llama.roll_kv_cache(cache_lin, cfg, t0)
    assert cache_roll["k"].shape[2] == 16  # O(W), independent of n_new
    step_roll = llama.make_decode_step(cfg, rolling=True)

    tok = jnp.argmax(logits_lin, axis=-1).astype(ids.dtype)
    for i in range(n_new):
        cache_lin, l_lin = step_lin(params, cache_lin, tok, t0 + i)
        cache_roll, l_roll = step_roll(params, cache_roll, tok, t0 + i)
        np.testing.assert_allclose(
            np.asarray(l_roll), np.asarray(l_lin), rtol=2e-4, atol=2e-4,
            err_msg=f"diverged at decode step {i} (pos {t0 + i})",
        )
        tok = jnp.argmax(l_lin, axis=-1).astype(ids.dtype)


def test_llama_rolling_cache_short_prompt():
    """t0 < W: unwritten ring slots must be masked, not attended."""
    cfg = llama.llama_tiny(sliding_window=8)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 3), 0, cfg.vocab_size)
    cache_lin, logits = llama.prefill(params, cfg, ids, 16)
    cache_roll = llama.roll_kv_cache(cache_lin, cfg, 3)
    step_lin = llama.make_decode_step(cfg)
    step_roll = llama.make_decode_step(cfg, rolling=True)
    tok = jnp.argmax(logits, axis=-1).astype(ids.dtype)
    for i in range(5):
        cache_lin, l_lin = step_lin(params, cache_lin, tok, 3 + i)
        cache_roll, l_roll = step_roll(params, cache_roll, tok, 3 + i)
        np.testing.assert_allclose(
            np.asarray(l_roll), np.asarray(l_lin), rtol=2e-4, atol=2e-4
        )
        tok = jnp.argmax(l_lin, axis=-1).astype(ids.dtype)


def test_llama_rolling_requires_window():
    cfg = llama.llama_tiny()
    with pytest.raises(ValueError, match="sliding_window"):
        llama.make_decode_step(cfg, rolling=True)
    with pytest.raises(ValueError, match="sliding_window"):
        llama.init_rolling_kv_cache(cfg, 1)


def test_llama_kv_quant_decode_close_and_compact():
    """int8 KV cache: decode logits track the exact forward closely
    (int8 error budget), greedy choices almost always agree, and the
    cache bytes shrink by the expected factor."""
    cfg0 = llama.llama_tiny()
    cfg = llama.llama_tiny(kv_quant=True)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg0.vocab_size)
    ref = llama.apply_llama(params, ids, cfg0)

    cache = llama.init_kv_cache(cfg, 2, 12)
    step = llama.make_decode_step(cfg)
    outs = []
    for t in range(12):
        cache, logits = step(params, cache, ids[:, t], t)
        outs.append(logits)
    dec = np.asarray(jnp.stack(outs, axis=1))
    assert np.max(np.abs(dec - np.asarray(ref))) < 0.15
    agree = (dec.argmax(-1) == np.asarray(ref).argmax(-1)).mean()
    assert agree >= 0.9, agree

    # f32 reference cache: int8 + per-(pos, head) f32 scales over
    # Dh=16 is 1.25 bytes/elem vs 4 → ~0.31.
    bytes_q = sum(v.nbytes for v in cache.values())
    bytes_f = sum(
        v.nbytes for v in llama.init_kv_cache(cfg0, 2, 12).values()
    )
    assert bytes_q / bytes_f < 0.35


def test_llama_kv_quant_prefill_matches_sequential():
    """Prefill's quantized cache agrees with sequentially-built cache
    (dequantized values; the projections differ by matmul-shape ulps)."""
    cfg = llama.llama_tiny(kv_quant=True)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)

    cache_p, _ = llama.prefill(params, cfg, ids, 12)
    cache_s = llama.init_kv_cache(cfg, 2, 12)
    step = llama.make_decode_step(cfg)
    for t in range(8):
        cache_s, _ = step(params, cache_s, ids[:, t], t)
    for plane, scale in (("k", "k_scale"), ("v", "v_scale")):
        deq_p = np.asarray(cache_p[plane], np.float32) * np.asarray(cache_p[scale])
        deq_s = np.asarray(cache_s[plane], np.float32) * np.asarray(cache_s[scale])
        # The projections differ by matmul-shape-dependent rounding,
        # which the per-row scale amplifies on small-magnitude rows —
        # so bound the error relative to each row's absmax (the int8
        # quantization budget), not elementwise.
        row_absmax = np.maximum(
            np.abs(deq_s).max(axis=-1, keepdims=True), 1e-9
        )
        assert np.max(np.abs(deq_p - deq_s) / row_absmax) < 0.05


def test_llama_kv_quant_generate():
    """End-to-end greedy generation runs under kv_quant and matches the
    exact-cache generation for a short horizon."""
    cfg0 = llama.llama_tiny()
    cfg = llama.llama_tiny(kv_quant=True)
    params = llama.init_llama(jax.random.PRNGKey(0), cfg0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg0.vocab_size)
    exact = llama.greedy_generate(params, cfg0, ids, 6)
    quant = llama.greedy_generate(params, cfg, ids, 6)
    assert quant.shape == exact.shape
    # Greedy paths can diverge once a near-tie flips; require agreement
    # on the first couple of generated tokens (deterministic seeds).
    np.testing.assert_array_equal(
        np.asarray(quant[:, :10]), np.asarray(exact[:, :10])
    )


def test_llama_prefill_matches_sequential_decode():
    """Batched prefill must produce the same cache + last-token logits
    as feeding the prompt through the decode step one token at a time."""
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)

    cache_p, logits_p = llama.prefill(params, cfg, ids, 12)
    cache_s = llama.init_kv_cache(cfg, 2, 12)
    step = llama.make_decode_step(cfg)
    for t in range(8):
        cache_s, logits_s = step(params, cache_s, ids[:, t], t)
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_p[key]), np.asarray(cache_s[key]),
            rtol=1e-4, atol=1e-5,
        )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_s), rtol=2e-4, atol=2e-4
    )
    with pytest.raises(ValueError, match="max_len"):
        llama.prefill(params, cfg, ids, 4)


def test_llama_greedy_generate():
    """Generated tokens must equal the full forward's argmax at each
    position (self-consistency of prefill + generation scans)."""
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, cfg.vocab_size)
    gen = llama.greedy_generate(params, cfg, prompt, 6)
    assert gen.shape == (2, 10)
    full = llama.apply_llama(params, gen, cfg)
    for t in range(4, 10):
        np.testing.assert_array_equal(
            np.asarray(gen[:, t]),
            np.asarray(jnp.argmax(full[:, t - 1], axis=-1)),
        )


def test_llama_sampled_generate():
    """Sampling: valid token range, deterministic per key, top_k
    truncation only draws from the k most likely tokens."""
    cfg = llama.llama_tiny()
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, cfg.vocab_size)

    g1 = llama.generate(
        params, cfg, prompt, 5, temperature=1.0, key=jax.random.PRNGKey(3)
    )
    g2 = llama.generate(
        params, cfg, prompt, 5, temperature=1.0, key=jax.random.PRNGKey(3)
    )
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    assert g1.shape == (2, 9)
    assert int(jnp.min(g1)) >= 0 and int(jnp.max(g1)) < cfg.vocab_size

    # top_k=1 must equal greedy regardless of temperature.
    topk1 = llama.generate(
        params, cfg, prompt, 5, temperature=2.0, top_k=1,
        key=jax.random.PRNGKey(4),
    )
    greedy = llama.greedy_generate(params, cfg, prompt, 5)
    np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))

    # top_k=2 at a hot temperature: every sampled token must be one of
    # the 2 most likely continuations of its prefix (truncation really
    # constrains the draw).
    topk2 = llama.generate(
        params, cfg, prompt, 8, temperature=5.0, top_k=2,
        key=jax.random.PRNGKey(5),
    )
    full = llama.apply_llama(params, topk2, cfg)
    for t in range(4, 12):
        allowed = jax.lax.top_k(full[:, t - 1], 2)[1]
        for row in range(2):
            assert int(topk2[row, t]) in np.asarray(allowed[row]), (row, t)

    with pytest.raises(ValueError, match="key"):
        llama.generate(params, cfg, prompt, 5, temperature=1.0)
    with pytest.raises(ValueError, match="sampling arguments"):
        llama.generate(params, cfg, prompt, 5, top_k=4)
    with pytest.raises(ValueError, match="temperature"):
        llama.generate(params, cfg, prompt, 5, temperature=-1.0)


def test_llama_remat_policy_validation():
    import pytest

    with pytest.raises(ValueError, match="remat_policy"):
        llama.llama_tiny(remat=True, remat_policy="bogus")
    with pytest.raises(ValueError, match="remat=False"):
        llama.llama_tiny(remat=False, remat_policy="dots")
