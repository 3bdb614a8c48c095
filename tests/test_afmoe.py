"""The configurable decoder (``rayfed_tpu.models.decoder``) and the
expert share (``moe.apply_expert_share``) against the plain AFMoE
reference (``benchmark/reference/afmoe.py``), at toy widths on the CPU.

The reference is the benchmark's (the cell's ``correct`` is decided by
the same functions at the published widths on the chip), so a change to
either side is caught here first.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.families import afmoe_lm
from benchmark.reference import afmoe as ref
from rayfed_tpu import telemetry
from rayfed_tpu.models import decoder, llama, lora, moe
from rayfed_tpu.ops.attention import dot_product_attention

# Toy widths: a dense windowed layer, a windowed expert layer, a full
# expert layer (two groups: the expert layers are one scan whose body
# picks the attention kind); the sequence (24) is three windows (8) long.
D, HEADS, KV, DH = 32, 4, 2, 8
E, HELD, TOPK, WINDOW, T, VOCAB = 8, (0, 1, 2, 3), 3, 8, 24, 64
LAYER_TYPES = ("sliding_attention", "sliding_attention", "full_attention")
SPECS = (
    decoder.LayerSpec("window", "dense"),
    decoder.LayerSpec("window", "moe"),
    decoder.LayerSpec("full", "moe"),
)


def toy_config(dtype=jnp.float32, held=HELD, **kw):
    experts = moe.ExpertShareConfig(
        num_experts=E, held=held, top_k=TOPK, d_model=D, d_ff=16,
        route_scale=2.826,
    )
    return decoder.DecoderConfig(
        layers=SPECS, vocab_size=VOCAB, hidden_size=D, num_heads=HEADS,
        num_kv_heads=KV, head_dim=DH, intermediate_size=48,
        sliding_window=WINDOW, embed_scale=D**0.5, experts=experts,
        dtype=dtype, param_dtype=jnp.float32, **kw,
    )


def ref_kwargs(cfg, **kw):
    return dict(
        layer_types=LAYER_TYPES, num_dense_layers=1, num_heads=HEADS,
        num_kv_heads=KV, head_dim=DH, window=WINDOW,
        rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
        embed_scale=cfg.embed_scale, held=cfg.experts.held, top_k=TOPK,
        route_scale=cfg.experts.route_scale, block=T, **kw,
    )


def make(seed=0, cfg=None, trained=True):
    """(config, base, adapters, ids).  ``trained``: adapters with a
    non-zero B, so that every adapter leaf has a gradient of its own."""
    cfg = cfg or toy_config()
    base = decoder.init_decoder(jax.random.PRNGKey(seed), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(seed + 1), base,
        lora.LoraConfig(rank=2, alpha=4.0, targets=decoder.ALL_LINEAR),
    )
    if trained:
        keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2), 200))
        adapters = jax.tree_util.tree_map_with_path(
            lambda path, x: x if x.ndim < 2 or path[-1].key != "b"
            else 0.05 * jax.random.normal(next(keys), x.shape),
            adapters,
        )
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (1, T), 0, VOCAB)
    return cfg, base, adapters, ids


def plain(tree, cfg):
    """The reference's layout: layer by layer."""
    return decoder.unstack(tree, cfg)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def test_layers_with_one_ffn_kind_are_one_stacked_group():
    """The published first nine layers are two groups (the dense layer;
    eight expert layers that mix window and full attention); parameters
    and adapters are stacked a group, and ``unstack`` gives both layer
    by layer, an adapter's one ``scale`` to each of its layers."""
    nine = (decoder.LayerSpec("window", "dense"),) + (
        decoder.LayerSpec("window", "moe"), decoder.LayerSpec("window", "moe"),
        decoder.LayerSpec("full", "moe"), decoder.LayerSpec("window", "moe"),
    ) * 2
    assert dataclasses.replace(toy_config(), layers=nine).groups() == ((0, 1), (1, 9))
    same = dataclasses.replace(toy_config(), layers=nine[1:3]).groups()
    assert same == ((0, 2),)
    cfg, base, adapters, _ = make()
    assert cfg.groups() == ((0, 1), (1, 3))
    assert base["layers"][1]["moe"]["router"].shape == (2, D, E)
    assert adapters["layers"]["1"]["moe"]["experts"]["w_up"]["a"].shape == (
        2, len(HELD), D, 2
    )
    layers = decoder.unstack(base, cfg)["layers"]
    assert len(layers) == 3 and "moe" not in layers[0]
    np.testing.assert_array_equal(
        layers[2]["wq"], base["layers"][1]["wq"][1]
    )
    by_layer = decoder.unstack(adapters, cfg)["layers"]
    assert sorted(by_layer) == ["0", "1", "2"]
    entry = by_layer["2"]["moe"]["shared"]["w_down"]
    assert entry["a"].shape == (16, 2) and float(entry["scale"]) == 2.0
    np.testing.assert_array_equal(
        entry["b"], adapters["layers"]["1"]["moe"]["shared"]["w_down"]["b"][1]
    )


def test_adapters_cover_every_linear_matrix_but_the_router():
    """Rank 8 on the published shapes, counted by hand in ISSUE 28:
    attention 188,416 a layer, the dense FFN 196,608, the shared expert
    73,728, sixteen held experts 1,179,648; depth 9 has one dense and
    eight expert layers."""
    experts = moe.ExpertShareConfig(route_scale=2.826)
    cfg = decoder.DecoderConfig(
        layers=(decoder.LayerSpec("window", "dense"),)
        + (decoder.LayerSpec("window", "moe"),) * 8,
        experts=experts,
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
    assert lora.num_lora_params(adapters) == 11_919_360
    assert (188_416 + 196_608) + 8 * (188_416 + 73_728 + 1_179_648) == 11_919_360
    paths = [
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(adapters)
    ]
    assert not any("router" in p for p in paths)
    assert any("moe/experts/w_down" in p for p in paths)


def test_float32_system_matches_the_reference(grouped):
    """Logits, loss and the gradient of every adapter leaf.  Both sides
    are float32 and differ in the order of their sums (the system sorts
    tokens by expert and adds a low-rank bypass; the reference merges
    ``W + AB`` and masks): 1e-4 relative is a hundred float32 roundings,
    and every mistake in the mathematics is of order one."""
    cfg, base, adapters, ids = make()
    kw = ref_kwargs(cfg)

    def sys_loss(a):
        logits, aux = decoder.apply_decoder(base, ids, cfg, lora=a)
        return llama.lm_loss(logits[:, :-1], ids[:, 1:]), (logits, aux)

    (loss, (logits, aux)), grads = jax.value_and_grad(
        sys_loss, has_aux=True
    )(adapters)
    want_logits, infos = ref.forward(
        plain(base, cfg), ids[0], lora=plain(adapters, cfg), **kw
    )
    want_loss, want_grads = ref.lora_gradients(
        plain(base, cfg), plain(adapters, cfg), ids[0], **kw
    )
    assert rel_rms(logits[0], want_logits) < 1e-4
    assert abs(float(loss) - float(want_loss)) < 1e-4 * float(want_loss)
    for i in (1, 2):
        np.testing.assert_array_equal(
            np.sort(aux[i]["selected"], -1), np.sort(infos[i]["selected"], -1)
        )
        np.testing.assert_array_equal(aux[i]["counts"], infos[i]["counts"])
    flat_got = jax.tree_util.tree_leaves_with_path(plain(grads, cfg))
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) > 40
    for (path, got), want in zip(flat_got, flat_want):
        if path[-1].key == "scale":
            continue  # the system holds it constant (stop_gradient)
        assert float(jnp.abs(want).max()) > 0, path
        assert rel_rms(got, want) < 1e-4, path


@pytest.mark.parametrize("attn", ["dense", "flash"])
def test_unrepeated_kv_matches_repeated(attn):
    """The decoder hands its 2 K/V heads to the ``attn_fn`` unrepeated
    (and rotates the unrepeated K): logits and adapter gradients equal
    those of the explicit repeat to 4 heads, dense and through the flash
    kernel (blocks of 8: one window)."""
    from rayfed_tpu.ops.flash_attention import flash_attention

    cfg, base, adapters, ids = make(seed=11)

    def repeated(q, k, v, **kw):
        rep = lambda x: jnp.repeat(x, HEADS // KV, axis=2)
        return dot_product_attention(q, rep(k), rep(v), **kw)

    attn_fn = dot_product_attention if attn == "dense" else (
        lambda q, k, v, **kw: flash_attention(q, k, v, block_q=8, block_k=8, **kw)
    )

    def loss(a, attn_fn):
        logits, _ = decoder.apply_decoder(base, ids, cfg, lora=a, attn_fn=attn_fn)
        return llama.lm_loss(logits[:, :-1], ids[:, 1:]), logits

    (_, got), g_got = jax.value_and_grad(loss, has_aux=True)(adapters, attn_fn)
    (_, want), g_want = jax.value_and_grad(loss, has_aux=True)(adapters, repeated)
    assert rel_rms(got, want) < 1e-5
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_got), jax.tree_util.tree_leaves(g_want)
    ):
        if path[-1].key == "scale":
            continue  # the system holds it constant (stop_gradient)
        assert rel_rms(a, b) < 1e-4, path


def test_recomputing_layers_changes_no_gradient():
    """``remat`` (``jax.checkpoint`` of the scanned body) keeps a layer's
    inputs and runs it again in the backward pass: the gradients, of the
    adapters and of the base alike, are the ones the plain backward pass
    gives, to float32 rounding (the compiler fuses the two programs
    differently: a few ulps on the smallest entries).  The held experts'
    matrices are the frozen base by statement (``stop_gradient`` in
    ``apply_expert_share``): zero either way."""
    cfg, base, adapters, ids = make(seed=7)

    def grads(cfg):
        def loss(a, b):
            logits, _ = decoder.apply_decoder(b, ids, cfg, lora=a)
            return llama.lm_loss(logits[:, :-1], ids[:, 1:])
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(adapters, base)

    once_loss, once = grads(cfg)
    again_loss, again = grads(dataclasses.replace(cfg, remat=True))
    assert abs(float(once_loss) - float(again_loss)) < 1e-6
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(once),
        jax.tree_util.tree_leaves(again),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7, err_msg=str(path))
    for of in (once, again):
        held = of[1]["layers"][1]["moe"]["experts"]
        assert all(not np.any(g) for g in jax.tree_util.tree_leaves(held))
        assert np.any(of[1]["layers"][1]["moe"]["shared"]["w_up"])
        assert np.any(of[1]["layers"][1]["wq"])


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight experts in four shares of two: the routed parts the shares
    compute, with the shared expert counted once, are the uncut
    reference's expert layer (float32, sums in another order: 1e-5)."""
    full = moe.ExpertShareConfig(
        num_experts=E, held=tuple(range(E)), top_k=TOPK, d_model=D,
        d_ff=16, route_scale=2.826,
    )
    p = moe.init_expert_share(jax.random.PRNGKey(4), full)
    m = jax.random.normal(jax.random.PRNGKey(5), (T, D))
    kw = dict(top_k=TOPK, route_scale=2.826)
    want, _ = ref.expert_layer(m, p, held=full.held, **kw)
    shared_only, _ = ref.expert_layer(
        m, dict(p, experts=jax.tree_util.tree_map(lambda w: w[:0], p["experts"])),
        held=(), **kw,
    )
    total = jnp.zeros_like(m)
    held_seen = 0
    for s in range(4):
        held = (2 * s, 2 * s + 1)
        share = dataclasses.replace(full, held=held)
        ps = dict(p, experts=jax.tree_util.tree_map(
            lambda w: w[jnp.asarray(held)], p["experts"]
        ))
        out, aux = moe.apply_expert_share(ps, m, share)
        total = total + (out - shared_only)
        held_seen += int(aux["held_assignments"])
        # the reference, given the same share, computes the same part
        part, _ = ref.expert_layer(m, ps, held=held, **kw)
        assert rel_rms(out, part) < 1e-5
    assert held_seen == T * TOPK  # every (token, choice) lives somewhere
    assert rel_rms(total + shared_only, want) < 1e-5


def test_no_token_is_dropped_under_imbalance(grouped, monkeypatch):
    """A selection bias that sends every token to held expert 1 (and
    most to 0 and 2): the held assignments fill more than a chunk sized
    for the expectation alone, so the loop takes a second, every
    assignment is multiplied (``counts`` are the rows the grouped
    products were given, chunk by chunk), and the result is the
    reference's."""
    monkeypatch.setattr(moe, "CHUNK_HEADROOM", 1.0)
    cfg = moe.ExpertShareConfig(
        num_experts=E, held=HELD, top_k=TOPK, d_model=D, d_ff=16,
        route_scale=2.826,
    )
    p = moe.init_expert_share(jax.random.PRNGKey(6), cfg)
    p["router_bias"] = jnp.asarray([2.0, 9.0, 1.0, 0, 0, 0, 0, 0])
    m = jax.random.normal(jax.random.PRNGKey(7), (64, D))
    rows, most = moe._chunk_rows(64, cfg)
    out, aux = jax.jit(
        lambda p, m: moe.apply_expert_share(p, m, cfg)
    )(p, m)
    want, info = ref.expert_layer(
        m, p, held=HELD, top_k=TOPK, route_scale=2.826
    )
    assert most == 2 and int(aux["held_assignments"]) > rows  # two chunks
    assert int(aux["counts"][1]) == 64  # every token reached expert 1
    assert int(aux["counts"].sum()) == int(aux["held_assignments"])
    np.testing.assert_array_equal(aux["counts"], info["counts"])
    assert rel_rms(out, want) < 1e-5
    # ... and the backward pass takes both chunks too
    g = jax.grad(lambda m: moe.apply_expert_share(p, m, cfg)[0].sum())(m)
    g_want = jax.grad(lambda m: ref.expert_layer(
        m, p, held=HELD, top_k=TOPK, route_scale=2.826)[0].sum())(m)
    assert rel_rms(g, g_want) < 1e-5


@pytest.mark.parametrize("piece", ref.PIECES)
def test_each_piece_of_the_mathematics_bites(piece):
    """The reference with one piece removed is no longer what the system
    computes: the logits differ by far more than the 1e-4 the float32
    comparison allows (each by over 1%)."""
    cfg, base, adapters, ids = make(seed=11)
    logits, _ = decoder.apply_decoder(base, ids, cfg, lora=adapters)
    pb, pa = plain(base, cfg), plain(adapters, cfg)
    whole, _ = ref.forward(pb, ids[0], lora=pa, **ref_kwargs(cfg))
    less, _ = ref.forward(pb, ids[0], lora=pa, **ref_kwargs(cfg, omit=(piece,)))
    assert rel_rms(logits[0], whole) < 1e-4
    assert rel_rms(logits[0], less) > 1e-2, piece


def test_bfloat16_against_float32_across_the_discontinuity():
    """The comparison that decides ``correct`` on the chip, at toy size.
    (a) every expert the bf16 system selected has a float32 score
    ``s + b`` within ``delta`` of the reference's k-th best: bf16 rounds
    the normed stream to 2^-9 relative, the router sums 32 such terms of
    unit size in float32, so scores move by about sqrt(32) * 2^-9 / 4
    (the sigmoid's slope) = 3e-3 at most a few times over; ``delta`` =
    0.02.  (b) with the reference given the system's selection the
    logits agree to bf16's accumulated rounding (1.5%, the bound the
    dense decoder's cell uses for as many layers)."""
    cfg, base, adapters, ids = make(seed=21, cfg=toy_config(jnp.bfloat16))
    logits, aux = decoder.apply_decoder(base, ids, cfg, lora=adapters)
    pb, pa = plain(base, cfg), plain(adapters, cfg)
    _, infos = ref.forward(pb, ids[0], lora=pa, **ref_kwargs(cfg))
    chosen = {i: aux[i]["selected"] for i in aux}
    want, _ = ref.forward(
        pb, ids[0], lora=pa, **ref_kwargs(cfg, selected=chosen)
    )
    for i in aux:
        shortfall, exact = ref.routing_agreement(
            infos[i]["biased"], aux[i]["selected"], TOPK
        )
        assert float(shortfall) < 0.02, i
        assert float(exact) > 0.9, i
    assert rel_rms(logits[0], want) < 0.015


def test_the_step_records_its_routing_only_while_armed():
    """Disarmed the step keeps nothing; armed it keeps each call's
    counts on the device and ``flush_routing`` writes one ``moe.counts``
    record a call (none before the flush: no step waits for a fetch)."""
    cfg, base, adapters, ids = make(seed=31, trained=False)
    step = decoder.make_lora_train_step(cfg, lr=1e-3)
    opt = llama.init_adam(adapters)
    assert telemetry.active() is None
    new, opt, loss, counts = step(adapters, opt, base, ids)
    assert counts.shape == (2, len(HELD) + 1) and np.isfinite(float(loss))
    rec = telemetry.install(capacity=64)
    try:
        step.flush_routing()  # the disarmed call left nothing behind
        new, opt, _, _ = step(new, opt, base, ids)
        step(new, opt, base, ids)
        assert not [r for r in rec.records() if r.phase == "moe.counts"]
        step.flush_routing()
        step.flush_routing()
        rows = [r for r in rec.records() if r.phase == "moe.counts"]
    finally:
        telemetry.uninstall()
    assert len(rows) == 2 and rows[0].t_start < rows[1].t_start
    detail = rows[0].detail
    assert detail["dropped"] == 0 and detail["tokens"] == T
    assert detail["chunk_rows"] == moe._chunk_rows(T, cfg.experts)[0]
    assert [layer["layer"] for layer in detail["layers"]] == [1, 2]
    for layer in detail["layers"]:
        assert len(layer["counts"]) == len(HELD)
        assert layer["held_share"] == sum(layer["counts"]) / (T * TOPK)
        assert layer["dropped"] == 0


@pytest.mark.parametrize("path", ["packed", "streaming"])
def test_two_parties_federate_the_adapters(path):
    """``fed.remote`` trainers and ``run_fedavg_rounds`` on the toy
    decoder: the aggregate is the numpy FedAvg of the two updates, and
    an expert that no token of a party reached comes back from that
    party exactly as it went out."""
    import rayfed_tpu as fed
    from rayfed_tpu import fl, inprocess
    from rayfed_tpu.fl.trainer import run_fedavg_rounds

    held = (0, 1, 2, 5)
    cfg, base, adapters, _ = make(
        seed=41, cfg=toy_config(held=held), trained=False
    )
    # Expert 5 is held and never selected: its bias sinks it.
    experts = base["layers"][1]["moe"]  # the group of both expert layers
    experts["router_bias"] = experts["router_bias"].at[:, 5].set(-9.0)
    step = decoder.make_lora_train_step(cfg, lr=1e-2)
    parties = ["alice", "bob"]
    sent = {}
    kwargs = dict(compress_wire=True, packed_wire=True)
    if path == "streaming":
        kwargs["streaming_agg"] = True

    def party_main(party):
        @fed.remote
        class Trainer:
            def __init__(self, owner, seed):
                self._owner = owner
                self._ids = jax.random.randint(
                    jax.random.PRNGKey(seed), (1, T), 0, VOCAB
                )

            def train(self, bundle):
                came = tree = fl.decompress(bundle)
                opt = llama.init_adam(tree)
                for _ in range(2):
                    tree, opt, _, counts = step(tree, opt, base, self._ids)
                sent[self._owner] = (tree, np.asarray(counts), came)
                return fl.compress(tree, packed=True)

        trainers = {
            p: Trainer.party(p).remote(p, 50 + i)
            for i, p in enumerate(parties)
        }
        final = run_fedavg_rounds(trainers, adapters, 1, **kwargs)
        return jax.tree_util.tree_map(np.asarray, final)

    out = inprocess.run_parties(
        party_main, inprocess.loopback_cluster(parties), timeout=180,
        logging_level="warning",
    )
    flat = lambda tree: np.concatenate([
        np.asarray(x, np.float32).ravel()
        for x in jax.tree_util.tree_leaves(tree)
    ])
    got = flat(out["alice"])
    np.testing.assert_array_equal(got, flat(out["bob"]))
    # Each update crosses the wire as bf16; the float32 mean of the two
    # is cast to bf16 once: half an ulp (2^-9) of the larger value.
    as_sent = [
        np.asarray(jnp.asarray(flat(sent[p][0]), jnp.bfloat16), np.float32)
        for p in parties
    ]
    want = (as_sent[0] + as_sent[1]) / np.float32(2)
    np.testing.assert_allclose(got, want, rtol=2.0**-8, atol=1e-7)
    for p in parties:
        tree, counts, came = sent[p]
        start = jax.tree_util.tree_map(np.asarray, came)
        assert (counts[:, 3] == 0).all() and (counts[:, 0] > 0).all()
        for name, entry in tree["layers"]["1"]["moe"]["experts"].items():
            was = start["layers"]["1"]["moe"]["experts"][name]
            for side in ("a", "b"):  # [expert layer, held expert, ...]
                np.testing.assert_array_equal(
                    np.asarray(entry[side])[:, 3], was[side][:, 3]
                )
                for i in (0, 1):
                    assert not np.array_equal(
                        np.asarray(entry[side])[i, 0], was[side][i, 0]
                    ), (p, i, name, side)


def test_balancing_the_selection_bias_evens_the_loads():
    """Random weights send most tokens to a few experts (their streams
    share a component); the family's ``selection_biases`` (set-up
    of the benchmark's random weights, layer by layer through
    ``decoder.apply_block``) leaves a bias under which every expert of
    every layer is chosen about ``top_k / E`` of the time, on the batch
    it was balanced on and on a fresh one."""
    cfg = toy_config(held=tuple(range(E)))
    base = decoder.init_decoder(jax.random.PRNGKey(3), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(4), (4, 64), 0, VOCAB)
    fresh = jax.random.randint(jax.random.PRNGKey(5), (4, 64), 0, VOCAB)

    def worst(params, ids):
        _, aux = decoder.apply_decoder(params, ids, cfg)
        return max(
            float(a["counts"].max() / a["counts"].mean()) for a in aux.values()
        )

    balanced = afmoe_lm.with_selection_biases(base, afmoe_lm.selection_biases(
        base, ids, cfg, attn_fn=dot_product_attention
    ))
    assert worst(base, ids) > 1.5 and worst(base, fresh) > 1.5
    assert worst(balanced, ids) < 1.25
    # 256 fresh tokens, 96 a expert expected: sampling noise of 10% each
    assert worst(balanced, fresh) < min(1.6, worst(base, fresh))
    bias = balanced["layers"][1]["moe"]["router_bias"]
    assert bias.shape == (2, E) and float(jnp.abs(bias).min(axis=0).max()) > 0
