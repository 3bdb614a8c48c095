"""Family ``afmoe_lm``: sparse-expert decoders of the AFMoE family
(arcee-ai Trinity) as one chip's share of a silo, through
``rayfed_tpu.models.decoder`` and ``moe.apply_expert_share``.

The interface of ``llama_lm.py``: the global tree (LoRA adapters), each
party's resident state (data pool, frozen base), one local step, items
and FLOPs per step, and the agreement check against the plain reference
(``benchmark/reference/afmoe.py``).  ``job`` keys as there: ``adapter``
(``rank``, ``alpha``, ``targets``), ``lr``, ``local_steps``, ``batch``,
``seq_len``; a party holds ``local_steps`` batches.  The step also
returns every expert layer's token counts; while the flight recorder is
armed the program keeps them on the device and writes them at the end
of the round (``moe.counts``); nothing is fetched otherwise.

Random weights bring no selection bias that balances the experts'
loads (a trained checkpoint brings its buffer), so set-up makes one
here (:func:`selection_biases`), layer by layer through
``decoder.apply_block``.
"""

from __future__ import annotations

import re
import threading

# The comparison across the selection's discontinuity has two parts;
# each limit lies between two readings taken on the chip at the
# published widths, 8,192 tokens, four layers (PERF.md section 4, PR 28):
# the bf16 system over its seeds, and the float32 reference recomputed
# with fp8 (e4m3) operands in every matrix product, which must fail.
#
# (a) DELTA: every expert the system selected must have a float32
# reference score ``s + b`` (on the stream the system's earlier choices
# made) no further than this below the reference's eighth best.  bf16
# rounds the normed stream to 2^-9 relative and the router sums 2,048
# products, so a score moves by a few thousandths: 0.0053-0.0079 read,
# fp8 0.108-0.115.
ROUTING_DELTA = 0.025
# ... and at least this share of (token, choice) pairs agree exactly:
# 0.9915-0.9927 read, fp8 0.893-0.899.
ROUTING_EXACT_MIN = 0.96
# (b) Logits of the last positions against the reference run with the
# system's own selection, relative RMS: 0.0085-0.0089 read (four layers
# of bf16: some tens of 2^-9 roundings a layer, in quadrature), fp8
# 0.133-0.135; a wrong band or full attention where a window is due
# gives errors of the logits' own size.
REFERENCE_REL_RMS_TOL = 0.03
# The loss over all 8,191 targets, relative.  Not a precision check (a
# mean over thousands of positions cancels rounding: 4e-7 to 8e-6 read,
# fp8 2e-5 to 5e-5, too close to part): it catches a wrong shift, wrong
# targets or a wrong reduction, which move it by a percent or more.
REFERENCE_LOSS_REL_TOL = 2e-4
REFERENCE_LAYERS = 4  # dense, two windowed expert layers, one full
BALANCE_SEQUENCES = 4  # sequences the selection bias is balanced on
REFERENCE_LAST = 256


def balancing_bias(scores, top_k: int):
    """The selection bias at which every expert clears the others for
    ``top_k / E`` of these tokens: minus each expert's ``1 - top_k / E``
    quantile of its scores, centred.  One step to where bias-only load
    balancing (no auxiliary loss: the bias of an overloaded expert is
    lowered, of a starved one raised, until loads are even) leaves the
    buffer of a trained model; random weights need it as much, since
    their streams share a component that sends every token to the same
    few experts."""
    import jax.numpy as jnp

    e = scores.shape[-1]
    q = jnp.quantile(scores.astype(jnp.float32), 1.0 - top_k / e, axis=0)
    return jnp.mean(q) - q


def selection_biases(params, input_ids, cfg, *, attn_fn) -> list:
    """Per group of layers the ``[layers, E]`` selection biases that
    balance the experts' loads on ``input_ids`` (None for a dense
    group): :func:`balancing_bias` layer by layer, each on the stream
    that the layers before it, already balanced, make (a layer runs
    twice: once for its scores, once with its bias for the stream).
    Give it several sequences: what one sequence alone prefers (random
    weights give every sequence a direction of its own) is not the
    weights' to balance."""
    import functools

    import jax

    from rayfed_tpu.models import decoder

    @functools.partial(jax.jit, static_argnames=("spec",))
    def block(x, group, j, bias, spec):
        lp = jax.tree_util.tree_map(lambda leaf: leaf[j], group)
        if bias is not None:
            lp = dict(lp, moe=dict(lp["moe"], router_bias=bias))
        x, aux = decoder.apply_block(
            x, lp, cfg, ffn=spec.ffn, attention=spec.attention,
            attn_fn=attn_fn,
        )
        if aux is None:
            return x, None
        scores = aux["scores"].reshape(-1, aux["scores"].shape[-1])
        return x, balancing_bias(scores, cfg.experts.top_k)

    x = jax.jit(lambda p, i: decoder.embed(p, i, cfg))(params, input_ids)
    out = []
    for group, (start, stop) in zip(params["layers"], cfg.groups()):
        biases = []
        for i in range(start, stop):
            after, bias = block(x, group, i - start, None, cfg.layers[i])
            if bias is not None:
                after, _ = block(x, group, i - start, bias, cfg.layers[i])
                biases.append(bias)
            x = after
        out.append(jax.numpy.stack(biases) if biases else None)
    return out


def with_selection_biases(params, biases: list):
    """``params`` with each expert group's ``router_bias`` replaced."""
    return dict(params, layers=[
        group if bias is None
        else dict(group, moe=dict(group["moe"], router_bias=bias))
        for group, bias in zip(params["layers"], biases)
    ])


def layer_specs(config: dict):
    from rayfed_tpu.models.decoder import LayerSpec

    kinds = config["layer_types"][: config["num_hidden_layers"]]
    return tuple(
        LayerSpec(
            "window" if kind == "sliding_attention" else "full",
            "dense" if i < config["num_dense_layers"] else "moe",
        )
        for i, kind in enumerate(kinds)
    )


class AfmoeLM:
    def __init__(self, config: dict, job: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from rayfed_tpu.models import decoder, llama, lora, moe
        from rayfed_tpu.ops.attention import dot_product_attention
        from rayfed_tpu.ops.flash_attention import flash_attention

        run = config["run"]
        assert config["score_func"] == "sigmoid" and config["route_norm"]
        assert config["n_group"] == config["topk_group"] == 1  # no group limit
        assert config["num_shared_experts"] == 1
        assert len(run["held_experts"]) == config["num_experts"]
        self.seed, self.config = seed, config
        self.experts = moe.ExpertShareConfig(
            num_experts=config["router_width"],
            held=tuple(run["held_experts"]),
            top_k=config["num_experts_per_tok"],
            d_model=config["hidden_size"],
            d_ff=config["moe_intermediate_size"],
            route_scale=config["route_scale"],
        )
        self.cfg = decoder.DecoderConfig(
            layers=layer_specs(config),
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            sliding_window=config["sliding_window"],
            rope_theta=float(config["rope_theta"]),
            rms_eps=config["rms_norm_eps"],
            embed_scale=(
                config["hidden_size"] ** 0.5 if config["mup_enabled"] else 1.0
            ),
            experts=self.experts,
            dtype=jnp.dtype(run["compute_dtype"]),
            param_dtype=jnp.dtype(run["param_dtype"]),
            remat=run["remat"],
        )
        self.attn_fn = {
            "flash": flash_attention, "dense": dot_product_attention,
        }[run["attention"]]
        self.local_steps = int(job["local_steps"])
        self.batch, self.seq = int(job["batch"]), int(job["seq_len"])
        self.items_per_step = self.batch * self.seq
        cfg = self.cfg
        a = job["adapter"]
        self.lcfg = lora.LoraConfig(
            rank=int(a["rank"]), alpha=float(a["alpha"]),
            targets=tuple(a["targets"]),
        )
        self._step = decoder.make_lora_train_step(
            cfg, lr=float(job["lr"]), attn_fn=self.attn_fn
        )
        shape = (self.local_steps, self.batch, self.seq)
        init_base = jax.jit(lambda key: decoder.init_decoder(key, cfg))
        balance_ids = jax.jit(lambda key: jax.random.randint(
            jax.random.fold_in(key, 1), (BALANCE_SEQUENCES, self.seq),
            0, cfg.vocab_size,
        ))

        biases, lock = [], threading.Lock()

        def make_base(key):
            # Random weights, then the selection bias that balances the
            # experts on sequences of the cell's own length (config
            # file, assumed.selection_bias).  Every copy of the base
            # comes from the one key, so the biases are made once.
            base = init_base(key)
            with lock:
                if not biases:
                    biases.extend(selection_biases(
                        base, balance_ids(key), cfg, attn_fn=self.attn_fn
                    ))
            return with_selection_biases(base, biases)

        self._make_base = make_base
        self._make_ids = jax.jit(
            lambda key: jax.random.randint(key, shape, 0, cfg.vocab_size)
        )
        self._init_opt = jax.jit(llama.init_adam)
        self._jax, self._decoder, self._lora = jax, decoder, lora

    # -- what is federated, and what stays with a party ----------------

    def base_key(self):
        return self._jax.random.PRNGKey(self.seed)

    def base_shapes(self):
        return self._jax.eval_shape(
            lambda: self._decoder.init_decoder(self.base_key(), self.cfg)
        )

    def init_global(self):
        jax = self._jax
        shapes = self.base_shapes()  # adapters mirror shapes alone
        return jax.jit(
            lambda key: self._lora.init_lora(key, shapes, self.lcfg)
        )(jax.random.PRNGKey(self.seed + 7))

    def party_state(self, index: int) -> dict:
        key = self._jax.random.PRNGKey(1000 * self.seed + 17 + index)
        ids = self._make_ids(key)
        return {
            "ids": [ids[k] for k in range(ids.shape[0])],
            "base": self._make_base(self.base_key()),
        }

    def resident_arrays(self, state) -> list:
        return self._jax.tree_util.tree_leaves((state["ids"], state["base"]))

    # -- one party-round: begin -> local_steps x step -> end -----------

    def begin_round(self, state, tree):
        return tree, self._init_opt(tree)  # Adam reset each round

    def step(self, state, carry, k: int):
        tree, opt = carry
        tree, opt, loss, _ = self._step(
            tree, opt, state["base"], state["ids"][k]
        )
        return (tree, opt), loss

    def end_round(self, carry):
        self._step.flush_routing()  # the armed rounds' routing records
        return carry[0]

    # -- the yardstick: FLOPs the model needs per token ----------------

    def flops_per_item(self) -> float:
        """Forward + backward FLOPs per trained token, from shapes.

        A frozen weight costs 4 FLOPs a token (forward, and the
        backward's activation gradient), an adapter factor 6 (its own
        gradient too).  Attention is banded on windowed layers and
        causal on full ones.  A routed expert is counted at the expected
        ``top_k * held / router width`` assignments a token (1 here),
        the router over its whole width, the head over the slice's rows.
        Recomputation (remat), sorting and gathering are not counted;
        the embedding gather has no matmul.
        """
        c, e = self.cfg, self.experts
        d, q_out = c.hidden_size, c.num_heads * c.head_dim
        kv_out = c.num_kv_heads * c.head_dim
        pats = [re.compile(p) for p in self.lcfg.targets]
        rank = self.lcfg.rank

        def matrices(shapes: dict, prefix: str) -> float:
            total = 0.0
            for name, (i, o) in shapes.items():
                total += 4 * i * o
                if any(p.search(f"{prefix}/{name}") for p in pats):
                    total += 6 * rank * (i + o)
            return total

        swiglu = lambda f: {
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
        }
        attn = matrices({
            "wq": (d, q_out), "wk": (d, kv_out), "wv": (d, kv_out),
            "wo": (q_out, d), "wz": (d, q_out),
        }, "layers/0")
        t = self.seq
        w = min(c.sliding_window, t)
        keys = {
            "window": (w * (w + 1) / 2 + (t - w) * w) / t,
            "full": (t + 1) / 2,
        }
        per_token = len(e.held) * e.top_k / e.num_experts
        ffn = {
            "dense": matrices(swiglu(c.intermediate_size), "layers/0"),
            "moe": (
                4 * d * e.num_experts
                + matrices(swiglu(e.d_ff), "layers/0/moe/shared")
                + per_token * matrices(swiglu(e.d_ff), "layers/0/moe/experts")
            ),
        }
        total = 4 * d * c.vocab_size
        for spec in c.layers:
            # QK^T and PV: 2 matmuls x 2 FLOPs x heads x head_dim x keys
            # forward, twice that again backward.
            total += attn + 12 * q_out * keys[spec.attention] + ffn[spec.ffn]
        return float(total)

    # -- what the per-layer readers read --------------------------------

    def step_program_text(self) -> str:
        """The compiled step as HLO text: every instruction with the
        ``op_name`` that holds its ``jax.named_scope``s (``layer3/
        moe.experts/grouped_matmul/...``).  A device trace names an
        operation by its instruction alone; the readers join the two.
        Lowered from shapes: the program the parties ran is in the
        compile cache."""
        jax, jnp = self._jax, self._jax.numpy
        base = self.base_shapes()
        tree = jax.eval_shape(self.init_global)
        opt = jax.eval_shape(self._init_opt, tree)
        ids = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32)
        return self._step.jitted.lower(tree, opt, base, ids).compile().as_text()

    # -- agreement with the plain reference ----------------------------

    def reference_kwargs(self, layers: int) -> dict:
        c, e, config = self.cfg, self.experts, self.config
        return dict(
            layer_types=config["layer_types"][:layers],
            num_dense_layers=config["num_dense_layers"],
            num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, window=c.sliding_window,
            rope_theta=c.rope_theta, rms_eps=c.rms_eps,
            embed_scale=c.embed_scale, held=e.held, top_k=e.top_k,
            route_scale=e.route_scale,
        )

    def reference_check(self, round_to=None) -> dict:
        """The system's forward (its dtype, its kernels) against the
        float32 reference on the first layers of the served weights, one
        sequence of the cell's length: (a) the selection within
        ``ROUTING_DELTA`` of the reference's, (b) logits of the last
        positions and the loss against the reference run with the
        system's selection.  ``round_to`` (the chip test's control):
        the reference with every matrix product's operands rounded to
        that type stands in for the system, and must come out not
        ``ok``."""
        import dataclasses

        import jax
        import numpy as np

        from benchmark.reference import afmoe

        c = self.cfg
        n = min(REFERENCE_LAYERS, len(c.layers))
        last = min(REFERENCE_LAST, self.seq)
        base = self._make_base(self.base_key())
        sub_cfg = dataclasses.replace(c, layers=c.layers[:n])
        # The first n layers as a tree of their own (copies, so that the
        # whole base can go), in the system's layout and the reference's.
        sub = dict(base, layers=[
            jax.tree_util.tree_map(lambda x: x[: stop - start], group)
            for group, (start, stop) in zip(base["layers"], sub_cfg.groups())
        ])
        del base
        plain = self._decoder.unstack(sub, sub_cfg)
        ids = jax.random.randint(
            jax.random.PRNGKey(self.seed + 3), (1, self.seq), 0, c.vocab_size
        )
        lm_loss = self._decoder.lm_loss

        def system(p, i):
            logits, aux = self._decoder.apply_decoder(
                p, i, sub_cfg, attn_fn=self.attn_fn
            )
            chosen = {k: a["selected"] for k, a in aux.items()}
            return (logits[0, -last:], lm_loss(logits[:, :-1], i[:, 1:]),
                    chosen)

        kw = self.reference_kwargs(n)
        if round_to is None:
            got, got_loss, chosen = jax.jit(system)(sub, ids)
        else:
            def rounded(p, i):
                logits, infos = afmoe.forward(p, i, round_to=round_to, **kw)
                chosen = {k: info["selected"] for k, info in infos.items()}
                return logits[-last:], afmoe.next_token_loss(logits, i), chosen

            with jax.default_matmul_precision("highest"):
                got, got_loss, chosen = jax.jit(rounded)(plain, ids[0])

        def reference(p, i, chosen):
            # One forward with the system's selection: each layer's
            # scores are then those of the stream the system's earlier
            # choices made, and the logits lie beyond the discontinuity.
            logits, infos = afmoe.forward(p, i, selected=chosen, **kw)
            loss = afmoe.next_token_loss(logits, i)
            agree = {
                k: afmoe.routing_agreement(
                    infos[k]["biased"], chosen[k], kw["top_k"]
                ) for k in chosen
            }
            return logits[-last:], loss, agree

        with jax.default_matmul_precision("highest"):
            want, want_loss, agree = jax.jit(reference)(plain, ids[0], chosen)
        got, want = np.asarray(got, np.float32), np.asarray(want)
        rel = float(
            np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2))
        )
        loss_rel = abs(float(got_loss) - float(want_loss)) / float(want_loss)
        shortfall = max(float(a[0]) for a in agree.values())
        exact = min(float(a[1]) for a in agree.values())
        return {
            "ok": bool(
                np.isfinite(rel) and rel <= REFERENCE_REL_RMS_TOL
                and loss_rel <= REFERENCE_LOSS_REL_TOL
                and shortfall <= ROUTING_DELTA and exact >= ROUTING_EXACT_MIN
            ),
            "rel_rms": rel, "tol": REFERENCE_REL_RMS_TOL,
            "loss": float(got_loss), "loss_reference": float(want_loss),
            "loss_rel": loss_rel, "loss_tol": REFERENCE_LOSS_REL_TOL,
            "routing_shortfall": shortfall, "routing_delta": ROUTING_DELTA,
            "routing_exact_share": exact,
            "routing_exact_min": ROUTING_EXACT_MIN,
            "layers": n,
            "positions": [self.seq - last, self.seq],
            "max_abs_err": float(np.abs(got - want).max()),
        }


def build(config: dict, job: dict, seed: int) -> AfmoeLM:
    return AfmoeLM(config, job, seed)
