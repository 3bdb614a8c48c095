"""Family ``kimi_k2_lm``: latent-attention expert decoders of the
``kimi_k2`` / DeepSeek-V3 block (moonshotai Kimi-K2) as one chip's share
of a silo, through ``rayfed_tpu.models.decoder`` (attention kind
``latent``) and ``moe.apply_expert_share``.

The interface of ``afmoe_lm.py``, whose rounds, adapters, selection bias
and step text it inherits: what differs is the block (the configuration
keys it reads), the FLOPs of the latent attention, the reference
(``benchmark/reference/kimi_k2.py``) and where the frozen base lives.
The base is 6.99 GB at this cut, and two silos are threads of one
process on one chip: it is made ONCE a process and the same device
arrays are handed to both parties and to the reference check (the
configuration file, ``assumed.frozen``).  It is read-only in the step
(no gradient, never donated), so sharing it changes no result; a silo's
own chip would hold its one copy.

The reference runs layer by layer (one layer's float32 copy at a time),
on the leading dense layer and the first two expert layers, beside the
base.
"""

from __future__ import annotations

import re
import threading

from benchmark.families import afmoe_lm

# The comparison that decides ``correct``, as ``afmoe_lm.py`` sets it out;
# each limit lies between two readings taken on the chip at the published
# widths, 8,192 tokens, three layers (PERF.md section 4, PR 33): the bf16
# system over ten seeds, and the float32 reference recomputed with fp8
# (e4m3) operands in every matrix product, which must fail.
#
# (a) every expert the system selected has a float32 reference score
# ``s + b`` no further than this below the reference's eighth best.  The
# router sums 7,168 products of a stream bf16 rounded to 2^-9 relative,
# and a layer's stream carries the roundings of two chained latent
# projections and of scores sharpened 2.005 times: 0.0080-0.0122 read,
# fp8 0.251-0.292.
ROUTING_DELTA = 0.03
# ... and at least this share of (token, choice) pairs agree exactly:
# 0.9761-0.9780 read (with 384 experts the eighth and ninth best lie
# closer together than among Trinity's 128: 0.990-0.993 there), fp8 0.647-0.651.
ROUTING_EXACT_MIN = 0.93
# (b) logits of the last positions against the reference run with the
# system's own selection, relative RMS: 0.0187-0.0191 read (twice
# Trinity's 0.009: no norm after a sub-block renormalises the stream
# here, and the dense layer's 18,432-wide products sum nine times as
# many terms), fp8 0.377-0.378; a shared rotary key left out, a latent norm
# skipped or plain frequencies give errors of the logits' own size
# (``tests/test_kimi_k2.py``).
REFERENCE_REL_RMS_TOL = 0.06
# The loss over all 8,191 targets, relative: the harness's accepted
# limit.  Not a precision check (a mean over thousands of positions
# cancels rounding: 1.0e-6 to 3.7e-5 read, fp8 7.6e-5 to 9.7e-5, inside it): it
# catches a wrong shift, target or reduction, which move it by a percent.
REFERENCE_LOSS_REL_TOL = afmoe_lm.REFERENCE_LOSS_REL_TOL
REFERENCE_LAYERS = 3  # the dense layer and two expert layers
REFERENCE_LAST = afmoe_lm.REFERENCE_LAST


def layer_specs(config: dict):
    from rayfed_tpu.models.decoder import LayerSpec

    assert config["moe_layer_freq"] == 1
    return tuple(
        LayerSpec("latent", "dense" if i < config["first_k_dense_replace"]
                  else "moe")
        for i in range(config["num_hidden_layers"])
    )


class KimiK2LM(afmoe_lm.AfmoeLM):
    def __init__(self, config: dict, job: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from rayfed_tpu.models import decoder, llama, lora, moe
        from rayfed_tpu.ops.attention import dot_product_attention
        from rayfed_tpu.ops.flash_attention import flash_attention

        run, yarn = config["run"], config["rope_scaling"]
        assert config["scoring_func"] == "sigmoid" and config["norm_topk_prob"]
        assert config["n_group"] == config["topk_group"] == 1  # no group limit
        assert config["n_shared_experts"] == 1 and yarn["type"] == "yarn"
        assert config["num_key_value_heads"] == config["num_attention_heads"]
        assert len(run["held_experts"]) == config["n_routed_experts"]
        self.seed, self.config = seed, config
        self.experts = moe.ExpertShareConfig(
            num_experts=config["router_width"],
            held=tuple(run["held_experts"]),
            top_k=config["num_experts_per_tok"],
            d_model=config["hidden_size"],
            d_ff=config["moe_intermediate_size"],
            route_scale=config["routed_scaling_factor"],
        )
        self.cfg = cfg = decoder.DecoderConfig(
            layers=layer_specs(config),
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            intermediate_size=config["intermediate_size"],
            rope_theta=float(config["rope_theta"]),
            rope_scaling=llama.YarnScaling(
                factor=yarn["factor"],
                original_max_position=yarn["original_max_position_embeddings"],
                beta_fast=yarn["beta_fast"], beta_slow=yarn["beta_slow"],
                mscale=yarn["mscale"], mscale_all_dim=yarn["mscale_all_dim"],
            ),
            latent=decoder.LatentConfig(
                q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
                nope_dim=config["qk_nope_head_dim"],
                rope_dim=config["qk_rope_head_dim"],
                v_dim=config["v_head_dim"],
            ),
            qk_norm=False, output_gate=False, post_norms=False,
            rms_eps=config["rms_norm_eps"],
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
            jax.random.fold_in(key, 1),
            (afmoe_lm.BALANCE_SEQUENCES, self.seq), 0, cfg.vocab_size,
        ))
        made, lock = [], threading.Lock()

        def make_base(key):
            # ONE device copy a process, whoever asks (both parties'
            # threads, the reference check): random weights, then the
            # selection bias that balances the experts on sequences of
            # the cell's own length (config file, assumed.selection_bias).
            with lock:
                if not made:
                    base = init_base(key)
                    made.append(afmoe_lm.with_selection_biases(
                        base, afmoe_lm.selection_biases(
                            base, balance_ids(key), cfg, attn_fn=self.attn_fn
                        )
                    ))
            return made[0]

        self._make_base = make_base
        self._make_ids = jax.jit(
            lambda key: jax.random.randint(key, shape, 0, cfg.vocab_size)
        )
        self._init_opt = jax.jit(llama.init_adam)
        self._jax, self._decoder, self._lora = jax, decoder, lora

    # -- the yardstick: FLOPs the model needs per token ----------------

    def flops_per_item(self) -> float:
        """Forward + backward FLOPs per trained token, from shapes: the
        family's convention (``afmoe_lm.py``: a frozen weight 4 FLOPs a
        token, an adapter factor 6).  The latent attention's pairs: a
        query-key product over ``nope + rope`` (192) and a value product
        over ``v_dim`` (128) a visible pair and head, forward and twice
        that backward; the five projections at their own shapes.  A
        routed expert at the expected ``top_k * held / router width``
        assignments a token (0.25 here), the router over its whole
        width, the head over the slice's rows."""
        c, e, m = self.cfg, self.experts, self.cfg.latent
        d, h = c.hidden_size, c.num_heads
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
            "wq_a": (d, m.q_rank),
            "wq_b": (m.q_rank, h * (m.nope_dim + m.rope_dim)),
            "wkv_a": (d, m.kv_rank + m.rope_dim),
            "wkv_b": (m.kv_rank, h * (m.nope_dim + m.v_dim)),
            "wo": (h * m.v_dim, d),
        }, "layers/0")
        keys = (self.seq + 1) / 2  # causal: a query's visible keys
        pairs = 6 * h * (m.nope_dim + m.rope_dim + m.v_dim) * keys
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
            total += attn + pairs + ffn[spec.ffn]
        return float(total)

    # -- agreement with the plain reference ----------------------------

    def reference_kwargs(self) -> dict:
        c, e, m = self.cfg, self.experts, self.cfg.latent
        return dict(
            num_heads=c.num_heads, kv_rank=m.kv_rank, nope_dim=m.nope_dim,
            rope_dim=m.rope_dim, v_dim=m.v_dim, rope_theta=c.rope_theta,
            yarn=self.config["rope_scaling"], rms_eps=c.rms_eps, held=e.held,
            top_k=e.top_k, route_scale=e.route_scale,
        )

    def reference_forward(self, base, ids, layers: int, last: int, *,
                          selected=None, round_to=None):
        """The reference on the first ``layers`` layers of the stacked
        ``base``, one sequence ``ids`` [T], a layer a jitted call (one
        layer's float32 copy lives at a time): ``(logits of the last
        positions, loss, {layer: info})``."""
        import jax

        from benchmark.reference import kimi_k2

        kw = self.reference_kwargs()
        dense = len([s for s in self.cfg.layers if s.ffn == "dense"])

        def one(x, group, j, chosen, is_dense):
            lp = jax.tree_util.tree_map(lambda leaf: leaf[j], group)
            return kimi_k2.layer(
                x, lp, dense=is_dense, selected=chosen, round_to=round_to, **kw
            )

        one = jax.jit(one, static_argnames=("is_dense",))

        def head(x, params, i):
            logits = kimi_k2.logits(
                x, params, rms_eps=kw["rms_eps"], round_to=round_to
            )
            return logits[-last:], kimi_k2.next_token_loss(logits, i)

        infos = {}
        with jax.default_matmul_precision("highest"):
            x = jax.jit(kimi_k2.embed)(base, ids)
            for group, (start, stop) in zip(base["layers"], self.cfg.groups()):
                for i in range(start, min(stop, layers)):
                    x, info = one(x, group, i - start,
                                  (selected or {}).get(i), i < dense)
                    if info is not None:
                        infos[i] = info
            top = {k: base[k] for k in ("final_norm", "lm_head")}
            got, loss = jax.jit(head)(x, top, ids)
        return got, loss, infos

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

        from benchmark.reference import kimi_k2

        c = self.cfg
        n = min(REFERENCE_LAYERS, len(c.layers))
        last = min(REFERENCE_LAST, self.seq)
        base = self._make_base(self.base_key())  # the parties' own copy
        sub_cfg = dataclasses.replace(c, layers=c.layers[:n])
        ids = jax.random.randint(
            jax.random.PRNGKey(self.seed + 3), (1, self.seq), 0, c.vocab_size
        )
        lm_loss = self._decoder.lm_loss

        def system(p, i):
            p = dict(p, layers=[
                jax.tree_util.tree_map(lambda x: x[: stop - start], group)
                for group, (start, stop) in zip(p["layers"], sub_cfg.groups())
            ])
            logits, aux = self._decoder.apply_decoder(
                p, i, sub_cfg, attn_fn=self.attn_fn
            )
            chosen = {k: a["selected"] for k, a in aux.items()}
            return (logits[0, -last:], lm_loss(logits[:, :-1], i[:, 1:]),
                    chosen)

        if round_to is None:
            got, got_loss, chosen = jax.jit(system)(base, ids)
        else:
            got, got_loss, infos = self.reference_forward(
                base, ids[0], n, last, round_to=round_to
            )
            chosen = {k: info["selected"] for k, info in infos.items()}
        # One forward with the system's selection: each layer's scores
        # are then those of the stream the system's earlier choices made,
        # and the logits lie beyond the discontinuity.
        want, want_loss, infos = self.reference_forward(
            base, ids[0], n, last, selected=chosen
        )
        agree = {
            k: kimi_k2.routing_agreement(
                infos[k]["biased"], chosen[k], self.experts.top_k
            ) for k in chosen
        }
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


def build(config: dict, job: dict, seed: int) -> KimiK2LM:
    return KimiK2LM(config, job, seed)
