"""Family ``nemotron_h_lm``: hybrid state-space latent-expert decoders of
the ``nemotron_h`` block (NVIDIA Nemotron-3 Super: Mamba-2 blocks with
grouped ``B``/``C`` and a grouped gated norm, latent mixture-of-experts
blocks with squared-ReLU experts, attention blocks without positions,
and a multi-token-prediction module) as one chip's share of a silo,
through ``rayfed_tpu.models.decoder`` (mixer kinds ``ssm`` and ``full``,
FFN kinds ``moe`` and ``none``, ``DecoderConfig.mtp``) and
``moe.apply_expert_share`` (``latent``, ``activation="relu2"``).

The published model is a stack of single-part blocks (``M`` a Mamba-2
mixer, ``E`` an expert layer, ``*`` attention), each ``x + part(norm(x))``.
The decoder's layer is a mixer and an FFN, each with its own pre-norm and
plain residual: a mixer block and the ``E`` block after it are one layer,
a mixer block followed by another mixer block a layer with FFN kind
``none`` (:func:`layer_specs`); the arithmetic is the same.  Layers 0-10,
``MEMEMEM*EME``, are then six layers in four scanned groups, and the MTP
module (``*E``) one layer in a fifth.

The interface of ``afmoe_lm.py``, whose rounds, adapters and step text it
inherits: what differs is the block (the configuration keys it reads),
the FLOPs, the reference (``benchmark/reference/nemotron_h.py``: block by
block, the recurrence token by token, the held experts one after
another) and where the frozen base lives (ONE device copy a process, as
in ``kimi_k2_lm.py``).  The selection bias is balanced as Trinity's
family balances it, the MTP module's expert layer on the stream the
balanced layers make.

The reference runs every block (all eleven and the MTP module's two: the
MTP loss needs the last block's output), a block a jitted call.
"""

from __future__ import annotations

import functools
import re
import threading

from benchmark.families import afmoe_lm

# The comparison that decides ``correct``, as ``afmoe_lm.py`` sets it out,
# with the MTP module's loss beside the main one.  Each limit lies between
# two readings taken on the chip at the published widths, 8,192 tokens,
# all eleven blocks and the MTP module (PERF.md section 4): the bf16
# system over five seeds, and the float32 reference recomputed with fp8
# (e4m3) operands in every matrix product and in the scan's, which fails
# by the three limits of (a) and (b).
#
# (a) every expert the system selected has a float32 reference score
# ``s + b`` no further than this below the reference's 22nd best.  The
# router sums 4,096 products of a stream bf16 rounded to 2^-9 relative,
# after up to ten blocks of bf16: 0.0077-0.0088 read (the worst of six
# expert layers, the later ones worst), fp8 0.193.
ROUTING_DELTA = 0.03
# ... and at least this share of (token, choice) pairs agree exactly:
# 0.9864-0.9868 read (between Trinity's 0.990-0.993 and Kimi's
# 0.976-0.978), fp8 0.807.
ROUTING_EXACT_MIN = 0.96
# (b) logits of the last positions against the reference run with the
# system's own selection, relative RMS: 0.01151-0.01162 read (eleven
# blocks of bf16 on a stream no norm after a block renormalises), fp8
# 0.208; a group's norm over all channels, an unsquared relu or the
# latent pair left out give errors of the logits' own size
# (``tests/test_nemotron_h.py``).
REFERENCE_REL_RMS_TOL = 0.03
# Both losses over all their targets, relative: the harness's accepted
# limit, ten times the largest reading (main 1.4e-6 to 2.0e-5, MTP 7.5e-7
# to 8.4e-6; fp8 1.5e-4 and 4.9e-5, inside it).  Not a precision check (a
# mean over thousands of positions cancels rounding): it catches a wrong
# shift, target or reduction, which move a loss by a percent.
REFERENCE_LOSS_REL_TOL = afmoe_lm.REFERENCE_LOSS_REL_TOL
REFERENCE_LAST = afmoe_lm.REFERENCE_LAST

MIXERS = {"M": "ssm", "*": "full"}


def layer_specs(pattern: str):
    """The decoder's layers for a ``hybrid_override_pattern``: each mixer
    block (``M``, ``*``) starts a layer, an ``E`` block is the FFN of the
    layer before it; a layer no ``E`` follows has FFN kind ``none``."""
    from rayfed_tpu.models.decoder import LayerSpec

    specs = []
    for kind in pattern:
        if kind == "E":
            if not specs or specs[-1].ffn != "none":
                raise ValueError(f"an E block follows no mixer in {pattern!r}")
            specs[-1] = LayerSpec(specs[-1].mixer, "moe")
        else:
            specs.append(LayerSpec(MIXERS[kind], "none"))
    return tuple(specs)


def _part(lp, part: str) -> dict:
    """One published block's weights out of a decoder layer's: the
    mixer's (``attn_norm`` and its matrices) or the expert layer's."""
    if part == "E":
        return {"norm": lp["mlp_norm"], **lp["moe"]}
    return {"norm": lp["attn_norm"], **{
        k: v for k, v in lp.items() if k not in ("attn_norm", "mlp_norm", "moe")
    }}


def mtp_selection_biases(params, ids, cfg, *, attn_fn) -> list:
    """Per group of the MTP module the ``[layers, E]`` selection biases
    (None for a group without experts), balanced as
    ``afmoe_lm.selection_biases`` balances the decoder's, on the module's
    input that the (already balanced) layers make."""
    import jax

    from rayfed_tpu.models import decoder

    @functools.partial(jax.jit, static_argnames=("spec",))
    def block(x, group, j, bias, spec):
        lp = jax.tree_util.tree_map(lambda leaf: leaf[j], group)
        if bias is not None:
            lp = dict(lp, moe=dict(lp["moe"], router_bias=bias))
        x, aux = decoder.apply_block(x, lp, cfg, ffn=spec.ffn,
                                     mixer=spec.mixer, attn_fn=attn_fn)
        if aux is None:
            return x, None
        scores = aux["scores"].reshape(-1, aux["scores"].shape[-1])
        return x, afmoe_lm.balancing_bias(scores, cfg.experts.top_k)

    @jax.jit
    def fused(params, ids):
        x0, h, _ = decoder.streams(params, ids, cfg, attn_fn=attn_fn)
        return decoder.mtp_fuse(params, x0, h, cfg)

    x = fused(params, ids)
    out = []
    for group, (start, stop) in zip(params["mtp"]["layers"], cfg.mtp_groups()):
        biases = []
        for i in range(start, stop):
            spec = cfg.stack[i]
            after, bias = block(x, group, i - start, None, spec)
            if bias is not None:
                after, _ = block(x, group, i - start, bias, spec)
                biases.append(bias)
            x = after
        out.append(jax.numpy.stack(biases) if biases else None)
    return out


class NemotronHLM(afmoe_lm.AfmoeLM):
    def __init__(self, config: dict, job: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from rayfed_tpu.models import decoder, llama, lora, moe
        from rayfed_tpu.ops.attention import dot_product_attention
        from rayfed_tpu.ops.flash_attention import flash_attention

        run = config["run"]
        assert config["model_type"] == "nemotron_h"
        assert config["mlp_hidden_act"] == "relu2"
        assert config["mamba_hidden_act"] == "silu"
        assert config["norm_topk_prob"] and config["n_shared_experts"] == 1
        assert config["n_group"] == config["topk_group"] == 1  # no group limit
        assert not config["tie_word_embeddings"]
        assert not (config["attention_bias"] or config["mlp_bias"]
                    or config["use_bias"] or config["mamba_proj_bias"])
        assert config["use_conv_bias"]
        assert config["num_nextn_predict_layers"] == 1
        assert len(run["held_experts"]) == config["n_routed_experts"]
        d = config["hidden_size"]
        heads, head_dim = config["mamba_num_heads"], config["mamba_head_dim"]
        assert config["expand"] * d == heads * head_dim
        self.seed, self.config = seed, config
        self.pattern = config["hybrid_override_pattern"][
            : config["num_hidden_layers"]
        ]
        self.mtp_pattern = config["mtp_hybrid_override_pattern"]
        self.experts = moe.ExpertShareConfig(
            num_experts=config["router_width"],
            held=tuple(run["held_experts"]),
            top_k=config["num_experts_per_tok"],
            d_model=d,
            d_ff=config["moe_intermediate_size"],
            route_scale=float(config["routed_scaling_factor"]),
            shared_d_ff=config["moe_shared_expert_intermediate_size"],
            latent=config["moe_latent_size"],
            activation="relu2",
        )
        self.cfg = cfg = decoder.DecoderConfig(
            layers=layer_specs(self.pattern),
            vocab_size=config["vocab_size"],
            hidden_size=d,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            rms_eps=config["layer_norm_epsilon"],
            ssm=decoder.SsmConfig(
                num_heads=heads, head_dim=head_dim,
                state=config["ssm_state_size"], groups=config["n_groups"],
                conv_width=config["conv_kernel"],
                chunk=config["chunk_size"],
            ),
            qk_norm=False, output_gate=False, post_norms=False,
            experts=self.experts,
            mtp=decoder.MtpConfig(layers=layer_specs(self.mtp_pattern)),
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
            # selection biases that balance the experts on sequences of
            # the cell's own length (config file, assumed.selection_bias).
            with lock:
                if not made:
                    base = init_base(key)
                    ids = balance_ids(key)
                    base = afmoe_lm.with_selection_biases(
                        base, afmoe_lm.selection_biases(
                            base, ids, cfg, attn_fn=self.attn_fn
                        )
                    )
                    biases = mtp_selection_biases(
                        base, ids, cfg, attn_fn=self.attn_fn
                    )
                    base["mtp"] = afmoe_lm.with_selection_biases(
                        base["mtp"], biases
                    )
                    made.append(base)
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
        token, an adapter factor 6; pairs, scan and convolution forward
        plus twice that backward).  A routed expert's rows once, at the
        expected ``top_k * held / router width`` assignments a token
        (2.75 here), in the latent width; the router over its whole
        width; the scan at the PUBLISHED chunk (the roofline reader's
        count, ``layer_metrics/ssm_scan_roofline.py::scan_flops``); the
        MTP module's projection, layers and head pass as the decoder's;
        both heads over the slice's rows."""
        from benchmark.layer_metrics.ssm_scan_roofline import scan_flops

        c, e, m = self.cfg, self.experts, self.cfg.ssm
        d = c.hidden_size
        q_out, kv_out = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        pats = [re.compile(p) for p in self.lcfg.targets]
        rank = self.lcfg.rank

        def matrices(shapes: dict, prefix: str) -> float:
            total = 0.0
            for name, (i, o) in shapes.items():
                total += 4 * i * o
                if any(p.search(f"{prefix}/{name}") for p in pats):
                    total += 6 * rank * (i + o)
            return total

        relu2 = lambda i, f: {"w_up": (i, f), "w_down": (f, i)}
        l = e.routed_width
        per_token = len(e.held) * e.top_k / e.num_experts
        mixer = {
            "ssm": (
                matrices({"w_in": (d, m.proj_dim), "w_out": (m.d_inner, d)},
                         "layers/0")
                + 3 * scan_flops(1, m.num_heads, m.head_dim, m.state,
                                 m.groups, self.config["chunk_size"])
                + 3 * 2 * m.conv_width * m.conv_dim
            ),
            "full": (
                matrices({"wq": (d, q_out), "wk": (d, kv_out),
                          "wv": (d, kv_out), "wo": (q_out, d)}, "layers/0")
                + 6 * c.num_heads * 2 * c.head_dim * (self.seq + 1) / 2
            ),
        }
        ffn = {
            "none": 0.0,
            "moe": (
                4 * d * e.num_experts
                + matrices({"w_lat_in": (d, l), "w_lat_out": (l, d)},
                           "layers/0/moe")
                + matrices(relu2(d, e.shared_width), "layers/0/moe/shared")
                + per_token * matrices(relu2(l, e.d_ff),
                                       "layers/0/moe/experts")
            ),
        }
        head = 4 * d * c.vocab_size
        total = 2 * head + matrices({"w_eh": (2 * d, d)}, "mtp")
        for spec in c.stack:
            total += mixer[spec.mixer] + ffn[spec.ffn]
        return float(total)

    # -- agreement with the plain reference ----------------------------

    def reference_kwargs(self) -> dict:
        c, e, m = self.cfg, self.experts, self.cfg.ssm
        return dict(
            rms_eps=c.rms_eps,
            ssm=dict(heads=m.num_heads, head_dim=m.head_dim, state=m.state,
                     groups=m.groups, conv_width=m.conv_width),
            attn=dict(num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                      attn_head_dim=c.head_dim),
            moe=dict(held=e.held, top_k=e.top_k, route_scale=e.route_scale),
        )

    def reference_forward(self, base, ids, last: int, *, selected=None,
                          round_to=None):
        """The reference on every block of the stacked ``base``, one
        sequence ``ids`` [T], a block a jitted call (one block's float32
        copy lives at a time): ``(logits of the last positions, main
        loss, MTP loss, [info of each E block, the module's last])``."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import nemotron_h as ref

        kw = self.reference_kwargs()
        c = self.cfg

        def one(x, group, j, chosen, kind, part):
            lp = jax.tree_util.tree_map(lambda leaf: leaf[j], group)
            return ref.block(x, _part(lp, part), kind=kind, selected=chosen,
                             round_to=round_to, **kw)

        one = jax.jit(one, static_argnames=("kind", "part"))
        picks = list(selected or [])
        infos = []

        def through(x, groups, bounds):
            for group, (start, stop) in zip(groups, bounds):
                for i in range(start, stop):
                    spec = c.stack[i]
                    kind = "M" if spec.mixer == "ssm" else "*"
                    x, _ = one(x, group, i - start, None, kind, "mixer")
                    if spec.ffn == "moe":
                        chosen = picks[len(infos)] if picks else None
                        x, info = one(x, group, i - start, chosen, "E", "E")
                        infos.append(info)
            return x

        def heads(x, top, i, shift):
            return (ref.logits(x, top["final_norm"], top["lm_head"],
                               rms_eps=kw["rms_eps"], last=last,
                               round_to=round_to),
                    ref.head_loss(x, top["final_norm"], top["lm_head"], i,
                                  shift=shift, rms_eps=kw["rms_eps"],
                                  round_to=round_to))

        heads = jax.jit(heads, static_argnames=("shift",))
        with jax.default_matmul_precision("highest"):
            x0 = jax.jit(ref.embed)(base, ids)
            h = through(x0, base["layers"], c.groups())
            top = {"final_norm": base["final_norm"], "lm_head": base["lm_head"]}
            got, main = heads(h, top, ids, 1)
            p = base["mtp"]
            m = jax.jit(functools.partial(
                ref.mtp_input, rms_eps=kw["rms_eps"], round_to=round_to
            ))(jnp.roll(x0, -1, axis=0), h, p)
            del x0, h
            m = through(m, p["layers"], c.mtp_groups())
            _, mtp = heads(m, dict(top, final_norm=p["final_norm"]), ids, 2)
        return got, main, mtp, infos

    def reference_check(self, round_to=None) -> dict:
        """The system's forward (its dtype, its kernels, its chunked
        scan, its grouped products, its fused head-and-loss) against the
        float32 reference on every block of the served weights and the
        MTP module, one sequence of the cell's length: (a) the selection
        within ``ROUTING_DELTA`` of the reference's in every expert
        layer, (b) logits of the last positions and both losses against
        the reference run with the system's selection.  ``round_to``
        (the chip test's control): the reference with every product's
        operands rounded to that type stands in for the system, and must
        come out not ``ok``."""
        import jax
        import numpy as np

        from benchmark.reference import nemotron_h as ref

        c = self.cfg
        last = min(REFERENCE_LAST, self.seq)
        base = self._make_base(self.base_key())  # the parties' own copy
        ids = jax.random.randint(
            jax.random.PRNGKey(self.seed + 3), (1, self.seq), 0, c.vocab_size
        )

        def system(p, i):
            logits, _ = self._decoder.apply_decoder(
                p, i, c, attn_fn=self.attn_fn, last=last
            )
            # the timed path's own losses (no [T, V] array)
            _, aux, (main, mtp) = self._decoder.lora_loss_terms(
                {}, p, i, c, attn_fn=self.attn_fn
            )
            return logits[0], main, mtp, [aux[k]["selected"] for k in sorted(aux)]

        if round_to is None:
            got, got_main, got_mtp, chosen = jax.jit(system)(base, ids)
            chosen = [s.reshape(self.seq, -1) for s in chosen]
        else:
            got, got_main, got_mtp, infos = self.reference_forward(
                base, ids[0], last, round_to=round_to
            )
            chosen = [info["selected"] for info in infos]
        # One forward with the system's selection: each layer's scores
        # are then those of the stream the system's earlier choices made,
        # and the logits lie beyond the discontinuity.
        want, want_main, want_mtp, infos = self.reference_forward(
            base, ids[0], last, selected=chosen
        )
        agree = [
            ref.routing_agreement(info["biased"], s, self.experts.top_k)
            for info, s in zip(infos, chosen)
        ]
        got, want = np.asarray(got, np.float32), np.asarray(want)
        rel = float(
            np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2))
        )
        main_rel = abs(float(got_main) - float(want_main)) / float(want_main)
        mtp_rel = abs(float(got_mtp) - float(want_mtp)) / float(want_mtp)
        shortfall = max(float(a[0]) for a in agree)
        exact = min(float(a[1]) for a in agree)
        return {
            "ok": bool(
                np.isfinite(rel) and rel <= REFERENCE_REL_RMS_TOL
                and main_rel <= REFERENCE_LOSS_REL_TOL
                and mtp_rel <= REFERENCE_LOSS_REL_TOL
                and shortfall <= ROUTING_DELTA and exact >= ROUTING_EXACT_MIN
            ),
            "rel_rms": rel, "tol": REFERENCE_REL_RMS_TOL,
            "loss": float(got_main), "loss_reference": float(want_main),
            "loss_rel": main_rel, "loss_tol": REFERENCE_LOSS_REL_TOL,
            "mtp_loss": float(got_mtp), "mtp_loss_reference": float(want_mtp),
            "mtp_loss_rel": mtp_rel,
            "routing_shortfall": shortfall, "routing_delta": ROUTING_DELTA,
            "routing_exact_share": exact,
            "routing_exact_min": ROUTING_EXACT_MIN,
            "routing_by_layer": [
                [float(a[0]), float(a[1])] for a in agree
            ],
            "blocks": len(self.pattern) + len(self.mtp_pattern),
            "positions": [self.seq - last, self.seq],
            "max_abs_err": float(np.abs(got - want).max()),
        }


def build(config: dict, job: dict, seed: int) -> NemotronHLM:
    return NemotronHLM(config, job, seed)
