"""Family ``granite_hybrid_lm``: state-space hybrid decoders of the
``granitemoehybrid`` block (ibm-granite granite-4.0-h: Mamba-2 mixers,
one attention layer without positions in ten, a dense SwiGLU after
each, the block's four multipliers, a head tied to the embedding) as
the first pipeline stage of a silo, through
``rayfed_tpu.models.decoder`` (mixer kind ``ssm``,
``rayfed_tpu.models.mamba2``, ``rayfed_tpu.ops.ssd``).

The interface of ``afmoe_lm.py``, whose rounds, adapters and step text
it inherits: what differs is the block (the configuration keys it
reads), the FLOPs, the reference (``benchmark/reference/
granite_hybrid.py``: the recurrence token by token) and where the frozen
base lives.  As ``kimi_k2_lm.py`` does, the base (3.40 GB at this cut)
is made ONCE a process and the same device arrays are handed to both
parties and to the reference check (the configuration file,
``assumed.frozen``): read-only in the step, no gradient, never donated.
There are no experts: no selection, no bias, no routing record.

The reference runs layer by layer (one layer's float32 copy at a time)
on layers 0-5: five Mamba layers and the first attention layer.
"""

from __future__ import annotations

import re
import threading

from benchmark.families import afmoe_lm

# The comparison that decides ``correct``, as ``afmoe_lm.py`` sets it out
# less the selection (there is none): each limit lies between two
# readings taken on the chip at the published widths, 8,192 tokens, six
# layers (PERF.md section 4, PR 35): the bf16 system over its seeds, and
# the float32 reference recomputed with fp8 (e4m3) operands in every
# matrix product and in the scan's, which must fail.
#
# Logits of the last positions against the reference, relative RMS:
# 0.01101-0.01156 read over sixteen seeds (six layers of bf16 on a stream
# that no norm after a sub-block renormalises; Trinity's four layers
# read 0.009), fp8 0.1486-0.1517 on two; a dropped chunk boundary, a
# multiplier left out or 1/8 for 1/64 give errors of the logits' own
# size (``tests/test_granite_hybrid.py``).
REFERENCE_REL_RMS_TOL = 0.03
# The loss over all 8,191 targets, relative: the harness's accepted
# limit.  Not a precision check (a mean over thousands of positions
# cancels rounding: 0 to 1.9e-6 read, fp8 1.1e-5 to 1.7e-5, inside it;
# a hundred times of room above the largest reading): it catches a wrong
# shift, target or reduction, which move it by a percent.
REFERENCE_LOSS_REL_TOL = afmoe_lm.REFERENCE_LOSS_REL_TOL
REFERENCE_LAYERS = 6  # five Mamba layers and the attention layer
REFERENCE_LAST = afmoe_lm.REFERENCE_LAST

MIXERS = {"mamba": "ssm", "attention": "full"}


def layer_specs(config: dict):
    from rayfed_tpu.models.decoder import LayerSpec

    kinds = config["layer_types"][: config["num_hidden_layers"]]
    return tuple(LayerSpec(MIXERS[kind], "dense") for kind in kinds)


class GraniteHybridLM(afmoe_lm.AfmoeLM):
    def __init__(self, config: dict, job: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from rayfed_tpu.models import decoder, llama, lora
        from rayfed_tpu.ops.attention import dot_product_attention
        from rayfed_tpu.ops.flash_attention import flash_attention

        run = config["run"]
        assert config["num_local_experts"] == 0  # every FFN is the shared one
        assert config["position_embedding_type"] == "nope"
        assert config["tie_word_embeddings"] and not config["attention_bias"]
        assert config["mamba_conv_bias"] and not config["mamba_proj_bias"]
        assert config["normalization_function"] == "rmsnorm"
        d = config["hidden_size"]
        assert config["mamba_expand"] * d == (
            config["mamba_n_heads"] * config["mamba_d_head"]
        )
        self.seed, self.config = seed, config
        self.cfg = cfg = decoder.DecoderConfig(
            layers=layer_specs(config),
            vocab_size=config["vocab_size"],
            hidden_size=d,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=d // config["num_attention_heads"],
            intermediate_size=config["shared_intermediate_size"],
            rms_eps=config["rms_norm_eps"],
            ssm=decoder.SsmConfig(
                num_heads=config["mamba_n_heads"],
                head_dim=config["mamba_d_head"],
                state=config["mamba_d_state"],
                groups=config["mamba_n_groups"],
                conv_width=config["mamba_d_conv"],
                chunk=config["mamba_chunk_size"],
            ),
            qk_norm=False, output_gate=False, post_norms=False,
            embed_scale=float(config["embedding_multiplier"]),
            residual_scale=float(config["residual_multiplier"]),
            attn_scale=float(config["attention_multiplier"]),
            logit_scale=1.0 / config["logits_scaling"],
            tie_embeddings=True,
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
        made, lock = [], threading.Lock()

        def make_base(key):
            # ONE device copy a process, whoever asks (both parties'
            # threads, the reference check).
            with lock:
                if not made:
                    made.append(init_base(key))
                    log_decays(made[0], cfg)
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
        plus twice that backward).  The scan at the PUBLISHED chunk
        whatever the program uses (the roofline reader's count,
        ``layer_metrics/ssm_scan_roofline.py::scan_flops``); the
        attention's pairs a query-key and a value product over the head
        width a visible pair and head; the head over the whole
        vocabulary."""
        from benchmark.layer_metrics.ssm_scan_roofline import scan_flops

        c, m = self.cfg, self.cfg.ssm
        d, f = c.hidden_size, c.intermediate_size
        q_out, kv_out = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        pats = [re.compile(p) for p in self.lcfg.targets]
        rank = self.lcfg.rank

        def matrices(shapes: dict) -> float:
            total = 0.0
            for name, (i, o) in shapes.items():
                total += 4 * i * o
                if any(p.search(f"layers/0/{name}") for p in pats):
                    total += 6 * rank * (i + o)
            return total

        ffn = matrices({"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)})
        mixer = {
            "ssm": (
                matrices({"w_in": (d, m.proj_dim), "w_out": (m.d_inner, d)})
                + 3 * scan_flops(
                    1, m.num_heads, m.head_dim, m.state, m.groups,
                    self.config["mamba_chunk_size"],
                )
                + 3 * 2 * m.conv_width * m.conv_dim
            ),
            "full": (
                matrices({"wq": (d, q_out), "wk": (d, kv_out),
                          "wv": (d, kv_out), "wo": (q_out, d)})
                + 6 * c.num_heads * 2 * c.head_dim * (self.seq + 1) / 2
            ),
        }
        total = 4 * d * c.vocab_size
        for spec in c.layers:
            total += mixer[spec.mixer] + ffn
        return float(total)

    # -- agreement with the plain reference ----------------------------

    def reference_kwargs(self, layers: int) -> dict:
        c, m, config = self.cfg, self.cfg.ssm, self.config
        return dict(
            layer_types=config["layer_types"][:layers],
            embedding_multiplier=c.embed_scale,
            residual_multiplier=c.residual_scale,
            rms_eps=c.rms_eps,
            ssm=dict(heads=m.num_heads, head_dim=m.head_dim, state=m.state,
                     groups=m.groups, conv_width=m.conv_width, chunk=m.chunk),
            attn=dict(num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                      attn_head_dim=c.head_dim,
                      attention_multiplier=c.attn_scale),
        )

    def reference_forward(self, base, ids, layers: int, last: int, *,
                          round_to=None):
        """The reference on the first ``layers`` layers of the stacked
        ``base``, one sequence ``ids`` [T], a layer a jitted call (one
        layer's float32 copy lives at a time): ``(logits of the last
        positions, loss)``."""
        import jax

        from benchmark.reference import granite_hybrid as ref

        kw = self.reference_kwargs(layers)
        kinds = kw.pop("layer_types")
        by = kw.pop("embedding_multiplier")
        scaling = self.config["logits_scaling"]

        def one(x, group, j, kind):
            lp = jax.tree_util.tree_map(lambda leaf: leaf[j], group)
            return ref.layer(x, lp, kind=kind, round_to=round_to, **kw)

        one = jax.jit(one, static_argnames=("kind",))

        def head(x, params, i):
            args = dict(rms_eps=kw["rms_eps"], logits_scaling=scaling,
                        round_to=round_to)
            return (ref.logits(x, params, last=last, **args),
                    ref.head_loss(x, params, i, **args))

        with jax.default_matmul_precision("highest"):
            x = jax.jit(
                lambda p, i: ref.embed(p, i, embedding_multiplier=by)
            )(base, ids)
            for group, (start, stop) in zip(base["layers"], self.cfg.groups()):
                for i in range(start, min(stop, layers)):
                    x = one(x, group, i - start, kinds[i])
            top = {k: base[k] for k in ("final_norm", "embed")}
            return jax.jit(head)(x, top, ids)

    def reference_check(self, round_to=None) -> dict:
        """The system's forward (its dtype, its kernels, its chunked
        scan, its fused head-and-loss) against the float32 reference
        (the recurrence token by token) on the first layers of the
        served weights, one sequence of the cell's length: logits of the
        last positions and the loss.  ``round_to`` (the chip test's
        control): the reference with every product's operands rounded to
        that type stands in for the system, and must come out not
        ``ok``."""
        import dataclasses

        import jax
        import numpy as np

        c = self.cfg
        n = min(REFERENCE_LAYERS, len(c.layers))
        last = min(REFERENCE_LAST, self.seq)
        base = self._make_base(self.base_key())  # the parties' own copy
        sub_cfg = dataclasses.replace(c, layers=c.layers[:n])
        ids = jax.random.randint(
            jax.random.PRNGKey(self.seed + 3), (1, self.seq), 0, c.vocab_size
        )

        def system(p, i):
            p = dict(p, layers=[
                jax.tree_util.tree_map(lambda x: x[: stop - start], group)
                for group, (start, stop) in zip(p["layers"], sub_cfg.groups())
            ])
            logits, _ = self._decoder.apply_decoder(
                p, i, sub_cfg, attn_fn=self.attn_fn, last=last
            )
            # the timed path's own head and loss (no [T, V] array)
            loss, _ = self._decoder.lora_loss(
                {}, p, i, sub_cfg, attn_fn=self.attn_fn
            )
            return logits[0], loss

        if round_to is None:
            got, got_loss = jax.jit(system)(base, ids)
        else:
            got, got_loss = self.reference_forward(
                base, ids[0], n, last, round_to=round_to
            )
        want, want_loss = self.reference_forward(base, ids[0], n, last)
        got, want = np.asarray(got, np.float32), np.asarray(want)
        rel = float(
            np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2))
        )
        loss_rel = abs(float(got_loss) - float(want_loss)) / float(want_loss)
        return {
            "ok": bool(
                np.isfinite(rel) and rel <= REFERENCE_REL_RMS_TOL
                and loss_rel <= REFERENCE_LOSS_REL_TOL
            ),
            "rel_rms": rel, "tol": REFERENCE_REL_RMS_TOL,
            "loss": float(got_loss), "loss_reference": float(want_loss),
            "loss_rel": loss_rel, "loss_tol": REFERENCE_LOSS_REL_TOL,
            "layers": n,
            "positions": [self.seq - last, self.seq],
            "max_abs_err": float(np.abs(got - want).max()),
        }


def log_decays(base, cfg) -> None:
    """The regime the served weights put the scan in, to the log: the
    per-step decay ``exp(dt A)`` at the bias alone (the projected ``dt``
    widens it), over every state-space layer's heads."""
    import numpy as np

    from benchmark.reduce import log

    decays = []
    for group, (start, _) in zip(base["layers"], cfg.groups()):
        if cfg.layers[start].mixer != "ssm":
            continue
        dt = np.logaddexp(0.0, np.asarray(group["dt_bias"], np.float64))
        decays.append(np.exp(-dt * np.exp(np.asarray(group["A_log"], np.float64))))
    if decays:
        decays = np.concatenate([d.ravel() for d in decays])
        log(ssm_decay_at_bias={
            "min": float(decays.min()), "median": float(np.median(decays)),
            "max": float(decays.max()), "heads": int(decays.size),
        })


def build(config: dict, job: dict, seed: int) -> GraniteHybridLM:
    return GraniteHybridLM(config, job, seed)
