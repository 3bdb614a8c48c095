"""Family ``llama_lm``: decoder LMs that run through
``rayfed_tpu.models.llama`` (Mistral-7B among them: ``LlamaConfig`` has
``sliding_window`` and the flash kernel skips out-of-band blocks).

A family turns a configuration file and a cell's ``job`` into what the
harness federates: the global tree, each party's resident state, one
local step, items and FLOPs per step, and the agreement check against
the plain reference (``benchmark/reference/mistral.py``).

The job is a LoRA fine-tune on a frozen base (a full fine-tune is a
later family or a later key).  ``job`` keys: ``adapter`` (``rank``,
``alpha``, ``targets``), ``lr``, ``local_steps``, ``batch``,
``seq_len``.
"""

from __future__ import annotations

import re

# Agreement of the system's bf16 flash forward with the float32
# reference on the logits of the last positions: relative RMS error.
# bf16 keeps 8 significant bits (rounding ~2^-9 = 0.2% per value) and
# the residual stream of two layers passes some tens of rounded
# operations whose errors add in quadrature: 0.45% measured on the chip
# at the published widths (TPU v5 lite, PR 23, every run of every cell).
# 1.5% leaves a factor of three; an fp8 forward (2-3 significant bits,
# > 5%) or a wrong band (keys missing: errors of the logits' own size)
# fails it.
REFERENCE_REL_RMS_TOL = 0.015
REFERENCE_LAYERS = 2
REFERENCE_LAST = 256


class LlamaLM:
    def __init__(self, config: dict, job: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from rayfed_tpu.models import llama, lora
        from rayfed_tpu.ops.attention import dot_product_attention
        from rayfed_tpu.ops.flash_attention import flash_attention

        run = config["run"]
        self.seed = seed
        self.cfg = llama.LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            intermediate_size=config["intermediate_size"],
            rope_theta=config["rope_theta"],
            rms_eps=config["rms_norm_eps"],
            max_seq_len=config["max_position_embeddings"],
            tie_embeddings=config["tie_word_embeddings"],
            sliding_window=config.get("sliding_window"),
            dtype=jnp.dtype(run["compute_dtype"]),
            param_dtype=jnp.dtype(run["param_dtype"]),
            remat=run["remat"],
        )
        assert config["head_dim"] == self.cfg.head_dim, "head_dim"
        self.attn_fn = {
            "flash": flash_attention, "dense": dot_product_attention,
        }[run["attention"]]
        self.local_steps = int(job["local_steps"])
        self.batch, self.seq = int(job["batch"]), int(job["seq_len"])
        self.items_per_step = self.batch * self.seq
        cfg, lr = self.cfg, float(job["lr"])
        a = job["adapter"]
        self.lcfg = lora.LoraConfig(
            rank=int(a["rank"]), alpha=float(a["alpha"]),
            targets=tuple(a["targets"]),
        )
        self._step = llama.make_lora_train_step(
            cfg, lr=lr, attn_fn=self.attn_fn
        )
        # One jitted call each: weights and data are made on the device
        # from the seed, in the type they are served in.
        self._make_base = jax.jit(
            lambda key: llama.init_llama(key, cfg)
        )
        shape = (self.local_steps, self.batch, self.seq)
        self._make_ids = jax.jit(
            lambda key: jax.random.randint(key, shape, 0, cfg.vocab_size)
        )
        # One dispatch, not one per leaf (init_adam is eager tree_maps).
        self._init_opt = jax.jit(llama.init_adam)
        self._jax, self._llama, self._lora = jax, llama, lora

    # -- what is federated, and what stays with a party ----------------

    def base_key(self):
        return self._jax.random.PRNGKey(self.seed)

    def init_global(self):
        """The tree every round starts from (identical on every party)."""
        jax = self._jax
        base = self._make_base(self.base_key())
        adapters = jax.jit(
            lambda key, b: self._lora.init_lora(key, b, self.lcfg)
        )(jax.random.PRNGKey(self.seed + 7), base)
        del base
        return adapters

    def party_state(self, index: int) -> dict:
        """What a silo keeps resident: its data pool (``local_steps``
        different batches of random token ids) and its own copy of
        the frozen base (the same weights everywhere, as silos that
        loaded one checkpoint)."""
        key = self._jax.random.PRNGKey(1000 * self.seed + 17 + index)
        ids = self._make_ids(key)
        return {
            "ids": [ids[k] for k in range(self.local_steps)],
            "base": self._make_base(self.base_key()),
        }

    def resident_arrays(self, state) -> list:
        return self._jax.tree_util.tree_leaves(state)

    # -- one party-round: begin -> local_steps x step -> end -----------

    def begin_round(self, state, tree):
        return tree, self._init_opt(tree)  # Adam reset each round

    def step(self, state, carry, k: int):
        tree, opt = carry
        tree, opt, loss = self._step(
            tree, opt, state["base"], state["ids"][k]
        )
        return (tree, opt), loss

    def end_round(self, carry):
        return carry[0]

    # -- the yardstick: FLOPs the model needs per token ----------------

    def flops_per_item(self) -> float:
        """Forward + backward FLOPs per trained token, from shapes.

        Recomputation (remat) is not counted.  Attention is counted as
        banded: position ``t`` attends ``min(t + 1, window)`` keys.  A
        frozen weight costs 4 FLOPs per token (forward, and the
        backward's activation gradient); an adapter factor 6 (its own
        gradient too).  The embedding gather has no matmul.
        """
        c = self.cfg
        d, f, v = c.hidden_size, c.intermediate_size, c.vocab_size
        q_out, kv_out = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
        shapes = {
            "wq": (d, q_out), "wk": (d, kv_out), "wv": (d, kv_out),
            "wo": (q_out, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d),
        }
        layer = sum(4 * i * o for i, o in shapes.values())
        pats = [re.compile(p) for p in self.lcfg.targets]
        for name, (i, o) in shapes.items():
            if any(p.search(f"layers/{name}") for p in pats):
                layer += 6 * self.lcfg.rank * (i + o)
        t, w = self.seq, c.sliding_window or self.seq
        w = min(w, t)
        keys = (w * (w + 1) / 2 + (t - w) * w) / t  # mean keys attended
        # QK^T and PV: 2 matmuls x 2 FLOPs x heads x head_dim x keys,
        # forward; twice that again backward.
        layer += 3 * 4 * q_out * keys
        head = 4 * d * v
        return float(c.num_layers * layer + head)

    # -- agreement with the plain reference ----------------------------

    def reference_check(self) -> dict:
        """The system's forward (its dtype, its attention kernel) against
        the float32 reference on the first layers of the served weights:
        one sequence of the cell's length, logits of the last positions.
        """
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmark.reference import mistral

        c = self.cfg
        n = min(REFERENCE_LAYERS, c.num_layers)
        last = min(REFERENCE_LAST, self.seq)
        base = self._make_base(self.base_key())
        sub = dict(base)
        sub["layers"] = jax.tree_util.tree_map(
            lambda x: x[:n] + 0, base["layers"]
        )
        del base
        sub_cfg = dataclasses.replace(c, num_layers=n)
        ids = jax.random.randint(
            jax.random.PRNGKey(self.seed + 3), (1, self.seq), 0, c.vocab_size
        )
        got = jax.jit(
            lambda p, i: self._llama.apply_llama(
                p, i, sub_cfg, attn_fn=self.attn_fn
            )[0, -last:]
        )(sub, ids)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(
                lambda p, i: mistral.forward_logits(
                    p, i, num_layers=n, num_heads=c.num_heads,
                    num_kv_heads=c.num_kv_heads, rope_theta=c.rope_theta,
                    rms_eps=c.rms_eps, window=c.sliding_window, last=last,
                )
            )(sub, ids[0])
        got, want = np.asarray(got, np.float32), np.asarray(want)
        rel = float(
            np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want**2))
        )
        return {
            "ok": bool(np.isfinite(rel) and rel <= REFERENCE_REL_RMS_TOL),
            "rel_rms": rel,
            "tol": REFERENCE_REL_RMS_TOL,
            "layers": n,
            "positions": [self.seq - last, self.seq],
            "max_abs_err": float(np.abs(got - want).max()),
        }


def build(config: dict, job: dict, seed: int) -> LlamaLM:
    return LlamaLM(config, job, seed)
