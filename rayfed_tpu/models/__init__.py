"""Model families for federated workloads (pure-JAX, pytree params).

The reference ships no models (SURVEY §1: "no model layer") — users bring
TF/Torch code inside Ray tasks.  Here the model zoo is part of the
framework, built TPU-first: functional ``init``/``apply`` pairs over
plain param pytrees (easy to shard with
:func:`rayfed_tpu.parallel.sharding.shard_params_by_rules`, easy to
FedAvg by tree-mapping), bfloat16-friendly compute, MXU-shaped matmuls,
and pluggable attention (dense / pallas flash / ring / Ulysses).

Families cover the BASELINE.md configs:

- :mod:`logistic`  — MNIST logistic regression + MLP (config #2)
- :mod:`resnet`    — ResNet-18 for CIFAR-10 (config #3)
- :mod:`bert`      — BERT-style encoder, split-FL friendly (config #5)
- :mod:`llama`     — Llama-3-style decoder (RoPE/GQA/SwiGLU) (config #4)
- :mod:`decoder`   — decoder described layer by layer (window / full
  attention, dense / routed + shared experts, q/k norm, output gate)
- :mod:`lora`      — LoRA adapters over any linear param (config #4)
- :mod:`moe`       — mixture-of-experts layer, expert-parallel over ep,
  and one chip's share of a published sparse-expert layer
- :mod:`quant`     — int8 weight-only quantization (frozen bases, KV)
- :mod:`hf`        — Hugging Face Llama checkpoint conversion
  (logit-parity verified against ``transformers``)
"""

from rayfed_tpu.models import (
    bert, decoder, hf, llama, logistic, lora, moe, quant, resnet,
)

__all__ = [
    "logistic", "resnet", "bert", "llama", "decoder", "lora", "moe", "quant",
    "hf",
]
