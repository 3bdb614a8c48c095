"""2-party cross-silo Llama-LoRA federated fine-tune (BASELINE config #4).

Each party holds the same frozen base model and its own private corpus;
only the low-rank adapter factors cross the wire each round (kilobytes
instead of the full model).  Run both parties in one go (spawns two
processes):

    JAX_PLATFORMS=cpu python examples/lora_finetune.py

or one party per terminal:

    python examples/lora_finetune.py alice
    python examples/lora_finetune.py bob
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CLUSTER = {
    "alice": {"address": "127.0.0.1:12020"},
    "bob": {"address": "127.0.0.1:12021"},
}

ROUNDS = 3
LOCAL_STEPS = 2
BATCH, SEQ = 4, 32


def run(party: str, rounds: int = ROUNDS) -> float:
    import jax

    import rayfed_tpu as fed
    from rayfed_tpu.fl import aggregate
    from rayfed_tpu.models import llama, lora

    fed.init(address="local", cluster=CLUSTER, party=party)

    cfg = llama.llama_tiny()
    # Adapters on attention projections + the lm_head.
    lcfg = lora.LoraConfig(rank=4, targets=(r"w[qv]$", r"lm_head$"))

    # Same tuner shape as tests/test_fl_lora.py — change them together
    # (CI drives this file directly via tests/test_examples.py).
    @fed.remote
    class Tuner:
        """Party-local fine-tuner: frozen base + private ids stay resident."""

        def __init__(self, seed: int):
            # Same base everywhere (fixed seed); real deployments load a
            # shared pretrained checkpoint instead.
            self._base = llama.init_llama(jax.random.PRNGKey(42), cfg)
            self._ids = jax.random.randint(
                jax.random.PRNGKey(seed), (BATCH, SEQ), 0, cfg.vocab_size
            )
            self._step = llama.make_lora_train_step(cfg, lr=5e-3)

        def train(self, adapters):
            opt = llama.init_adam(adapters)
            for _ in range(LOCAL_STEPS):
                adapters, opt, loss = self._step(
                    adapters, opt, self._base, self._ids
                )
            return adapters

        def loss(self, adapters) -> float:
            logits = llama.apply_llama(
                self._base, self._ids, cfg, lora=adapters
            )
            return float(llama.lm_loss(logits[:, :-1], self._ids[:, 1:]))

    tuners = {p: Tuner.party(p).remote(i + 10) for i, p in enumerate(CLUSTER)}

    base = llama.init_llama(jax.random.PRNGKey(42), cfg)
    adapters = lora.init_lora(jax.random.PRNGKey(7), base, lcfg)
    n_params = lora.num_lora_params(adapters)
    first = fed.get(tuners["alice"].loss.remote(adapters))

    for _ in range(rounds):
        adapters = aggregate(
            [tuners[p].train.remote(adapters) for p in CLUSTER]
        )

    last = fed.get(tuners["alice"].loss.remote(adapters))
    print(
        f"[{party}] {n_params} adapter params; loss@alice "
        f"{first:.3f} -> {last:.3f} over {rounds} rounds",
        flush=True,
    )
    fed.shutdown()
    return last


def main():
    if len(sys.argv) > 1:
        run(sys.argv[1])
        return
    import multiprocessing as mp

    # Every party on this one host: a chip belongs to one process at a
    # time, so the local demonstration runs on the CPU.  (On the chip:
    # `python chip_smoke.py`; one party per host: pass the party name.)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ctx = mp.get_context("spawn")
    procs = [ctx.Process(target=run, args=(p,)) for p in ("alice", "bob")]
    for p in procs:
        p.start()
    for p in procs:
        p.join(300)
    for p in procs:
        if p.is_alive():  # a hung party must fail the run, not wedge it
            p.terminate()
            p.join(10)
    codes = [p.exitcode for p in procs]
    assert codes == [0, 0], codes
    print("lora_finetune: both parties exited 0")


if __name__ == "__main__":
    main()
