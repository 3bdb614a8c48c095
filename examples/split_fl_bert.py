"""Vertical/split federated learning: BERT encoder@alice → head@bob
(BASELINE config #5).

Alice owns the embeddings, transformer layers, and pooler, and her token
ids never leave her silo; bob owns the classification head and the
labels, which never leave his.  Each step alice *pushes* pooled [CLS]
activations (owner-initiated, per the framework's push perimeter), bob
steps the head and pushes the activation gradient back, and alice closes
the backward.  ``step_pipelined`` streams K microbatches back-to-back so
wire and both parties' compute overlap.

Run both parties in one go (spawns two processes):

    JAX_PLATFORMS=cpu python examples/split_fl_bert.py

or one party per terminal:

    python examples/split_fl_bert.py alice
    python examples/split_fl_bert.py bob
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CLUSTER = {
    "alice": {"address": "127.0.0.1:12030"},
    "bob": {"address": "127.0.0.1:12031"},
}

STEPS = 8
N, T = 32, 8
MICROBATCHES = 4


def run(party: str, steps: int = STEPS) -> float:
    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import SplitTrainer
    from rayfed_tpu.models import bert
    from rayfed_tpu.models.logistic import softmax_cross_entropy

    fed.init(address="local", cluster=CLUSTER, party=party)

    cfg = bert.BertConfig(
        vocab_size=128,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position=16,
        num_classes=2,
    )

    # Both controllers derive the same initial split deterministically;
    # each party's actor keeps only its own half resident.
    full = bert.init_bert(jax.random.PRNGKey(0), cfg)
    enc_params, head_params = bert.split_params(full)

    @fed.remote
    def load_ids(mb):
        ids = jax.random.randint(
            jax.random.PRNGKey(5), (N, T), 0, cfg.vocab_size
        )
        return ids if mb is None else jnp.array_split(ids, MICROBATCHES)[mb]

    @fed.remote
    def load_labels(mb):
        # Learnable signal: label = parity of the first token id.
        ids = jax.random.randint(
            jax.random.PRNGKey(5), (N, T), 0, cfg.vocab_size
        )
        y = (ids[:, 0] % 2).astype(jnp.int32)
        return y if mb is None else jnp.array_split(y, MICROBATCHES)[mb]

    def encoder_apply(params, ids):
        hidden = bert.apply_encoder(params, ids, cfg)
        return bert.apply_pooler(params, hidden)

    trainer = SplitTrainer(
        encoder_party="alice",
        head_party="bob",
        encoder_params=enc_params,
        encoder_apply=encoder_apply,
        head_params=head_params,
        head_apply=bert.apply_head,
        loss_fn=softmax_cross_entropy,
        lr=0.05,
        wire_dtype=jnp.bfloat16,  # half the activation bytes per hop
    )

    ids_obj = load_ids.party("alice").remote(None)
    y_obj = load_labels.party("bob").remote(None)
    first = float(fed.get(trainer.step(ids_obj, y_obj)))

    # Microbatched steps: K activation pushes stream while the next
    # microbatch computes; one accumulated update at the end of each.
    x_mbs = [load_ids.party("alice").remote(i) for i in range(MICROBATCHES)]
    y_mbs = [load_labels.party("bob").remote(i) for i in range(MICROBATCHES)]
    last = first
    for _ in range(steps):
        losses = trainer.step_pipelined(x_mbs, y_mbs)
        last = sum(float(x) for x in fed.get(losses)) / len(losses)

    print(
        f"[{party}] split BERT: loss {first:.3f} -> {last:.3f} over "
        f"{steps} pipelined steps ({MICROBATCHES} microbatches each, "
        f"bf16 wire)",
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
    print("split_fl_bert: both parties exited 0")


if __name__ == "__main__":
    main()
