"""2-party FedAvg on synthetic MNIST-shaped data (BASELINE config #2).

Run both parties in one go (spawns two processes):

    JAX_PLATFORMS=cpu python examples/fedavg_mnist.py

or one party per terminal:

    python examples/fedavg_mnist.py alice
    python examples/fedavg_mnist.py bob
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CLUSTER = {
    "alice": {"address": "127.0.0.1:12010"},
    "bob": {"address": "127.0.0.1:12011"},
}

ROUNDS = 5
LOCAL_EPOCHS = 2
N, D, CLASSES = 512, 784, 10


def run(party: str, rounds: int = ROUNDS) -> float:
    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import aggregate
    from rayfed_tpu.models import logistic

    fed.init(address="local", cluster=CLUSTER, party=party)

    @fed.remote
    class Trainer:
        """Party-local trainer: data + jitted train step stay resident."""

        def __init__(self, seed: int):
            key = jax.random.PRNGKey(seed)
            self._x = jax.random.normal(key, (N, D))
            w = jax.random.normal(jax.random.PRNGKey(0), (D, CLASSES))
            self._y = jnp.argmax(self._x @ w, axis=-1)
            self._step = logistic.make_train_step(logistic.apply_logistic, lr=0.2)

        def train(self, params):
            for _ in range(LOCAL_EPOCHS):
                params, loss = self._step(params, self._x, self._y)
            return params

        def accuracy(self, params) -> float:
            return float(
                logistic.accuracy(logistic.apply_logistic(params, self._x), self._y)
            )

    alice = Trainer.party("alice").remote(1)
    bob = Trainer.party("bob").remote(2)

    params0 = logistic.init_logistic(jax.random.PRNGKey(0), D, CLASSES)

    # The explicit loop (how the pieces compose):
    params = params0
    for _ in range(rounds):
        params = aggregate([alice.train.remote(params), bob.train.remote(params)])

    # ...or, equivalently, the one-call driver from the same start — it
    # also pipelines rounds and can add a server optimizer /
    # checkpointing (see docs "Federated averaging").
    from rayfed_tpu.fl import run_fedavg_rounds

    via_driver = run_fedavg_rounds(
        {"alice": alice, "bob": bob}, params0, rounds=rounds
    )
    assert jnp.allclose(via_driver["w"], params["w"], atol=1e-5)

    acc = fed.get(alice.accuracy.remote(params))
    print(f"[{party}] final train accuracy@alice: {acc:.3f}", flush=True)
    fed.shutdown()
    return acc


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
    print("fedavg_mnist: both parties exited 0")


if __name__ == "__main__":
    main()
