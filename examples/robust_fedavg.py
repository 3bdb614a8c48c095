"""Byzantine-robust FedAvg: one poisoned party, a trimmed-mean reducer.

Three parties train a shared logistic model; carol is compromised and
pushes garbage updates every round.  The round loop swaps the mean for
a coordinate-wise trimmed mean (``fl.tree_trimmed_mean``) via the
driver's ``aggregator=`` hook — the reducer runs coordinator-side (one
party reduces, the result broadcasts) and carol's updates never move
the global model.

Run all parties in one go (spawns three processes):

    python examples/robust_fedavg.py

or one party per terminal:

    python examples/robust_fedavg.py alice   # and bob, carol
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CLUSTER = {
    "alice": {"address": "127.0.0.1:12050"},
    "bob": {"address": "127.0.0.1:12051"},
    "carol": {"address": "127.0.0.1:12052"},
}

ROUNDS = 4
N, D, CLASSES = 256, 32, 4


def run(party: str, rounds: int = ROUNDS) -> float:
    import functools

    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import run_fedavg_rounds, tree_trimmed_mean
    from rayfed_tpu.models import logistic

    fed.init(address="local", cluster=CLUSTER, party=party)

    @fed.remote
    class Trainer:
        def __init__(self, seed: int, byzantine: bool):
            key = jax.random.PRNGKey(seed)
            self._x = jax.random.normal(key, (N, D))
            w = jax.random.normal(jax.random.PRNGKey(0), (D, CLASSES))
            self._y = jnp.argmax(self._x @ w, axis=-1)
            self._byzantine = byzantine
            self._step = logistic.make_train_step(
                logistic.apply_logistic, lr=0.3
            )

        def train(self, params):
            if self._byzantine:
                # A compromised silo: huge adversarial updates.
                return jax.tree_util.tree_map(
                    lambda p: p + 1e6, params
                )
            for _ in range(2):
                params, _ = self._step(params, self._x, self._y)
            return params

        def accuracy(self, params) -> float:
            return float(
                logistic.accuracy(
                    logistic.apply_logistic(params, self._x), self._y
                )
            )

    trainers = {
        p: Trainer.party(p).remote(i + 1, p == "carol")
        for i, p in enumerate(("alice", "bob", "carol"))
    }
    params = logistic.init_logistic(jax.random.PRNGKey(0), D, CLASSES)

    # trim=1 tolerates one Byzantine party per coordinate: carol's 1e6
    # outliers are dropped before averaging, every round.
    final = run_fedavg_rounds(
        trainers,
        params,
        rounds=rounds,
        aggregator=functools.partial(tree_trimmed_mean, trim=1),
    )

    # The model must have LEARNED (not been dragged to 1e6-land).
    assert float(jnp.max(jnp.abs(final["w"]))) < 1e3
    acc = fed.get(trainers["alice"].accuracy.remote(final))
    assert acc > 0.5, acc
    print(
        f"[{party}] robust fedavg survived the Byzantine party: "
        f"accuracy@alice {acc:.3f}",
        flush=True,
    )
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
    procs = [
        ctx.Process(target=run, args=(p,)) for p in ("alice", "bob", "carol")
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(300)
    for p in procs:
        if p.is_alive():
            p.terminate()
            p.join(10)
    codes = [p.exitcode for p in procs]
    assert codes == [0, 0, 0], codes
    print("robust_fedavg: all parties exited 0")


if __name__ == "__main__":
    main()
