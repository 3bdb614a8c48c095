"""Two-party hello-world: the canonical cross-silo program.

Run the SAME script once per party (multi-controller execution):

    python examples/simple_example.py alice &
    python examples/simple_example.py bob

or with no argument to launch both parties as local processes.

Semantics match the reference's ``tests/simple_example.py``: actors pinned
to parties, cross-party results pushed by the owner, aggregate fetched on
both sides.
"""

import multiprocessing
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

cluster = {
    "alice": {"address": "127.0.0.1:21010"},
    "bob": {"address": "127.0.0.1:21011"},
}


def run(party):
    import numpy as np

    import rayfed_tpu as fed

    @fed.remote
    class MyActor:
        def __init__(self, party, data):
            self._data = data
            self._party = party

        def f(self):
            return f"f({self._party})"

        def weights(self):
            return np.full((4,), self._data, dtype=np.float32)

    @fed.remote
    def agg_fn(obj1, obj2):
        return f"agg-{obj1}-{obj2}"

    @fed.remote
    def mean_fn(w1, w2):
        return (w1 + w2) / 2

    fed.init(address="local", cluster=cluster, party=party)
    print(f"Running the script in party {party}")

    actor_alice = MyActor.party("alice").remote(party, 1.0)
    actor_bob = MyActor.party("bob").remote(party, 3.0)

    obj = agg_fn.party("bob").remote(
        actor_alice.f.remote(), actor_bob.f.remote()
    )
    result = fed.get(obj)
    print(f"[{party}] string aggregate: {result}")
    assert result == "agg-f(alice)-f(bob)", result

    mean = mean_fn.party("alice").remote(
        actor_alice.weights.remote(), actor_bob.weights.remote()
    )
    mean_value = fed.get(mean)
    print(f"[{party}] federated mean: {mean_value}")
    assert float(mean_value[0]) == 2.0
    fed.shutdown()
    print(f"[{party}] OK")


def main():
    # Every party on this one host: a chip belongs to one process at a
    # time, so the local demonstration runs on the CPU.  (On the chip:
    # `python chip_smoke.py`; one party per host: pass the party name.)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    procs = [
        multiprocessing.get_context("spawn").Process(target=run, args=(p,))
        for p in ("alice", "bob")
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    assert all(p.exitcode == 0 for p in procs), [p.exitcode for p in procs]
    print("simple_example: both parties exited 0")


if __name__ == "__main__":
    if len(sys.argv) > 1:
        run(sys.argv[1])
    else:
        main()
