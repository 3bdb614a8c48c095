"""FedAvg between MESH parties (BASELINE config #3's program shape).

Each party is a multi-device mesh: its model is fsdp-sharded over the
party mesh, contributions cross the wire shard-streamed, land on the
peer's mesh via the sender's sharding description (`resolve_sharding` —
per-shard device_put, no host re-assembly), and the round average runs
as jitted sharded tree arithmetic.  The cross-party hop is the only
"DCN" traffic; everything inside a party rides the mesh.

This file is the CPU / multi-host demonstration: one OS process per
party, 8 virtual CPU devices standing in for a pod slice.  Run both
parties in one go (spawns two processes):

    python examples/mesh_fedavg.py

or one party per terminal:

    python examples/mesh_fedavg.py alice
    python examples/mesh_fedavg.py bob

A chip belongs to one process at a time, so on a machine with chips the
same program (:func:`fedavg_rounds`) runs with both parties as threads
of one process, each on its own two-chip mesh: ``python chip_smoke.py
--chips 4``.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CLUSTER = {
    "alice": {"address": "127.0.0.1:12040"},
    "bob": {"address": "127.0.0.1:12041"},
}
PARTIES = tuple(CLUSTER)

ROUNDS = 3
ROWS, COLS = 2048, 1024  # 8.4 MB f32 leaf — rides the wire per shard


def make_train_step(delta: float):
    """The party-local step: sharding-preserving (inputs sharded over
    ``fsdp`` stay sharded — no gather)."""
    import jax

    return jax.jit(
        lambda p: jax.tree_util.tree_map(lambda x: x + delta, p)
    )


def fedavg_rounds(party: str, rounds: int = ROUNDS):
    """The federated program, between ``fed.init`` and ``fed.shutdown``:
    ``rounds`` of FedAvg over the calling party's mesh.  Returns the
    final params (sharded over that mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import rayfed_tpu as fed
    from rayfed_tpu.api import get_runtime
    from rayfed_tpu.fl import aggregate

    mesh = get_runtime().mesh

    @fed.remote
    class Trainer:
        """Party-pinned trainer; params stay sharded on the party mesh."""

        def __init__(self, delta: float):
            self._step = make_train_step(delta)

        def train(self, params):
            # The incoming tree landed sharded over THIS party's mesh.
            mine = set(get_runtime().mesh.devices.flat)
            assert params["w"].sharding.device_set == mine, (
                params["w"].sharding, mine
            )
            return self._step(params)

    trainers = {
        p: Trainer.party(p).remote(float(i + 1))
        for i, p in enumerate(PARTIES)
    }

    w = jnp.zeros((ROWS, COLS), jnp.float32)
    params = {"w": jax.device_put(w, NamedSharding(mesh, P("fsdp", None)))}

    for _ in range(rounds):
        updates = [trainers[p].train.remote(params) for p in trainers]
        params = aggregate(updates)  # mean(w+1, w+2) = w + 1.5 per round

    got = float(jnp.mean(params["w"]))
    expected = 1.5 * rounds
    assert abs(got - expected) < 1e-4, (party, got, expected)
    return params


def run(party: str, rounds: int = ROUNDS) -> None:
    from rayfed_tpu.utils import force_cpu_devices

    force_cpu_devices(8)

    import rayfed_tpu as fed

    fed.init(
        address="local", cluster=CLUSTER, party=party, mesh_shape={"fsdp": 8}
    )
    params = fedavg_rounds(party, rounds)
    print(
        f"[{party}] {rounds} mesh-party rounds ok: result sharded "
        f"{params['w'].sharding.spec} over {params['w'].sharding.mesh.shape}",
        flush=True,
    )
    fed.shutdown()


def main():
    if len(sys.argv) > 1:
        run(sys.argv[1])
        return
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    procs = [ctx.Process(target=run, args=(p,)) for p in ("alice", "bob")]
    for p in procs:
        p.start()
    for p in procs:
        p.join(300)
    for p in procs:
        if p.is_alive():
            p.terminate()
            p.join(10)
    codes = [p.exitcode for p in procs]
    assert codes == [0, 0], codes
    print("mesh_fedavg: both parties exited 0")


if __name__ == "__main__":
    main()
