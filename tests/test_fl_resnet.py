"""BASELINE #3 shape: 4-party ResNet FedAvg over the real transport.

Four OS processes, one per party, real TCP pushes, coordinator-mode
aggregation (the ``auto`` switch at N>2) — the first multi-party
exercise of ``aggregate(mode="coordinator")``.  Mirrors the reference's
multi-party test pattern (``/root/reference/tests/test_fed_get.py:47-82``)
with a CV workload instead of scalars.

The model is a deliberately tiny ResNet (``chip_smoke.py`` runs the full
ResNet-18; this host's test mesh is 1 CPU core shared by 4 processes) —
what's under test is the cross-party protocol, not conv throughput.
"""

import jax
import jax.numpy as jnp
import pytest

from tests.multiproc import make_cluster, run_parties

PARTIES = ["alice", "bob", "carol", "dave"]
RESNET_CLUSTER = make_cluster(PARTIES)


def run_resnet_fedavg(party, cluster=RESNET_CLUSTER):
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu.fl import aggregate, tree_average
    from rayfed_tpu.models import resnet

    fed.init(address="local", cluster=cluster, party=party)

    cfg = resnet.ResNetConfig(stage_sizes=(1, 1), width=8, num_classes=4)
    n, hw = 32, 8  # 8x8 images: conv stack is real, compute is tiny

    # Same trainer shape as chip_smoke.py's ResNet phase (full ResNet-18
    # there; tiny config here) — change them together: the fused
    # wire-dtype round (make_fed_train_step, bf16 bundles on the wire)
    # is exactly the program that phase runs on the chip.
    @fed.remote
    class Trainer:
        def __init__(self, seed: int):
            key = jax.random.PRNGKey(seed)
            self._x = jax.random.normal(key, (n, hw, hw, 3))
            # Learnable signal: labels from a fixed linear probe on the
            # channel-mean pixels (same probe every party, different data).
            probe = jax.random.normal(jax.random.PRNGKey(0), (3, cfg.num_classes))
            self._y = jnp.argmax(jnp.mean(self._x, axis=(1, 2)) @ probe, axis=-1)
            self._step = resnet.make_fed_train_step(cfg, lr=0.05, local_steps=2)

        def train(self, bundle):
            out, _loss = self._step(bundle, self._x, self._y)
            return out

        def loss(self, bundle):
            from rayfed_tpu.fl import decompress

            params, state = decompress(bundle)
            logits, _ = resnet.apply_resnet(
                params, state, self._x, cfg, train=False
            )
            from rayfed_tpu.models.logistic import softmax_cross_entropy

            return float(softmax_cross_entropy(logits, self._y))

    trainers = {p: Trainer.party(p).remote(i + 1) for i, p in enumerate(PARTIES)}

    from rayfed_tpu.fl import compress

    bundle = compress(resnet.init_resnet(jax.random.PRNGKey(0), cfg))
    first_loss = fed.get(trainers["alice"].loss.remote(bundle))

    for _round in range(3):
        updates = [trainers[p].train.remote(bundle) for p in PARTIES]
        # N=4 -> "auto" must route through the coordinator (2(N-1)
        # transfers), exercising push-to-coordinator + broadcast.
        bundle = aggregate(updates)

    last_loss = fed.get(trainers["alice"].loss.remote(bundle))
    assert last_loss < first_loss, (first_loss, last_loss)

    # Coordinator result must equal the local average of the same
    # contributions (seq-id-deterministic: same calls on every party).
    updates = [trainers[p].train.remote(bundle) for p in PARTIES]
    via_coord = aggregate(updates, mode="coordinator", coordinator="carol")
    local = tree_average(fed.get(updates))
    for a, b in zip(
        jax.tree_util.tree_leaves(via_coord), jax.tree_util.tree_leaves(local)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    # Weighted coordinator aggregation (example-count weighting) must
    # match the local weighted average of the same contributions.
    w = [1.0, 2.0, 3.0, 4.0]
    updates = [trainers[p].train.remote(bundle) for p in PARTIES]
    weighted = aggregate(updates, weights=w)
    local_w = tree_average(fed.get(updates), weights=w)
    for a, b in zip(
        jax.tree_util.tree_leaves(weighted), jax.tree_util.tree_leaves(local_w)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    fed.shutdown()


# slow: heaviest tier-1 fixture (~55s idle: 4 subprocess JAX imports +
# resnet jit compiles).  The 4-party coordinator round stays covered in
# tier-1 by test_streaming_agg's fed-API round, the ring suite and the
# overlap suite (toy models — same aggregation path, fraction of the
# cost), and the resnet packed train step by test_packed_codec.
@pytest.mark.slow
def test_resnet_fedavg_4party_coordinator():
    run_parties(run_resnet_fedavg, PARTIES, args=(RESNET_CLUSTER,), timeout=300)
