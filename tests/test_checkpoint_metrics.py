"""Checkpoint/resume + metrics subsystems."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rayfed_tpu.checkpoint import FedCheckpointer
from rayfed_tpu.metrics import TransferLog


@pytest.mark.parametrize("use_orbax", [True, False])
def test_checkpoint_save_restore(tmp_path, use_orbax):
    ckpt = FedCheckpointer(str(tmp_path), "alice", use_orbax=use_orbax)
    state = {
        "params": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros((3,))},
        "round": np.int64(7),
    }
    ckpt.save(3, state, metadata={"note": "test"})
    assert ckpt.latest_round() == 3
    r, restored = ckpt.restore(target=state)
    assert r == 3
    np.testing.assert_allclose(restored["params"]["w"], state["params"]["w"])
    np.testing.assert_allclose(restored["params"]["b"], state["params"]["b"])


def test_checkpoint_gc_and_rounds(tmp_path):
    ckpt = FedCheckpointer(str(tmp_path), "bob", max_to_keep=2, use_orbax=False)
    state = {"x": jnp.ones((2,))}
    for r in (1, 2, 3, 4):
        ckpt.save(r, state)
    assert ckpt.rounds() == [3, 4]
    r, restored = ckpt.restore(target=state)
    assert r == 4


def test_checkpoint_restore_specific_round(tmp_path):
    ckpt = FedCheckpointer(str(tmp_path), "alice", use_orbax=False)
    for r in (1, 2):
        ckpt.save(r, {"x": jnp.full((2,), float(r))})
    r, restored = ckpt.restore(1, target={"x": jnp.zeros((2,))})
    np.testing.assert_allclose(restored["x"], [1.0, 1.0])


@pytest.mark.parametrize("use_orbax", [True, False])
def test_checkpoint_int8_roundtrip(tmp_path, use_orbax):
    """A quantized base (QTensor leaves) restores bit-exactly — the 8B
    LoRA resume path never materializes a full-precision tree."""
    from rayfed_tpu.models.quant import QTensor, quantize_int8

    tree = {
        "w": quantize_int8(jax.random.normal(jax.random.PRNGKey(0), (8, 16))),
        "b": jnp.ones((4,)),
    }
    ckpt = FedCheckpointer(str(tmp_path), "alice", use_orbax=use_orbax)
    ckpt.save(1, tree)
    _, restored = ckpt.restore(target=tree)
    assert isinstance(restored["w"], QTensor)
    assert restored["w"].q.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(restored["w"].q), np.asarray(tree["w"].q)
    )
    np.testing.assert_allclose(
        np.asarray(restored["w"].scale), np.asarray(tree["w"].scale)
    )


def test_checkpoint_missing_raises(tmp_path):
    ckpt = FedCheckpointer(str(tmp_path), "carol", use_orbax=False)
    with pytest.raises(FileNotFoundError):
        ckpt.restore()


def test_transfer_log_throughput():
    log = TransferLog(capacity=4)
    log.record("send", "bob", "1#0", "2", 1_000_000_000, 1.0)
    log.record("send", "bob", "3#0", "4", 1_000_000_000, 1.0)
    log.record("recv", "bob", "5#0", "6", 500, 0.001)
    assert abs(log.throughput_gbps("send") - 1.0) < 1e-6
    assert len(log.records()) == 3
    # Ring buffer bound
    for i in range(10):
        log.record("send", "bob", str(i), "x", 1, 0.1)
    assert len(log.records()) == 4


def test_stats_through_fed_api():
    """fed.get_stats returns transport counters inside an active runtime."""
    from tests.multiproc import make_cluster, run_parties

    cluster = make_cluster(["alice", "bob"])
    run_parties(_stats_party_run, ["alice", "bob"], args=(cluster,))


def _stats_party_run(party, cluster):
    import numpy as np

    import rayfed_tpu as fed

    fed.init(address="local", cluster=cluster, party=party)

    @fed.remote
    def produce():
        return np.arange(1000, dtype=np.float32)

    obj = produce.party("alice").remote()
    val = fed.get(obj)
    assert val.shape == (1000,)
    import time

    stats = fed.get_stats()
    if party == "alice":
        assert stats["send_op_count"] >= 1, stats
        # Bytes are counted on ACK (async) — poll.  Generous
        # deadline: under full-suite load on a busy CI box the ACK can
        # lag well past the 10s that suffices on an idle machine.
        deadline = time.time() + 45
        while stats.get("send_bytes", 0) == 0 and time.time() < deadline:
            time.sleep(0.05)
            stats = fed.get_stats()
        assert stats["send_bytes"] > 0, stats
    else:
        assert stats["receive_op_count"] >= 1, stats
    # Mailbox observability rides along: dedup/expiry/fail-fast counters
    # and the currently-poisoned party set.
    assert stats["peer_failed_recvs"] == 0, stats
    assert stats["dead_parties"] == [], stats
    fed.shutdown()
