"""Several parties as threads of ONE process (rayfed_tpu.inprocess).

The launch model the chip needs (a chip belongs to one process at a
time): every party calls ``fed.init(..., process_default=False)`` on
its own thread and the whole round — ``@fed.remote`` trainers,
``run_fedavg_rounds``, ``fed.shutdown`` — runs through the public entry
points over the real loopback-TCP transport.  Toy model, no
subprocesses (tier-1 budget rule).
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import rayfed_tpu as fed
from rayfed_tpu import inprocess, telemetry
from rayfed_tpu.fl import compression as C
from rayfed_tpu.fl import quantize as qz
from rayfed_tpu.fl.streaming import StreamingAggregator
from rayfed_tpu.fl.trainer import run_fedavg_rounds
from rayfed_tpu.models import logistic
from rayfed_tpu.runtime import get_runtime, get_runtime_or_none

D, CLASSES, N = 16, 3, 64
ROUNDS = 3

WIRE_FORMS = {
    "bf16": dict(compress_wire=True, packed_wire=True),
    "uint8": dict(
        compress_wire=True, packed_wire=True, wire_quant="uint8",
        streaming_agg=True,
    ),
}


def _flat(tree) -> np.ndarray:
    return np.concatenate(
        [np.asarray(x, np.float32).ravel()
         for x in jax.tree_util.tree_leaves(tree)]
    )


def _fedavg_in_process(parties, wire_kw, rounds=ROUNDS):
    """Run ``rounds`` of FedAvg with ``parties`` as threads; returns
    ``({party: final flat f32}, {party: wire bytes sent},
    {party: uplink error-feedback residual},
    {(party, round): update flat f32})``."""
    cluster = inprocess.loopback_cluster(parties)
    updates = {}
    step = logistic.make_train_step(logistic.apply_logistic, lr=0.3)

    def party_main(party):
        @fed.remote
        class Trainer:
            def __init__(self, owner, seed):
                # The actor lane is bound to ITS party's runtime.
                assert get_runtime().party == owner
                self._owner = owner
                self._round = 0
                self._x = jax.random.normal(jax.random.PRNGKey(seed), (N, D))
                w = jax.random.normal(jax.random.PRNGKey(9), (D, CLASSES))
                self._y = jnp.argmax(self._x @ w, axis=-1)

            def train(self, params):
                params = C.decompress(params, jnp.float32)
                for _ in range(2):
                    params, _ = step(params, self._x, self._y)
                out = C.compress(params, packed=True)
                updates[(self._owner, self._round)] = np.asarray(
                    out.buf
                ).astype(np.float32)
                self._round += 1
                return out

        trainers = {
            p: Trainer.party(p).remote(p, i + 1)
            for i, p in enumerate(parties)
        }
        params = logistic.init_logistic(jax.random.PRNGKey(0), D, CLASSES)
        final = run_fedavg_rounds(trainers, params, rounds, **wire_kw)
        from rayfed_tpu.metrics import get_stats

        resid = qz.compressor("fedavg").residual
        return (
            _flat(final),
            get_stats()["send_bytes"],
            None if resid is None else np.asarray(resid),
        )

    out = inprocess.run_parties(
        party_main, cluster, timeout=120, logging_level="warning"
    )
    return tuple({p: v[i] for p, v in out.items()} for i in range(3)) + (
        updates,
    )


@pytest.mark.parametrize("n_parties", [2, 4])
@pytest.mark.parametrize("wire_form", list(WIRE_FORMS))
def test_inprocess_fedavg_matches_numpy_reference(n_parties, wire_form):
    parties = ["alice", "bob", "carol", "dave"][:n_parties]
    finals, sent, resids, updates = _fedavg_in_process(
        parties, WIRE_FORMS[wire_form]
    )

    # Every controller holds the identical final model, byte for byte.
    want = finals[parties[0]].tobytes()
    for p in parties[1:]:
        assert finals[p].tobytes() == want, p

    # Plain float32 numpy FedAvg of the same local updates (the flat
    # update buffers are in tree_leaves order, like _flat).
    last = [updates[(p, ROUNDS - 1)] for p in parties]
    ref = np.sum(last, axis=0, dtype=np.float32) / np.float32(n_parties)
    got = finals[parties[0]]
    assert got.shape == ref.shape
    if wire_form == "bf16":
        # The mean is cast to the bf16 wire dtype once: half an ulp.
        np.testing.assert_allclose(got, ref, rtol=2.0**-8, atol=1e-6)
    else:
        # 8-bit codes on a grid ranged by QUANT_DELTA_EXPAND (4) x the
        # previous round's delta: half a step is ~1/64 of that delta,
        # the carried residual and the downlink recode add as much
        # again — a fraction of the delta, not of the weights.
        prev = [updates[(p, ROUNDS - 2)] for p in parties]
        delta = np.abs(
            ref - np.sum(prev, axis=0, dtype=np.float32) / n_parties
        ).max()
        assert np.abs(got - ref).max() <= 0.05 * delta + 1e-6
        # Error feedback is per SENDER: each in-process party carried
        # its own residual (different data, different residuals).
        for i, p in enumerate(parties):
            for q in parties[i + 1:]:
                assert not np.array_equal(resids[p], resids[q]), (p, q)

    # The bytes really crossed the wire between distinct parties.
    assert all(sent[p] > 0 for p in parties), sent


def test_quantized_round_keeps_reference_and_delta_on_the_device():
    """The engagement counter of the device-resident quantized round:
    ``nbytes`` of the four ``fl.quant.*`` spans is what crossed to the
    host inside them.  One bootstrap round, then three quantized ones."""
    parties = ["alice", "bob"]
    rounds = 4
    rec = telemetry.install(capacity=1 << 16)
    try:
        finals, _, resids, _ = _fedavg_in_process(
            parties, WIRE_FORMS["uint8"], rounds=rounds
        )
    finally:
        telemetry.uninstall()
    total = D * CLASSES + CLASSES
    assert resids["alice"].size == total
    nb = 1  # one canonical block covers the toy model
    spans = {}
    for r in rec.records():
        if r.phase.startswith("fl.quant."):
            spans.setdefault(r.phase, []).append(r)

    quantized = range(1, rounds)
    for phase in ("fl.quant.ref", "fl.quant.delta"):
        seen = {(r.party, r.round) for r in spans[phase]}
        assert seen == {(p, q) for p in parties for q in range(rounds)}
        assert all(r.nbytes == 0 for r in spans[phase]), phase
    grids = spans["fl.quant.grid"]
    assert grids and all(r.nbytes == nb * 12 for r in grids)
    recodes = spans["fl.quant.recode"]
    assert [r.round for r in recodes] == list(quantized)
    assert all(r.nbytes == total for r in recodes)  # uint8 codes

    # Every controller derived the round's uplink grid itself, from the
    # statistics it took of the decoded broadcast: same fingerprint.
    for q in quantized:
        fps = {
            r.party: r.detail["fp"] for r in grids
            if r.round == q and r.detail["side"] == "up"
        }
        assert set(fps) == set(parties), (q, fps)
        assert len(set(fps.values())) == 1, (q, fps)
    assert finals["alice"].tobytes() == finals["bob"].tobytes()


def test_shutdown_of_one_party_leaves_the_others_working():
    """alice holds the process default; bob and carol are in-process
    parties on their own threads.  carol's fed.shutdown() must not take
    alice's process default (or bob's binding) away."""
    parties = ["alice", "bob", "carol"]
    cluster = inprocess.loopback_cluster(parties)
    carol_down = threading.Event()
    bob_got = []
    bob_done = threading.Event()
    errors = []

    def program():
        # Each party decorates its own copy, as the other tests of this
        # file do inside party_main: FedRemoteFunction.party() binds the
        # SHARED decorated object to the calling thread's runtime, so
        # two party threads calling one object race (alice's call then
        # draws its seq id from bob's runtime and bob waits for a push
        # that is never made: ROADMAP Queue 3 item 13).
        @fed.remote
        def produce():
            return {"x": np.arange(8, dtype=np.float32)}

        @fed.remote
        def consume(v):
            return float(np.sum(v["x"]))

        return consume.party("bob").remote(produce.party("alice").remote())

    def carol_main():
        try:
            fed.init(address="local", cluster=cluster, party="carol",
                     process_default=False, logging_level="warning")
            fed.shutdown()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)
        finally:
            carol_down.set()

    def bob_main():
        try:
            fed.init(address="local", cluster=cluster, party="bob",
                     process_default=False, logging_level="warning")
            assert carol_down.wait(60)
            assert get_runtime().party == "bob"
            bob_got.append(program().get_local_ref().resolve(timeout=60))
            fed.shutdown()
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(e)
        finally:
            bob_done.set()

    fed.init(address="local", cluster=cluster, party="alice",
             logging_level="warning")
    try:
        threads = [
            threading.Thread(target=carol_main, daemon=True),
            threading.Thread(target=bob_main, daemon=True),
        ]
        for t in threads:
            t.start()
        assert carol_down.wait(60)
        # The process default survived carol's shutdown: an unbound
        # helper thread still resolves alice's runtime.
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(get_runtime_or_none()), daemon=True
        )
        t.start()
        t.join(10)
        assert seen and seen[0] is not None and seen[0].party == "alice"
        # alice runs the same program: her task's result is pushed to
        # its consumer, bob.
        program()
        assert bob_done.wait(60)
        assert not errors, errors
        assert bob_got == [28.0]
    finally:
        fed.shutdown()
    assert get_runtime_or_none() is None


def test_jitted_integer_fold_bitexact_vs_numpy_fold(monkeypatch):
    """On the CPU backend the quantized aggregator folds with numpy
    slice-adds; on an accelerator it dispatches quantized_accum_kernel.
    Steer the backend test from here and require identical bytes."""
    rng = np.random.default_rng(3)
    size, ce = 40_000, 1 << 12
    ref = rng.normal(size=(size,)).astype(np.float32)
    packeds = [
        C.pack_tree(
            {"w": jnp.asarray(
                ref + 0.01 * rng.normal(size=(size,)).astype(np.float32)
            )},
            jnp.float32,
        )
        for _ in range(3)
    ]
    grid = qz.make_round_grid(
        0.01 * rng.normal(size=(size,)).astype(np.float32),
        chunk_elems=ce, mode="delta", expand=4.0,
    )
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]

    def fold():
        agg = StreamingAggregator(
            3, weights=[3, 1, 2], chunk_elems=ce, quant=grid, quant_ref=ref
        )
        for i, q in enumerate(qts):
            agg.add_local(i, q)
        out = agg.result(timeout=60)
        return agg._np_fold, np.asarray(out.buf)

    np_fold, via_numpy = fold()
    assert np_fold is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    np_fold, via_kernel = fold()
    assert np_fold is False
    assert via_kernel.tobytes() == via_numpy.tobytes()
