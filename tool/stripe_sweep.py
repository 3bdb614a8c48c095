#!/usr/bin/env python3
"""Time the striped receive path on the host: in-process parties whose
trainer does no work, so a round is the wire and the fold alone.

    python tool/stripe_sweep.py [--parties 2 4] [--bytes 62918762]
                                [--rounds 6] [--warm 2] [--out FILE]

Every party is a thread of this process (``rayfed_tpu.inprocess``, real
loopback TCP between them), pinned to the CPU.  They run
``run_fedavg_rounds`` with ``compress_wire``, ``packed_wire`` and
``streaming_agg``, the coordinator last, as the benchmark's
``qlora-wire`` cells do.  A contribution is one packed bf16 leaf of
``--bytes / 2`` elements (the default is the Mistral cells' 62,918,762
bytes; the packed header adds a few hundred); the trainer adds a
constant to it.  With the default two connections a
peer, every payload of at least ``wire.STRIPE_MIN_BYTES`` goes out as
stripe frames of ``wire.DELTA_CHUNK_BYTES``.

Prints one JSON line a party count, over the ``--rounds`` rounds after
``--warm`` rounds of warm-up (medians unless named otherwise):

- ``round_s``: one round's wall, from the earliest entry into ``train``
  to the next (the last round ends when every party holds the model);
- ``fold_ms``: the coordinator's FR ``agg.fold``, first byte to last
  arrival; ``wire_send_ms``: FR ``wire.send``, every party's sends;
- ``receive_read_ms`` / ``receive_apply_ms``: the receiving threads'
  time a striped payload, in the off-loop socket read of its frames and
  in ``server._apply_stripe_frame`` (wrapped here, summed over frames);
- ``striped_bytes_measured`` / ``in_place_bytes_measured``: the bytes
  of the striped payloads received, and of those read in place, by
  their FR ``wire.reassemble`` records (null where the record has no
  ``in_place_bytes``);
- the servers' counters summed over parties: ``receive_stripe_frames``,
  ``receive_striped_payloads``, ``receive_stripe_inplace_frames``,
  ``receive_stripe_inplace_bytes`` (null where the tree has no such
  counter).

CPU numbers: they say how the host code compares between two trees on
one machine, never what a chip's round takes.  Run by no cell.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PARTY_NAMES = ("alice", "bob", "carol", "dave", "erin", "frank")
COUNTERS = (
    "receive_stripe_frames", "receive_striped_payloads",
    "receive_stripe_inplace_frames", "receive_stripe_inplace_bytes",
)


class StageClock:
    """Seconds the receiving threads spend in the striped receive
    stages, by wrapping them (any thread)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.read_s = 0.0
        self.apply_s = 0.0
        self.armed = False

    def add(self, name: str, seconds: float) -> None:
        if self.armed:
            with self.lock:
                setattr(self, name, getattr(self, name) + seconds)

    def install(self) -> None:
        from rayfed_tpu.transport import server

        raw_read = server._FrameProtocol._raw_read
        apply = server._apply_stripe_frame
        clock = self

        def timed_raw_read(proto, fd):
            t0 = time.perf_counter()
            try:
                return raw_read(proto, fd)
            finally:
                if proto._header.get("stp") is not None:
                    clock.add("read_s", time.perf_counter() - t0)

        def timed_apply(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return apply(*args, **kwargs)
            finally:
                clock.add("apply_s", time.perf_counter() - t0)

        server._FrameProtocol._raw_read = timed_raw_read
        server._apply_stripe_frame = timed_apply


def sweep(n_parties: int, nbytes: int, rounds: int, warm: int,
          clock: StageClock) -> dict:
    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu import fl, inprocess, telemetry
    from rayfed_tpu.fl.trainer import run_fedavg_rounds
    from rayfed_tpu.metrics import get_stats
    from rayfed_tpu.runtime import get_runtime

    parties = list(PARTY_NAMES[:n_parties])
    t_in: dict = {}
    t_lock = threading.Lock()
    gate = {"measure_from": None}

    def party_main(party):
        @fed.remote
        class Trainer:
            def __init__(self, owner):
                self._owner, self._round = owner, 0

            def train(self, bundle):
                r, self._round = self._round, self._round + 1
                t = time.time()
                with t_lock:
                    t_in[r] = min(t_in.get(r, t), t)
                    if r == warm and gate["measure_from"] is None:
                        gate["measure_from"] = t
                        clock.armed = True
                tree = fl.decompress(bundle)
                out = jax.tree_util.tree_map(lambda x: x + 1e-3, tree)
                return fl.compress(out, packed=True)

        trainers = {p: Trainer.party(p).remote(p) for p in parties}
        params = {"w": jnp.zeros((nbytes // 2,), jnp.float32)}
        final = run_fedavg_rounds(
            trainers, params, warm + rounds, compress_wire=True,
            packed_wire=True, streaming_agg=True, coordinator=parties[-1],
        )
        jax.block_until_ready(jax.tree_util.tree_leaves(final))
        t_end = time.time()
        get_runtime().cleanup_manager.wait_sending()
        return t_end, get_stats()

    clock.read_s = clock.apply_s = 0.0
    clock.armed = False
    recorder = telemetry.install(capacity=1 << 18)
    try:
        out = inprocess.run_parties(
            party_main, inprocess.loopback_cluster(parties), timeout=900,
            logging_level="warning",
        )
    finally:
        clock.armed = False
        telemetry.uninstall()
    t_end = max(v[0] for v in out.values())
    edges = [t_in[r] for r in range(warm, warm + rounds)] + [t_end]
    walls = [b - a for a, b in zip(edges, edges[1:])]
    t0 = gate["measure_from"]
    recs = [r for r in recorder.records() if r.t_start >= t0]
    folds = [r.dur_s * 1e3 for r in recs
             if r.phase == "agg.fold" and r.party == parties[-1]]
    sends = [r.dur_s * 1e3 for r in recs if r.phase == "wire.send"]
    stats = [v[1] for v in out.values()]
    counters = {
        k: (sum(s[k] for s in stats) if all(k in s for s in stats) else None)
        for k in COUNTERS
    }
    # Striped payloads received in the measured rounds, by their FR
    # record (written when a payload's assembly completes).
    asm = [r for r in recs if r.phase == "wire.reassemble"]
    payloads = len(asm) or None
    in_place = (
        sum(r.detail["in_place_bytes"] for r in asm)
        if asm and all("in_place_bytes" in r.detail for r in asm) else None
    )

    def med(xs):
        return round(statistics.median(xs), 3) if xs else None

    return {
        "parties": n_parties,
        "contribution_bytes": nbytes,
        "rounds": rounds,
        "round_s": med(walls),
        "round_s_each": [round(w, 4) for w in walls],
        "fold_ms": med(folds),
        "wire_send_ms": med(sends),
        "striped_payloads_measured": payloads,
        "striped_bytes_measured": sum(
            r.detail["shipped_bytes"] for r in asm
        ),
        "in_place_bytes_measured": in_place,
        "receive_read_ms": (
            round(clock.read_s * 1e3 / payloads, 2) if payloads else None
        ),
        "receive_apply_ms": (
            round(clock.apply_s * 1e3 / payloads, 2) if payloads else None
        ),
        **counters,
        "platform": jax.devices()[0].platform,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--parties", type=int, nargs="+", default=[2, 4])
    parser.add_argument("--bytes", type=int, default=62_918_762)
    parser.add_argument("--rounds", type=int, default=6)
    parser.add_argument("--warm", type=int, default=2)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    if max(args.parties) > len(PARTY_NAMES) or min(args.parties) < 2:
        parser.error(f"--parties takes 2 to {len(PARTY_NAMES)}")

    from rayfed_tpu.utils import force_cpu_devices

    force_cpu_devices(1)
    clock = StageClock()
    clock.install()
    lines = []
    for n in args.parties:
        line = sweep(n, args.bytes, args.rounds, args.warm, clock)
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
