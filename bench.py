"""Benchmark harness — prints ONE JSON line with the headline metric.

Four measurements, one JSON line (extra configs appear as extra fields
on the headline line so the driver records them all):

1. **fedavg_mnist_2party_rounds_per_sec** (headline, BASELINE.md #2):
   2-party FedAvg over the real push transport, two OS processes.
2. **split_fl_GBps** (BASELINE.md #5): split-FL activation-push
   throughput through the send proxy.
3. **llama_tokens_per_sec / llama_mfu**: full-parameter Adam train step
   of a ~250M-param Llama (bf16, flash attention) on the real
   accelerator, everything device-resident, donated buffers.
4. **flash_speedup**: pallas flash-attention kernel vs dense attention
   at T=2048 on the real accelerator.

Placement policy: the federated configs (1, 2) pin party compute to the
host CPU backend — they measure the framework's control plane and wire
transport, one spawned OS process per party.  A chip belongs to one
process at a time, so that launch model can never put party compute on
the chip: every spawned child here is CPU-only by construction (see
``_party_child``), and the federated round with party compute on the
chip runs through ``chip_smoke.py`` (in-process parties) instead.  The
compute configs (3, 4) run in THIS process on the real chip, after
every CPU child has exited; without an accelerator they fail.

The reference (fengsp/rayfed) publishes no benchmark numbers
(SURVEY §6); ``vs_baseline`` compares against the first recorded
round of this framework itself (``BENCH_r*.json``), else 1.0.

Usage: ``python bench.py`` (all configs; first run needs a few
minutes for compiles).  ``python bench.py --fed-only`` skips the
accelerator configs; ``--compute-only`` skips the federated ones;
``--smoke`` runs the streaming-aggregation, ring-aggregation (incl.
the quantized-ring bytes probe), pipelined-overlap, send-path,
compressed-aggregation, secure-aggregation, hierarchy traffic-vs-N
(N∈{4,16,64} virtual parties) and chaos benches at reduced scale (the
CI gate test.sh drives; see test.sh for the full gate list —
``coord_bytes_in_frac <= 0.4``, ``overlap_hidden_comm_frac >= 0.5``,
the compressed/secagg exactness gates, and the hierarchy
flat-traffic gates).
"""

from __future__ import annotations

import contextlib
import functools
import glob
import json
import multiprocessing as mp
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Importing jax does not initialize a backend — the spawn children pin
# jax.config to CPU before first use, the parent initializes the real
# accelerator lazily in the compute benches.  This only works because
# every child is forced onto the CPU: a child that opened the chip would
# fail or hang once this parent holds it (one process per chip).
import jax  # noqa: E402

CLUSTER = {
    "alice": {"address": "127.0.0.1:13010"},
    "bob": {"address": "127.0.0.1:13011"},
}

N, D, CLASSES = 1024, 784, 10
LOCAL_STEPS = 4
WARMUP_ROUNDS = 3
MEASURE_ROUNDS = 20


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Federated configs (CPU party compute; measures control plane + wire)
# --------------------------------------------------------------------------

def _run_fedavg_party(party: str, result_q) -> None:
    import logging

    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import aggregate
    from rayfed_tpu.models import logistic

    logging.disable(logging.WARNING)
    fed.init(address="local", cluster=CLUSTER, party=party)

    @fed.remote
    class Trainer:
        def __init__(self, seed: int):
            key = jax.random.PRNGKey(seed)
            self._x = jax.random.normal(key, (N, D))
            w = jax.random.normal(jax.random.PRNGKey(0), (D, CLASSES))
            self._y = jnp.argmax(self._x @ w, axis=-1)
            self._step = logistic.make_train_step(logistic.apply_logistic, lr=0.2)

        def train(self, params):
            for _ in range(LOCAL_STEPS):
                params, _loss = self._step(params, self._x, self._y)
            jax.block_until_ready(params["w"])
            return params

    alice = Trainer.party("alice").remote(1)
    bob = Trainer.party("bob").remote(2)

    params = logistic.init_logistic(jax.random.PRNGKey(0), D, CLASSES)

    def do_round(params):
        return aggregate([alice.train.remote(params), bob.train.remote(params)])

    for _ in range(WARMUP_ROUNDS):
        params = do_round(params)
    jax.block_until_ready(params["w"])

    t0 = time.perf_counter()
    for _ in range(MEASURE_ROUNDS):
        params = do_round(params)
    jax.block_until_ready(params["w"])
    elapsed = time.perf_counter() - t0

    if result_q is not None:
        result_q.put((party, MEASURE_ROUNDS / elapsed))
    fed.shutdown()


def _run_split_party(party: str, result_q) -> None:
    """Split-FL activation-push throughput (config #5 shape).

    Uses the pipelined (GPipe-microbatched) split step: K forwards
    stream their activation pushes back-to-back, so the wire and both
    parties' compute overlap — the measured GB/s is the send-proxy
    path's, not the latency of a serialized round trip.

    Beyond the headline GB/s, the run decomposes the step with the
    transport's TransferLog (socket-read time vs send-path time vs
    everything else — compute + actor scheduling), and measures a second
    exchange with ``wire_dtype=bf16`` (half the wire bytes) to separate
    wire cost from compute cost.  On the 1-core bench host every phase
    serializes, so split_fl_GBps's ceiling is
    bytes / (compute_s + bytes/wire_GBps) — the breakdown makes that
    ceiling visible in the artifact.
    """
    import logging

    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu import metrics
    from rayfed_tpu.fl import SplitTrainer
    from rayfed_tpu.models.logistic import softmax_cross_entropy

    logging.disable(logging.WARNING)
    fed.init(address="local", cluster=CLUSTER, party=party)

    # Compute-light halves (relu, small d_in): the metric is send-proxy
    # GB/s, so the parties' CPU FLOPs must not be the bottleneck.
    n, d_in, d_hidden, classes, k_mb = 4096, 16, 1024, 10, 8

    # ONE set of constructors for the trainer, the data loaders, AND the
    # compute probe — the probe's ceiling only corresponds to the
    # benchmarked step while these stay shared.
    def make_encoder_params():
        return {
            "k": jax.random.normal(jax.random.PRNGKey(0), (d_in, d_hidden)) * 0.05
        }

    def make_head_params():
        return {
            "k": jax.random.normal(jax.random.PRNGKey(1), (d_hidden, classes)) * 0.05
        }

    def make_x(mb):
        return jax.random.normal(jax.random.PRNGKey(70 + mb), (n, d_in))

    def make_y(mb):
        return jax.random.randint(jax.random.PRNGKey(80 + mb), (n,), 0, classes)

    load_x = fed.remote(make_x)
    load_y = fed.remote(make_y)

    def encoder_apply(params, x):
        return jax.nn.relu(x @ params["k"])

    def head_apply(params, h):
        return h @ params["k"]

    def make_trainer(wire_dtype):
        return SplitTrainer(
            encoder_party="alice",
            head_party="bob",
            encoder_params=make_encoder_params(),
            encoder_apply=encoder_apply,
            head_params=make_head_params(),
            head_apply=head_apply,
            loss_fn=softmax_cross_entropy,
            lr=0.1,
            wire_dtype=wire_dtype,
        )

    x_objs = [load_x.party("alice").remote(mb) for mb in range(k_mb)]
    y_objs = [load_y.party("bob").remote(mb) for mb in range(k_mb)]

    # Microbatch count: on a multi-core host the pipelined step overlaps
    # K transfers with compute; this 1-core bench host time-slices
    # everything, so in-flight buffers only add scheduling pressure —
    # use the serialized step there (k_mb=1 path).
    k_mb_eff = k_mb if os.cpu_count() and os.cpu_count() > 2 else 1
    steps = 8 if k_mb_eff > 1 else 24
    xs = x_objs[:k_mb_eff]
    ys = y_objs[:k_mb_eff]

    def timed(trainer, windows=3):
        """Best-of-``windows`` timing (plus that window's decomposition).

        One window at a time is not interpretable on the shared bench
        host: r4's split section happened to run during a load spike and
        recorded 0.056 GB/s for a path that measures ~0.3 GB/s on a
        quiet host — a 5.7× f32-vs-bf16 'anomaly' that was entirely host
        state (the raw transport is bytes-linear: 16.8 MB pushes at
        ~30 ms, 8.4 MB at ~14 ms round-trip, no threshold cliff).
        """
        trainer.step_pipelined(xs, ys)  # warmup + compile
        best = None
        for _w in range(windows):
            # Barrier on the *encoder* queue: get_params is ordered after
            # every backward/apply, so prior traffic fully drains before
            # t0 and the window includes the last step's reverse traffic.
            fed.get(trainer.encoder_params())
            total0 = metrics.get_transfer_log().total_recorded
            t0 = time.perf_counter()
            for _ in range(steps):
                trainer.step_pipelined(xs, ys)
            fed.get(trainer.encoder_params())
            elapsed = time.perf_counter() - t0
            recs, complete = metrics.get_transfer_log().records_since(total0)
            if complete:
                read_s = sum(r.seconds for r in recs if r.direction == "recv")
                send_s = sum(r.seconds for r in recs if r.direction == "send")
            else:  # ring evicted part of the window
                read_s = send_s = float("nan")
            # Prefer complete windows: a faster ring-evicted window must
            # not discard a complete window's decomposition (NaNs would
            # propagate into the artifact).
            key = (not complete, elapsed)
            if best is None or key < best[0]:
                best = (key, (elapsed, read_s, send_s))
        return best[1]

    # Local-compute probe: ALICE alone times BOTH halves of the step's
    # math back-to-back (same constructors as the trainer, jitted, no
    # transport) so the parent can print the serialized 1-core ceiling
    # bytes/(compute_s + bytes/wire_GBps) next to the measured number.
    # One process probing serially is the point: with both parties
    # probing concurrently on the 1-core host, each wall-clock includes
    # the other's compute and the summed "ceiling" would be understated
    # (even reading as measured > ceiling).  While alice probes, bob is
    # parked at its first recv.
    def compute_probe_ms() -> float:
        if party != "alice":
            return 0.0
        k_enc = make_encoder_params()["k"]
        k_head = make_head_params()["k"]
        x = make_x(0)
        y = make_y(0)

        # Encoder: forward + recompute-backward (same shape of work as
        # _EncoderActor._fwd/_grads).
        fwd = jax.jit(lambda p, x: encoder_apply({"k": p}, x))
        h = fwd(k_enc, x)

        def bwd(p, x, g):
            out, vjp = jax.vjp(lambda p: encoder_apply({"k": p}, x), p)
            return vjp(g)[0]

        bwd = jax.jit(bwd)
        g = jnp.ones_like(h)

        # Head: loss + grads wrt head params and activations (same shape
        # of work as _HeadActor._grads).
        def f(p, h):
            return softmax_cross_entropy(head_apply({"k": p}, h), y)

        head_grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))

        def one_step():
            jax.block_until_ready(
                (fwd(k_enc, x), bwd(k_enc, x, g), head_grads(k_head, h))
            )

        one_step()  # compile
        t0 = time.perf_counter()
        for _ in range(4):
            one_step()
        return (time.perf_counter() - t0) / 4 * 1e3

    probe_ms = compute_probe_ms() * k_mb_eff
    el_f32, read_f32, send_f32 = timed(make_trainer(None))
    el_bf16, _read, _send = timed(make_trainer(jnp.bfloat16))

    # Per step: K x (activations alice->bob + grads bob->alice), f32.
    bytes_per_step = 2 * k_mb_eff * n * d_hidden * 4
    if result_q is not None:
        result_q.put(
            (
                party,
                {
                    "gbps": steps * bytes_per_step / el_f32 / 1e9,
                    "steps_per_sec": steps / el_f32,
                    "bf16_steps_per_sec": steps / el_bf16,
                    # Per-step decomposition (this party's view).
                    "wire_read_ms": read_f32 / steps * 1e3,
                    "send_path_ms": send_f32 / steps * 1e3,
                    "other_ms": max(el_f32 - read_f32 - send_f32, 0.0)
                    / steps
                    * 1e3,
                    "compute_probe_ms": probe_ms,
                },
            )
        )
    fed.shutdown()


def _run_push_bench(_party: str, result_q) -> None:
    """Raw send-proxy throughput: 128MB mesh-sharded pushes on loopback.

    Measures the wire path itself (shard-streamed encode → native writev
    → socket → zero-copy frame assembly → decode to host arrays) with no
    model in the loop — the send-proxy GB/s capability number
    (BASELINE.md #5's metric).

    Ceiling note (this 1-CPU bench host): every stage serializes on one
    core, so the composite floor is ~0.46 s/GB of kernel loopback copies
    + ~0.19 s/GB of CRC both sides ≈ 1.5 GB/s with *zero* framework
    overhead; the framework lands within ~2x of that.  On a multi-core
    host the stages (device fetch, checksum, writev, receive, decode)
    run on separate threads and pipeline.

    ``push_GBps`` decodes to *host* arrays:
    on real hardware the final placement is an H2D DMA (covered by the
    compute configs), while on this CPU-only bench host an emulated
    device_put would bill ~1.3 s/GB of memcpy to the wire.  The re-shard
    path (per-shard device_put onto the receiver's mesh) is still
    measured separately as ``push_reshard_GBps``.
    """
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.transport.manager import TransportManager

    def mk(party, device_put_received, options=None):
        pc = {"address": "127.0.0.1:13050"}, {"address": "127.0.0.1:13051"}
        if options:
            pc = tuple(dict(d, transport_options=options) for d in pc)
        cc = ClusterConfig(
            parties={
                "alice": PartyConfig.from_dict(pc[0]),
                "bob": PartyConfig.from_dict(pc[1]),
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(
                device_put_received=device_put_received,
                zero_copy_host_arrays=not device_put_received,
            ),
        )

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    x = jnp.arange(32 * 1024 * 1024, dtype=jnp.float32).reshape(8192, 4096)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    jax.block_until_ready(xs)

    def run(device_put_received, steps):
        a, b = mk("alice", device_put_received), mk("bob", device_put_received)
        b.mesh_provider = lambda: mesh
        a.start()
        b.start()
        a.send("bob", xs, "warm", "0").resolve()
        b.recv("alice", "warm", "0").resolve()
        # Best-of-reps: wire timings on a shared host are noisy (r3→r4
        # looked like a regression that was load); the max over windows
        # is the capability number, like the compute benches' min-of-reps.
        best_dt = float("inf")
        seq = 0
        for _rep in range(3):
            send_refs = []
            t0 = time.perf_counter()
            for _ in range(steps):
                send_refs.append(a.send("bob", xs, f"p{seq}", "0"))
                b.recv("alice", f"p{seq}", "0").resolve()
                seq += 1
            dt = time.perf_counter() - t0
            # Drain EVERY send result BEFORE stop(): stop cancels loop
            # tasks, and abandoning the final ACK wait logged a spurious
            # send failure into the recorded bench artifact (r3 judge
            # finding).  Resolve outside the assert so python -O can't
            # strip the drain.
            results = [r.resolve(timeout=60) for r in send_refs]
            if not all(results):
                raise RuntimeError(f"push send failed: {results}")
            best_dt = min(best_dt, dt)
        a.stop()
        b.stop()
        return x.nbytes * steps / best_dt / 1e9

    wire_gbps = run(device_put_received=False, steps=6)
    reshard_gbps = run(device_put_received=True, steps=4)

    # Multi-rail striping (wire v4): ONE payload's chunks fanned over
    # the per-destination connection pool vs pinned to a single rail.
    # On a real multi-core sender with a fat link the rails pipeline
    # d2h/CRC/writev; on a CPU-bound 1-2 core loopback box every rail
    # shares the same core so the numbers converge — recorded, not
    # gated (docs/source/send_path.rst covers when striping is a wash).
    def run_rails(rails, steps=3, reps=2):
        # stripe_rails explicit: the host-adaptive default turns
        # striping off on few-core hosts, and this probe measures it.
        a = mk("alice", False, {"connections_per_peer": rails,
                                "stripe_rails": rails})
        b = mk("bob", False)
        a.start()
        b.start()
        a.send("bob", xs, "warmr", "0").resolve()
        b.recv("alice", "warmr", "0").resolve()
        best_dt = float("inf")
        for rep in range(reps):
            refs = []
            t0 = time.perf_counter()
            for i in range(steps):
                refs.append(a.send("bob", xs, f"mr{rep}-{i}", "0"))
                b.recv("alice", f"mr{rep}-{i}", "0").resolve()
            dt = time.perf_counter() - t0
            results = [r.resolve(timeout=60) for r in refs]
            if not all(results):
                raise RuntimeError(f"multirail push failed: {results}")
            best_dt = min(best_dt, dt)
        a.stop()
        b.stop()
        return x.nbytes * steps / best_dt / 1e9

    multirail_gbps = run_rails(4)
    singlerail_gbps = run_rails(1)

    # Packed-tree codec push: a ResNet-scale many-leaf float tree (64
    # leaves, 45 MB f32) compressed to bf16 and pushed end-to-end
    # (compress → send → recv → decompress to f32), packed single-buffer
    # form vs the per-leaf form.  GB/s over the bf16 wire bytes; the
    # packed form rides the chunked streaming path (one buffer) while
    # the per-leaf form moves 64 small buffers with upfront checksum.
    from rayfed_tpu.fl import compression as fl_comp

    tree = {
        f"layer{i}": jnp.arange(
            44 * 4096, dtype=jnp.float32
        ).reshape(44, 4096)
        + i
        for i in range(64)
    }
    jax.block_until_ready(jax.tree_util.tree_leaves(tree))

    def run_tree(packed, steps=3, reps=2):
        a, b = mk("alice", False), mk("bob", False)
        a.start()
        b.start()
        payload = fl_comp.compress(tree, packed=packed)
        wire_bytes = sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(payload)
        )
        a.send("bob", payload, "warmt", "0").resolve()
        fl_comp.decompress(b.recv("alice", "warmt", "0").resolve(timeout=60))
        # Snapshot AFTER warmup: the overlap decomposition must cover
        # only the timed steps, not the compile/first-fetch-heavy warmup.
        stats0 = a.get_stats()
        best_dt = float("inf")
        seq = 0
        for _rep in range(reps):
            send_refs = []
            t0 = time.perf_counter()
            for _ in range(steps):
                payload = fl_comp.compress(tree, packed=packed)
                send_refs.append(a.send("bob", payload, f"t{seq}", "0"))
                out = fl_comp.decompress(
                    b.recv("alice", f"t{seq}", "0").resolve(timeout=60)
                )
                jax.block_until_ready(
                    [l for l in jax.tree_util.tree_leaves(out)
                     if isinstance(l, jax.Array)]
                )
                seq += 1
            dt = time.perf_counter() - t0
            results = [r.resolve(timeout=60) for r in send_refs]
            if not all(results):
                raise RuntimeError(f"tree push send failed: {results}")
            best_dt = min(best_dt, dt)
        stats1 = a.get_stats()
        stats = {
            k: stats1[k] - stats0[k]
            for k in ("send_prepare_s", "send_write_s", "send_frame_wall_s")
        }
        a.stop()
        b.stop()
        return wire_bytes * steps / best_dt / 1e9, stats

    packed_gbps, packed_stats = run_tree(packed=True)
    perleaf_gbps, _stats = run_tree(packed=False)
    busy = packed_stats["send_prepare_s"] + packed_stats["send_write_s"]
    saved = max(0.0, busy - packed_stats["send_frame_wall_s"])
    overlap_frac = saved / busy if busy > 0 else 0.0
    result_q.put(
        (
            "push",
            (wire_gbps, reshard_gbps, packed_gbps, perleaf_gbps,
             overlap_frac, multirail_gbps, singlerail_gbps),
        )
    )


def _smoke_tree():
    """The smoke benches' shared synthetic tree (~12 MB bf16 = 3 delta
    chunks).  ONE producer: the stream-agg and ring smoke sections must
    aggregate the identical payload shape so their delta caches engage
    identically and hub-vs-ring numbers compare like for like."""
    import jax.numpy as jnp

    return {
        f"l{i}": jnp.arange(1_500_000, dtype=jnp.float32) * 1e-6 + i
        for i in range(4)
    }


def _run_stream_agg_bench(_party: str, result_q) -> None:
    """ResNet-scale streaming FedAvg round: delta cache + on-the-wire agg.

    4 parties (in-process TransportManagers over real loopback sockets,
    like the push bench): three peers push their packed bf16 ResNet-18
    bundles to the coordinator on per-peer **delta streams**, the
    coordinator folds each arriving chunk into a donated on-device
    accumulator (``fl.streaming.StreamingAggregator``) while later
    chunks are still on the wire, then broadcasts the aggregate back on
    a delta stream.

    Update shape: each round every party updates ONE rotating quarter of
    its parameter buffer (the head-only / adapter fine-tune shape where
    delta caching pays — full-model SGD touches every chunk and
    degenerates to full sends, which the cache detects and ships
    plainly).  Consecutive rounds therefore differ in ~2 quarters
    (revert + new), so the expected delta saving is ~50% minus chunk-
    alignment slop.

    Reports ``cross_party_stream_agg_GBps`` (logical contribution bytes
    over the receive+aggregate phase), ``agg_overlap_frac`` (fraction of
    aggregation busy time hidden under the wire), ``delta_bytes_saved_
    frac`` (stream bytes the caches kept off the wire), and the round
    latency breakdown.
    """
    import numpy as np
    import jax

    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl.streaming import StreamingAggregator
    from rayfed_tpu.transport.manager import TransportManager

    smoke = bool(os.environ.get("RAYFED_BENCH_SMOKE"))
    parties = ("alice", "bob", "carol", "dave")
    ports = {p: 13080 + i for i, p in enumerate(parties)}

    def mk(party):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict({"address": f"127.0.0.1:{ports[p]}"})
                for p in parties
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(device_put_received=False, zero_copy_host_arrays=True),
        )

    mgrs = {p: mk(p) for p in parties}
    for m in mgrs.values():
        m.start()

    if smoke:
        bundle = fl_comp.compress(_smoke_tree(), packed=True)
        rounds = 2
    else:
        from rayfed_tpu.models import resnet

        cfg = resnet.resnet18(num_classes=10)
        bundle = fl_comp.compress(
            resnet.init_resnet(jax.random.PRNGKey(0), cfg), packed=True
        )
        rounds = 3

    base32 = np.asarray(bundle.buf).astype(np.float32)
    n_elems = base32.size
    bundle_bytes = np.asarray(bundle.buf).nbytes
    wire_dt = np.asarray(bundle.buf).dtype

    def contribution(party_idx: int, r: int) -> "fl_comp.PackedTree":
        """Quarter (r % 4) perturbed, party-specific; rest byte-stable."""
        arr = base32.copy()
        q = n_elems // 4
        lo = (r % 4) * q
        arr[lo : lo + q] += 1e-3 * (party_idx + 1) * (r + 1)
        return fl_comp.PackedTree(
            arr.astype(wire_dt), bundle.passthrough, bundle.spec
        )

    peers = [p for p in parties if p != "alice"]

    def do_round(r: int):
        t0 = time.perf_counter()
        contribs = {
            p: contribution(i + 1, r) for i, p in enumerate(peers)
        }
        send_refs = [
            mgrs[p].send(
                "alice", contribs[p], f"c{r}-{p}", "0",
                stream=f"sagg/up/{p}",
            )
            for p in peers
        ]
        agg = StreamingAggregator(len(parties))
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"c{r}-{p}", "0", agg.sink(i + 1))
        agg.add_local(0, contribution(0, r))
        result = agg.result(timeout=300)
        t_agg = time.perf_counter()
        bcast_refs = mgrs["alice"].send_many(
            peers, result, f"b{r}", "0", stream="sagg/down"
        )
        for p in peers:
            out = mgrs[p].recv("alice", f"b{r}", "0").resolve(timeout=300)
            np.asarray(out.buf[:64])  # touch: decode really happened
        for ref in send_refs + list(bcast_refs.values()):
            if not ref.resolve(timeout=300):
                raise RuntimeError("stream agg bench send failed")
        t_end = time.perf_counter()
        return t0, t_agg, t_end, dict(agg.stats)

    do_round(0)  # warmup: compiles + seeds every delta cache

    def delta_totals():
        logical = wire_b = 0
        for m in mgrs.values():
            st = m.get_stats()
            logical += st["delta_logical_bytes"]
            wire_b += st["delta_wire_bytes"]
        return logical, wire_b

    logical0, wire0 = delta_totals()
    agg_s = bcast_s = wall_s = 0.0
    overlaps, busys, tails, wires = [], [], [], []
    for r in range(1, rounds + 1):
        t0, t_agg, t_end, stats = do_round(r)
        agg_s += t_agg - t0
        bcast_s += t_end - t_agg
        wall_s += t_end - t0
        overlaps.append(stats["agg_overlap_frac"])
        busys.append(stats["agg_busy_s"])
        tails.append(stats["agg_tail_s"])
        wires.append(stats["agg_wire_s"])
    logical1, wire1 = delta_totals()
    for m in mgrs.values():
        m.stop()

    contrib_bytes = len(peers) * bundle_bytes
    logical = logical1 - logical0
    shipped = wire1 - wire0
    result_q.put(
        (
            "stream",
            {
                "gbps": contrib_bytes * rounds / agg_s / 1e9,
                "overlap": sum(overlaps) / len(overlaps),
                "delta_saved": (logical - shipped) / logical
                if logical
                else 0.0,
                "round_ms": wall_s / rounds * 1e3,
                "contrib_agg_ms": agg_s / rounds * 1e3,
                "bcast_ms": bcast_s / rounds * 1e3,
                "agg_busy_ms": sum(busys) / rounds * 1e3,
                "agg_tail_ms": sum(tails) / rounds * 1e3,
                "agg_wire_ms": sum(wires) / rounds * 1e3,
                "bundle_mb": bundle_bytes / 1e6,
            },
        )
    )


def _run_compressed_agg_bench(_party: str, result_q) -> None:
    """Compressed-domain (shared-grid uint8) aggregation vs the bf16
    path — the THC-style homomorphic fold (fl.quantize).

    Same in-process 4-party TransportManager shape as the stream-agg
    bench.  Three phases:

    1. **Bytes on wire**: R rounds of the bf16 pipeline (bf16 packed
       contributions up, bf16 aggregate broadcast down) vs R rounds of
       the quantized pipeline (uint8 codes both directions, grids in
       payload/metadata), fresh payloads each round and no delta
       streams — so the measured ratio is the CODEC's, not the cache's.
       Gate: ``compressed_bytes_on_wire_frac <= 0.55``.
    2. **Fold throughput**: folding the arrived uint8 codes into the
       donated i32 accumulator (ONE widening multiply-add dispatch per
       chunk, rescale once at finalize) vs the dequantize-first
       baseline (dequantize kernel to f32, then the f32 accumulate —
       two dispatches and an extra O(chunk) f32 intermediate).  Gate:
       ``compressed_fold_speedup >= 1.0``.
    3. **Convergence**: a 2-party quadratic FedAvg recurrence, 8-bit +
       error feedback vs exact f32 — ``compressed_loss_ratio`` must
       stay ~1 (equal converged accuracy; the residual carries what
       the grid drops).

    Also asserts the streamed integer fold is BIT-identical to the
    one-shot ``packed_quantized_sum`` (``compressed_agg_bitexact``).
    """
    import numpy as np
    import jax.numpy as jnp

    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl import fedavg as fl_fedavg
    from rayfed_tpu.fl import quantize as qz
    from rayfed_tpu.fl.streaming import StreamingAggregator
    from rayfed_tpu.transport.manager import TransportManager

    parties = ("alice", "bob", "carol", "dave")
    ports = {p: 13140 + i for i, p in enumerate(parties)}

    def mk(party):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict({"address": f"127.0.0.1:{ports[p]}"})
                for p in parties
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(device_put_received=False, zero_copy_host_arrays=True),
        )

    mgrs = {p: mk(p) for p in parties}
    for m in mgrs.values():
        m.start()

    bundle16 = fl_comp.compress(_smoke_tree(), packed=True)  # bf16
    ref32 = np.asarray(bundle16.buf).astype(np.float32)
    n_elems = ref32.size
    rng = np.random.default_rng(0)
    prev_delta = (1e-3 * rng.standard_normal(n_elems)).astype(np.float32)
    grid = qz.make_round_grid(prev_delta, mode="delta", expand=4.0)
    peers = [p for p in parties if p != "alice"]
    rounds = 2

    def contribution32(party_idx: int, r: int) -> np.ndarray:
        # FULLY fresh each round (seeded noise everywhere): the delta
        # cache must have nothing to skip — this measures the codec.
        noise = np.random.default_rng(100 * r + party_idx)
        return ref32 + (1e-3 * noise.standard_normal(n_elems)).astype(
            np.float32
        )

    def sent_bytes() -> int:
        return sum(m.get_stats()["send_bytes"] for m in mgrs.values())

    def tree_of(buf, dtype):
        return fl_comp.PackedTree(
            np.asarray(jnp.asarray(buf).astype(dtype)),
            bundle16.passthrough,
            fl_comp.PackSpec(
                bundle16.spec.entries, bundle16.spec.treedef,
                np.dtype(dtype).name,
            ),
        )

    def do_round_bf16(r: int) -> float:
        t0 = time.perf_counter()
        send_refs = [
            mgrs[p].send("alice", tree_of(contribution32(i + 1, r),
                                          jnp.bfloat16),
                         f"b16-{r}-{p}", "0")
            for i, p in enumerate(peers)
        ]
        agg = StreamingAggregator(len(parties))
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"b16-{r}-{p}", "0",
                                      agg.sink(i + 1))
        agg.add_local(0, tree_of(contribution32(0, r), jnp.bfloat16))
        result = agg.result(timeout=300)
        bcast = mgrs["alice"].send_many(peers, result, f"b16b-{r}", "0")
        for p in peers:
            mgrs[p].recv("alice", f"b16b-{r}", "0").resolve(timeout=300)
        for ref in send_refs + list(bcast.values()):
            if not ref.resolve(timeout=300):
                raise RuntimeError("bf16 round send failed")
        return time.perf_counter() - t0

    bitexact = True

    def do_round_quant(r: int) -> float:
        nonlocal bitexact
        t0 = time.perf_counter()
        qts = [
            qz.quantize_packed(tree_of(contribution32(i, r), jnp.float32),
                               grid, ref=ref32)
            for i in range(len(parties))
        ]
        gd = qz.grid_descriptor(grid)
        send_refs = [
            mgrs[p].send("alice", qts[i + 1], f"q-{r}-{p}", "0",
                         quant_meta=gd)
            for i, p in enumerate(peers)
        ]
        agg = StreamingAggregator(len(parties), quant=grid,
                                  quant_ref=ref32)
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"q-{r}-{p}", "0",
                                      agg.sink(i + 1))
        agg.add_local(0, qts[0])
        result = agg.result(timeout=300)
        if r == 0:
            want = fl_fedavg.packed_quantized_sum(qts, ref=ref32)
            bitexact = bitexact and np.array_equal(
                np.asarray(result.buf), np.asarray(want.buf)
            )
        # Quantized downlink: fresh grid from the aggregate's delta,
        # carried in the payload.
        down = qz.make_round_grid(
            np.asarray(result.buf) - ref32, mode="delta"
        )
        wire_result = qz.quantize_packed(result, down, ref=ref32)
        bcast = mgrs["alice"].send_many(
            peers, wire_result, f"qb-{r}", "0",
            quant_meta=qz.grid_descriptor(down),
        )
        for p in peers:
            got = mgrs[p].recv("alice", f"qb-{r}", "0").resolve(timeout=300)
            got.dequantize(np.float32, ref=ref32)
        for ref in send_refs + list(bcast.values()):
            if not ref.resolve(timeout=300):
                raise RuntimeError("quant round send failed")
        return time.perf_counter() - t0

    do_round_bf16(99)  # warmup: compiles both stacks
    do_round_quant(98)

    b0 = sent_bytes()
    bf16_s = sum(do_round_bf16(r) for r in range(rounds))
    bf16_bytes = sent_bytes() - b0
    b0 = sent_bytes()
    quant_s = sum(do_round_quant(r) for r in range(rounds))
    quant_bytes = sent_bytes() - b0
    for m in mgrs.values():
        m.stop()

    # --- fold throughput: integer fold vs dequantize-first ------------
    from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS, _accum_kernel

    ce = DEFAULT_CHUNK_ELEMS
    nb = fl_fedavg.packed_block_grid(n_elems, ce)
    codes = [
        np.asarray(qz.quantize_packed(
            tree_of(contribution32(i, 0), jnp.float32), grid, ref=ref32
        ).buf)
        for i in range(len(parties))
    ]
    pad = nb * ce - n_elems
    padded = [np.concatenate([c, np.zeros(pad, c.dtype)]) for c in codes]

    int_kernel = fl_fedavg.quantized_accum_kernel(ce, "uint8")
    f32_kernel = _accum_kernel(ce, "float32", "float32")
    dq_kernel = qz._dequantize_kernel(ce, ce, "uint8", "float32", False)

    # Fold-only timing (the finalize is one dispatch either way); 6
    # passes over every contribution per sample so the window holds
    # ~100 chunk dispatches instead of a dispatch-jitter-dominated 12.
    fold_passes = 6

    def run_int() -> float:
        acc = jnp.zeros(nb * ce, jnp.int32)
        t0 = time.perf_counter()
        for _ in range(fold_passes):
            for c in padded:
                for b in range(nb):
                    acc = int_kernel(
                        acc, c[b * ce:(b + 1) * ce], np.int32(b * ce),
                        np.int32(1),
                    )
        acc.block_until_ready()
        return time.perf_counter() - t0

    sc_rows = grid.scales.reshape(-1, 1)
    zp_rows = grid.zps.reshape(-1, 1)

    def run_dequant_first() -> float:
        acc = jnp.zeros(nb * ce, jnp.float32)
        t0 = time.perf_counter()
        for _ in range(fold_passes):
            for c in padded:
                for b in range(nb):
                    chunk = dq_kernel(
                        c[b * ce:(b + 1) * ce],
                        jnp.zeros(0, jnp.float32),
                        sc_rows[b], zp_rows[b],
                    )
                    acc = f32_kernel(acc, chunk, np.int32(b * ce),
                                     np.float32(1.0))
        acc.block_until_ready()
        return time.perf_counter() - t0

    run_int(), run_dequant_first()  # warmup compiles
    # min-of-N on an alternating schedule: both paths see the same
    # host-load profile, so the RATIO stays stable under CI noise.
    int_times, dq_times = [], []
    for _ in range(5):
        int_times.append(run_int())
        dq_times.append(run_dequant_first())
    int_s = min(int_times)
    dq_s = min(dq_times)

    # --- convergence: 8-bit+EF vs exact f32 on a quadratic -------------
    rng = np.random.default_rng(3)
    target = rng.normal(size=(1 << 16,)).astype(np.float32)
    shift = [0.3 * rng.normal(size=target.shape).astype(np.float32)
             for _ in range(2)]

    def conv(quantized: bool) -> float:
        x = np.zeros_like(target)
        comps = [qz.QuantCompressor() for _ in range(2)]
        prev = None
        for _r in range(20):
            ups = [x - 0.3 * (x - (target + s)) for s in shift]
            if quantized and prev is not None:
                g = qz.make_round_grid(prev, chunk_elems=1 << 14,
                                       mode="delta", expand=4.0)
                qts = []
                for c, u in zip(comps, ups):
                    qts.append(c.quantize(
                        fl_comp.pack_tree({"w": jnp.asarray(u)},
                                          jnp.float32), g, ref=x))
                    c.commit()
                agg = np.asarray(
                    fl_fedavg.packed_quantized_sum(qts, ref=x).buf
                )
            else:
                agg = np.mean(ups, axis=0).astype(np.float32)
            prev = agg - x
            x = agg
        return float(np.mean((x - target) ** 2))

    loss_f32 = conv(False)
    loss_q = conv(True)

    contrib_bytes = len(peers) * np.asarray(bundle16.buf).nbytes
    result_q.put(
        (
            "cagg",
            {
                "bytes_frac": quant_bytes / bf16_bytes if bf16_bytes else 0.0,
                "bf16_bytes": bf16_bytes,
                "quant_bytes": quant_bytes,
                "round_ms_bf16": bf16_s / rounds * 1e3,
                "round_ms_quant": quant_s / rounds * 1e3,
                "gbps": contrib_bytes * rounds / quant_s / 1e9,
                "fold_speedup": dq_s / int_s if int_s else 0.0,
                "fold_int_gbps": (
                    fold_passes * len(codes) * n_elems / int_s / 1e9
                ),
                "fold_dq_gbps": (
                    fold_passes * len(codes) * n_elems / dq_s / 1e9
                ),
                "bitexact": bool(bitexact),
                "loss_ratio": loss_q / loss_f32 if loss_f32 else 0.0,
            },
        )
    )


def _run_secagg_bench(_party: str, result_q) -> None:
    """Masked (secure-aggregation) rounds vs plain quantized rounds —
    fl.secagg over the compressed-domain fold (fl.quantize).

    Same in-process 4-party TransportManager shape as the compressed
    bench; key agreement rides the real HELLO handshake (one ping per
    pair).  Each round is the realistic federated shape — every party
    runs a small jitted local step, quantizes its update onto the
    round's shared grid, pushes to the coordinator, and the integer
    fold + ONE rescale finalizes — timed twice: plain codes (uint8)
    and masked codes (``w·q + pairwise masks``, i32, unit-weight fold;
    mask keystreams prefetch on a background thread while the local
    step runs, exactly as the round driver does).

    Gates (test.sh):

    - ``secagg_bitexact`` — the masked round's aggregate bytes EQUAL
      the plain round's over the same contributions (the masks cancel
      exactly, not approximately).
    - ``secagg_overhead_frac <= 0.05`` — masking adds at most 5% to
      the round wall (masks ship zero bytes; the mask PRG + the i32
      code widening are the only costs, and the PRG hides under the
      local step).  Measured as the MIN over three 3-pair block
      medians of order-balanced paired round deltas, over the fastest
      plain round — host drift cancels in-pair and scheduler noise
      must strike all three blocks (the telemetry gate's estimator; a
      fixed leg order on a 1-core box read ±10% drift as overhead
      against the 5% gate).

    ``secagg_mask_gen_ms`` reports the raw (unhidden) keystream cost
    so the overlap can never silently mask a PRG regression.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    os.environ.setdefault("RAYFED_SECAGG_GROUP_KEY", "bench-secagg-key")

    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl import fedavg as fl_fedavg
    from rayfed_tpu.fl import quantize as qz
    from rayfed_tpu.fl import secagg as sa
    from rayfed_tpu.fl.streaming import StreamingAggregator
    from rayfed_tpu.transport.manager import TransportManager

    parties = ("alice", "bob", "carol", "dave")
    ports = {p: 13180 + i for i, p in enumerate(parties)}

    def mk(party):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict({"address": f"127.0.0.1:{ports[p]}"})
                for p in parties
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(device_put_received=False, zero_copy_host_arrays=True),
        )

    mgrs = {p: mk(p) for p in parties}
    for m in mgrs.values():
        m.start()
    # Key agreement over the real HELLO handshake: one ping per pair.
    for p in parties:
        mgrs[p].ensure_secagg_peer_keys(parties)

    n = 1 << 16
    ce = 1 << 16
    ref = np.linspace(-0.5, 0.5, n, dtype=np.float32)
    tmpl = fl_comp.pack_tree({"w": jnp.asarray(ref)}, jnp.float32)
    rng = np.random.default_rng(0)
    grid = qz.make_round_grid(
        (1e-3 * rng.standard_normal(n)).astype(np.float32),
        mode="delta", expand=4.0, chunk_elems=ce,
    )
    weights = [2.0, 1.0, 3.0, 1.0]
    wmap = dict(zip(parties, weights))
    peers = [p for p in parties if p != "alice"]

    # The local step: a fixed jitted matmul chain per party per round —
    # the compute share every real round carries, and the window the
    # mask PRG prefetch hides under.
    # ~65 ms of jitted compute per party — a modest stand-in for the
    # local train step every real round carries (the keystream prefetch
    # thread interleaves with it: XLA releases the GIL, so the numpy
    # PRG genuinely overlaps; without ANY local compute a federated
    # round is pure transport, which no deployment is).
    @jax.jit
    def _local_step(x):
        for _ in range(32):
            x = jnp.tanh(x @ x) + 0.1
        return x

    step_x = jnp.ones((512, 512), jnp.float32) * 0.01

    def contribution(i: int, r: int):
        return fl_comp.PackedTree(
            ref + (1e-3 * np.random.default_rng(100 * r + i)
                   .standard_normal(n)).astype(np.float32),
            tmpl.passthrough, tmpl.spec,
        )

    mask_gen_s = [0.0]

    def do_round(r: int, masked: bool):
        t0 = time.perf_counter()
        maskers = {}
        if masked:
            for p in parties:
                maskers[p] = sa.RoundMasker(
                    mgrs[p].secagg_keys, p,
                    [q for q in parties if q != p],
                    session="bench", stream="sab", round_index=r,
                    weight=int(wmap[p]),
                )
                # Prefetch the keystream under the local step, exactly
                # as the round driver does.
                maskers[p].prefetch(n)
        wires = {}
        for i, p in enumerate(parties):
            jax.block_until_ready(_local_step(step_x))  # the local step
            up = contribution(i, r)
            if masked:
                wires[p] = sa.MaskedRoundCodec(
                    grid, ref, None, maskers[p]
                ).to_wire(up)
            else:
                wires[p] = qz.quantize_packed(up, grid, ref=ref)
        gd = qz.grid_descriptor(grid)
        tag = "m" if masked else "q"
        send_refs = [
            mgrs[p].send("alice", wires[p], f"sab-{tag}-{r}-{p}", "0",
                         quant_meta=gd)
            for p in peers
        ]
        agg = StreamingAggregator(
            len(parties), weights=weights, quant=grid, quant_ref=ref,
            chunk_elems=ce, masked=masked, labels=list(parties),
        )
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"sab-{tag}-{r}-{p}", "0",
                                      agg.sink(i + 1))
        agg.add_local(0, wires["alice"])
        result = agg.result(timeout=300)
        bcast = mgrs["alice"].send_many(peers, result, f"sabb-{tag}-{r}", "0")
        for p in peers:
            mgrs[p].recv("alice", f"sabb-{tag}-{r}", "0").resolve(timeout=300)
        for ref_ in send_refs + list(bcast.values()):
            if not ref_.resolve(timeout=300):
                raise RuntimeError("secagg bench round send failed")
        return time.perf_counter() - t0, result

    # Raw (unhidden) keystream cost, reported alongside: one party's
    # net mask for one round, generated synchronously.
    t0 = time.perf_counter()
    probe = sa.RoundMasker(
        mgrs["alice"].secagg_keys, "alice", list(peers),
        session="probe", stream="sab", round_index=0, weight=1,
    )
    probe.net_mask(n)
    mask_gen_s[0] = time.perf_counter() - t0

    do_round(90, False)  # warm both stacks (compiles, delta caches)
    do_round(91, True)
    rounds = 9
    plain_walls, masked_walls = [], []
    plain_res = masked_res = None
    # Order-balanced pairs (the PR 15 telemetry-gate lesson): the
    # masked leg always running second measured host drift within the
    # pair as "masking overhead" — a ~250ms round on a 1-core box
    # wanders ±10% run to run, twice the 5% gate.  Alternating which
    # leg goes first cancels the drift in-pair; the gate below takes
    # the MIN over three 3-pair block medians, so scheduler noise must
    # strike every block to fail the build while a real hot-path cost
    # shifts all three.
    for r in range(rounds):
        if r % 2 == 0:
            w_p, plain_res = do_round(r, False)
            w_m, masked_res = do_round(r, True)
        else:
            w_m, masked_res = do_round(r, True)
            w_p, plain_res = do_round(r, False)
        plain_walls.append(w_p)
        masked_walls.append(w_m)
    # Same contributions each (r, masked) pair → the aggregates must be
    # BYTE-identical: the pairwise masks cancel exactly.
    bitexact = bool(np.array_equal(
        np.asarray(plain_res.buf), np.asarray(masked_res.buf)
    ))
    from rayfed_tpu.fl.secagg import SECAGG_STATS

    stats = {p: mgrs[p].get_stats()["secagg"] for p in parties}
    for m in mgrs.values():
        m.stop()
    plain_s = min(plain_walls)
    masked_s = min(masked_walls)
    deltas = [m - p for p, m in zip(plain_walls, masked_walls)]
    block_meds = [
        sorted(deltas[i: i + 3])[1] for i in range(0, len(deltas), 3)
    ]
    result_q.put((
        "secagg",
        {
            "plain_round_ms": plain_s * 1e3,
            "masked_round_ms": masked_s * 1e3,
            "overhead_frac": max(0.0, min(block_meds) / plain_s),
            "bitexact": bitexact,
            "mask_gen_ms": mask_gen_s[0] * 1e3,
            "keygen_ms": float(SECAGG_STATS["keygen_ms"]),
            "suite": stats["alice"]["kex"] + "/" + stats["alice"]["prg"],
            "peers_keyed": min(
                len(stats[p]["peers"]) for p in parties
            ),
        },
    ))


def _fill_secagg_extra(extra: dict, s: dict) -> None:
    extra["secagg_bitexact"] = s["bitexact"]
    extra["secagg_overhead_frac"] = round(s["overhead_frac"], 3)
    extra["secagg_round_ms"] = round(s["masked_round_ms"], 1)
    extra["secagg_plain_round_ms"] = round(s["plain_round_ms"], 1)
    extra["secagg_mask_gen_ms"] = round(s["mask_gen_ms"], 2)
    extra["secagg_keygen_ms"] = round(s["keygen_ms"], 2)
    extra["secagg_suite"] = s["suite"]
    extra["secagg_peers_keyed"] = s["peers_keyed"]
    _log(
        f"  secagg: masked round {s['masked_round_ms']:.0f} ms vs plain "
        f"quantized {s['plain_round_ms']:.0f} ms "
        f"({s['overhead_frac']:.1%} overhead; raw keystream "
        f"{s['mask_gen_ms']:.1f} ms/party hidden under the local step), "
        f"suite {s['suite']}, masked bytes "
        f"{'IDENTICAL' if s['bitexact'] else 'DIVERGED'} to unmasked"
    )


def _run_objectplane_bench(_party: str, result_q) -> None:
    """Content-addressed pull-on-demand object plane (transport/
    objectstore.py): welcome-by-handle vs the eager welcome push, and
    concurrent-fetch dedup.

    In-process 4-manager shape (real loopback sockets) like the secagg
    bench.  Three measurements:

    1. **Eager welcome** — the coordinator pushes a welcome carrying
       the model inline (the pre-object-plane behavior): the baseline
       payload bytes.
    2. **Warm rejoin by handle** — the joiner's content cache already
       holds the round model (what every quorum participant publishes
       per round, so a graceful leave/rejoin inside one round is warm):
       the welcome carries only the FINGERPRINT handle, the resolve is
       a cache hit, and ~zero payload bytes cross the wire.  Gate
       (test.sh): ``rejoin_welcome_bytes_frac <= 0.1``.
    3. **Dedup** — N concurrent local fetches of one cold fingerprint
       trigger exactly ONE wire transfer from the holder.  Gate:
       ``blob_dedup_single_transfer``.

    A cold handle rejoin is also reported (``blob_pull_GBps`` — the
    BLOB_GET/BLOB_PUT pull path at payload scale) but not gated: cold
    moves the same bytes as eager, just by pull.
    """
    import socket
    import threading

    import numpy as np
    import jax.numpy as jnp

    from rayfed_tpu import objects as rf_objects
    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.transport.manager import TransportManager

    parties = ("alice", "bob", "carol", "dave")

    def free_ports(k):
        socks = [socket.socket() for _ in range(k)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports_ = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        return ports_

    ports = dict(zip(parties, free_ports(len(parties))))

    def mk(party):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict(
                    {"address": f"127.0.0.1:{ports[p]}"}
                )
                for p in parties
            },
            current_party=party,
        )
        return TransportManager(
            cc, JobConfig(device_put_received=False, cross_silo_timeout_s=60),
        )

    mgrs = {p: mk(p) for p in parties}
    for m in mgrs.values():
        m.start()

    n = 1 << 20  # ~4 MB f32 model — payload-scale, sockets-real
    rng = np.random.default_rng(0)

    def model(r):
        return fl_comp.pack_tree(
            {"w": jnp.asarray(
                rng.standard_normal(n).astype(np.float32) + r
            )},
            jnp.float32,
        )

    def payload_bytes(mgr):
        return mgr.get_stats()["send_payload_bytes"]

    def welcome_of(m_r, handle=None):
        w = {"round": 1, "session": "op", "epoch": 1,
             "members": list(parties), "coordinator": "alice"}
        if handle is None:
            w["params"] = m_r
        else:
            w["model"] = handle
        return w

    # --- 1. eager welcome baseline (alice -> dave, params inline) ----
    m0 = model(0)
    m0c = rf_objects.canonical_host(m0)
    b0 = payload_bytes(mgrs["alice"])
    mgrs["alice"].send("dave", welcome_of(m0), "w.eager", "roster")
    eager_val = mgrs["dave"].recv("alice", "w.eager", "roster").resolve(
        timeout=120
    )["params"]
    eager_bytes = payload_bytes(mgrs["alice"]) - b0

    # --- 2a. COLD handle rejoin (carol has nothing cached) -----------
    fp, nb = mgrs["alice"].objects.publish(m0c)
    handle = mgrs["alice"].objects.handle_for(fp, nb)
    b1 = payload_bytes(mgrs["alice"])
    t0 = time.perf_counter()
    mgrs["alice"].send("carol", welcome_of(None, handle), "w.cold", "roster")
    wc = mgrs["carol"].recv("alice", "w.cold", "roster").resolve(timeout=120)
    cold_val = rf_objects.maybe_resolve_handle(mgrs["carol"], wc["model"])
    cold_s = time.perf_counter() - t0
    cold_bytes = payload_bytes(mgrs["alice"]) - b1

    # --- 2b. WARM handle rejoin (dave's cache holds the model) -------
    # Every quorum participant publishes each round's broadcast; a
    # leaver that rejoins within the round IS this warm case.  dave
    # decoded the eager welcome above — publishing its value derives
    # the SAME fingerprint alice's handle names.
    mgrs["dave"].objects.publish(rf_objects.canonical_host(eager_val))
    b2 = payload_bytes(mgrs["alice"])
    mgrs["alice"].send("dave", welcome_of(None, handle), "w.warm", "roster")
    ww = mgrs["dave"].recv("alice", "w.warm", "roster").resolve(timeout=120)
    warm_val = rf_objects.maybe_resolve_handle(mgrs["dave"], ww["model"])
    warm_bytes = payload_bytes(mgrs["alice"]) - b2

    # Byte-identity across all three paths (the acceptance identity:
    # handle-resolved state == eager-push state, receiver-decoded).
    identical = bool(
        np.array_equal(np.asarray(eager_val.buf), np.asarray(cold_val.buf))
        and np.array_equal(
            np.asarray(eager_val.buf), np.asarray(warm_val.buf)
        )
    )

    # --- 3. concurrent-fetch single-transfer dedup -------------------
    m1 = model(1)
    fp1, nb1 = mgrs["alice"].objects.publish(
        rf_objects.canonical_host(m1)
    )
    h1 = mgrs["alice"].objects.handle_for(fp1, nb1)
    serves0 = mgrs["alice"].objects.stats["blob_serves"]
    errs: list = []

    def _fetch():
        try:
            mgrs["bob"].objects.fetch(h1, timeout_s=120)
        except Exception as exc:  # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=_fetch) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    serves = mgrs["alice"].objects.stats["blob_serves"] - serves0
    dedup_ok = bool(not errs and serves == 1)

    for m in mgrs.values():
        m.stop()
    result_q.put((
        "object_plane",
        {
            "eager_welcome_bytes": int(eager_bytes),
            "cold_welcome_bytes": int(cold_bytes),
            "warm_welcome_bytes": int(warm_bytes),
            "rejoin_welcome_bytes_frac": (
                warm_bytes / eager_bytes if eager_bytes else 1.0
            ),
            "blob_pull_GBps": (nb / cold_s / 1e9) if cold_s > 0 else 0.0,
            "dedup_single_transfer": dedup_ok,
            "dedup_serves": int(serves),
            "handle_state_identical": identical,
        },
    ))


def _fill_objectplane_extra(extra: dict, s: dict) -> None:
    extra["rejoin_welcome_bytes_frac"] = round(
        s["rejoin_welcome_bytes_frac"], 4
    )
    extra["blob_dedup_single_transfer"] = s["dedup_single_transfer"]
    extra["blob_handle_state_identical"] = s["handle_state_identical"]
    extra["blob_pull_GBps"] = round(s["blob_pull_GBps"], 3)
    extra["eager_welcome_bytes"] = s["eager_welcome_bytes"]
    extra["warm_welcome_bytes"] = s["warm_welcome_bytes"]
    _log(
        f"  object plane: warm rejoin {s['warm_welcome_bytes']} B vs "
        f"eager {s['eager_welcome_bytes']} B "
        f"(frac {s['rejoin_welcome_bytes_frac']:.4f}); cold pull "
        f"{s['blob_pull_GBps']:.2f} GB/s; dedup single transfer: "
        f"{s['dedup_single_transfer']} ({s['dedup_serves']} serve(s) "
        f"for 6 concurrent fetches)"
    )


def _run_hierarchy_bench(_party: str, result_q) -> None:
    """Hierarchical aggregation traffic-vs-N: region rings + quantized
    cross-region partial-sum streaming at N ∈ {4, 16, 64, 256}
    (fl.hierarchy), with N in-process VIRTUAL parties — one
    TransportManager per party, real loopback sockets, party threads
    driving the same ``HierarchyRound`` the fed driver ships (the
    multi-manager shape of the secagg bench, NOT 256 subprocesses — the
    tier-1 budget is binding).

    N ≤ 64 keeps the fixed region COUNT (2) with growing region size
    (the historical 2-level gates); N=256 is the MULTI-LEVEL leg — 16
    regions of 16 folding through branch=4 interior nodes (16 → 4 →
    1), quorum-hub leaves, region-ring downlink, an FD-ceiling check
    before the 256 managers are built, and a seeded straggling-region
    chaos round that the per-region quorum cutoff must absorb with
    zero flatten-fallbacks.  Per round and per N the parent gates
    (test.sh):

    - ``hier_bitexact`` — the hierarchical aggregate is BYTE-identical
      (on every one of the N parties) to the one-shot
      ``packed_quantized_sum`` over all N contributions, re-coded by
      the SAME shared quantize_downlink producer the flat streaming
      path uses (integer folds are exact + associative: regrouping by
      region reproduces the flat accumulator bit for bit).
    - ``hier_party_bytes_frac_{N}`` ≤ 1.25 — mean per-party
      bytes-on-wire within 1.25× of 2·|model| (|model| = the bf16
      bundle bytes: one contribution out + one broadcast in is the
      flat-traffic budget; uint8 codes and int16 partial sums are what
      keep the tree's extra hops inside it).
    - ``hier_ingress_flatness`` ≤ 1.6 — max-ingress-at-any-node ratio
      between N=64 and N=4: no O(N) hub at ANY level (the flat hub's
      coordinator ingress grows ~16× over the same range —
      reported as ``hier_vs_hub_max_ingress_64``).
    - ``hier_round_ratio_64_over_16`` ≤ 12 — the N=64 round wall stays
      well sublinear in the ~14× message-count growth (the local-link
      fast path's per-message cost is what keeps the wall from
      tracking it; ~23× before it).  The denominator is the SLOWER of
      two N=16 measurements bracketing the N=64 leg (the
      order-balanced idiom of the secagg and telemetry gates): host
      drift between windows minutes apart cannot fake a regression, a
      real one trips against both brackets.  12, not 8: identical
      code (clean HEAD included) measured 6.8-10.2 across
      back-to-back runs on a 1-vCPU host — the ~200ms N=16 leg's
      min-of-3 swings 40% on scheduler luck.
      The flight recorder runs over the measured rounds at N ∈ {16,
      64, 256} and the per-phase wall attribution lands in the report
      (``trace_phases``), so a regression arrives with its own
      diagnosis attached.
    - ``hier_round_ratio_256_over_64`` ≤ 4 — the thousand-silo scaling
      gate; ``hier_root_egress_frac_256`` ≤ 8 — root bytes out stay
      ~O(branch·|model|), flat in N (the region-ring downlink's whole
      point); ``hier_chaos_fallbacks`` = 0 with ≥ 1 region cutoff —
      the straggling region is absorbed, not flattened.

    Colocated parties upgrade to the shm local link (``local_link:
    "auto"``) — this bench IS the colocated topology the fast path
    exists for.  The measured rounds run with the collector frozen +
    disabled (re-enabled after each N): with N in-process virtual
    parties every collection pass walks N parties' object graphs AND
    re-enters jax's per-collection hook, a cost that exists only
    because the simulation packs N parties into one interpreter — a
    real deployment runs one party per process.
    """
    import gc
    import resource
    import socket
    import threading
    from collections import defaultdict

    import numpy as np
    import jax.numpy as jnp

    from rayfed_tpu import telemetry
    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl import fedavg as fl_fedavg
    from rayfed_tpu.fl import quantize as qz
    from rayfed_tpu.fl import hierarchy as fl_hier
    from rayfed_tpu.fl.hierarchy import HierarchyRound
    from rayfed_tpu.transport.manager import TransportManager

    def free_ports(k):
        socks = [socket.socket() for _ in range(k)]
        for s in socks:
            s.bind(("127.0.0.1", 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        return ports

    n_elems = 1 << 17  # 128Ki f32 elems; bf16 |model| = 256 KiB
    ce = 1 << 11  # 64 blocks: every stripe owner owns blocks at S=32
    model_bytes = 2 * n_elems  # bf16 bundle bytes (the |model| unit)
    ref = np.linspace(-0.5, 0.5, n_elems, dtype=np.float32)
    tmpl = fl_comp.pack_tree({"w": jnp.asarray(ref)}, jnp.float32)
    rng = np.random.default_rng(0)
    grid = qz.make_round_grid(
        (1e-3 * rng.standard_normal(n_elems)).astype(np.float32),
        mode="delta", expand=4.0, chunk_elems=ce,
    )

    def contribution(i: int, r: int):
        return fl_comp.PackedTree(
            ref + (1e-3 * np.random.default_rng(1000 * r + i)
                   .standard_normal(n_elems)).astype(np.float32),
            tmpl.passthrough, tmpl.spec,
        )

    report = {"model_bytes": model_bytes}
    # N=256 packs ~256 listening sockets + local-link endpoints + the
    # lazy per-peer connections of a constant-degree tree into ONE
    # process: raise the FD soft ceiling toward the hard one up front
    # and check the headroom BEFORE building 256 managers.
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < 16_384:
        try:
            resource.setrlimit(
                resource.RLIMIT_NOFILE, (min(16_384, hard), hard)
            )
        except (ValueError, OSError):
            pass
        soft, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
    report["fd_soft_limit"] = int(soft)

    # (N, region_size, branch, hub leaves): the first three legs keep
    # the fixed-2-region shape (the historical PR 12/16 gates); N=256
    # is the multi-level leg — 16 regions of 16 fold through branch=4
    # interior nodes (16 -> 4 -> 1), the deadline-capable quorum hub
    # replaces the stripe ring at the leaves, and the region-ring
    # downlink carries the broadcast (root egress ~O(branch·|model|),
    # flat in N).
    sweep = [
        (4, 2, None, False),
        (16, 8, None, False),
        (64, 32, None, False),
        # A SECOND N=16 measurement bracketing the N=64 leg ("n16b"):
        # the 64/16 gate is a ratio of walls measured minutes apart on
        # a shared host, and sustained host-speed drift between the
        # two windows reads as a per-message regression (observed:
        # identical code measured 6.8x and 10.2x across back-to-back
        # runs on a 1-vCPU box).  The gate divides by the SLOWER of
        # the two N=16 walls — the order-balanced bracketing idiom the
        # secagg and telemetry gates already use — so drift in either
        # direction cannot fake a regression, while a real
        # per-message cost still inflates N=64 against BOTH brackets.
        (16, 8, None, False),
        (256, 16, 4, True),
    ]
    for n_parties, region_size, branch, hub in sweep:
        if n_parties >= 256 and soft < 4_096:
            report["n256_skipped"] = (
                f"fd soft ceiling {soft} < 4096 (hard {hard})"
            )
            break
        parties = [f"h{i:03d}" for i in range(n_parties)]
        lay = fl_hier.region_layout(parties, region_size, branch=branch)
        hier_kw = {}
        if branch is not None:
            hier_kw["branch"] = branch
        if hub:
            # Full-region quorum for the measured rounds: the hub path
            # is exercised, no member is cut, bitexact covers ALL N.
            hier_kw["region_quorum"] = region_size
        ports = dict(zip(parties, free_ports(n_parties)))

        def mk(party):
            cc = ClusterConfig(
                parties={
                    p: PartyConfig.from_dict(
                        {"address": f"127.0.0.1:{ports[p]}"}
                    )
                    for p in parties
                },
                current_party=party,
            )
            return TransportManager(
                cc,
                JobConfig(
                    device_put_received=False,
                    zero_copy_host_arrays=True,
                    # The topology this bench simulates IS colocated:
                    # auto-upgrade to the in-process shm handoff.
                    local_link="auto",
                ),
            )

        mgrs = {p: mk(p) for p in parties}
        for m in mgrs.values():
            m.start()

        def do_round(r: int, tag: str, delays=None, extra_kw=None):
            results, errors = {}, {}

            def run_party(p, i):
                try:
                    rnd = HierarchyRound(
                        mgrs[p], party=p, members=parties,
                        region_size=region_size, grid=grid,
                        quant_ref=ref,
                        keys=[f"{tag}{r}k{j}" for j in range(6)],
                        stream="hb", backstop=300,
                        quant_downlink=True,
                        **{**hier_kw, **(extra_kw or {})},
                    )
                    if delays and p in delays:
                        time.sleep(delays[p])
                    results[p] = rnd.run(contribution(i, r))
                except BaseException as e:  # surfaces in the parent
                    errors[p] = e

            threads = [
                threading.Thread(
                    target=run_party, args=(p, i), daemon=True
                )
                for i, p in enumerate(parties)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(300)
            if errors:
                raise RuntimeError(
                    f"hierarchy round failed at N={n_parties}: "
                    f"{ {p: repr(e) for p, e in errors.items()} }"
                )
            return time.perf_counter() - t0, results

        do_round(0, "w")  # warm: compiles + connections
        rx0 = {
            p: int(m.get_stats()["receive_bytes"])
            for p, m in mgrs.items()
        }
        tx0 = {
            p: int(m.get_stats()["send_bytes"])
            for p, m in mgrs.items()
        }
        # Flight recorder over the measured rounds at the two gated N:
        # per-phase wall attribution ships WITH the number it explains.
        traced = n_parties in (16, 64, 256)
        if traced:
            telemetry.install(f"hier_bench_n{n_parties}",
                              capacity=1 << 20)
        rounds = 3
        walls = []
        results = None
        # N in-process parties make every collection pass O(N) object
        # graphs + one jax gc-hook re-entry — simulation overhead, not
        # transport work (one party per process in deployment).
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            for r in range(1, 1 + rounds):
                wall, results = do_round(r, "m")
                walls.append(wall)
        finally:
            gc.enable()
            gc.unfreeze()
        trace_phases = None
        if traced:
            agg = defaultdict(float)
            for rec in telemetry.active().records():
                if rec.phase and rec.dur_s:
                    agg[rec.phase] += rec.dur_s
            telemetry.uninstall()
            trace_phases = {
                ph: round(tot, 3)
                for ph, tot in sorted(agg.items(), key=lambda kv: -kv[1])
            }
        rx = {
            p: int(mgrs[p].get_stats()["receive_bytes"]) - rx0[p]
            for p in parties
        }
        tx = {
            p: int(mgrs[p].get_stats()["send_bytes"]) - tx0[p]
            for p in parties
        }
        link_backend = (
            mgrs[parties[0]]
            .effective_transport_options(parties[1])
            .get("local_link", {})
            .get("backend")
        )

        # Seeded chaos schedule (multi-level leg only): one region's
        # members straggle past the region deadline; the per-region
        # quorum cutoff absorbs them (the arrived subset's partial sum
        # folds up, the root reweights) — the round COMPLETES, zero
        # abort-and-flatten fallbacks, every party byte-agrees.
        chaos = None
        if hub:
            chaos_rng = np.random.default_rng(2026)
            cg = int(chaos_rng.integers(1, len(lay.regions)))
            coord_cg = lay.coordinators[cg]
            stragglers = [
                p for p in lay.live[cg] if p != coord_cg
            ][:5]
            cutoffs0 = fl_hier.HIER_STATS["region_cutoffs"]
            aborted0 = fl_hier.HIER_STATS["rounds_aborted"]
            _, cres = do_round(
                9, "c", delays={p: 2.0 for p in stragglers},
                extra_kw={
                    "region_quorum": region_size - len(stragglers),
                    "region_deadline_s": 0.75,
                },
            )
            cblobs = {
                np.asarray(t.buf).tobytes() for t in cres.values()
            }
            chaos = {
                "straggler_region": cg,
                "stragglers": len(stragglers),
                "completed": len(cres),
                "cutoffs": int(
                    fl_hier.HIER_STATS["region_cutoffs"] - cutoffs0
                ),
                "fallbacks": int(
                    fl_hier.HIER_STATS["rounds_aborted"] - aborted0
                ),
                "agree": len(cblobs) == 1,
            }
        for m in mgrs.values():
            m.stop()

        # Byte-exactness vs the one-shot compressed-domain reduce,
        # re-coded by the shared downlink producer (what the flat
        # streaming path's quant_downlink rounds return).
        last_r = rounds
        qts = [
            qz.quantize_packed(contribution(i, last_r), grid, ref=ref)
            for i in range(n_parties)
        ]
        exact = fl_fedavg.packed_quantized_sum(qts, ref=ref)
        down = qz.make_round_grid(
            np.asarray(exact.buf, np.float32) - ref,
            chunk_elems=ce, wire_dtype=grid.wire_dtype, mode="delta",
        )
        expect = qz.quantize_packed(exact, down, ref=ref).dequantize(
            np.float32, ref=ref
        )
        blobs = {
            p: np.asarray(results[p].buf).tobytes() for p in parties
        }
        bitexact = (
            len(set(blobs.values())) == 1
            and blobs[parties[0]] == np.asarray(expect.buf).tobytes()
        )
        total_rx = sum(rx.values())
        # The bracketing re-measure of an already-reported N lands
        # under "n{N}b" (only round_s/bitexact are consumed from it).
        rkey = f"n{n_parties}"
        if rkey in report:
            rkey = f"n{n_parties}b"
        report[rkey] = {
            "bitexact": bool(bitexact),
            "party_bytes": total_rx / n_parties / rounds,
            "max_ingress": max(rx.values()) / rounds,
            # The root's per-round bytes OUT: the region-ring downlink
            # keeps this ~O(branch·|model|), FLAT in N (coordinator
            # fan-out would grow it O(N·|model|)).
            "root_egress": tx[lay.root] / rounds,
            "round_s": min(walls),
            "link_backend": link_backend,
            # What the flat hub's coordinator would ingest per round
            # over the same payloads (N-1 uint8 contributions), for
            # the no-O(N)-hub headline.
            "hub_max_ingress": (n_parties - 1) * n_elems,
        }
        if branch is not None:
            # Per-level max ingress: parties grouped by the HIGHEST
            # tree level they coordinate (0 = plain member, 1 = leaf
            # region coordinator, 1+k = level-k interior coordinator;
            # coordinatorship is prefix-closed so max() is the role).
            role = {p: 0 for p in parties}
            for g in lay.active:
                role[lay.coordinators[g]] = 1
            for k, level in enumerate(lay.levels, start=2):
                for nd in level.values():
                    role[nd.coordinator] = max(role[nd.coordinator], k)
            by_role = defaultdict(list)
            for p in parties:
                by_role[role[p]].append(rx[p])
            report[rkey]["per_level_ingress_frac"] = {
                f"l{k}": round(
                    max(v) / rounds / (2.0 * model_bytes), 3
                )
                for k, v in sorted(by_role.items())
            }
        if chaos is not None:
            report[rkey]["chaos"] = chaos
        if trace_phases is not None:
            report[rkey]["trace_phases"] = trace_phases
    result_q.put(("hierarchy", report))


def _fill_hierarchy_extra(extra: dict, s: dict) -> None:
    model2 = 2.0 * s["model_bytes"]  # the 2·|model| flat-traffic budget
    bitexact = True
    for n in (4, 16, 64, 256):
        sec = s.get(f"n{n}")
        if sec is None:  # N=256 skipped below the FD ceiling
            continue
        bitexact = bitexact and sec["bitexact"]
        extra[f"hier_party_bytes_frac_{n}"] = round(
            sec["party_bytes"] / model2, 3
        )
        extra[f"hier_max_ingress_frac_{n}"] = round(
            sec["max_ingress"] / model2, 3
        )
        extra[f"hier_root_egress_frac_{n}"] = round(
            sec["root_egress"] / model2, 3
        )
        extra[f"hier_round_ms_{n}"] = round(sec["round_s"] * 1e3, 1)
    n16b = s.get("n16b")
    if n16b is not None:
        bitexact = bitexact and n16b["bitexact"]
        extra["hier_round_ms_16b"] = round(n16b["round_s"] * 1e3, 1)
    extra["hier_bitexact"] = bitexact
    extra["hier_link_backend"] = s["n64"].get("link_backend")
    # The N=64 hierarchy wall, gated as a RATIO to N=16 (machine-speed
    # independent): raw message count grows ~14x across that span, so
    # holding the wall ratio well under it is the per-message-cost
    # regression gate the local-link fast path is accountable to.  The
    # denominator
    # is the SLOWER of the two N=16 walls bracketing the N=64 leg, so
    # host-speed drift between the measurement windows cannot read as a
    # regression (a real per-message cost inflates N=64 against both
    # brackets).  trace_phases in the section JSON says where the time
    # went when it trips.
    n16_wall = s["n16"]["round_s"]
    if n16b is not None:
        n16_wall = max(n16_wall, n16b["round_s"])
    extra["hier_round_ratio_64_over_16"] = round(
        s["n64"]["round_s"] / max(1e-9, n16_wall), 2
    )
    extra["hier_ingress_flatness"] = round(
        s["n64"]["max_ingress"] / max(1.0, s["n4"]["max_ingress"]), 3
    )
    extra["hier_vs_hub_max_ingress_64"] = round(
        s["n64"]["hub_max_ingress"] / max(1.0, s["n64"]["max_ingress"]),
        2,
    )
    n256 = s.get("n256")
    if n256 is not None:
        # THE thousand-silo gate: the N=256 multi-level round wall
        # within 4x of the N=64 wall (message count grows ~4x; the
        # constant-degree tree + region-ring downlink keep per-node
        # work flat), with the root's egress flat in N.
        extra["hier_round_ratio_256_over_64"] = round(
            n256["round_s"] / max(1e-9, s["n64"]["round_s"]), 2
        )
        chaos = n256.get("chaos") or {}
        extra["hier_chaos_fallbacks"] = chaos.get("fallbacks")
        extra["hier_chaos_cutoffs"] = chaos.get("cutoffs")
        extra["hier_chaos_agree"] = chaos.get("agree")
        extra["hier_level_ingress_256"] = n256.get(
            "per_level_ingress_frac"
        )
    else:
        extra["hier_n256_skipped"] = s.get("n256_skipped", "missing")
    _log(
        f"  hierarchy: per-party bytes "
        f"{extra['hier_party_bytes_frac_4']:.2f}x / "
        f"{extra['hier_party_bytes_frac_16']:.2f}x / "
        f"{extra['hier_party_bytes_frac_64']:.2f}x of 2|model| at "
        f"N=4/16/64 (budget <= 1.25x), max-node ingress "
        f"{extra['hier_max_ingress_frac_4']:.2f}x / "
        f"{extra['hier_max_ingress_frac_16']:.2f}x / "
        f"{extra['hier_max_ingress_frac_64']:.2f}x "
        f"(N=64/N=4 flatness {extra['hier_ingress_flatness']:.2f}, "
        f"hub would be {extra['hier_vs_hub_max_ingress_64']:.1f}x "
        f"worse at N=64); bitexact={bitexact}; round "
        f"{extra['hier_round_ms_4']:.0f} / "
        f"{extra['hier_round_ms_16']:.0f} / "
        f"{extra['hier_round_ms_64']:.0f} ms "
        f"(N=16 re-bracket {extra.get('hier_round_ms_16b', '-')} ms; "
        f"64/16 ratio {extra['hier_round_ratio_64_over_16']:.1f}, "
        f"link={extra['hier_link_backend']})"
    )
    if n256 is not None:
        _log(
            f"  hierarchy N=256 (multi-level, 16 regions x 16, "
            f"branch=4): round {extra['hier_round_ms_256']:.0f} ms "
            f"(256/64 ratio "
            f"{extra['hier_round_ratio_256_over_64']:.1f}, gate <= 4), "
            f"root egress {extra['hier_root_egress_frac_256']:.2f}x of "
            f"2|model| (N=64: "
            f"{extra['hier_root_egress_frac_64']:.2f}x), per-level "
            f"ingress {extra['hier_level_ingress_256']}, chaos "
            f"straggling-region: {extra['hier_chaos_cutoffs']} "
            f"cutoff(s), {extra['hier_chaos_fallbacks']} fallback(s), "
            f"agree={extra['hier_chaos_agree']}"
        )
    else:
        _log(
            f"  hierarchy N=256 SKIPPED: {extra['hier_n256_skipped']}"
        )


def _fill_compressed_extra(extra: dict, s: dict) -> None:
    extra["compressed_bytes_on_wire_frac"] = round(s["bytes_frac"], 3)
    extra["compressed_agg_GBps"] = round(s["gbps"], 3)
    extra["compressed_round_ms"] = round(s["round_ms_quant"], 1)
    extra["bf16_round_ms"] = round(s["round_ms_bf16"], 1)
    extra["compressed_fold_speedup"] = round(s["fold_speedup"], 3)
    extra["compressed_fold_int_GBps"] = round(s["fold_int_gbps"], 3)
    extra["compressed_fold_dequant_GBps"] = round(s["fold_dq_gbps"], 3)
    extra["compressed_agg_bitexact"] = s["bitexact"]
    extra["compressed_loss_ratio"] = round(s["loss_ratio"], 4)
    _log(
        f"  compressed-agg: {s['bytes_frac']:.3f}x the bf16 wire bytes "
        f"({s['quant_bytes'] / 1e6:.1f} vs {s['bf16_bytes'] / 1e6:.1f} "
        f"MB), fold {s['fold_speedup']:.2f}x vs dequant-first "
        f"({s['fold_int_gbps']:.2f} vs {s['fold_dq_gbps']:.2f} Gelem/s), "
        f"bitexact={s['bitexact']}, quadratic loss ratio "
        f"{s['loss_ratio']:.4f}; round {s['round_ms_quant']:.0f} ms vs "
        f"bf16 {s['round_ms_bf16']:.0f} ms"
    )


def _run_server_opt_bench(_party: str, result_q) -> None:
    """FedAC server optimization in the packed domain (fl.server_opt)
    — the rounds-to-target probe (ROADMAP item 4: the north-star
    seconds-per-round ratio closed at 0.93, so further time-to-accuracy
    comes from needing FEWER rounds).

    Three phases, all in-process (the aggregation bricks are the real
    kernels; no sockets — the wire shape is gated by the other smoke
    sections and the fed-API e2e leg in tests/test_streaming_agg.py):

    1. **Quadratic rounds/wall-to-target**: the 2-party heterogeneous
       quadratic FedAvg recurrence (zero-sum local-optima shifts,
       per-coordinate curvature) driven through the REAL step + resync
       kernels.  Gate: ``fedac_rounds_to_target_frac <= 0.8`` (FedAC
       reaches the target loss in at most 0.8x plain FedAvg's rounds;
       spectral analysis of the coupled recurrence puts it at ~0.15).
       ``fedac_wall_to_target_frac`` reports the wall-clock version of
       the same ratio (the step adds ONE fused kernel per round, so
       wall tracks rounds).
    2. **Toy-logistic rounds-to-target** (reported, not gated): same
       recurrence on the 2-party softmax-regression workload the e2e
       tests train — evidence the cut is not a quadratic artifact.
    3. **Topology byte-identity** (``server_opt_agg_bitexact``): the
       post-step quantized downlink decoded from its SERIALIZED wire
       bytes — what a receiving controller holds — is byte-identical
       across the streaming fold, the quorum path (and a quorum-CUTOFF
       round whose subset refold feeds the step at the subset's
       effective Σw), and the hierarchy's regrouped presummed fold,
       all stepping from identical replicated state.
    """
    import numpy as np
    import jax.numpy as jnp

    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl import fedavg as fl_fedavg
    from rayfed_tpu.fl import quantize as qz
    from rayfed_tpu.fl import server_opt as so
    from rayfed_tpu.fl.streaming import StreamingAggregator
    from rayfed_tpu.transport import wire as wire_mod

    # --- 1. quadratic rounds/wall-to-target ----------------------------
    size = 1 << 14
    rng = np.random.default_rng(11)
    opt_point = rng.normal(size=(size,)).astype(np.float32)
    shift = 0.3 * rng.normal(size=(size,)).astype(np.float32)
    curv = np.linspace(0.02, 0.12, size).astype(np.float32)
    tmpl = fl_comp.pack_tree({"w": jnp.zeros(size)}, jnp.float32)
    target = 1e-3 * float(np.mean(opt_point**2))

    def quad_run(opt_spec, max_rounds=450):
        runner = (
            so.PackedServerOptimizer(opt_spec)
            if opt_spec is not None else None
        )
        x = np.zeros(size, np.float32)
        t0 = time.perf_counter()
        for r in range(max_rounds):
            ups = [x - curv * (x - (opt_point + s))
                   for s in (shift, -shift)]
            avg = np.mean(ups, axis=0).astype(np.float32)
            if runner is not None:
                runner.ensure(x)
                res = fl_comp.PackedTree(
                    jnp.asarray(avg), tmpl.passthrough, tmpl.spec
                )
                new_x = np.asarray(runner.step_fn(x)(res).buf)
                runner.resync(x, new_x)
                x = new_x
            else:
                x = avg
            if float(np.mean((x - opt_point) ** 2)) <= target:
                return r + 1, time.perf_counter() - t0
        return max_rounds, time.perf_counter() - t0

    quad_run(so.fedac(1.0, 6.0, 0.7), max_rounds=3)  # compile warmup
    plain_rounds, plain_wall = quad_run(None)
    fedac_rounds, fedac_wall = quad_run(so.fedac(1.0, 6.0, 0.7))

    # --- 2. toy logistic (reported, not gated) -------------------------
    import jax

    from rayfed_tpu.models import logistic

    # Sized so the jitted local training dominates the round wall (the
    # step adds a handful of fused kernels per round; on a
    # dispatch-dominated toy, wall would measure Python overhead, not
    # the round economics).
    d, classes, n = 64, 5, 2048
    key = jax.random.PRNGKey(0)
    xs, ys = [], []
    w_true = jax.random.normal(jax.random.PRNGKey(9), (d, classes))
    for i in range(2):
        xp = jax.random.normal(jax.random.PRNGKey(i + 1), (n, d))
        xs.append(xp)
        ys.append(jnp.argmax(xp @ w_true, axis=-1))
    step_fn = logistic.make_train_step(logistic.apply_logistic, lr=0.3)
    ptree0 = logistic.init_logistic(key, d, classes)

    def log_loss(params):
        tot = 0.0
        for xp, yp in zip(xs, ys):
            tot += float(logistic.softmax_cross_entropy(
                logistic.apply_logistic(params, xp), yp
            ))
        return tot / 2

    def log_run(opt_spec, target_loss, max_rounds=80):
        runner = (
            so.PackedServerOptimizer(opt_spec)
            if opt_spec is not None else None
        )
        params = ptree0
        losses = []
        t0 = time.perf_counter()
        for r in range(max_rounds):
            ups = []
            for xp, yp in zip(xs, ys):
                local = params
                for _ in range(4):
                    local, _l = step_fn(local, xp, yp)
                ups.append(fl_comp.pack_tree(local, jnp.float32))
            avg = fl_fedavg.packed_weighted_sum(
                ups, out_dtype="float32"
            )
            if runner is not None:
                x = np.asarray(
                    fl_comp.pack_tree(params, jnp.float32).buf
                )
                runner.ensure(x)
                new_x = np.asarray(runner.step_fn(x)(avg).buf)
                runner.resync(x, new_x)
                avg = fl_comp.PackedTree(
                    jnp.asarray(new_x), avg.passthrough, avg.spec
                )
            params = avg.unpack(jnp.float32)
            losses.append(log_loss(params))
            if target_loss is not None and losses[-1] <= target_loss:
                return r + 1, losses, time.perf_counter() - t0
        return max_rounds, losses, time.perf_counter() - t0

    # Compile warmup for BOTH timed paths: train/loss kernels, plus the
    # exact fedac step/resync kernels the timed run uses (lru_cache is
    # keyed on the hyperparameters — the quadratic warmup above used
    # different ones, so skipping this would bill first-time jit
    # compilation to fedac_wall_to_target_s).
    log_run(None, None, max_rounds=2)
    log_run(so.fedac(1.0, 2.0, 0.3), None, max_rounds=2)
    _, plain_losses, _w = log_run(None, None)
    # The target plain FedAvg needs ~70% of its budget to reach.
    log_target = plain_losses[int(0.7 * len(plain_losses)) - 1]
    # Wall-to-target measured on THIS workload (real jitted local
    # training per round — the quadratic's numpy rounds are so cheap
    # that kernel-dispatch noise would swamp the wall signal there).
    log_plain_rounds, _ls, log_plain_wall = log_run(None, log_target)
    log_fedac_rounds, _ls2, log_fedac_wall = log_run(
        so.fedac(1.0, 2.0, 0.3), log_target
    )

    # --- 3. post-step downlink byte-identity across topologies ---------
    from rayfed_tpu import native
    from rayfed_tpu.fl.compression import PackSpec
    from rayfed_tpu.fl.hierarchy import RegionSumTree, partial_sum_dtype

    ce = 1 << 12
    asize = 40_000
    ref = rng.normal(size=(asize,)).astype(np.float32)
    packeds = [
        fl_comp.pack_tree(
            {"w": jnp.asarray(ref + 0.01 * rng.normal(size=(asize,))
                              .astype(np.float32))},
            jnp.float32,
        )
        for _ in range(4)
    ]
    grid = qz.make_round_grid(
        0.01 * rng.normal(size=(asize,)).astype(np.float32),
        chunk_elems=ce, mode="delta", expand=4.0,
    )
    ws = [3, 1, 2, 1]
    qts = [qz.quantize_packed(p, grid, ref=ref) for p in packeds]
    opt_spec = so.fedac(1.0, 3.0, 0.5)

    def payload_of(tree):
        bufs = wire_mod.encode_payload(tree)
        return native.gather_copy(
            [
                memoryview(b) if isinstance(b, (bytes, bytearray)) else b
                for b in bufs
            ]
        )

    def step_and_downlink(result):
        runner = so.PackedServerOptimizer(opt_spec)
        runner.ensure(ref)
        stepped = runner.step_fn(ref)(result)
        wire_result, decoded, _descr = qz.quantize_downlink(
            stepped, grid, ref, None
        )
        # Decode from the SERIALIZED bytes, as a receiver would.
        got = wire_mod.decode_payload(
            memoryview(payload_of(wire_result)), zero_copy=True
        )
        receiver = got.dequantize(np.float32, ref=ref)
        return (np.asarray(decoded.buf), np.asarray(receiver.buf))

    def stream_fold(indices, weights):
        n = len(indices)
        agg = StreamingAggregator(
            n, weights=weights, chunk_elems=ce, quant=grid,
            quant_ref=ref,
        )
        for j, i in enumerate(indices):
            agg.add_local(j, qts[i])
        return agg.result(timeout=120)

    bitexact = True
    # Full set: streaming == hierarchy (presummed regroup) == the
    # quorum path with everyone arriving (the quorum round IS the
    # quorum-aware streaming fold, asserted by its own tests).
    coord_full, recv_full = step_and_downlink(
        stream_fold([0, 1, 2, 3], ws)
    )
    bitexact &= bool(np.array_equal(coord_full, recv_full))
    ps_dt = partial_sum_dtype(grid.qabs_max, sum(ws))
    region_sums = []
    for members in ((0, 1), (2, 3)):
        acc = np.zeros(grid.total_elems, np.int64)
        for i in members:
            acc += ws[i] * np.asarray(qts[i].buf).astype(np.int64)
        spec = PackSpec(qts[0].spec.entries, qts[0].spec.treedef, ps_dt)
        region_sums.append(RegionSumTree(
            acc.astype(np.dtype(ps_dt)), grid.scales, grid.zps, (),
            spec, grid.meta(),
        ))
    root = StreamingAggregator(
        2, weights=[float(ws[0] + ws[1]), float(ws[2] + ws[3])],
        chunk_elems=ce, quant=grid, quant_ref=ref, presummed=ps_dt,
    )
    for g, rs in enumerate(region_sums):
        root.add_local(g, rs)
    hier_coord, hier_recv = step_and_downlink(root.result(timeout=120))
    bitexact &= bool(np.array_equal(hier_coord, coord_full))
    bitexact &= bool(np.array_equal(hier_recv, recv_full))
    # Quorum-cutoff subset feeding the step: the refold over the
    # arrived members reweights the step's effective Σw — must equal
    # the one-shot subset reduce + the SAME step.
    qagg = StreamingAggregator(
        4, weights=ws, chunk_elems=ce, quant=grid, quant_ref=ref,
        quorum=3, labels=["a", "b", "c", "d"],
    )
    qagg.sink(1)  # never arrives
    for i in (0, 2, 3):
        qagg.add_local(i, qts[i])
    cut = qagg.result(timeout=120, deadline_s=0.4)
    cut_coord, cut_recv = step_and_downlink(cut)
    subset = fl_fedavg.packed_quantized_sum(
        [qts[0], qts[2], qts[3]], [ws[0], ws[2], ws[3]], ref=ref
    )
    sub_coord, sub_recv = step_and_downlink(subset)
    bitexact &= bool(np.array_equal(cut_coord, sub_coord))
    bitexact &= bool(np.array_equal(cut_recv, sub_recv))
    bitexact &= bool(np.array_equal(cut_coord, cut_recv))

    result_q.put(
        (
            "sopt",
            {
                "plain_rounds": plain_rounds,
                "fedac_rounds": fedac_rounds,
                "rounds_frac": fedac_rounds / plain_rounds,
                "quad_plain_wall_s": plain_wall,
                "quad_fedac_wall_s": fedac_wall,
                "plain_wall_s": log_plain_wall,
                "fedac_wall_s": log_fedac_wall,
                "wall_frac": (
                    log_fedac_wall / log_plain_wall
                    if log_plain_wall else 0.0
                ),
                "log_plain_rounds": log_plain_rounds,
                "log_fedac_rounds": log_fedac_rounds,
                "log_frac": log_fedac_rounds / log_plain_rounds,
                "bitexact": bool(bitexact),
            },
        )
    )


def _fill_server_opt_extra(extra: dict, s: dict) -> None:
    extra["fedavg_rounds_to_target"] = s["plain_rounds"]
    extra["fedac_rounds_to_target"] = s["fedac_rounds"]
    extra["fedac_rounds_to_target_frac"] = round(s["rounds_frac"], 3)
    extra["fedavg_wall_to_target_s"] = round(s["plain_wall_s"], 3)
    extra["fedac_wall_to_target_s"] = round(s["fedac_wall_s"], 3)
    extra["fedac_wall_to_target_frac"] = round(s["wall_frac"], 3)
    extra["fedac_logistic_rounds_frac"] = round(s["log_frac"], 3)
    extra["server_opt_agg_bitexact"] = s["bitexact"]
    _log(
        f"  server-opt: FedAC reaches the quadratic target in "
        f"{s['fedac_rounds']} rounds vs plain {s['plain_rounds']} "
        f"(frac {s['rounds_frac']:.3f}; wall frac {s['wall_frac']:.3f}"
        f"), logistic frac {s['log_frac']:.3f}, post-step downlink "
        f"bitexact across streaming/quorum-subset/hierarchy = "
        f"{s['bitexact']}"
    )


def _run_send_path_bench(_party: str, result_q) -> None:
    """FedAvg coordinator send-path probe — the ISSUE-5 gap gate.

    The r05 verdict's top perf finding: the FedAvg round used the
    transport at a quarter of its demonstrated capacity
    (``cross_party_wire_GBps`` 0.216 vs the push bench's 0.904) because
    the coordinator's send path burned 454 ms of encode/checksum/
    loop-handoff against 167 ms of actual socket read (2.7× overhead).
    This section reproduces exactly that exchange shape — (N-1)
    contributions into the coordinator, the aggregate broadcast back out
    — with in-process TransportManagers over real loopback sockets and
    packed bf16 bundles large enough to engage the arena path (and,
    on hosts with the cores for it, multi-rail striping), and reports:

    - ``cross_party_wire_GBps``: the coordinator's session bytes over
      its round comms wall (contributions-in + broadcast-out phases) —
      the FedAvg-path wire rate.
    - ``push_capability_GBps``: sequential single-payload pushes of the
      SAME bundle on the same box at the same moment — the transport's
      demonstrated capacity, the yardstick the r05 verdict compared
      against (0.904 there).
    - ``wire_vs_push_capability``: their ratio — THE gap number.  r05
      sat at 0.216/0.904 = 0.24 (the "4× gap"); test.sh gates >= 0.5
      ("closed to <= 2×").  Relative to the same-box capability, like
      the other smoke gates (coord_bytes_in_frac, hidden_comm_frac),
      because absolute GB/s tracks the host, not the code: the r05
      numbers' host sustains ~5× this CI box.
    - ``send_vs_read_wall_ratio``: broadcast-out phase wall over
      contributions-in phase wall (median of rounds) — symmetric byte
      volumes, so with the full-payload serialization barrier gone this
      sits near 1.0 (gated <= 1.5; the r05 shape of the same quantity
      was the 2.7× send/read session imbalance).
    - ``coord_wire_read_ms`` / ``coord_send_path_ms`` (summed transfer-
      log sessions, the r05 decomposition — sessions of concurrent
      peers overlap, so these sums exceed wall) and their ratio
      ``send_path_overhead_ratio``, recorded for continuity.
    - ``send_path_breakdown_ms``: the per-stage split (encode/d2h/crc/
      loop_wait/socket) from ``get_stats`` — where any reopened gap
      lives.
    """
    import numpy as np
    import jax

    from rayfed_tpu import metrics
    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.transport.manager import TransportManager

    smoke = bool(os.environ.get("RAYFED_BENCH_SMOKE"))
    parties = ("alice", "bob", "carol", "dave")
    ports = {p: 13160 + i for i, p in enumerate(parties)}

    def mk(party):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict({"address": f"127.0.0.1:{ports[p]}"})
                for p in parties
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(device_put_received=False, zero_copy_host_arrays=True),
        )

    mgrs = {p: mk(p) for p in parties}
    for m in mgrs.values():
        m.start()

    if smoke:
        import jax.numpy as jnp

        # ~24 MB bf16 packed bundle: 6 wire chunks, stripes across the
        # pool — big enough to be wire-bound, small enough for CI.
        tree = {
            f"l{i}": jnp.arange(3_000_000, dtype=jnp.float32) * 1e-6 + i
            for i in range(4)
        }
        rounds = 3
    else:
        from rayfed_tpu.models import resnet

        cfg = resnet.resnet18(num_classes=10)
        tree = resnet.init_resnet(jax.random.PRNGKey(0), cfg)
        rounds = 3
    bundle = fl_comp.compress(tree, packed=True)
    jax.block_until_ready(bundle.buf)
    bundle_bytes = np.asarray(bundle.buf).nbytes
    peers = [p for p in parties if p != "alice"]
    # Distinct per-peer contributions (realistic: every peer's bytes
    # differ), pre-built so construction stays outside the window.
    contribs = {
        p: fl_comp.PackedTree(
            np.asarray(bundle.buf).copy(), bundle.passthrough, bundle.spec
        )
        for p in peers
    }

    def do_round(r):
        t0 = time.perf_counter()
        send_refs = [
            mgrs[p].send("alice", contribs[p], f"c{r}-{p}", "0")
            for p in peers
        ]
        got = [
            mgrs["alice"].recv(p, f"c{r}-{p}", "0").resolve(timeout=300)
            for p in peers
        ]
        t_in = time.perf_counter()
        bcast = mgrs["alice"].send_many(peers, got[0], f"b{r}", "0")
        for p in peers:
            mgrs[p].recv("alice", f"b{r}", "0").resolve(timeout=300)
        for ref in send_refs + list(bcast.values()):
            if not ref.resolve(timeout=300):
                raise RuntimeError("send-path bench send failed")
        t_end = time.perf_counter()
        return t_in - t0, t_end - t_in

    do_round(0)  # warmup: connections, codec pools, first fetches
    # The coordinator's PER-MANAGER transfer log (runtime-less child —
    # the module-global ring no longer sees manager traffic): both the
    # contributions-in recv records and the broadcast-out send records
    # are alice's view.
    log = mgrs["alice"].transfer_log
    total0 = log.total_recorded
    stats0 = mgrs["alice"].get_stats()
    bk0 = stats0["send_path_breakdown_ms"]
    # Best-of-reps like every wire bench here: a shared box's noise must
    # not fail the gate, the capability number is the max over windows.
    comms_wall = float("inf")
    wall_ratios = []
    for r in range(1, rounds + 1):
        in_s, out_s = do_round(r)
        comms_wall = min(comms_wall, in_s + out_s)
        wall_ratios.append(out_s / in_s)
    wall_ratios.sort()
    wall_ratio = wall_ratios[len(wall_ratios) // 2]  # median
    recs, complete = log.records_since(total0)
    stats1 = mgrs["alice"].get_stats()
    bk1 = stats1["send_path_breakdown_ms"]

    # In-situ capability yardstick: sequential single-payload pushes of
    # the same bundle, alice → bob, wall-clocked — what the wire
    # demonstrably sustains on THIS box right now (the r05 verdict's
    # 0.904 came from the equivalent dedicated push bench).
    cap_wall = float("inf")
    for rep in range(2):
        t0 = time.perf_counter()
        for i in range(3):
            ref = mgrs["alice"].send("bob", bundle, f"cap{rep}-{i}", "0")
            mgrs["bob"].recv("alice", f"cap{rep}-{i}", "0").resolve(
                timeout=300
            )
            if not ref.resolve(timeout=300):
                raise RuntimeError("capability probe send failed")
        cap_wall = min(cap_wall, time.perf_counter() - t0)
    cap_gbps = 3 * bundle_bytes / cap_wall / 1e9
    for m in mgrs.values():
        m.stop()

    # Local-link leg: the SAME sequential push shape over a fresh
    # colocated pair, once per backend — "auto" upgrades to the
    # in-process shm handoff, "uds" pins the AF_UNIX twin listener.
    # ``local_link_GBps`` (the shm number) over ``send_path_wire_GBps``
    # is the fast path's speedup gate (test.sh: >= 2.0): colocated
    # parties must beat the loopback-TCP coordinator path by at least
    # 2x, or the upgrade machinery is dead weight.
    lparties = ("alice", "bob")
    lports = {p: 13168 + i for i, p in enumerate(lparties)}

    def mk_local(party, mode):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict(
                    {"address": f"127.0.0.1:{lports[p]}"}
                )
                for p in lparties
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(
                device_put_received=False, zero_copy_host_arrays=True,
                local_link=mode,
            ),
        )

    local_legs = {}
    for mode in ("auto", "uds"):
        la, lb = mk_local("alice", mode), mk_local("bob", mode)
        la.start()
        lb.start()
        ref = la.send("bob", bundle, f"lw-{mode}", "0")  # warm+decide
        lb.recv("alice", f"lw-{mode}", "0").resolve(timeout=300)
        if not ref.resolve(timeout=300):
            raise RuntimeError(f"local-link warm send failed ({mode})")
        lwall = float("inf")
        for rep in range(2):
            t0 = time.perf_counter()
            for i in range(3):
                ref = la.send("bob", bundle, f"l{mode}{rep}-{i}", "0")
                lb.recv("alice", f"l{mode}{rep}-{i}", "0").resolve(
                    timeout=300
                )
                if not ref.resolve(timeout=300):
                    raise RuntimeError(
                        f"local-link probe send failed ({mode})"
                    )
            lwall = min(lwall, time.perf_counter() - t0)
        backend = (
            la.effective_transport_options("bob")
            .get("local_link", {})
            .get("backend")
        )
        local_legs[mode] = {
            "gbps": 3 * bundle_bytes / lwall / 1e9,
            "backend": backend,
        }
        la.stop()
        lb.stop()

    if not complete:
        raise RuntimeError("transfer log ring evicted the bench window")
    # The r05 decomposition for continuity: summed transfer-log wire
    # sessions — contributions read in ("c*" recv records land on
    # alice's manager), aggregate broadcast out ("b*" send records are
    # alice's).  Sessions of concurrent peers overlap, so these sums
    # exceed the wall above; the overhead RATIO is what they gate.
    read_s = sum(
        r.seconds for r in recs
        if r.direction == "recv" and r.up_id.startswith("c")
    )
    send_s = sum(
        r.seconds for r in recs
        if r.direction == "send" and r.up_id.startswith("b")
    )
    coord_bytes = 2 * len(peers) * bundle_bytes
    wire_gbps = coord_bytes / comms_wall / 1e9
    result_q.put(
        (
            "send_path",
            {
                "wire_gbps": wire_gbps,
                "cap_gbps": cap_gbps,
                "vs_cap": wire_gbps / cap_gbps if cap_gbps > 0 else None,
                "wall_ratio": wall_ratio,
                "read_ms": read_s / rounds * 1e3,
                "send_ms": send_s / rounds * 1e3,
                "overhead_ratio": send_s / read_s if read_s > 0 else None,
                "bundle_mb": bundle_bytes / 1e6,
                "breakdown_ms": {
                    k: round(bk1[k] - bk0[k], 2) for k in bk1
                },
                "striped_payloads": (
                    stats1["send_striped_payloads"]
                    - stats0["send_striped_payloads"]
                ),
                "local_legs": local_legs,
            },
        )
    )


def _fill_send_path_extra(extra: dict, s: dict) -> None:
    # cross_party_wire_GBps is the gateable FedAvg-path rate; the full
    # resnet e2e section later overwrites it with its own (compute-
    # embedded) measurement, so the probe's number also keeps its own
    # key.
    extra["cross_party_wire_GBps"] = round(s["wire_gbps"], 3)
    extra["send_path_wire_GBps"] = round(s["wire_gbps"], 3)
    extra["push_capability_GBps"] = round(s["cap_gbps"], 3)
    extra["wire_vs_push_capability"] = (
        round(s["vs_cap"], 3) if s["vs_cap"] else None
    )
    extra["send_vs_read_wall_ratio"] = round(s["wall_ratio"], 3)
    extra["coord_wire_read_ms"] = round(s["read_ms"], 2)
    extra["coord_send_path_ms"] = round(s["send_ms"], 2)
    extra["send_path_overhead_ratio"] = (
        round(s["overhead_ratio"], 3) if s["overhead_ratio"] else None
    )
    extra["send_path_breakdown_ms"] = s["breakdown_ms"]
    extra["send_path_striped_payloads"] = s["striped_payloads"]
    legs = s.get("local_legs") or {}
    if legs:
        # The shm ("auto" on one interpreter) number is THE gated one;
        # uds rides along as the cross-process colocation yardstick.
        extra["local_link_GBps"] = round(legs["auto"]["gbps"], 3)
        extra["local_link_backend"] = legs["auto"]["backend"]
        extra["local_link_uds_GBps"] = round(legs["uds"]["gbps"], 3)
        extra["local_link_vs_wire"] = round(
            legs["auto"]["gbps"] / max(1e-9, s["wire_gbps"]), 2
        )
    _log(
        f"  send path: {s['wire_gbps']:.3f} GB/s FedAvg-path wire vs "
        f"{s['cap_gbps']:.3f} GB/s push capability "
        f"({s['vs_cap']:.2f} of capability; r05 gap was 0.24) — "
        f"{s['bundle_mb']:.1f} MB bundles, {s['striped_payloads']} "
        f"striped payloads; send/read phase-wall ratio "
        f"{s['wall_ratio']:.2f} (r05 session imbalance was 2.7); "
        f"coordinator read {s['read_ms']:.1f} ms vs send "
        f"{s['send_ms']:.1f} ms session sum per round "
        f"({s['overhead_ratio']:.2f}x); breakdown {s['breakdown_ms']}"
    )
    if legs:
        _log(
            f"  local link: {legs['auto']['gbps']:.3f} GB/s "
            f"{legs['auto']['backend']} / "
            f"{legs['uds']['gbps']:.3f} GB/s {legs['uds']['backend']} "
            f"vs {s['wire_gbps']:.3f} GB/s tcp wire "
            f"({extra['local_link_vs_wire']:.1f}x, gate >= 2.0)"
        )


RINGB_PARTIES = ("alice", "bob", "carol", "dave")
RINGB_CLUSTER = {
    p: {"address": f"127.0.0.1:{13110 + i}"}
    for i, p in enumerate(RINGB_PARTIES)
}


def _run_ring_agg_party(party: str, result_q) -> None:
    """Ring vs hub FedAvg round over the fed API (4 parties, real wire).

    Same rotating-quarter update shape as the stream-agg bench (so the
    delta caches engage identically in both topologies), aggregated two
    ways per child process:

    - **hub**: ``streaming_aggregate`` — contributions funnel into the
      coordinator (alice), which folds and broadcasts back.
    - **ring**: ``ring_aggregate`` — chunk-striped reduce-scatter +
      all-gather around the sorted ring.

    Each party reports its wall time and its server-side ingress bytes
    for both phases.  The parent derives:

    - ``ring_agg_GBps``: logical contribution bytes over the ring
      round (N·|bundle|·rounds / wall).
    - ``ring_vs_coord_speedup``: hub wall / ring wall.  NB loopback
      under-rewards the ring — every "link" shares one host NIC/CPU,
      so the hub's per-node serialization (the thing the ring removes)
      is partially hidden; on real cross-silo links the hub coordinator
      is the bottleneck the speedup tracks.
    - ``coord_bytes_in_frac``: the coordinator's share of the round's
      TOTAL cross-party ingress bytes in ring mode — the de-bottleneck
      invariant.  Hub topology pins this at ~0.5 regardless of N (the
      coordinator receives half of all bytes the cluster receives);
      the ring spreads it to ~1/N (0.25 at N=4).  Gated ≤ 0.4 by
      test.sh's smoke.
    """
    import numpy as np
    import jax

    import rayfed_tpu as fed
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl.ring import ring_aggregate
    from rayfed_tpu.fl.streaming import streaming_aggregate
    from rayfed_tpu.runtime import get_runtime

    smoke = bool(os.environ.get("RAYFED_BENCH_SMOKE"))
    fed.init(address="local", cluster=RINGB_CLUSTER, party=party)

    if smoke:
        tree = _smoke_tree()
        rounds = 2
        chunk_elems = 1 << 19  # 1 MB bf16 blocks: 12 blocks / 4 stripes
    else:
        from rayfed_tpu.models import resnet

        cfg = resnet.resnet18(num_classes=10)
        tree = resnet.init_resnet(jax.random.PRNGKey(0), cfg)
        rounds = 3
        chunk_elems = None  # canonical 4 MB grid (~6 blocks)

    bundle = fl_comp.compress(tree, packed=True)
    base32 = np.asarray(bundle.buf).astype(np.float32)
    n_elems = base32.size
    bundle_bytes = np.asarray(bundle.buf).nbytes
    wire_dt = np.asarray(bundle.buf).dtype

    def contribution(party_idx: int, r: int) -> "fl_comp.PackedTree":
        arr = base32.copy()
        q = n_elems // 4
        lo = (r % 4) * q
        arr[lo : lo + q] += 1e-3 * (party_idx + 1) * (r + 1)
        return fl_comp.PackedTree(
            arr.astype(wire_dt), bundle.passthrough, bundle.spec
        )

    produce = fed.remote(contribution)

    def do_rounds(mode: str, r0: int, nrounds: int) -> float:
        t0 = time.perf_counter()
        for r in range(r0, r0 + nrounds):
            objs = [
                produce.party(p).remote(i, r)
                for i, p in enumerate(RINGB_PARTIES)
            ]
            if mode == "ring":
                out = ring_aggregate(
                    objs, stream="rg", chunk_elems=chunk_elems
                )
            else:
                out = streaming_aggregate(
                    objs, stream="hub", coordinator=RINGB_PARTIES[0]
                )
            np.asarray(out.buf[:64])  # touch: the round really landed
        return time.perf_counter() - t0

    def ingress() -> int:
        return int(get_runtime().transport.get_stats()["receive_bytes"])

    report = {"bundle_mb": bundle_bytes / 1e6}
    for mode in ("hub", "ring"):
        do_rounds(mode, 0, 1)  # warmup: compiles + seeds delta caches
        in0 = ingress()
        report[f"{mode}_s"] = do_rounds(mode, 1, rounds)
        report[f"{mode}_in"] = ingress() - in0

    # Quantized ring (ROADMAP 2a closed: uint8 reduce-scatter AND the
    # gather hop re-coded on the shared round grid — both halves ride
    # integer bytes).  Cold streams each round on BOTH legs so the
    # bytes compare codec-vs-codec: the bf16 legs above intentionally
    # ride warm delta caches, while a quantized round's codes change
    # nearly everywhere round-over-round — cache effects would
    # conflate the dtype comparison.
    from rayfed_tpu.fl import quantize as qz

    q_ce = chunk_elems if chunk_elems else (1 << 21)
    q_rng = np.random.default_rng(7)
    q_grid = qz.make_round_grid(
        (5e-3 * q_rng.standard_normal(n_elems)).astype(np.float32),
        mode="delta", expand=4.0, chunk_elems=q_ce,
    )

    def do_rounds_cold(tag: str, use_quant: bool, r0: int,
                       nrounds: int) -> float:
        t0 = time.perf_counter()
        for r in range(r0, r0 + nrounds):
            objs = [
                produce.party(p).remote(i, r)
                for i, p in enumerate(RINGB_PARTIES)
            ]
            out = ring_aggregate(
                objs, stream=f"{tag}{r}", chunk_elems=q_ce,
                quant=q_grid if use_quant else None,
                quant_ref=base32 if use_quant else None,
            )
            np.asarray(out.buf[:64])  # touch: the round really landed
        return time.perf_counter() - t0

    do_rounds_cold("rfw", False, 0, 1)  # warm compiles (f32 out path)
    in0 = ingress()
    report["ringf_s"] = do_rounds_cold("rfc", False, 1, rounds)
    report["ringf_in"] = ingress() - in0
    do_rounds_cold("rqw", True, 0, 1)  # warm the quantized kernels
    in0 = ingress()
    report["ringq_s"] = do_rounds_cold("rqc", True, 1, rounds)
    report["ringq_in"] = ingress() - in0

    report["rounds"] = rounds
    if result_q is not None:
        result_q.put((party, report))
    fed.shutdown()


def _ring_bench_metrics(res: dict) -> dict:
    """Reduce the per-party ring-bench reports to the headline metrics."""
    coord = RINGB_PARTIES[0]
    rounds = res[coord]["rounds"]
    bundle = res[coord]["bundle_mb"] * 1e6
    hub_wall = sum(v["hub_s"] for v in res.values()) / len(res)
    ring_wall = sum(v["ring_s"] for v in res.values()) / len(res)
    total_ring_in = sum(v["ring_in"] for v in res.values())
    total_hub_in = sum(v["hub_in"] for v in res.values())
    return {
        "ring_agg_GBps": round(
            len(res) * bundle * rounds / ring_wall / 1e9, 3
        ),
        "ring_vs_coord_speedup": round(hub_wall / ring_wall, 3),
        "coord_bytes_in_frac": round(
            res[coord]["ring_in"] / total_ring_in, 3
        ),
        "coord_bytes_in_frac_hub": round(
            res[coord]["hub_in"] / total_hub_in, 3
        ),
        "ring_coord_ingress_vs_hub": round(
            res[coord]["ring_in"] / max(1, res[coord]["hub_in"]), 3
        ),
        "ring_round_ms": round(ring_wall / rounds * 1e3, 1),
        "hub_round_ms": round(hub_wall / rounds * 1e3, 1),
        "ring_bundle_mb": round(bundle / 1e6, 1),
        # Quantized ring vs bf16 ring, both on cold streams: with the
        # reduce-scatter at uint8 AND the gather re-coded on the round
        # grid (rsm v3), the whole round's bytes should sit near the
        # dtype ratio (~0.5 of bf16) plus grid/manifest slack.
        "ring_quant_bytes_frac": round(
            sum(v["ringq_in"] for v in res.values())
            / max(1, sum(v["ringf_in"] for v in res.values())), 3
        ),
        "ring_quant_round_ms": round(
            sum(v["ringq_s"] for v in res.values()) / len(res)
            / rounds * 1e3, 1
        ),
        "ring_f32cold_round_ms": round(
            sum(v["ringf_s"] for v in res.values()) / len(res)
            / rounds * 1e3, 1
        ),
    }


def _fill_ring_extra(extra: dict, res: dict) -> None:
    m = _ring_bench_metrics(res)
    extra.update(m)
    _log(
        f"  ring-agg: {m['ring_agg_GBps']:.3f} GB/s logical through the "
        f"ring round; coordinator takes {m['coord_bytes_in_frac']:.0%} "
        f"of cluster ingress (hub: {m['coord_bytes_in_frac_hub']:.0%}), "
        f"{m['ring_coord_ingress_vs_hub']:.2f}x its hub ingress bytes; "
        f"round {m['ring_round_ms']:.0f} ms vs hub "
        f"{m['hub_round_ms']:.0f} ms "
        f"(speedup {m['ring_vs_coord_speedup']:.2f}x — loopback "
        f"under-rewards the ring; the ingress fraction is the "
        f"topology invariant); quantized ring "
        f"{m['ring_quant_bytes_frac']:.3f}x the bf16 ring's bytes "
        f"(uint8 reduce-scatter + round-grid-coded gather), round "
        f"{m['ring_quant_round_ms']:.0f} ms vs f32-cold "
        f"{m['ring_f32cold_round_ms']:.0f} ms"
    )


CHAOSB_PARTIES = ("alice", "bob", "carol", "dave")
CHAOSB_CLUSTER = {
    p: {"address": f"127.0.0.1:{13170 + i}"}
    for i, p in enumerate(CHAOSB_PARTIES)
}
# Fast death detection ONLY for the party the schedule crashes (the
# per-party health knobs); a loaded-but-healthy coordinator must never
# be falsely declared dead by aggressive global knobs.
CHAOSB_CLUSTER["dave"]["transport_options"] = {
    "heartbeat_interval_s": 0.3, "death_deadline_s": 0.9,
}
CHAOSB_ROUNDS = 3
CHAOSB_DEADLINE_S = 3.0


def _run_chaos_party(party: str, result_q) -> None:
    """The robustness smoke: a quorum round under injected faults.

    4 parties run ``run_fedavg_rounds(quorum=2, round_deadline_s=...)``
    with a seeded chaos schedule: carol straggles 6s past the 3s round
    deadline in round 1, dave HARD-crashes at the same boundary
    (``os._exit`` — sockets die, no goodbyes), and the COORDINATOR
    (alice) hard-crashes mid-round 2, between its quorum cutoff and the
    result broadcast — the nastiest failover window.  The gate: every
    SURVIVING controller completes all rounds, agrees on the final
    bytes, round 1 aggregated a strict quorum subset, the roster epoch
    advanced at least twice (both corpses dropped without any runtime
    restart), and every survivor performed at least one coordinator
    failover (the round was re-established at the deterministic
    successor).  This is the failure story the quorum/membership/
    failover/chaos machinery exists for, exercised over real sockets on
    every CI run.
    """
    import numpy as np

    import rayfed_tpu as fed
    from rayfed_tpu import chaos
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl import run_fedavg_rounds
    from rayfed_tpu.fl.quorum import QUORUM_STATS

    import jax
    import jax.numpy as jnp

    chaos.install({
        "seed": 11,
        "rules": [
            {"hook": "round", "party": "carol", "match": {"round": 1},
             "op": "delay_ms", "value": 8000},
            {"hook": "round", "party": "dave", "match": {"round": 1},
             "op": "crash_party"},
            # Kill the coordinator AFTER round 2's cutoff pinned the
            # member set but BEFORE anyone heard the result: only the
            # survivors' health monitors + deterministic failover can
            # finish the round (at the successor, bob).
            {"hook": "announce", "party": "alice", "match": {"round": 2},
             "op": "crash_party"},
        ],
    })

    dim = 1024
    deltas = {p: 0.25 * (i + 1) for i, p in enumerate(CHAOSB_PARTIES)}

    # Warm every jitted program the round touches: the first deadline
    # must measure the protocol, not 4-way XLA compile contention.
    params = {"w": jnp.zeros((dim,), jnp.float32)}
    packed = fl_comp.compress(params, packed=True, wire_dtype=jnp.float32)
    from rayfed_tpu.fl.fedavg import (
        finalize_packed_stripe,
        packed_weighted_sum,
    )
    from rayfed_tpu.fl.overlap import dga_correct
    from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS, _accum_kernel

    for n in (2, 3, 4):
        packed_weighted_sum([packed] * n, None)
    jax.block_until_ready(dga_correct(packed, packed, packed).buf)
    kern = _accum_kernel(DEFAULT_CHUNK_ELEMS, "float32", "float32")
    acc = kern(
        jnp.zeros(DEFAULT_CHUNK_ELEMS, jnp.float32),
        np.zeros(DEFAULT_CHUNK_ELEMS, np.float32),
        np.int32(0), np.float32(1.0),
    )
    jax.block_until_ready(finalize_packed_stripe(acc, 2.0, dim, jnp.float32))

    fed.init(
        address="local", cluster=CHAOSB_CLUSTER, party=party,
        enable_waiting_for_other_parties_ready=True,
        peer_health_interval_in_seconds=1.0, peer_death_pings=3,
        cross_silo_timeout_in_seconds=15,
        cross_silo_retry_policy={
            "maxAttempts": 2, "initialBackoff": "0.2s",
            "maxBackoff": "0.5s",
        },
        recv_backstop_in_seconds=120,
    )

    @fed.remote
    class Trainer:
        def __init__(self, delta):
            self._d = float(delta)

        def train(self, p):
            tree = fl_comp.decompress(p, jnp.float32)
            return fl_comp.compress(
                {"w": tree["w"] + self._d}, packed=True,
                wire_dtype=jnp.float32,
            )

    trainers = {
        p: Trainer.party(p).remote(deltas[p]) for p in CHAOSB_PARTIES
    }
    log: list = []
    t0 = time.perf_counter()
    try:
        final = run_fedavg_rounds(
            trainers, params, rounds=CHAOSB_ROUNDS, compress_wire=True,
            packed_wire=True, wire_dtype=jnp.float32, quorum=2,
            round_deadline_s=CHAOSB_DEADLINE_S, round_log=log,
            coordinator=CHAOSB_PARTIES[0],
        )
    except chaos.ChaosPartyCrash:
        # Hard crash: report, then die without any goodbye — the
        # survivors' health monitors and quorum cutoff are the test.
        # (The queue feeder thread must flush before os._exit or the
        # report is lost with the process.)
        if result_q is not None:
            result_q.put((party, {"crashed": True}))
            result_q.close()
            result_q.join_thread()
        os._exit(0)
    wall = time.perf_counter() - t0
    buf = np.asarray(final["w"], dtype=np.float32)
    report = {
        "crashed": False,
        "rounds": len(log),
        "round1_members": sorted(
            next(e for e in log if e["round"] == 1)["members"]
        ),
        "final_crc": int(np.frombuffer(buf.tobytes(), np.uint8).sum()),
        "final_head": float(buf[0]),
        # The FINAL roster epoch (log entries carry round-START epochs,
        # which lag the last round's own announce — here the one that
        # dropped the crashed coordinator).
        "epoch": int(fed.runtime.get_runtime().transport.roster.epoch),
        "coordinator_failovers": int(
            QUORUM_STATS["coordinator_failovers"]
        ),
        "final_coordinator": log[-1]["coordinator"],
        "wall_s": wall,
    }
    if result_q is not None:
        result_q.put((party, report))
    fed.shutdown()


def _fill_chaos_extra(extra: dict, res: dict) -> None:
    survivors = {p: r for p, r in res.items() if not r.get("crashed")}
    crashed = [p for p, r in res.items() if r.get("crashed")]
    finals = {(r["final_crc"], r["final_head"]) for r in survivors.values()}
    extra["chaos_survivors"] = len(survivors)
    extra["chaos_crashed_parties"] = crashed
    extra["chaos_rounds_completed"] = min(
        (r["rounds"] for r in survivors.values()), default=0
    )
    extra["chaos_round1_members"] = (
        next(iter(survivors.values()))["round1_members"]
        if survivors else []
    )
    extra["chaos_final_consistent"] = len(finals) == 1
    extra["chaos_roster_epoch"] = max(
        (r["epoch"] for r in survivors.values()), default=0
    )
    # Every survivor must have re-established the coordinator-killed
    # round at the successor — gate on the MINIMUM so one stale
    # controller can't hide behind the others.
    extra["chaos_coordinator_failovers"] = min(
        (r.get("coordinator_failovers", 0) for r in survivors.values()),
        default=0,
    )
    extra["chaos_final_coordinator"] = next(
        (r.get("final_coordinator") for r in survivors.values()), None
    )
    extra["chaos_round_wall_s"] = round(
        max((r["wall_s"] for r in survivors.values()), default=0.0)
        / max(1, CHAOSB_ROUNDS), 2,
    )
    _log(
        f"  chaos: {len(survivors)} survivors completed "
        f"{extra['chaos_rounds_completed']}/{CHAOSB_ROUNDS} rounds under "
        f"1 straggler + 2 crashes (incl. the coordinator mid-round); "
        f"round-1 quorum {extra['chaos_round1_members']}, roster epoch "
        f"{extra['chaos_roster_epoch']}, "
        f"{extra['chaos_coordinator_failovers']} failovers (lease now at "
        f"{extra['chaos_final_coordinator']}), finals "
        f"{'IDENTICAL' if extra['chaos_final_consistent'] else 'DIVERGED'}"
    )


TELEB_PARTIES = ("alice", "bob", "carol", "dave")


def _run_telemetry_bench(_party: str, result_q) -> None:
    """Flight-recorder cost + fidelity (rayfed_tpu/telemetry.py).

    One child, 4 in-process TransportManagers over real loopback
    sockets (the stream-agg bench's shape), running the SAME
    streaming-aggregation round in PAIRED disarmed/armed measurements
    — same warmed caches, same contributions.  Gates (test.sh):

    - ``trace_overhead_frac`` ≤ 0.03 — per-pair armed-vs-disarmed
      round-wall deltas (pair order swapped every other pair so
      warm-second bias cancels), gated on the MIN over three 8-pair
      block medians, within 3%%; an emission is a ring append, so
      tracing must be ~free and the gate really catches a new
      sleep/I/O on the hot path;
    - ``trace_critical_path_agrees`` — the armed rounds' records,
      collected from every peer manager over the wire
      (``collect_trace``, the TRACE_GET/TRACE_PUT round trip), merged
      (clock offsets applied) and fed to ``tool/trace_report``, yield
      per-round walls that reconcile with the driver's own measured
      walls within 25%%, and the merged timeline exports as non-empty
      Perfetto ``trace_event`` JSON.
    """
    import numpy as np

    from rayfed_tpu import telemetry
    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.fl import compression as fl_comp
    from rayfed_tpu.fl.streaming import StreamingAggregator
    from rayfed_tpu.transport.manager import TransportManager
    from tool.trace_report import round_report

    parties = TELEB_PARTIES
    ports = {p: 13200 + i for i, p in enumerate(parties)}

    def mk(party):
        cc = ClusterConfig(
            parties={
                p: PartyConfig.from_dict({"address": f"127.0.0.1:{ports[p]}"})
                for p in parties
            },
            current_party=party,
        )
        return TransportManager(
            cc,
            JobConfig(device_put_received=False, zero_copy_host_arrays=True),
        )

    mgrs = {p: mk(p) for p in parties}
    for m in mgrs.values():
        m.start()

    bundle = fl_comp.compress(_smoke_tree(), packed=True)
    base32 = np.asarray(bundle.buf).astype(np.float32)
    n_elems = base32.size
    wire_dt = np.asarray(bundle.buf).dtype

    def contribution(party_idx: int, r: int):
        arr = base32.copy()
        q = n_elems // 4
        lo = (r % 4) * q
        arr[lo : lo + q] += 1e-3 * (party_idx + 1) * (r + 1)
        return fl_comp.PackedTree(
            arr.astype(wire_dt), bundle.passthrough, bundle.spec
        )

    peers = [p for p in parties if p != "alice"]

    def do_round(r: int) -> float:
        t0_wall = time.time()
        t0 = time.perf_counter()
        contribs = {p: contribution(i + 1, r) for i, p in enumerate(peers)}
        send_refs = [
            mgrs[p].send(
                "alice", contribs[p], f"t{r}-{p}", "0",
                stream=f"tele/up/{p}", round_tag=r,
            )
            for p in peers
        ]
        agg = StreamingAggregator(len(parties), party="alice")
        for i, p in enumerate(peers):
            mgrs["alice"].recv_stream(p, f"t{r}-{p}", "0", agg.sink(i + 1))
        agg.add_local(0, contribution(0, r))
        result = agg.result(timeout=300)
        bcast = mgrs["alice"].send_many(
            peers, result, f"tb{r}", "0", stream="tele/down", round_tag=r
        )
        for p in peers:
            out = mgrs[p].recv("alice", f"tb{r}", "0").resolve(timeout=300)
            np.asarray(out.buf[:64])  # touch: decode really happened
        for ref in send_refs + list(bcast.values()):
            if not ref.resolve(timeout=300):
                raise RuntimeError("telemetry bench send failed")
        wall = time.perf_counter() - t0
        # The driver's round record — disarmed this is ONE global read.
        telemetry.emit(
            "driver.round", party="alice", round=r, t_start=t0_wall,
            dur_s=wall, detail={"local_s": 0.0},
        )
        return wall

    reps = 7  # the collect/report window size (below)
    # Overhead probe: the true armed cost is ~µs of ring appends per
    # round against ~ms loopback/scheduler jitter, so the gate really
    # asserts "no new sleep/I/O on the hot path" and the estimator
    # must not let jitter masquerade as overhead.  PAIRED rounds,
    # order swapped every other pair (within a pair the SECOND round
    # runs warmer — page cache, branch predictors — so a fixed order
    # biases one arm; two sequential blocks measured drift as ±9%%
    # "overhead" against a 3%% gate), and the gate value is the MEDIAN
    # of the per-pair relative deltas: drift cancels inside each pair,
    # outlier rounds fall out of the median, and the estimator's noise
    # shrinks with pair count (~1%% at 24 pairs on the CI box).
    probe_pairs = 24
    do_round(0)  # warmup: compiles + seeds every delta cache
    assert telemetry.installed() is None
    disarmed = []
    armed_probe = []
    r_next = 1
    for k in range(probe_pairs):
        if k % 2 == 0:
            disarmed.append(do_round(r_next))
            r_next += 1
            telemetry.install()  # throwaway ring: overhead probe only
            armed_probe.append(do_round(r_next))
            r_next += 1
            telemetry.uninstall()
        else:
            telemetry.install()
            armed_probe.append(do_round(r_next))
            r_next += 1
            telemetry.uninstall()
            disarmed.append(do_round(r_next))
            r_next += 1
    deltas = [
        (a - d) / d for a, d in zip(armed_probe, disarmed)
    ]

    def _median(xs):
        xs = sorted(xs)
        mid = len(xs) // 2
        return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])

    # Gate value = MIN over three independent 8-pair blocks' medians: a
    # REAL hot-path regression (a sleep or I/O is >= ms on every round)
    # shifts every block's median, while a scheduler-noise spike must
    # strike all three blocks at once to masquerade as overhead — the
    # single 24-pair median still flaked ~3%% right after the full
    # pytest load's thermal/cache drift.
    block = len(deltas) // 3
    overhead_frac = min(
        _median(deltas[i * block : (i + 1) * block]) for i in range(3)
    )

    # The collect/report window: ONE persistent recorder across reps
    # armed rounds — what the cross-manager collection, merge, Perfetto
    # export and critical-path report run against.
    telemetry.install()  # party=None: every seam stamps its own party
    armed_r0 = r_next
    armed = [do_round(armed_r0 + i) for i in range(reps)]

    # Cross-manager collection over the wire (the TRACE_GET round trip)
    # from alice against every peer; alice's own window is read locally.
    me = "alice"
    rec = telemetry.installed()
    party_records = {
        me: [x for x in rec.records() if x.party is None or x.party == me]
    }
    offsets = {me: {"offset_s": 0.0, "rtt_s": 0.0, "bound_s": 0.0}}
    for p in peers:
        records, offset, rep_meta = mgrs[me].collect_trace(p, timeout_s=60)
        if not rep_meta["armed"]:
            raise RuntimeError(f"peer {p} served a disarmed trace window")
        party_records[p] = records
        offsets[p] = offset
    merged = telemetry.merge_records(party_records, offsets)
    perfetto = telemetry.to_trace_events(merged, offsets)
    report = round_report(merged, tolerance=0.25)

    agrees = True
    for i, wall in enumerate(armed):
        info = report.get(armed_r0 + i)
        if info is None or not info["wall_agrees"]:
            agrees = False
            break
        if abs(info["wall_s"] - wall) > 0.25 * max(wall, info["wall_s"]):
            agrees = False
            break
    if not perfetto.get("traceEvents"):
        agrees = False

    spans_from = {
        str(d.get("party")) for d in merged if d.get("phase") != "driver.round"
    }
    stats = rec.stats()
    telemetry.uninstall()
    for m in mgrs.values():
        m.stop()
    result_q.put((
        "solo",
        {
            "overhead_frac": overhead_frac,
            "agrees": agrees,
            "disarmed_wall_s": min(disarmed),
            "armed_wall_s": min(armed),
            "merged_records": len(merged),
            "parties_with_spans": sorted(spans_from),
            "trace_dropped": stats["trace_dropped"],
        },
    ))


def _fill_telemetry_extra(extra: dict, s: dict) -> None:
    extra["trace_overhead_frac"] = round(s["overhead_frac"], 4)
    extra["trace_critical_path_agrees"] = bool(
        s["agrees"] and len(s["parties_with_spans"]) == len(TELEB_PARTIES)
    )
    extra["trace_merged_records"] = s["merged_records"]
    extra["trace_dropped"] = s["trace_dropped"]
    _log(
        f"  telemetry: armed round wall {s['armed_wall_s'] * 1e3:.1f} ms "
        f"vs disarmed {s['disarmed_wall_s'] * 1e3:.1f} ms (overhead "
        f"{100 * s['overhead_frac']:+.2f}%); merged "
        f"{s['merged_records']} records from "
        f"{len(s['parties_with_spans'])} parties "
        f"({s['trace_dropped']} dropped); critical path "
        f"{'agrees' if extra['trace_critical_path_agrees'] else 'DISAGREES'}"
    )


ASYNCB_PARTIES = ("coord", "p1", "p2", "p3", "p4")  # p4 is the straggler
ASYNCB_DIM = 4096
ASYNCB_BASE_S = 0.05       # deterministic per-step "compute" (sleep);
                           # sized so the straggler's stretched step —
                           # the thing the barrier pays — dominates the
                           # fleet's per-push loopback RTT
ASYNCB_LR = 0.5
ASYNCB_TARGET_FRAC = 0.05  # stop when excess loss <= 5% of initial
ASYNCB_SYNC_ROUNDS = 6     # fixed sync schedule; target lands ~round 3
ASYNCB_CHAOS = {
    "seed": 11,
    "rules": [{
        "hook": "local_step", "party": "p4",
        "op": "local_slowdown", "value": [2.0, 10.0],
    }],
}
ASYNCB_N64 = 64            # versions/sec leg: 1 coordinator + 63 members


def _run_async_bench(_party: str, result_q) -> None:
    """Buffered asynchronous rounds vs the synchronous barrier
    (rayfed_tpu/fl/async_rounds.py), one child, in-process virtual
    parties (the PR 16/17 fleet shape — no party subprocesses).

    Leg 1 — time-to-target-loss under a seeded 2-10x straggler
    spread.  Same quadratic workload both ways (every party steps
    ``w + lr*(c - w)`` toward a shared optimum after a fixed
    ``ASYNCB_BASE_S`` compute sleep; heterogeneity is SPEED, not
    data), same seeded ``local_slowdown`` chaos schedule on p4:

    - sync: thread-barrier FedAvg — every round's wall is the slowest
      party's stretched step, by construction;
    - async: ``fl.run_async_fleet`` (buffer_k=3) — fast parties keep
      pushing while p4 stalls; its contributions land stale and
      shift-decayed instead of holding a barrier.

    ``async_tt_frac`` = async/sync wall to the SAME target excess
    loss (async stamps ride the coordinator's version_log).  Gate
    ≤ 0.8 (ROADMAP item 2); the barrier pays the straggler every
    round, so the observed ratio sits well under it.

    Leg 2 — coordinator throughput at fleet scale: N=64 in-process
    virtual parties (63 members, no chaos, no compute sleep) pushing
    2 cycles each through the running donated-i32 fold;
    ``async_versions_per_sec`` gates the version emission rate.

    Exactness rides along: leg 1's recorded per-version fold sets
    refold through ``packed_quantized_sum`` sorted-by-party and must
    be byte-identical to every emitted model
    (``async_refold_bitexact``) — the buffered fold is order-free.
    """
    import collections
    import threading

    import numpy as np

    from rayfed_tpu import chaos
    from rayfed_tpu.fl import async_rounds as ar
    from rayfed_tpu.fl import run_async_fleet
    from rayfed_tpu.fl.compression import PackedTree
    from rayfed_tpu.fl.fedavg import packed_quantized_sum

    rng = np.random.default_rng(7)
    c_vec = (0.25 + 0.5 * rng.random(ASYNCB_DIM)).astype(np.float32)
    # Random init, NOT zeros: version 0's negotiation-free grid is an
    # abs-mode grid over the initial params, so their value range must
    # cover the early contributions (an all-constant init degenerates
    # it to a clip-everything grid — same constraint as real models,
    # which never initialize identically-zero).
    w0 = rng.random(ASYNCB_DIM).astype(np.float32)

    def loss(w):
        return float(0.5 * np.mean((w - c_vec) ** 2))

    loss0 = loss(w0)
    target = ASYNCB_TARGET_FRAC * loss0
    members = [p for p in ASYNCB_PARTIES if p != "coord"]

    def _local_step(party, packed, version, cycle):
        buf = np.asarray(packed.buf).astype(np.float32)
        time.sleep(ASYNCB_BASE_S)
        new = buf + np.float32(ASYNCB_LR) * (c_vec - buf)
        return PackedTree(new, packed.passthrough, packed.spec)

    # Warm the quantize/fold jit kernels OUTSIDE the timed legs — the
    # first fleet otherwise pays XLA compiles inside its version walls.
    run_async_fleet(
        ["coord", "p1"], {"w": w0}, _local_step, cycles=2,
        buffer_k=1, timeout_s=120,
    )
    ar.reset_async_stats()

    # --- sync leg: thread-barrier FedAvg under the chaos schedule ---
    chaos.install(ASYNCB_CHAOS)
    barrier = threading.Barrier(len(members))
    model = {"w": w0.copy()}
    contribs: dict = {}
    sync_curve: list = []
    t0 = time.time()

    def _sync_member(p):
        for rnd in range(ASYNCB_SYNC_ROUNDS):
            w = model["w"]
            t1 = time.perf_counter()
            time.sleep(ASYNCB_BASE_S)
            new = w + np.float32(ASYNCB_LR) * (c_vec - w)
            dur = time.perf_counter() - t1
            chaos.fire(
                "local_step", p, version=rnd, cycle=rnd, baseline_s=dur,
            )
            contribs[p] = new
            if barrier.wait() == 0:
                model["w"] = np.mean(
                    [contribs[m] for m in members], axis=0,
                ).astype(np.float32)
                sync_curve.append((time.time() - t0, loss(model["w"])))
            barrier.wait()

    threads = [
        threading.Thread(target=_sync_member, args=(p,), daemon=True)
        for p in members
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    chaos.uninstall()
    tt_sync = next((t for t, l in sync_curve if l <= target), None)

    # --- async leg: same workload, same chaos schedule, no barrier ---
    chaos.install(ASYNCB_CHAOS)
    vlog: list = []
    folds: list = []
    t0 = time.time()
    out = run_async_fleet(
        ASYNCB_PARTIES, {"w": w0}, _local_step,
        cycles={"p1": 10, "p2": 10, "p3": 10, "p4": 4},
        # Weight 16: staleness s folds at 16 >> s, so a straggler's
        # contribution lands decayed instead of decaying OUT (weight 1
        # zeroes at s=1 — fine for a drop policy, not for a bench
        # whose point is absorbing stale work).
        weights={p: 16 for p in members},
        buffer_k=3, timeout_s=120,
        version_log=vlog, record_folds=folds,
    )
    chaos.uninstall()
    leg1_hist = {
        str(k): v for k, v in ar.ASYNC_STATS["staleness_hist"].items()
    }
    tt_async = next(
        (r["t_wall"] - t0 for r in vlog
         if loss(r["model"][: ASYNCB_DIM]) <= target),
        None,
    )

    # Per-version refold oracle (the test suite's identity, riding the
    # bench so the gate also certifies exactness on THIS host).
    by_v = collections.defaultdict(list)
    for f in folds:
        if f["w_eff"] > 0:
            by_v[f["version"]].append(f)
    bitexact = bool(vlog)
    prev_model = None
    for rec in vlog:
        fset = sorted(by_v[rec["version"] - 1], key=lambda f: f["party"])
        if not fset:
            bitexact = False
            break
        qts = [f["qt"] for f in fset]
        ref = prev_model if qts[0].grid().mode == "delta" else None
        oracle = packed_quantized_sum(
            qts, [f["w_eff"] for f in fset], ref=ref,
        )
        if not np.array_equal(np.asarray(oracle.buf), rec["model"]):
            bitexact = False
            break
        prev_model = rec["model"]

    # --- N=64 throughput leg: no chaos, no compute sleep ---
    def _fast_step(party, packed, version, cycle):
        buf = np.asarray(packed.buf).astype(np.float32)
        new = buf + np.float32(ASYNCB_LR) * (c_vec[:256] - buf)
        return PackedTree(new, packed.passthrough, packed.spec)

    ar.reset_async_stats()
    n64 = ["coord"] + [f"m{i:02d}" for i in range(ASYNCB_N64 - 1)]
    t1 = time.time()
    out64 = run_async_fleet(
        n64, {"w": w0[:256]}, _fast_step,
        cycles=2, weights={p: 16 for p in n64[1:]},
        buffer_k=8, timeout_s=240,
    )
    n64_wall = time.time() - t1

    result_q.put(("solo", {
        "tt_sync_s": tt_sync,
        "tt_async_s": tt_async,
        "sync_wall_s": sync_curve[-1][0] if sync_curve else None,
        "versions": out["versions"],
        "folds": out["folds"],
        "staleness_hist": leg1_hist,
        "refold_bitexact": bitexact,
        "n64_versions": out64["versions"],
        "n64_folds": out64["folds"],
        "n64_wall_s": n64_wall,
    }))


def _fill_async_extra(extra: dict, s: dict) -> None:
    tt_a, tt_s = s["tt_async_s"], s["tt_sync_s"]
    extra["async_tt_frac"] = (
        round(tt_a / tt_s, 3)
        if tt_a is not None and tt_s else None
    )
    extra["async_time_to_target_s"] = (
        round(tt_a, 3) if tt_a is not None else None
    )
    extra["sync_time_to_target_s"] = (
        round(tt_s, 3) if tt_s is not None else None
    )
    extra["async_refold_bitexact"] = bool(s["refold_bitexact"])
    extra["async_versions"] = s["versions"]
    extra["async_staleness_hist"] = s["staleness_hist"]
    extra["async_versions_per_sec"] = (
        round(s["n64_versions"] / s["n64_wall_s"], 2)
        if s["n64_wall_s"] else None
    )
    extra["async_n64_wall_s"] = round(s["n64_wall_s"], 3)
    _log(
        f"  async: time-to-target {tt_a if tt_a is None else round(tt_a, 3)}s "
        f"vs sync {tt_s if tt_s is None else round(tt_s, 3)}s "
        f"(frac {extra['async_tt_frac']}); {s['versions']} versions / "
        f"{s['folds']} folds, staleness hist {s['staleness_hist']}, "
        f"refold {'bit-exact' if extra['async_refold_bitexact'] else 'MISMATCH'}; "
        f"N=64: {s['n64_versions']} versions in {s['n64_wall_s']:.2f}s "
        f"({extra['async_versions_per_sec']}/s, {s['n64_folds']} folds)"
    )


OVERLAPB_PARTIES = ("alice", "bob", "carol", "dave")
OVERLAPB_CLUSTER = {
    p: {"address": f"127.0.0.1:{13120 + i}"}
    for i, p in enumerate(OVERLAPB_PARTIES)
}


def _run_overlap_party(party: str, result_q) -> None:
    """Pipelined (overlap=True) vs synchronous FedAvg rounds, 4 parties.

    Each party runs the SAME jitted matmul-chain trainer twice through
    ``run_fedavg_rounds`` — once synchronous (streaming aggregation, the
    pre-overlap round shape) and once pipelined — from the same warmed
    state (compiles done, delta caches seeded).  Each party reports its
    two walls plus the pipelined per-round timing breakdown; the parent
    derives:

    - ``overlap_hidden_comm_frac``: Σ hidden_s / Σ agg_s over the
      pipelined rounds — the share of the comms wall (contribution
      ready → aggregate landed) that ran UNDER the next round's local
      compute instead of exposing the training thread.  The last round
      has nothing to hide behind (though its window is also the
      shortest — no concurrent compute stretching it); the CI gate is
      ≥ 0.5.
    - ``round_wall_speedup``: sync wall / overlap wall.  Ceiling is
      (compute + comms) / max(compute, comms) ≤ 2; with compute sized
      several × comms here the expected value is a modest 1.0–1.3 — the
      hidden fraction is the structural invariant, the speedup is the
      honest end-to-end number on THIS host's compute/comms ratio.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import run_fedavg_rounds

    smoke = bool(os.environ.get("RAYFED_BENCH_SMOKE"))
    fed.init(address="local", cluster=OVERLAPB_CLUSTER, party=party)

    # Model + local-step sizing: compute must be a healthy multiple of
    # the loopback comms or there is nothing to hide the comms under.
    # The bundle is kept SMALL (dim=512 → 0.5 MB bf16) so the comms
    # wall is the 4-party round's fixed latency (pushes + fold +
    # broadcast + ACK waits ≈ 100-300 ms on loopback) — genuinely idle
    # time, hideable even on a saturated box.  Bigger bundles turn
    # comms into CPU work (codec + fold) that CONTENDS with training
    # instead of hiding under it.  steps=50 measures ≈ 170 ms of
    # jitted compute per train single-process and ~0.8 s under 4-party
    # contention on the 2-core bench host — comfortably above the
    # comms window it has to cover.
    dim = 512
    steps = 50
    rounds = 6 if smoke else 8

    @fed.remote
    class Trainer:
        def __init__(self, seed: int):
            self._a = jax.random.normal(
                jax.random.PRNGKey(seed), (dim, dim)
            ) / np.sqrt(dim)

            @jax.jit
            def _steps(a, w):
                for _ in range(steps):
                    w = 0.99 * w + 0.01 * jnp.tanh(a @ w)
                return w

            self._steps = _steps

        def train(self, params):
            from rayfed_tpu.fl import compression as C

            w = C.decompress(params, jnp.float32)["w"]
            w = self._steps(self._a, w)
            out = C.compress({"w": w}, packed=True)
            # Materialize INSIDE the train body: jax dispatches async, so
            # without this the jitted chain would return in ~1 ms and the
            # actual compute would lazily execute inside the comms lane's
            # payload encode — "comms" would absorb the round's compute
            # and there would be nothing left on the training side to
            # hide it under (real trainers synchronize every round on
            # data loading / metrics anyway).
            jax.block_until_ready(out.buf)
            return out

    params = {
        "w": jax.random.normal(jax.random.PRNGKey(99), (dim, dim))
    }
    trainers = {
        p: Trainer.party(p).remote(i)
        for i, p in enumerate(OVERLAPB_PARTIES)
    }

    def run(overlap: bool, nrounds: int, timings=None):
        kw = (
            {"overlap": True}
            if overlap
            else {"streaming_agg": True}
        )
        t0 = time.perf_counter()
        out = run_fedavg_rounds(
            trainers, params, rounds=nrounds, compress_wire=True,
            packed_wire=True, timings=timings, **kw,
        )
        jax.block_until_ready(out["w"])
        return time.perf_counter() - t0

    run(False, 1)  # warmup: train/fold compiles + delta-cache seed
    run(True, 2)  # warmup: DGA-correction compile + lane spin-up
    sync_t: list = []
    sync_s = run(False, rounds, timings=sync_t)
    ov_t: list = []
    overlap_s = run(True, rounds, timings=ov_t)

    report = {
        "rounds": rounds,
        "sync_s": sync_s,
        "overlap_s": overlap_s,
        "hidden_s": sum(r["hidden_s"] for r in ov_t),
        "agg_s": sum(r["agg_s"] for r in ov_t),
        "local_s": sum(r["local_s"] for r in ov_t),
        "sync_agg_s": sum(r["agg_s"] for r in sync_t),
    }
    if result_q is not None:
        result_q.put((party, report))
    fed.shutdown()


def _overlap_bench_metrics(res: dict) -> dict:
    n = len(res)
    rounds = next(iter(res.values()))["rounds"]
    sync_wall = sum(v["sync_s"] for v in res.values()) / n
    ov_wall = sum(v["overlap_s"] for v in res.values()) / n
    hidden = sum(v["hidden_s"] for v in res.values())
    agg = sum(v["agg_s"] for v in res.values())
    return {
        "overlap_hidden_comm_frac": round(hidden / max(agg, 1e-9), 3),
        "round_wall_speedup": round(sync_wall / ov_wall, 3),
        "overlap_round_ms": round(ov_wall / rounds * 1e3, 1),
        "sync_round_ms": round(sync_wall / rounds * 1e3, 1),
        "overlap_comms_ms_per_round": round(
            agg / n / rounds * 1e3, 1
        ),
        "overlap_local_ms_per_round": round(
            sum(v["local_s"] for v in res.values()) / n / rounds * 1e3, 1
        ),
    }


def _fill_overlap_extra(extra: dict, res: dict) -> None:
    m = _overlap_bench_metrics(res)
    extra.update(m)
    _log(
        f"  overlap: {m['overlap_hidden_comm_frac']:.0%} of the comms "
        f"wall hidden under local compute "
        f"(comms {m['overlap_comms_ms_per_round']:.0f} ms under local "
        f"{m['overlap_local_ms_per_round']:.0f} ms per round); round "
        f"{m['overlap_round_ms']:.0f} ms vs sync "
        f"{m['sync_round_ms']:.0f} ms "
        f"(speedup {m['round_wall_speedup']:.2f}x; ceiling is "
        f"compute-bound — the hidden fraction is the invariant)"
    )


RESNET_PARTIES = ("alice", "bob", "carol", "dave")
RESNET_CLUSTER = {
    p: {"address": f"127.0.0.1:{13060 + i}"} for i, p in enumerate(RESNET_PARTIES)
}


RESNET_N_PER_PARTY, RESNET_HW = 32, 32  # CIFAR-10-shaped shard per party
RESNET_ROUNDS = 3


def _resnet_party_data(cfg, seed: int, batch: int = RESNET_N_PER_PARTY):
    """Synthetic CIFAR-shaped shard — ONE recipe for the fedavg trainer,
    the in-process contention floor, and the DP control (at its larger
    batch), so the controls provably run the identical program."""
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(
        jax.random.PRNGKey(seed), (batch, RESNET_HW, RESNET_HW, 3)
    )
    probe = jax.random.normal(jax.random.PRNGKey(0), (3, cfg.num_classes))
    y = jnp.argmax(jnp.mean(x, axis=(1, 2)) @ probe, axis=-1)
    return x, y


def _run_resnet_party(party: str, result_q, barrier=None) -> None:
    """BASELINE.md #3: 4-party ResNet-18 FedAvg over the real transport.

    Coordinator-mode aggregation (auto at N=4), **pipelined rounds**:
    ``aggregate(..., materialize=False)`` returns the averaged model as a
    FedObject that feeds the next round's ``train.remote`` directly — no
    per-round ``fed.get`` barrier, so the coordinator's average/broadcast
    overlaps the workers' training and the wire rides under compute.
    Party compute stays on the host CPU (same placement policy as the
    other federated configs); records rounds/s and cross-party GB/s.
    """
    import logging

    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import aggregate
    from rayfed_tpu.models import resnet

    logging.disable(logging.WARNING)
    fed.init(address="local", cluster=RESNET_CLUSTER, party=party)

    cfg = resnet.resnet18(num_classes=10)
    phases: dict = {}

    # Same trainer shape as tests/test_fl_resnet.py (full ResNet-18 and
    # one local step here; tiny config there) — change them together.
    # Wire compression: contributions and the averaged model travel as
    # bf16 (fl.compression); the whole local round (wire→f32 cast, fresh
    # momentum, SGD step, f32→wire cast) is ONE jitted call
    # (make_fed_train_step) so XLA fuses the casts instead of the party
    # paying separate decompress/compress passes per round.
    # ONE jit instance shared by the trainer actor and the in-process
    # floor: same compiled program, and only one ResNet-18 XLA compile
    # per party process.
    fed_step = resnet.make_fed_train_step(cfg, lr=0.05)

    @fed.remote
    class Trainer:
        def __init__(self, seed: int):
            self._x, self._y = _resnet_party_data(cfg, seed)
            self._step = fed_step

        def train(self, bundle):
            t0 = time.perf_counter()
            out, loss = self._step(bundle, self._x, self._y)
            jax.block_until_ready(loss)
            phases["step_s"] = phases.get("step_s", 0.0) + time.perf_counter() - t0
            return out

    from rayfed_tpu.fl import compress

    trainers = {
        p: Trainer.party(p).remote(i + 1) for i, p in enumerate(RESNET_PARTIES)
    }
    # Packed wire form: the whole model crosses parties as ONE bf16
    # buffer (fused cast+concat) instead of ~60 per-leaf buffers; the
    # fed step unpacks/repacks inside its jit, and the coordinator's
    # average fuses over the single buffer.
    bundle = compress(
        resnet.init_resnet(jax.random.PRNGKey(0), cfg), packed=True
    )
    bundle_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(bundle)
    )

    def do_round(bundle_or_obj):
        return aggregate(
            [trainers[p].train.remote(bundle_or_obj) for p in RESNET_PARTIES],
            materialize=False,
        )

    # Warmup: one materialized round (compiles + first full exchange).
    bundle = fed.get(do_round(bundle))
    jax.block_until_ready(jax.tree_util.tree_leaves(bundle)[0])

    from rayfed_tpu import metrics
    from rayfed_tpu.runtime import get_runtime_or_none

    def _drain_sends():
        # Barrier on in-flight sends (peers' fed.get triggers pushes on
        # transport threads): without it the warmup's broadcast could
        # land inside the decomposition window — and the final round's
        # trailing pushes outside it.  The watchdog restarts on the next
        # tracked send.
        rt = get_runtime_or_none()
        cm = rt.cleanup_manager if rt is not None else None
        if cm is not None:
            cm.wait_sending()

    _drain_sends()
    phases.clear()
    rounds = RESNET_ROUNDS

    # Contention floor, measured IN the same four processes bracketing
    # the fedavg window (one leg before, one after, averaged): each
    # party runs its bare local round — the identical jitted fed-step,
    # NO transport/aggregation — mp-Barrier-synced per round so all four
    # windows truly overlap.  In-process + bracketing because the shared
    # bench host speeds up over a section's lifetime (~10-20% "later
    # runs faster" order effect) and drifts ±15% between separately
    # spawned sections; r4's separately-spawned, unsynced floor read
    # ~25% too fast and mis-billed the difference to the framework.
    # The per-round barrier is not a bias: the fedavg DAG itself syncs
    # all parties once per round (every party's round k+1 train consumes
    # the aggregate of ALL round-k trains, pipelined or not), so the
    # floor mirrors the treatment's per-round all-party dependency.
    def floor_leg(seed_bundle, floor_step, x_loc, y_loc):
        # Bounded waits: a crashed sibling must break the barrier (and
        # this child, which _multi_party detects) rather than stall the
        # survivors until the harness's 900s timeout.
        barrier.wait(timeout=300)
        fcpu0, ft0 = _cpu_seconds(), time.perf_counter()
        fb = seed_bundle
        for _ in range(rounds):
            fb, floss = floor_step(fb, x_loc, y_loc)
            jax.block_until_ready(floss)
            barrier.wait(timeout=300)
        return rounds / (time.perf_counter() - ft0), (_cpu_seconds() - fcpu0) / rounds

    floor_rps = floor_cpu = float("nan")
    if barrier is not None:
        x_loc, y_loc = _resnet_party_data(cfg, RESNET_PARTIES.index(party) + 1)
        floor_step = fed_step  # already compiled by the warmup round
        _fb, _fl = floor_step(bundle, x_loc, y_loc)  # warm cache hit
        jax.block_until_ready(_fl)
        floor_pre = floor_leg(bundle, floor_step, x_loc, y_loc)

    total0 = metrics.get_transfer_log().total_recorded
    cpu0 = _cpu_seconds()
    t0 = time.perf_counter()
    obj = do_round(bundle)
    for _ in range(rounds - 1):
        obj = do_round(obj)  # lazy: rounds pipeline through the DAG
    bundle = fed.get(obj)
    jax.block_until_ready(jax.tree_util.tree_leaves(bundle)[0])
    elapsed = time.perf_counter() - t0
    cpu_s = _cpu_seconds() - cpu0
    _drain_sends()

    if barrier is not None:
        floor_post = floor_leg(bundle, floor_step, x_loc, y_loc)
        floor_rps = 2.0 / (1.0 / floor_pre[0] + 1.0 / floor_post[0])
        floor_cpu = (floor_pre[1] + floor_post[1]) / 2.0

    # Wire-decompress probe: eager decompression of the round's actual
    # wire bundle, packed fast path (one fused cast + zero-copy views)
    # vs the per-leaf tree_map path (one astype dispatch per leaf) —
    # min-of-reps wall ms.  This is what a consumer pays on fed.get of
    # a compressed model OUTSIDE a fused train step.
    from rayfed_tpu.fl import compression as _comp

    def _probe(fn, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(
                [l for l in jax.tree_util.tree_leaves(out)
                 if isinstance(l, jax.Array)]
            )
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    decomp_packed_ms = _probe(lambda: _comp.decompress(bundle, jnp.float32))
    leaf_tree = _comp.unpack_tree(bundle)  # per-leaf bf16 wire form
    decomp_perleaf_ms = _probe(
        lambda: _comp.cast_floats(leaf_tree, jnp.float32)
    )

    # Per-round decomposition, this party's view: the jitted local round
    # (train step incl. fused wire casts), wire read/send sessions, and
    # this process's total CPU seconds.  On the 1-core bench host the
    # round is CPU-bound, so step + (cpu - step) + idle ≈ 100% of wall —
    # the r4 gap ("5s invisible") was contended *wall* inflation of the
    # step, not hidden framework work (see the floor control below).
    recs, complete = metrics.get_transfer_log().records_since(total0)
    if complete:
        read_ms = sum(r.seconds for r in recs if r.direction == "recv") / rounds * 1e3
        send_ms = sum(r.seconds for r in recs if r.direction == "send") / rounds * 1e3
    else:  # ring evicted part of the window
        read_ms = send_ms = float("nan")

    # Coordinator topology: (N-1) contributions in + (N-1) results out.
    wire_bytes = 2 * (len(RESNET_PARTIES) - 1) * bundle_bytes * rounds
    if result_q is not None:
        result_q.put(
            (
                party,
                (
                    rounds / elapsed,
                    wire_bytes / elapsed / 1e9,
                    read_ms,
                    send_ms,
                    phases.get("step_s", 0.0) / rounds * 1e3,  # step ms
                    cpu_s / rounds,  # this party's CPU seconds per round
                    elapsed / rounds,  # wall seconds per round
                    floor_rps,
                    floor_cpu,
                    decomp_packed_ms,
                    decomp_perleaf_ms,
                ),
            )
        )
    fed.shutdown()


def _resnet_solo_rounds_per_sec(batch: int, seed: int):
    """The DP control's body: the same ResNet-18 + synthetic data at
    ``batch``, compile, slope-time RESNET_ROUNDS steps.  (The contention
    floor is measured inside the fedavg party processes themselves — see
    _run_resnet_party — so the fedavg/floor ratio can't be skewed by
    host-speed drift between separately-spawned sections.)

    Returns (rounds_per_sec, cpu_seconds_per_round).
    """
    import jax

    from rayfed_tpu.models import resnet

    cfg = resnet.resnet18(num_classes=10)
    x, y = _resnet_party_data(cfg, seed, batch=batch)
    params, state = resnet.init_resnet(jax.random.PRNGKey(0), cfg)
    opt = resnet.init_opt_state(params)
    step = resnet.make_train_step(cfg, lr=0.05)
    params, state, opt, loss = step(params, state, opt, x, y)  # compile
    jax.block_until_ready(loss)

    rounds = RESNET_ROUNDS
    cpu0 = _cpu_seconds()
    t0 = time.perf_counter()
    for _ in range(rounds):
        params, state, opt, loss = step(params, state, opt, x, y)
    jax.block_until_ready(loss)
    elapsed = time.perf_counter() - t0
    return rounds / elapsed, (_cpu_seconds() - cpu0) / rounds


def _run_resnet_dp_control(_party: str, result_q) -> None:
    """North-star denominator: single-process data-parallel control.

    Same ResNet-18, same TOTAL batch (4 x 32), one jitted train step —
    the strongest centralized baseline on the same host.  BASELINE.json
    config #3's target is fedavg >= 90%% of this in rounds/s.
    """
    batch = RESNET_N_PER_PARTY * len(RESNET_PARTIES)
    rps, cpu = _resnet_solo_rounds_per_sec(batch, 0)
    result_q.put(("dp", (rps, cpu)))


def _run_lora_party(party: str, result_q) -> None:
    """BASELINE.md #4: 2-party cross-silo Llama-LoRA federated fine-tune.

    Parties train adapters on a frozen base locally and FedAvg the
    adapters each round (all-to-all at N=2: 2 pushes/round).  Records
    rounds/s and the adapter payload per push (2x that crosses the wire
    each round).  Same trainer shape as tests/test_fl_lora.py (bigger
    model here) — change them together.
    """
    import logging

    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu.fl import aggregate
    from rayfed_tpu.models import llama, lora

    logging.disable(logging.WARNING)
    fed.init(address="local", cluster=CLUSTER, party=party)

    cfg = llama.LlamaConfig(
        vocab_size=2048,
        hidden_size=256,
        num_layers=4,
        num_heads=8,
        num_kv_heads=4,
        intermediate_size=1024,
        max_seq_len=256,
        dtype=jnp.float32,
    )
    lcfg = lora.LoraConfig(rank=8, targets=(r"w[qv]$", r"lm_head$"))
    seq, batch = 128, 4

    @fed.remote
    class Tuner:
        def __init__(self, seed: int):
            self._base = llama.init_llama(jax.random.PRNGKey(42), cfg)
            self._ids = jax.random.randint(
                jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size
            )
            self._step = llama.make_lora_train_step(cfg, lr=1e-3)

        def train(self, adapters):
            opt = llama.init_adam(adapters)
            adapters, opt, loss = self._step(adapters, opt, self._base, self._ids)
            jax.block_until_ready(loss)
            return adapters

    tuners = {p: Tuner.party(p).remote(i + 10) for i, p in enumerate(("alice", "bob"))}
    base = llama.init_llama(jax.random.PRNGKey(42), cfg)
    adapters = lora.init_lora(jax.random.PRNGKey(7), base, lcfg)
    adapter_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(adapters)
    )

    def do_round(adapters):
        return aggregate([tuners[p].train.remote(adapters) for p in ("alice", "bob")])

    adapters = do_round(adapters)  # warmup: compiles + first exchange
    jax.block_until_ready(jax.tree_util.tree_leaves(adapters)[0])

    rounds = 5
    t0 = time.perf_counter()
    for _ in range(rounds):
        adapters = do_round(adapters)
    jax.block_until_ready(jax.tree_util.tree_leaves(adapters)[0])
    elapsed = time.perf_counter() - t0

    if result_q is not None:
        result_q.put((party, (rounds / elapsed, adapter_bytes / 1e6)))
    fed.shutdown()


def _party_child(
    fn_name: str, party: str, result_q, ndev: int = 8, barrier=None
) -> None:
    """Spawn-process entry: pin JAX to a virtual CPU mesh before backend init.

    Every spawned bench child goes through here and is therefore
    CPU-only.  That is a constraint, not a default: the parent has
    imported JAX and may hold the chip, and a chip belongs to one
    process at a time — do not spawn anything that needs the chip from
    this file (chip work runs in one process: ``chip_smoke.py``).

    ``ndev``: virtual device count.  Configs that never shard use 1 —
    on the 1-core bench host each extra virtual device adds XLA client
    overhead per party (~35%% of the 4-party ResNet round at ndev=8).
    ``barrier``: optional multiprocessing Barrier handed to benchmark fns
    that accept one (control configs that must contend *concurrently*).
    """
    from rayfed_tpu.utils import force_cpu_devices, use_compilation_cache

    force_cpu_devices(ndev)
    use_compilation_cache()
    if barrier is not None:
        globals()[fn_name](party, result_q, barrier)
    else:
        globals()[fn_name](party, result_q)


def _cpu_seconds() -> float:
    """This process's consumed CPU time (user+sys) — saturation accounting."""
    import resource

    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def _one_child(fn_name: str, ndev: int = 8, timeout: int = 300) -> float:
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    proc = ctx.Process(target=_party_child, args=(fn_name, "solo", q, ndev))
    proc.start()
    try:
        _name, value = q.get(timeout=timeout)
    finally:
        proc.join(30)
        if proc.is_alive():
            proc.terminate()
    return value


def _multi_party(
    fn_name: str, parties=("alice", "bob"), timeout=900, ndev=8,
    use_barrier=False,
) -> dict:
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    barrier = ctx.Barrier(len(parties)) if use_barrier else None
    procs = [
        ctx.Process(target=_party_child, args=(fn_name, p, q, ndev, barrier))
        for p in parties
    ]
    for p in procs:
        p.start()
    results = {}
    deadline = time.time() + timeout
    while len(results) < len(parties) and time.time() < deadline:
        try:
            party, value = q.get(timeout=5)
            results[party] = value
        except Exception:
            # Fail fast: a crashed child (nonzero exit) or all children
            # gone with results still missing means no full set is coming.
            if any(p.exitcode not in (None, 0) for p in procs):
                break
            if all(p.exitcode is not None for p in procs) and q.empty():
                break
    for p in procs:
        p.join(30)
        if p.is_alive():
            p.terminate()
    if len(results) < len(parties):
        raise RuntimeError(f"benchmark failed; partial results: {results}")
    return results


def _two_party(fn_name: str) -> float:
    results = _multi_party(fn_name)
    return sum(results.values()) / len(results)


# --------------------------------------------------------------------------
# Accelerator compute configs (real chip, device-resident data)
# --------------------------------------------------------------------------

# Peak dense bf16 FLOP/s by device kind (for MFU).  A device that is not
# in the table is an error, not a default.
_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e
}

# Peak HBM bandwidth (bytes/s) by device kind — the decode roofline
# denominator: a KV-cached decode step is memory-bound (reads every
# param + the cache once per token).
_PEAK_HBM_BPS = {
    "TPU v5 lite": 819e9,  # v5e
    "TPU v5e": 819e9,
    "TPU v4": 1228e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,  # v6e
}


def _peak_lookup(table: dict) -> float:
    kind = jax.devices()[0].device_kind
    for name, peak in table.items():
        if name.lower() in kind.lower():
            return peak
    raise RuntimeError(
        f"device_kind {kind!r} is not in the peaks table "
        f"({sorted(table)}): a utilization against a made-up peak is not "
        f"a measurement — add the device's published peak with its source"
    )


def _peak_flops() -> float:
    return _peak_lookup(_PEAK_FLOPS)


def _peak_hbm_bps() -> float:
    return _peak_lookup(_PEAK_HBM_BPS)


def bench_llama() -> dict:
    """Full-param Adam training of a ~1.07B Llama, bf16 + flash attention.

    All N steps run inside ONE compiled program (``lax.scan``) and the
    per-step time is the **slope** between a short and a long run, which
    cancels the per-dispatch constant; each run ends in a ``device_get``
    of the final loss.  (ROADMAP Queue 3 item 6 replaces this with
    ``block_until_ready`` windows in the benchmark PR.)

    bf16 params + first moment (second moment f32, arithmetic f32 inside
    the update) and scan-layer remat are what fit 1B params of
    model+optimizer state on one 16 GB v5e chip.
    """
    import jax.numpy as jnp

    from rayfed_tpu.models import llama
    from rayfed_tpu.ops.flash_attention import flash_attention

    cfg = llama.LlamaConfig(
        vocab_size=16384,
        hidden_size=2048,
        num_layers=16,
        num_heads=16,
        num_kv_heads=8,
        intermediate_size=8192,
        max_seq_len=2048,
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        remat=True,
        # Selective remat: keep non-batch matmul outputs resident.
        # On-chip shape/policy sweep (4096 tokens/step each, scored by
        # THIS bench's attention-aware MFU): b=2 s=2048 "dots" = 0.572
        # vs b=1 s=4096 "dots" 0.547, b=4 s=2048 full-remat 0.540;
        # b=2 s=2048 no-remat and b=1 s=8192 exceed HBM.
        remat_policy="dots",
    )
    batch, seq = 2, 2048
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size)

    def timed_run(n_steps: int) -> float:
        params = llama.init_llama(jax.random.PRNGKey(0), cfg)
        opt = llama.init_adam(params)
        loop = llama.make_train_loop(cfg, n_steps, attn_fn=flash_attention)
        params, opt, losses = loop(params, opt, ids)  # compile + warm
        float(jax.device_get(losses[-1]))
        params = llama.init_llama(jax.random.PRNGKey(0), cfg)
        opt = llama.init_adam(params)
        _ = float(jax.device_get(jnp.zeros(())))  # drain queue
        t0 = time.perf_counter()
        params, opt, losses = loop(params, opt, ids)
        final = float(jax.device_get(losses[-1]))
        assert final == final, "loss is NaN"
        return time.perf_counter() - t0

    _log("  compiling llama train loops (short+long)...")
    n_short, n_long = 2, 12
    t_short = timed_run(n_short)
    t_long = timed_run(n_long)
    step_time = max((t_long - t_short) / (n_long - n_short), 1e-9)

    tokens = batch * seq
    tokens_per_sec = tokens / step_time
    # Model FLOPs: 6 * matmul-params * tokens (fwd 2NT + bwd 4NT; the
    # embedding gather does no matmul FLOPs, lm_head does) plus causal
    # attention 6 * L*B*T^2*d (12*L*B*T^2*d full, halved for causal).
    # eval_shape counts without allocating another ~1GB model.
    abstract = jax.eval_shape(
        lambda: llama.init_llama(jax.random.PRNGKey(0), cfg)
    )
    n_matmul = llama.param_count(abstract, exclude_embed=True)
    flops_per_step = (
        6 * n_matmul * tokens
        + 6 * cfg.num_layers * batch * seq**2 * cfg.hidden_size
    )
    mfu = flops_per_step / step_time / _peak_flops()
    out = {
        "llama_tokens_per_sec": round(tokens_per_sec, 1),
        "llama_mfu": round(mfu, 4),
        "llama_params_millions": round(llama.param_count(abstract) / 1e6, 1),
        "llama_step_ms": round(step_time * 1e3, 2),
    }
    try:
        out.update(_llama_mfu_breakdown(cfg, batch, seq, step_time))
    except Exception as e:  # pragma: no cover - smaller devices
        _log(f"  mfu breakdown skipped: {e!r}")
    return out


def _llama_mfu_breakdown(cfg, batch, seq, step_time) -> dict:
    """Where the train step's time goes — the MFU ceiling memo.

    Each component is probed as its own scanned jitted program at the
    EXACT bench shapes (same slope methodology as the step itself) and
    scaled by layer count: the flash-attention core (fwd+bwd), the
    layer matmuls (qkv/o projections + SwiGLU FFN, fwd+bwd), the
    lm_head (fwd+bwd), the full-tree Adam update, the norms + RoPE
    elementwise (fwd+bwd), and the remat recompute (one full extra
    layer FORWARD per layer — under ``remat_policy="dots"`` the
    backward replays the whole layer forward, since every activation
    dot has batch dims and is therefore not saved).  The residual
    ``llama_other_ms`` (step − sum) is scan plumbing + embed/final-norm
    + dispatch gaps — the r05 verdict flagged the then-unattributed
    63.8 ms (27% of the step) as a blind spot; the two named spans
    above are that attribution.  Single chip, so no collectives line.
    The probes are a shape model, not a trace: components measured in
    isolation can overlap differently inside the fused step — good to
    ~10%, which is enough to tell "attention is the ceiling" from "the
    optimizer eats 15%".
    """
    import jax.numpy as jnp

    from rayfed_tpu.ops.flash_attention import flash_attention

    B, T, D, L = batch, seq, cfg.hidden_size, cfg.num_layers
    H, Dh, F, V = cfg.num_heads, cfg.head_dim, cfg.intermediate_size, cfg.vocab_size
    dt = cfg.dtype
    key = jax.random.PRNGKey(7)

    def slope(build, make_init, n_short=2, n_long=8):
        """Per-iteration seconds of ``body = build()`` via scan slope.

        ``make_init()`` produces a FRESH carry per loop call: the carry
        is donated (the Adam probe's 8.5 GB params+moments would
        otherwise need input+output copies resident at once).
        """
        body = build()

        def run(n):
            @functools.partial(jax.jit, donate_argnums=0)
            def loop(c):
                return jax.lax.scan(lambda c, _: (body(c), None), c, length=n)[0]

            def once():
                c = loop(make_init())
                return float(
                    jax.device_get(
                        jnp.sum(
                            jax.tree_util.tree_leaves(c)[0].astype(jnp.float32)
                        )
                    )
                )

            once()  # compile + warm
            t0 = time.perf_counter()
            once()
            return time.perf_counter() - t0

        t_s = run(n_short)
        t_l = run(n_long)
        return max((t_l - t_s) / (n_long - n_short), 0.0)

    # 1. Flash-attention core, one layer (fwd+bwd via grad), x L.
    k_attn = jax.random.normal(key, (B, T, H, Dh), dt) * 0.02
    v_attn = jax.random.normal(key, (B, T, H, Dh), dt) * 0.02
    mk_attn = jax.jit(lambda: jax.random.normal(key, (B, T, H, Dh), dt) * 0.02)

    def build_attn():
        def body(q):
            # Differentiate wrt q AND k/v: the real step computes all
            # three cotangents in the attention backward.
            gq, gk, gv = jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=True).astype(jnp.float32)
                    ** 2
                ),
                argnums=(0, 1, 2),
            )(q, k_attn, v_attn)
            # Fold the k/v cotangents into the carry so XLA cannot
            # dead-code-eliminate their computation.
            pert = (jnp.sum(gk.astype(jnp.float32)) + jnp.sum(gv.astype(jnp.float32))) * 1e-20
            return (gq + pert.astype(gq.dtype)).astype(dt)

        return body

    attn_s = slope(build_attn, mk_attn, n_short=8, n_long=2048) * L

    # 2. Layer matmuls: qkv + o projections and the SwiGLU FFN, x L.
    kv_dim = cfg.num_kv_heads * Dh
    w = {
        "wq": jax.random.normal(key, (D, H * Dh), dt) * 0.02,
        "wk": jax.random.normal(key, (D, kv_dim), dt) * 0.02,
        "wv": jax.random.normal(key, (D, kv_dim), dt) * 0.02,
        "wo": jax.random.normal(key, (H * Dh, D), dt) * 0.02,
        "w1": jax.random.normal(key, (D, F), dt) * 0.02,
        "w3": jax.random.normal(key, (D, F), dt) * 0.02,
        "w2": jax.random.normal(key, (F, D), dt) * 0.02,
    }
    mk_x = jax.jit(lambda: jax.random.normal(key, (B, T, D), dt) * 0.02)

    def build_matmuls():
        def fwd(x, w):
            q = x @ w["wq"]
            k = x @ w["wk"]
            v = x @ w["wv"]
            o = q @ w["wo"]
            mlp = (jax.nn.silu(x @ w["w1"]) * (x @ w["w3"])) @ w["w2"]
            # Quadratic loss: a LINEAR sum's gradient needs no forward
            # (d sum(xW)/dx = 1 @ W.T) and XLA dead-code-eliminates the
            # probe; sum(out^2) keeps fwd AND bwd live.
            return (
                jnp.sum(o.astype(jnp.float32) ** 2)
                + jnp.sum(mlp.astype(jnp.float32) ** 2)
                + jnp.sum(k.astype(jnp.float32) ** 2)
                + jnp.sum(v.astype(jnp.float32) ** 2)
            )

        def body(x):
            # dL/dx AND dL/dW — training backward computes both (the
            # dW half is the same FLOPs again).
            gx, gw = jax.grad(fwd, argnums=(0, 1))(x, w)
            pert = sum(
                jnp.sum(g.astype(jnp.float32))
                for g in jax.tree_util.tree_leaves(gw)
            ) * 1e-20
            return (gx + pert.astype(gx.dtype)).astype(dt)

        return body

    matmul_s = slope(build_matmuls, mk_x, n_short=4, n_long=256) * L

    # 3. lm_head (fwd+bwd).
    w_head = jax.random.normal(key, (D, V), dt) * 0.02

    def build_head():
        def body(x):
            gx, gw = jax.grad(
                lambda x, wh: jnp.sum((x @ wh).astype(jnp.float32) ** 2),
                argnums=(0, 1),
            )(x, w_head)
            pert = jnp.sum(gw.astype(jnp.float32)) * 1e-20
            return (gx + pert.astype(gx.dtype)).astype(dt)

        return body

    head_s = slope(build_head, mk_x, n_short=4, n_long=512)

    # 4. Full-tree Adam update (elementwise over params + both moments).
    from rayfed_tpu.models import llama as _llama

    def mk_adam():
        params = _llama.init_llama(jax.random.PRNGKey(0), cfg)
        return params, _llama.init_adam(params)

    def build_adam():
        def body(c):
            p, o = c
            p2, o2 = _llama._adam_update(p, p, o, 1e-4, 0.9, 0.999, 1e-8)
            return (p2, o2)

        return body

    adam_s = slope(build_adam, mk_adam, n_short=4, n_long=48)

    # 5. Norms + RoPE elementwise (fwd+bwd), x L — the named span for
    # part of what r05 lumped into "other".
    g_norm1 = jnp.ones((D,), dt)
    g_norm2 = jnp.ones((D,), dt)
    cos_t, sin_t = _llama.rope_tables(
        jnp.arange(T), Dh, cfg.rope_theta
    )
    KV = cfg.num_kv_heads

    def build_norms_rope():
        def fwd(x):
            a = _llama._rms_norm(x, g_norm1, cfg.rms_eps)
            b2 = _llama._rms_norm(x, g_norm2, cfg.rms_eps)
            q = _llama.apply_rope(
                x.reshape(B, T, H, Dh), cos_t, sin_t
            )
            k = _llama.apply_rope(
                x[..., : KV * Dh].reshape(B, T, KV, Dh), cos_t, sin_t
            )
            return (
                jnp.sum(a.astype(jnp.float32) ** 2)
                + jnp.sum(b2.astype(jnp.float32) ** 2)
                + jnp.sum(q.astype(jnp.float32) ** 2)
                + jnp.sum(k.astype(jnp.float32) ** 2)
            )

        def body(x):
            return jax.grad(fwd)(x).astype(dt)

        return body

    norms_s = slope(build_norms_rope, mk_x, n_short=4, n_long=256) * L

    # 6. Remat recompute: ONE extra full-layer forward per layer — the
    # price of fitting 1B params + Adam in HBM.  Probed as the real
    # layer forward (llama._layer_fwd: norm→qkv→RoPE→GQA flash→out→
    # MLP) at the bench shapes; under the "dots" policy every
    # activation dot has batch dims and is recomputed in the backward.
    lp_probe = {
        "attn_norm": jnp.ones((D,), dt),
        "mlp_norm": jnp.ones((D,), dt),
        "wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
        "w_gate": w["w1"], "w_up": w["w3"], "w_down": w["w2"],
    }

    def build_layer_fwd():
        def body(x):
            out, _kv = _llama._layer_fwd(
                x, lp_probe, cfg, cos_t, sin_t, flash_attention, B, T
            )
            return out.astype(dt)

        return body

    remat_s = (
        slope(build_layer_fwd, mk_x, n_short=4, n_long=64) * L
        if cfg.remat
        else 0.0
    )

    # Probes are isolation measurements (~10% error, no overlap
    # credit) — a small overshoot past the step time clamps to 0.
    other_s = max(
        step_time - attn_s - matmul_s - head_s - adam_s - norms_s
        - remat_s,
        0.0,
    )
    _log(
        "  mfu breakdown (shape-model probes, per step):\n"
        f"    attention core (flash, fwd+bwd) {attn_s*1e3:7.1f} ms ({attn_s/step_time:5.1%})\n"
        f"    layer matmuls (qkv/o + ffn)     {matmul_s*1e3:7.1f} ms ({matmul_s/step_time:5.1%})\n"
        f"    lm_head                         {head_s*1e3:7.1f} ms ({head_s/step_time:5.1%})\n"
        f"    adam update                     {adam_s*1e3:7.1f} ms ({adam_s/step_time:5.1%})\n"
        f"    norms + rope (fwd+bwd)          {norms_s*1e3:7.1f} ms ({norms_s/step_time:5.1%})\n"
        f"    remat recompute (layer fwd x L) {remat_s*1e3:7.1f} ms ({remat_s/step_time:5.1%})\n"
        f"    other (scan plumbing, embeds,   {other_s*1e3:7.1f} ms ({other_s/step_time:5.1%})\n"
        f"      dispatch gaps)"
    )
    # Per-layer counted matmul FLOPs at nominal peak — the yardstick
    # for whether the measured per-layer time is a kernel gap.
    layer_matmul_flops = 6 * (
        D * H * Dh + 2 * D * kv_dim + H * Dh * D + 3 * D * F
    ) * B * T
    layer_peak_ms = layer_matmul_flops / _peak_flops() * 1e3
    _log(
        f"  ceiling memo: layer matmuls measure {matmul_s/L*1e3:.1f} "
        f"ms/layer vs {layer_peak_ms:.1f} ms of counted FLOPs at nominal "
        f"peak ({layer_peak_ms/(matmul_s/L*1e3):.0%} of peak), so the MFU "
        f"number is structural, not a kernel gap: the MFU numerator "
        f"counts only model FLOPs while "
        f"{(remat_s + norms_s)/step_time:.0%} of the step is remat "
        f"recompute + norm/rope elementwise ('dots' remat is the "
        f"price of fitting 1B params + Adam on one 16 GB chip) and "
        f"{adam_s/step_time:.0%} is the memory-bound Adam update.  "
        f"Raising MFU here means spending HBM on less remat, not faster "
        f"kernels."
    )
    return {
        "llama_attn_ms": round(attn_s * 1e3, 1),
        "llama_matmul_ms": round(matmul_s * 1e3, 1),
        "llama_head_ms": round(head_s * 1e3, 1),
        "llama_adam_ms": round(adam_s * 1e3, 1),
        "llama_norms_rope_ms": round(norms_s * 1e3, 1),
        "llama_remat_ms": round(remat_s * 1e3, 1),
        "llama_other_ms": round(other_s * 1e3, 1),
    }


def _decode_slope(cfg, params, prompt, n_short, n_long, attn_fn, reps=3):
    """Steady-state decode seconds/token by slope between two generation
    lengths (same prompt/prefill work in both → the delta is pure
    decode), median-of-``reps``.  Returns ``(per_tok, eff_len)``.

    ``eff_len``: the decode step streams the FULL padded cache buffer
    (t0 + n_new) every step — validity is a mask, not a dynamic extent —
    so the slope's effective per-token cache traffic is the difference
    of the two runs' total cache reads, not the mean live length.
    """
    import jax.numpy as jnp

    from rayfed_tpu.models import llama

    t0 = prompt.shape[1]

    def timed(n_new):
        g = jax.jit(
            lambda p, pr: llama.greedy_generate(
                p, cfg, pr, n_new, attn_fn=attn_fn
            )
        )
        out = g(params, prompt)
        jax.block_until_ready(out)
        vals = []
        for _ in range(reps):
            t = time.perf_counter()
            out = g(params, prompt)
            float(jax.device_get(jnp.sum(out)))
            vals.append(time.perf_counter() - t)
        return sorted(vals)[len(vals) // 2]

    per_tok = max(
        (timed(n_long) - timed(n_short)) / (n_long - n_short), 1e-9
    )
    eff_len = (
        n_long * (t0 + n_long) - n_short * (t0 + n_short)
    ) / (n_long - n_short)
    return per_tok, eff_len


def _kv_cache_bytes(cfg, batch, eff_len):
    """HBM bytes of live KV cache streamed per decode step.

    Derived from ``cfg.kv_quant``: bf16 is 2 bytes/element; int8 is
    1 byte plus the f32 per-(position, head) scale amortized over the
    head dim.
    """
    per_elem = (1 + 4 / cfg.head_dim) if cfg.kv_quant else 2
    return int(
        2 * cfg.num_layers * batch * eff_len
        * cfg.num_kv_heads * cfg.head_dim * per_elem
    )


def bench_lora_8b() -> dict:
    """BASELINE.md #4 at literal scale: Llama-3-8B LoRA on one chip.

    int8 frozen base (per-channel scales, dequant fused into the MXU
    matmuls) + bf16/f32 LoRA adapters + Adam — ~9 GB of weights on a
    16 GB v5e.  The base is initialized DIRECTLY as int8 on device
    (``init_llama_int8``): no 16 GB bf16 intermediate and no host→device
    copy of the base.  Slope-timed like the other compute
    benches.  The federated adapter exchange is covered by the 2-party
    LoRA config; this records the per-party step at the honest scale.
    """
    import jax.numpy as jnp

    from rayfed_tpu.models import llama, lora
    from rayfed_tpu.ops.flash_attention import flash_attention

    cfg = llama.llama3_8b(
        max_seq_len=2048,
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
        remat=True,
    )
    batch, seq = 1, 2048
    base = jax.jit(lambda k: llama.init_llama_int8(k, cfg))(jax.random.PRNGKey(0))
    jax.block_until_ready(jax.tree_util.tree_leaves(base)[0])
    lcfg = lora.LoraConfig(rank=16, targets=(r"w[qv]$",))
    adapters0 = lora.init_lora(jax.random.PRNGKey(1), base, lcfg)
    adapter_mb = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(adapters0)
    ) / 1e6
    ids = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0, cfg.vocab_size)

    def timed_run(n_steps: int) -> float:
        # Fresh adapters per run: the loop DONATES its adapter/opt args,
        # so a prior run's inputs are dead buffers.
        adapters = lora.init_lora(jax.random.PRNGKey(1), base, lcfg)
        opt = llama.init_adam(adapters)
        loop = llama.make_lora_train_loop(
            cfg, n_steps, attn_fn=flash_attention
        )
        adapters, opt, losses = loop(adapters, opt, base, ids)  # compile
        float(jax.device_get(losses[-1]))
        adapters = lora.init_lora(jax.random.PRNGKey(1), base, lcfg)
        opt = llama.init_adam(adapters)
        _ = float(jax.device_get(jnp.zeros(())))  # drain queue
        t0 = time.perf_counter()
        adapters, opt, losses = loop(adapters, opt, base, ids)
        final = float(jax.device_get(losses[-1]))
        assert final == final, "loss is NaN"
        return time.perf_counter() - t0

    _log("  compiling 8B int8-base LoRA train loops (short+long)...")
    n_short, n_long = 1, 5
    t_short = timed_run(n_short)
    t_long = timed_run(n_long)
    step_time = max((t_long - t_short) / (n_long - n_short), 1e-9)

    from rayfed_tpu.models.quant import tree_nbytes

    abstract = jax.eval_shape(lambda: llama.init_llama(jax.random.PRNGKey(0), cfg))
    n_params = llama.param_count(abstract)

    out = {
        "lora_8b_tokens_per_sec": round(batch * seq / step_time, 1),
        "lora_8b_step_ms": round(step_time * 1e3, 2),
        "lora_8b_params_b": round(n_params / 1e9, 2),
        "lora_8b_base_gb": round(tree_nbytes(base) / 1e9, 2),
        "lora_8b_adapter_mb": round(adapter_mb, 2),
    }

    # 8B int8 serving on the same chip: KV-cache greedy decode over the
    # already-resident base (the decode step streams ~8.6 GB of weights
    # + the live cache per token — the serving-side complement of the
    # train number above).  A decode failure must not discard the train
    # numbers already measured.
    try:
        _log("  compiling 8B int8 decode generations (short+long)...")
        dbatch = 4
        prompt = jax.random.randint(
            jax.random.PRNGKey(3), (dbatch, 128), 0, cfg.vocab_size
        )
        per_tok, eff_len = _decode_slope(
            cfg, base, prompt, 16, 272, flash_attention
        )
        membw_util = (
            (tree_nbytes(base) + _kv_cache_bytes(cfg, dbatch, eff_len))
            / per_tok
            / _peak_hbm_bps()
        )
        out.update(
            decode_8b_tokens_per_sec=round(dbatch / per_tok, 1),
            decode_8b_step_ms=round(per_tok * 1e3, 2),
            decode_8b_membw_util=round(membw_util, 4),
        )
    except Exception as e:  # pragma: no cover - chip-memory dependent
        _log(f"  8B decode skipped: {e!r}")
        out["decode_8b_error"] = repr(e)[:200]
    return out


def bench_decode() -> dict:
    """KV-cache greedy decoding throughput on the 1B bench model.

    Slope between a short and a long generation (same prompt/prefill
    work in both → the delta is pure steady-state decode), median-of-3.
    """
    import jax.numpy as jnp

    from rayfed_tpu.models import llama
    from rayfed_tpu.ops.flash_attention import flash_attention

    cfg = llama.LlamaConfig(
        vocab_size=16384,
        hidden_size=2048,
        num_layers=16,
        num_heads=16,
        num_kv_heads=8,
        intermediate_size=8192,
        max_seq_len=2048,
        dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16,
    )
    params = llama.init_llama(jax.random.PRNGKey(0), cfg)
    batch, t0 = 8, 128
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, t0), 0, cfg.vocab_size
    )

    _log("  compiling decode generations (short+long)...")
    n_short, n_long = 16, 528
    per_tok, eff_len = _decode_slope(
        cfg, params, prompt, n_short, n_long, flash_attention
    )

    # int8 weight-only decode: the step is memory-bound, so halving the
    # streamed weight bytes (quantize_llama_base) is ~free throughput —
    # the dequant fuses into each matmul's operand read.
    _log("  compiling int8 decode generations (short+long)...")
    from rayfed_tpu.models.quant import tree_nbytes

    qparams = llama.quantize_llama_base(params)
    per_tok_q, _ = _decode_slope(
        cfg, qparams, prompt, n_short, n_long, flash_attention
    )
    qparam_bytes = tree_nbytes(qparams)

    # Memory-bandwidth roofline (mirrors how llama_mfu anchors the train
    # bench): each decode step streams every parameter (bf16) plus the
    # live KV cache region once from HBM; cache-extent model documented
    # on _decode_slope.
    abstract = jax.eval_shape(lambda: llama.init_llama(jax.random.PRNGKey(0), cfg))
    param_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(abstract)
    )
    kv_bytes = _kv_cache_bytes(cfg, batch, eff_len)
    membw_util = (param_bytes + kv_bytes) / per_tok / _peak_hbm_bps()
    membw_util_q = (qparam_bytes + kv_bytes) / per_tok_q / _peak_hbm_bps()
    # Scale context for the int8 utilization number: at 1B the int8
    # weight read is a small slice of the step (the rest — attention,
    # cache reads, per-step dispatch — is dtype-independent), so
    # dividing by int8 bytes mechanically deflates "utilization" even
    # when the weight path is perfect.  The weight-read fraction makes
    # that legible next to the 8B config, where weights dominate and the
    # same int8 path measures ~0.84 util (decode_8b_membw_util).
    weight_frac_q = qparam_bytes / _peak_hbm_bps() / per_tok_q
    _log(
        f"  decode int8 1B: weight reads are {weight_frac_q:.0%} of the "
        f"step at roofline — util {membw_util_q:.2f} reflects the "
        f"dtype-independent remainder, not the int8 path (see the 8B "
        f"config where weights dominate)"
    )
    out = {
        "decode_tokens_per_sec": round(batch / per_tok, 1),
        "decode_step_ms": round(per_tok * 1e3, 2),
        "decode_membw_util": round(membw_util, 4),
        "decode_int8_tokens_per_sec": round(batch / per_tok_q, 1),
        "decode_int8_step_ms": round(per_tok_q * 1e3, 2),
        "decode_int8_membw_util": round(membw_util_q, 4),
        "decode_int8_weight_read_frac": round(weight_frac_q, 3),
        "decode_int8_speedup": round(per_tok / per_tok_q, 3),
    }

    # Long-context serving: at t0=1536 the bf16 cache reads rival the
    # weight reads, so int8 weights + int8 KV cache (kv_quant) nearly
    # halve the whole step's HBM traffic — the case the quantized cache
    # exists for.
    _log("  compiling long-context decode (bf16 vs int8 w+kv)...")
    import dataclasses as _dc

    t0_long = 1536
    prompt_long = jax.random.randint(
        jax.random.PRNGKey(2), (batch, t0_long), 0, cfg.vocab_size
    )
    per_tok_l, eff_len_l = _decode_slope(
        cfg, params, prompt_long, 16, 272, flash_attention
    )
    # int8 weights with the bf16 cache isolates the weight effect from
    # the cache effect at this context length.
    per_tok_lw, _ = _decode_slope(
        cfg, qparams, prompt_long, 16, 272, flash_attention
    )
    cfg_q = _dc.replace(cfg, kv_quant=True)
    per_tok_lq, _ = _decode_slope(
        cfg_q, qparams, prompt_long, 16, 272, flash_attention
    )
    util_l = (
        (param_bytes + _kv_cache_bytes(cfg, batch, eff_len_l))
        / per_tok_l / _peak_hbm_bps()
    )
    util_lq = (
        (qparam_bytes + _kv_cache_bytes(cfg_q, batch, eff_len_l))
        / per_tok_lq / _peak_hbm_bps()
    )
    out.update(
        decode_long_tokens_per_sec=round(batch / per_tok_l, 1),
        decode_long_membw_util=round(util_l, 4),
        decode_long_int8w_tokens_per_sec=round(batch / per_tok_lw, 1),
        decode_long_int8_tokens_per_sec=round(batch / per_tok_lq, 1),
        decode_long_int8_membw_util=round(util_lq, 4),
        # Full int8 (weights + cache) over bf16, and the cache's own
        # contribution on top of int8 weights.
        decode_long_int8_speedup=round(per_tok_l / per_tok_lq, 3),
        decode_long_kv_quant_speedup=round(per_tok_lw / per_tok_lq, 3),
    )
    return out


def bench_flash() -> dict:
    """Flash (pallas) vs dense attention, fwd+bwd, causal, T=2048 + 4096.

    Same slope-timing discipline as :func:`bench_llama`, but with a
    60-iteration scan delta (28 at T=4096, where per-iter times are ~2×
    longer) and median-of-3: the per-dispatch constant is noisy, so
    short deltas (10 iterations, once) can swing the slope by several
    ms per iter.
    """
    import jax.numpy as jnp

    from rayfed_tpu.ops.attention import dot_product_attention
    from rayfed_tpu.ops.flash_attention import flash_attention

    def timed(fn, q0, k0, v0, n_short=4, n_long=64) -> float:
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32) ** 2)

        grad_fn = jax.grad(loss, argnums=(0, 1, 2))

        def build(n):
            @jax.jit
            def run(q, k, v):
                def body(carry, _):
                    q, k, v = carry
                    gq, gk, gv = grad_fn(q, k, v)
                    # Data dependency so scan iterations can't be elided.
                    return (q - 1e-6 * gq, k - 1e-6 * gk, v - 1e-6 * gv), None

                carry, _ = jax.lax.scan(body, (q, k, v), None, length=n)
                return carry[0]

            out = run(q0, k0, v0)  # compile + warm
            float(jax.device_get(jnp.sum(out.astype(jnp.float32))))
            return run

        def once(run):
            t0 = time.perf_counter()
            out = run(q0, k0, v0)
            float(jax.device_get(jnp.sum(out.astype(jnp.float32))))
            return time.perf_counter() - t0

        run_s, run_l = build(n_short), build(n_long)
        slopes = sorted(
            (once(run_l) - once(run_s)) / (n_long - n_short) for _ in range(3)
        )
        return max(slopes[1], 1e-9)

    def shape(b, t):
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        return [
            jax.random.normal(kk, (b, t, 16, 64), jnp.bfloat16) for kk in keys
        ]

    _log("  compiling flash/dense attention chains (T=2048)...")
    args = shape(4, 2048)
    dense_t = timed(dot_product_attention, *args)
    flash_t = timed(flash_attention, *args)
    _log("  compiling flash/dense attention chains (T=4096)...")
    # Half batch at 4096 so dense's [B,H,T,T] f32 score tensor fits.
    args4k = shape(2, 4096)
    dense4k = timed(dot_product_attention, *args4k, n_long=32)
    flash4k = timed(flash_attention, *args4k, n_long=32)
    # Sliding window at T=4096, W=1024: out-of-band kv blocks never
    # launch.  At the default 1024-wide blocks the 4×4 grid keeps 7 of
    # the causal path's 10 blocks (diagonal + one sub-diagonal), so the
    # expected speedup here is ~10/7 ≈ 1.4× — smaller blocks or larger
    # T/W ratios approach the asymptotic O(T·W).
    _log("  compiling windowed flash chain (T=4096, W=1024)...")
    import functools as _ft

    swa4k = timed(
        _ft.partial(flash_attention, window=1024), *args4k, n_long=32
    )
    return {
        "flash_speedup": round(dense_t / flash_t, 3),
        "flash_ms": round(flash_t * 1e3, 2),
        "dense_ms": round(dense_t * 1e3, 2),
        "flash_speedup_t4096": round(dense4k / flash4k, 3),
        "flash_ms_t4096": round(flash4k * 1e3, 2),
        "dense_ms_t4096": round(dense4k * 1e3, 2),
        "flash_window_ms_t4096": round(swa4k * 1e3, 2),
        "flash_window_speedup": round(flash4k / swa4k, 3),
    }


def bench_moe() -> dict:
    """Scatter vs one-hot-einsum MoE dispatch at T=4096, E=16 (fwd+bwd).

    The einsum path's [B,T,k,E,C] mask is 84M elements (168 MB bf16) per
    batch row here and its dispatch einsum does O(T·E·C·d) FLOPs; the
    scatter path routes in O(T·k·d) with no mask tensor.  Slope-timed on
    the real chip at B=1 — the einsum mask and its gradient already
    dominate the step there, and the element guard trips at B≥13.
    """
    import jax.numpy as jnp

    from rayfed_tpu.models import moe as moe_mod

    cfg = moe_mod.MoeConfig(
        num_experts=16, top_k=2, d_model=1024, d_ff=4096, capacity_factor=1.25
    )
    params = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4096, 1024), jnp.bfloat16)

    def timed(mode, n_short=2, n_long=10) -> float:
        def loss(p, x):
            return jnp.sum(
                moe_mod.apply_moe(p, x, cfg, dispatch=mode).astype(jnp.float32)
                ** 2
            )

        grad_fn = jax.grad(loss)

        def build(n):
            @jax.jit
            def run(p, x):
                def body(p, _):
                    g = grad_fn(p, x)
                    return jax.tree_util.tree_map(
                        lambda a, b: a - 1e-6 * b.astype(a.dtype), p, g
                    ), None

                p, _ = jax.lax.scan(body, p, None, length=n)
                return p["gate"]

            out = run(params, x)
            float(jax.device_get(jnp.sum(out.astype(jnp.float32))))
            return run

        def once(run):
            t0 = time.perf_counter()
            out = run(params, x)
            float(jax.device_get(jnp.sum(out.astype(jnp.float32))))
            return time.perf_counter() - t0

        run_s, run_l = build(n_short), build(n_long)
        slopes = sorted(
            (once(run_l) - once(run_s)) / (n_long - n_short) for _ in range(3)
        )
        return max(slopes[1], 1e-9)

    _log("  compiling moe scatter/einsum chains (T=4096, E=16)...")
    scatter_t = timed("scatter")
    einsum_t = timed("einsum")
    return {
        "moe_scatter_ms": round(scatter_t * 1e3, 2),
        "moe_einsum_ms": round(einsum_t * 1e3, 2),
        "moe_scatter_speedup": round(einsum_t / scatter_t, 3),
    }


def _run_pp_vs_dp(_party: str, result_q) -> None:
    """1F1B pipeline (pp=4) vs data-parallel (dp=4) train step at equal
    params/batch on a 4-device virtual CPU mesh.

    No multi-chip hardware is attached to the bench host, so this
    measures the *program* cost (schedule + collectives as compiled by
    XLA) rather than real ICI; the gradient math of both programs is
    test-verified identical.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from rayfed_tpu.parallel import create_mesh
    from rayfed_tpu.parallel.pipeline import (
        make_pipeline_train,
        stack_params,
    )

    # M=8: 1F1B ideal ratio is M/(M+2(S-1)) = 8/14 = 0.57 — the measured
    # ratio (0.52 in r4's artifact; run-to-run 0.5-0.6 on this shared
    # host) sits at that bubble-limited bound.  More microbatches
    # amortize the bubble only when ticks overlap collectives with
    # compute (real ICI); on this serialized 1-core mesh extra ticks
    # just add fixed per-tick cost (M=32 measured 0.38, M=16/width=1024
    # 0.58).  The interleaved schedule (v=2) measured alongside shrinks
    # the ideal bubble to 2(S-1)/v ticks: vM/(vM+2(S-1)) at tick=T/v ->
    # ratio bound M/(M+2(S-1)/v) = 8/11 = 0.73.
    width, layers, batch, num_mb = 512, 8, 64, 8
    keys = jax.random.split(jax.random.PRNGKey(0), layers)
    params = stack_params(
        [
            {
                "w": jax.random.normal(k, (width, width)) * width**-0.5,
                "b": jnp.zeros((width,)),
            }
            for k in keys
        ]
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, width))
    tgt = jax.random.normal(jax.random.PRNGKey(2), (batch, width))

    def stage_fn(stage_params, h):
        def body(h, layer):
            return jnp.tanh(h @ layer["w"] + layer["b"]), None

        out, _ = jax.lax.scan(body, h, stage_params)
        return out

    def mse(y, t):
        return jnp.mean((y - t) ** 2)

    def timed(step, args, n=4, reps=3):
        # Min over independent windows: a host-side CPU burst during one
        # window (this box runs other things) poisons an average but not
        # the min.
        out = step(*args)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                out = step(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    # pp=4: 1F1B schedule.
    pp_mesh = create_mesh({"pp": 4}, devices=jax.devices()[:4])
    pp_step = jax.jit(
        make_pipeline_train(pp_mesh, stage_fn, mse, num_microbatches=num_mb)
    )
    pp_t = timed(pp_step, (params, x, tgt))

    # pp=4, v=2 virtual stages: interleaved schedule (half the bubble).
    ppi_step = jax.jit(
        make_pipeline_train(
            pp_mesh, stage_fn, mse, num_microbatches=num_mb,
            virtual_stages=2,
        )
    )
    ppi_t = timed(ppi_step, (params, x, tgt))

    # dp=4: same model, batch sharded, grads all-reduced by XLA.
    dp_mesh = create_mesh({"dp": 4}, devices=jax.devices()[:4])

    def dp_loss(p, x, t):
        return mse(stage_fn(p, x), t)

    xs = jax.device_put(x, NamedSharding(dp_mesh, P("dp")))
    ts = jax.device_put(tgt, NamedSharding(dp_mesh, P("dp")))
    with jax.sharding.set_mesh(dp_mesh):
        dp_step = jax.jit(jax.value_and_grad(dp_loss))
        dp_t = timed(dp_step, (params, xs, ts))

    result_q.put(("pp", (pp_t, ppi_t, dp_t)))


def _prior_baseline(metric: str):
    """Earliest recorded value of ``metric`` across driver BENCH files.

    The driver nests the JSON line this script prints under a ``parsed``
    key; accept both that and a bare record (hand-run copies).
    """
    values = []
    for path in sorted(glob.glob(os.path.join(os.path.dirname(__file__), "BENCH_r*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
            for r in (rec.get("parsed") or {}, rec):
                if r.get("metric") == metric and r.get("value"):
                    values.append(float(r["value"]))
                    break
        except Exception:
            continue
    return values[0] if values else None


def _fill_stream_extra(extra: dict, s: dict) -> None:
    extra["cross_party_stream_agg_GBps"] = round(s["gbps"], 3)
    extra["agg_overlap_frac"] = round(s["overlap"], 3)
    extra["delta_bytes_saved_frac"] = round(s["delta_saved"], 3)
    extra["stream_agg_round_ms"] = round(s["round_ms"], 1)
    extra["stream_agg_contrib_ms"] = round(s["contrib_agg_ms"], 1)
    extra["stream_agg_bcast_ms"] = round(s["bcast_ms"], 1)
    extra["stream_agg_busy_ms"] = round(s["agg_busy_ms"], 1)
    extra["stream_agg_tail_ms"] = round(s["agg_tail_ms"], 1)
    extra["stream_agg_wire_ms"] = round(s["agg_wire_ms"], 1)
    extra["stream_agg_bundle_mb"] = round(s["bundle_mb"], 1)
    _log(
        f"  stream-agg: {s['gbps']:.3f} GB/s through receive+aggregate, "
        f"overlap {s['overlap']:.0%} of agg busy hidden under the wire, "
        f"delta cache saved {s['delta_saved']:.0%} of stream bytes; "
        f"round {s['round_ms']:.0f} ms = contrib+agg "
        f"{s['contrib_agg_ms']:.0f} + bcast {s['bcast_ms']:.0f} "
        f"(agg busy {s['agg_busy_ms']:.0f}, tail {s['agg_tail_ms']:.0f})"
    )


@contextlib.contextmanager
def _section(extra: dict, name: str):
    """Isolate one benchmark section: a failure records
    ``{name}_error`` in the artifact and the remaining sections still
    run and report — one bad section must not void a ~45-minute
    one-shot round-end run.  ``main`` exits non-zero when any section
    recorded an error."""
    try:
        yield
    except Exception as e:
        _log(f"  section {name} FAILED: {e!r}")
        extra[f"{name}_error"] = repr(e)[:200]


def main() -> None:
    fed_only = "--fed-only" in sys.argv
    compute_only = "--compute-only" in sys.argv
    if fed_only and compute_only:
        raise SystemExit("--fed-only and --compute-only are mutually exclusive")

    from rayfed_tpu.utils import use_compilation_cache

    use_compilation_cache()

    if "--smoke" in sys.argv:
        # Fast CI smoke (test.sh): ONLY the streaming-aggregation round
        # bench at reduced scale — exercises the whole delta + streaming
        # pipeline end-to-end over real sockets in well under a minute,
        # and fails the build when it breaks.
        os.environ["RAYFED_BENCH_SMOKE"] = "1"
        extra = {}
        with _section(extra, "stream_agg"):
            _log("streaming-aggregation smoke (small bundles, 4 parties)...")
            s = _one_child("_run_stream_agg_bench", ndev=1, timeout=420)
            _fill_stream_extra(extra, s)
        with _section(extra, "ring_agg"):
            _log("ring-aggregation smoke (4-party ring vs hub)...")
            rres = _multi_party(
                "_run_ring_agg_party", parties=RINGB_PARTIES, ndev=1,
                timeout=420,
            )
            _fill_ring_extra(extra, rres)
        with _section(extra, "overlap"):
            _log("pipelined-rounds smoke (4-party overlap vs sync)...")
            ores = _multi_party(
                "_run_overlap_party", parties=OVERLAPB_PARTIES, ndev=1,
                timeout=420,
            )
            _fill_overlap_extra(extra, ores)
        with _section(extra, "send_path"):
            _log("coordinator send-path smoke (4-party hub, striped "
                 "bundles, arena + multi-rail)...")
            sp = _one_child("_run_send_path_bench", ndev=1, timeout=420)
            _fill_send_path_extra(extra, sp)
        with _section(extra, "compressed_agg"):
            _log("compressed-domain aggregation smoke (shared-grid "
                 "uint8 folds vs bf16, 4 parties)...")
            ca = _one_child("_run_compressed_agg_bench", ndev=1,
                            timeout=420)
            _fill_compressed_extra(extra, ca)
        with _section(extra, "secagg"):
            _log("secure-aggregation smoke (pairwise-masked integer "
                 "folds vs plain quantized rounds, 4 parties)...")
            sg = _one_child("_run_secagg_bench", ndev=1, timeout=420)
            _fill_secagg_extra(extra, sg)
        with _section(extra, "server_opt"):
            _log("server-optimization smoke (packed FedAC rounds-to-"
                 "target + post-step downlink byte-identity across "
                 "streaming/quorum-subset/hierarchy)...")
            sv = _one_child("_run_server_opt_bench", ndev=1,
                            timeout=420)
            _fill_server_opt_extra(extra, sv)
        with _section(extra, "object_plane"):
            _log("object-plane smoke (welcome-by-handle vs eager push, "
                 "concurrent-fetch dedup, 4 managers)...")
            op = _one_child("_run_objectplane_bench", ndev=1, timeout=420)
            _fill_objectplane_extra(extra, op)
        with _section(extra, "hierarchy"):
            _log("hierarchical-aggregation smoke (region rings + "
                 "quantized cross-region streaming, traffic-vs-N at "
                 "N=4/16/64 virtual parties)...")
            hr = _one_child("_run_hierarchy_bench", ndev=1, timeout=600)
            _fill_hierarchy_extra(extra, hr)
        with _section(extra, "chaos"):
            _log("chaos smoke (quorum=2 rounds under injected straggler "
                 "+ party crash + coordinator kill mid-round, 4 "
                 "parties)...")
            cres = _multi_party(
                "_run_chaos_party", parties=CHAOSB_PARTIES, ndev=1,
                timeout=420,
            )
            _fill_chaos_extra(extra, cres)
        with _section(extra, "telemetry"):
            _log("telemetry smoke (flight-recorder overhead armed vs "
                 "disarmed + cross-manager trace collection / critical-"
                 "path reconciliation, 4 managers)...")
            tl = _one_child("_run_telemetry_bench", ndev=1, timeout=420)
            _fill_telemetry_extra(extra, tl)
        with _section(extra, "async_rounds"):
            _log("buffered-async smoke (time-to-target vs sync barrier "
                 "under seeded 2-10x straggler chaos + versions/sec at "
                 "N=64 in-process virtual parties)...")
            ab = _one_child("_run_async_bench", ndev=1, timeout=600)
            _fill_async_extra(extra, ab)
        record = {
            "metric": "cross_party_stream_agg_GBps",
            "value": extra.get("cross_party_stream_agg_GBps", 0.0),
            "unit": "GB/s",
            "vs_baseline": 1.0,
            "smoke": True,
        }
        record.update(extra)
        print(json.dumps(record), flush=True)
        if (
            "stream_agg_error" in extra
            or "ring_agg_error" in extra
            or "overlap_error" in extra
            or "send_path_error" in extra
            or "compressed_agg_error" in extra
            or "secagg_error" in extra
            or "server_opt_error" in extra
            or "object_plane_error" in extra
            or "hierarchy_error" in extra
            or "chaos_error" in extra
            or "telemetry_error" in extra
            or "async_rounds_error" in extra
        ):
            raise SystemExit(1)
        # CI gates (test.sh): aggregation in the compressed domain must
        # actually pay — (1) the quantized round's wire bytes at or
        # under 0.55x the bf16 path (uint8 codes are half of bf16; the
        # grid vectors and manifests are the slack), (2) the integer
        # fold at least as fast as dequantize-first (it does strictly
        # less work: one dispatch, no f32 intermediate), (3) the
        # streamed integer fold BIT-identical to the one-shot
        # packed_quantized_sum, and (4) equal converged accuracy on the
        # quadratic recurrence (error feedback carries the grid's
        # dropped mass).
        cfrac = extra.get("compressed_bytes_on_wire_frac")
        if cfrac is None or cfrac > 0.55:
            _log(
                f"compressed-agg smoke gate FAILED: "
                f"compressed_bytes_on_wire_frac={cfrac} (must be <= "
                f"0.55 of the bf16 path)"
            )
            raise SystemExit(1)
        cfold = extra.get("compressed_fold_speedup")
        if cfold is None or cfold < 1.0:
            _log(
                f"compressed-agg smoke gate FAILED: "
                f"compressed_fold_speedup={cfold} (the integer fold "
                f"must be >= the dequant-first path)"
            )
            raise SystemExit(1)
        if not extra.get("compressed_agg_bitexact"):
            _log(
                "compressed-agg smoke gate FAILED: streamed integer "
                "fold != one-shot packed_quantized_sum"
            )
            raise SystemExit(1)
        clr = extra.get("compressed_loss_ratio")
        if clr is None or not clr <= 1.05:
            _log(
                f"compressed-agg smoke gate FAILED: "
                f"compressed_loss_ratio={clr} (8-bit+EF must converge "
                f"with f32 on the quadratic, ratio <= 1.05)"
            )
            raise SystemExit(1)
        # CI gates (test.sh): server optimization must actually cut
        # ROUNDS — (1) FedAC reaches the quadratic target loss in at
        # most 0.8x plain FedAvg's rounds (the spectral bound on this
        # workload is ~0.15, so 0.8 has a wide noise margin), and (2)
        # the post-step quantized downlink is BYTE-identical across
        # the streaming fold, the quorum-cutoff subset refold feeding
        # the step, and the hierarchy's regrouped presummed fold, as
        # decoded from serialized wire bytes on a receiving controller.
        rfrac = extra.get("fedac_rounds_to_target_frac")
        if rfrac is None or rfrac > 0.8:
            _log(
                f"server-opt smoke gate FAILED: "
                f"fedac_rounds_to_target_frac={rfrac} (FedAC must reach "
                f"the quadratic target in <= 0.8x plain FedAvg's rounds)"
            )
            raise SystemExit(1)
        if not extra.get("server_opt_agg_bitexact"):
            _log(
                "server-opt smoke gate FAILED: post-step downlink not "
                "byte-identical across streaming/quorum-subset/"
                "hierarchy folds"
            )
            raise SystemExit(1)
        # CI gates (test.sh): secure aggregation must be exact and
        # near-free — (1) the masked round's aggregate BYTE-identical
        # to the plain quantized round's (pairwise masks cancel in the
        # integer ring, not approximately), (2) masking adds at most 5%
        # to the round wall (masks ship zero bytes; the keystream
        # prefetch hides under the local step).
        if not extra.get("secagg_bitexact"):
            _log(
                "secagg smoke gate FAILED: masked aggregate != plain "
                "quantized aggregate (the masks must cancel bit-exactly)"
            )
            raise SystemExit(1)
        sof = extra.get("secagg_overhead_frac")
        if sof is None or sof > 0.05:
            _log(
                f"secagg smoke gate FAILED: secagg_overhead_frac={sof} "
                f"(masked rounds must cost <= 5% over plain quantized "
                f"rounds)"
            )
            raise SystemExit(1)
        # CI gates (test.sh): the object plane must actually deliver
        # pull-on-demand — (1) a WARM welcome-by-handle rejoin moves at
        # most 0.1x the eager welcome push's payload bytes (the handle
        # is a few hundred bytes; a cache hit pulls nothing), (2) N
        # concurrent fetches of one fingerprint trigger exactly ONE
        # wire transfer (in-flight dedup), and (3) handle-resolved
        # state is byte-identical to the eager-push state.
        rwf = extra.get("rejoin_welcome_bytes_frac")
        if rwf is None or rwf > 0.1:
            _log(
                f"object-plane smoke gate FAILED: "
                f"rejoin_welcome_bytes_frac={rwf} (a warm rejoin must "
                f"move <= 0.1x the eager welcome's payload bytes)"
            )
            raise SystemExit(1)
        if not extra.get("blob_dedup_single_transfer"):
            _log(
                "object-plane smoke gate FAILED: concurrent fetches of "
                "one fingerprint did not collapse to a single transfer"
            )
            raise SystemExit(1)
        if not extra.get("blob_handle_state_identical"):
            _log(
                "object-plane smoke gate FAILED: handle-resolved model "
                "!= eager-push model (receiver-decoded bytes)"
            )
            raise SystemExit(1)
        # CI gates (test.sh): hierarchical aggregation must scale flat
        # — (1) byte-identical to the one-shot compressed-domain
        # reduce at every N (integer folds regroup exactly), (2) mean
        # per-party bytes within 1.25x of the 2·|model| flat-traffic
        # budget at N=4/16/64, (3) max-node-ingress ~flat in N (no
        # O(N) hub at any level of the tree).
        if not extra.get("hier_bitexact"):
            _log(
                "hierarchy smoke gate FAILED: hierarchical aggregate "
                "!= one-shot packed_quantized_sum (+ shared downlink "
                "recode) on some party/N"
            )
            raise SystemExit(1)
        for _n in (4, 16, 64, 256):
            hpf = extra.get(f"hier_party_bytes_frac_{_n}")
            if _n == 256 and hpf is None:
                continue  # leg skipped below the FD ceiling
            if hpf is None or hpf > 1.25:
                _log(
                    f"hierarchy smoke gate FAILED: "
                    f"hier_party_bytes_frac_{_n}={hpf} (per-party "
                    f"bytes-on-wire must stay <= 1.25x of 2|model|)"
                )
                raise SystemExit(1)
        hflat = extra.get("hier_ingress_flatness")
        if hflat is None or hflat > 1.6:
            _log(
                f"hierarchy smoke gate FAILED: "
                f"hier_ingress_flatness={hflat} (max-node ingress must "
                f"stay ~flat from N=4 to N=64, ratio <= 1.6; the flat hub "
                f"grows ~16x over the same range)"
            )
            raise SystemExit(1)
        # CI gate (test.sh): the N=64 round wall must stay well
        # sublinear in the ~14x message-count growth over N=16
        # (before the local-link fast path this ratio sat at ~23).
        # Gate at 12: identical code measured 6.8-10.2 across
        # back-to-back runs on a 1-vCPU CI host (clean HEAD and
        # branch overlapped; the denominator is a ~200ms leg whose
        # min-of-3 swings 40% on scheduler luck), so 8 could not
        # separate noise from regression — the bracketed denominator
        # plus 12 catches the message-cost blowup class, and
        # trace_phases says where the time went on a trip.
        hratio = extra.get("hier_round_ratio_64_over_16")
        if hratio is None or hratio > 12.0:
            _log(
                f"hierarchy smoke gate FAILED: "
                f"hier_round_ratio_64_over_16={hratio} (must be <= 12; "
                f"per-message transport cost is regressing — see "
                f"trace_phases in the hierarchy section)"
            )
            raise SystemExit(1)
        # CI gates (test.sh), multi-level leg — skipped only when the
        # FD ceiling forced the N=256 leg off: (5) the N=256 round
        # wall within 4x of N=64 (the thousand-silo scaling gate),
        # (6) root egress flat in N (region-ring downlink: coordinator
        # fan-out would sit ~32x of 2|model| at N=256), (7) the seeded
        # straggling-region chaos round completes with ZERO
        # abort-and-flatten fallbacks (the per-region cutoff absorbs
        # it) and full cross-party byte agreement.
        if "hier_round_ratio_256_over_64" in extra:
            hr256 = extra["hier_round_ratio_256_over_64"]
            if hr256 is None or hr256 > 4.0:
                _log(
                    f"hierarchy smoke gate FAILED: "
                    f"hier_round_ratio_256_over_64={hr256} (must be "
                    f"<= 4; see the per-level trace_phases +"
                    f" hier_level_ingress_256 for which tree level "
                    f"regressed)"
                )
                raise SystemExit(1)
            regress = extra.get("hier_root_egress_frac_256")
            if regress is None or regress > 8.0:
                _log(
                    f"hierarchy smoke gate FAILED: "
                    f"hier_root_egress_frac_256={regress} (root bytes "
                    f"out must stay ~O(branch·|model|), <= 8x of "
                    f"2|model| — O(N) coordinator fan-out is back)"
                )
                raise SystemExit(1)
            if (
                extra.get("hier_chaos_fallbacks") != 0
                or extra.get("hier_chaos_agree") is not True
                or not extra.get("hier_chaos_cutoffs")
            ):
                _log(
                    f"hierarchy smoke gate FAILED: seeded "
                    f"straggling-region chaos round — fallbacks="
                    f"{extra.get('hier_chaos_fallbacks')} (must be 0), "
                    f"cutoffs={extra.get('hier_chaos_cutoffs')} (must "
                    f"be >= 1), agree={extra.get('hier_chaos_agree')}"
                )
                raise SystemExit(1)
        else:
            _log(
                "hierarchy N=256 gates SKIPPED (FD ceiling): "
                + str(extra.get("hier_n256_skipped"))
            )
        # CI gate (test.sh): the ring must actually de-bottleneck the
        # coordinator — its share of cluster ingress bytes at or near
        # 1/N, never above 0.4 (the hub pins ~0.5 regardless of N).
        frac = extra.get("coord_bytes_in_frac")
        if frac is None or frac > 0.4:
            _log(
                f"ring smoke gate FAILED: coord_bytes_in_frac={frac} "
                f"(must be <= 0.4)"
            )
            raise SystemExit(1)
        # CI gate (test.sh): the pipelined engine must actually hide
        # comms under compute — at least half of the per-round comms
        # wall (the structural ceiling is (R-1)/R = 0.75 at R=4).
        hfrac = extra.get("overlap_hidden_comm_frac")
        if hfrac is None or hfrac < 0.5:
            _log(
                f"overlap smoke gate FAILED: "
                f"overlap_hidden_comm_frac={hfrac} (must be >= 0.5)"
            )
            raise SystemExit(1)
        # CI gates (test.sh): the r05 send-path gap must stay closed.
        # (1) The FedAvg exchange must sustain at least HALF of the
        # same-box demonstrated push capability (r05 sat at 0.24 — the
        # "4× gap"; relative to in-situ capability because absolute
        # GB/s tracks the host, not the code).
        vs_cap = extra.get("wire_vs_push_capability")
        if vs_cap is None or vs_cap < 0.5:
            _log(
                f"send-path smoke gate FAILED: "
                f"wire_vs_push_capability={vs_cap} (must be >= 0.5; "
                f"the r05 gap was 0.24)"
            )
            raise SystemExit(1)
        # (2) With the full-payload serialization barrier gone, the
        # coordinator's broadcast-out wall must stay within 1.5× its
        # contributions-in wall (symmetric bytes; the r05 send/read
        # session imbalance was 2.7×).
        wr = extra.get("send_vs_read_wall_ratio")
        if wr is None or wr > 1.5:
            _log(
                f"send-path smoke gate FAILED: "
                f"send_vs_read_wall_ratio={wr} (must be <= 1.5; was "
                f"2.7 in r05)"
            )
            raise SystemExit(1)
        # (3) Colocated parties must beat the loopback-TCP wire by at
        # least 2x on the same payload shape, and "auto" must have
        # actually picked the shm handoff (one interpreter) — the
        # local-link upgrade machinery earning its keep.
        lvw = extra.get("local_link_vs_wire")
        if lvw is None or lvw < 2.0:
            _log(
                f"local-link smoke gate FAILED: "
                f"local_link_vs_wire={lvw} (local_link_GBps must be >= "
                f"2x send_path_wire_GBps)"
            )
            raise SystemExit(1)
        if extra.get("local_link_backend") != "shm":
            _log(
                f"local-link smoke gate FAILED: auto picked "
                f"{extra.get('local_link_backend')!r}, expected 'shm' "
                f"for a same-interpreter pair"
            )
            raise SystemExit(1)
        # CI gate (test.sh): the round must SURVIVE partial failure —
        # under 1 injected straggler past the deadline + 1 hard party
        # crash + a coordinator kill mid-round 2, every surviving
        # controller completes every quorum round, they agree on the
        # bytes, round 1 actually aggregated a strict subset (the
        # cutoff fired), the roster epoch advanced at least twice (both
        # corpses dropped, no runtime restart), and every survivor
        # performed >= 1 coordinator failover (the killed round was
        # re-established at the deterministic successor).
        if (
            extra.get("chaos_rounds_completed") != CHAOSB_ROUNDS
            or extra.get("chaos_survivors") != len(CHAOSB_PARTIES) - 2
            or not extra.get("chaos_final_consistent")
            or not (
                2 <= len(extra.get("chaos_round1_members", []))
                < len(CHAOSB_PARTIES)
            )
            or extra.get("chaos_roster_epoch", 0) < 2
            or extra.get("chaos_coordinator_failovers", 0) < 1
        ):
            _log(
                f"chaos smoke gate FAILED: rounds="
                f"{extra.get('chaos_rounds_completed')}/{CHAOSB_ROUNDS} "
                f"survivors={extra.get('chaos_survivors')} "
                f"consistent={extra.get('chaos_final_consistent')} "
                f"round1_members={extra.get('chaos_round1_members')} "
                f"epoch={extra.get('chaos_roster_epoch')} "
                f"failovers={extra.get('chaos_coordinator_failovers')}"
            )
            raise SystemExit(1)
        # CI gates (test.sh): observability must be ~free and honest —
        # (1) the armed flight-recorder round wall within 3% of the
        # disarmed wall (an emission is a ring append, never I/O), and
        # (2) the cross-manager merged trace's per-round critical-path
        # walls reconcile with the driver's own measured walls (and the
        # timeline exports as valid Perfetto trace_event JSON, with
        # spans from every party).
        tof = extra.get("trace_overhead_frac")
        if tof is None or tof > 0.03:
            _log(
                f"telemetry smoke gate FAILED: trace_overhead_frac="
                f"{tof} (armed round wall must stay <= 1.03x disarmed)"
            )
            raise SystemExit(1)
        if not extra.get("trace_critical_path_agrees"):
            _log(
                "telemetry smoke gate FAILED: the merged trace's per-"
                "round walls do not reconcile with the driver's "
                "measured walls (or the Perfetto export / per-party "
                "span coverage came up empty)"
            )
            raise SystemExit(1)
        # CI gates (test.sh): buffered-async rounds must actually kill
        # the barrier — (1) time-to-target-loss under the seeded 2-10x
        # straggler spread at most 0.8x the synchronous barrier on the
        # SAME workload + chaos schedule (the barrier pays the
        # straggler's stretched step every round; the buffer absorbs
        # it as stale decayed folds), (2) every emitted version
        # byte-identical to a sorted refold of its recorded fold set
        # (the order-free exact-integer contract on this host), and
        # (3) the N=64 in-process fleet emits versions at a floor rate
        # (the coordinator's running fold + re-park loop must not
        # degrade to per-push model rebuilds).
        atf = extra.get("async_tt_frac")
        if atf is None or atf > 0.8:
            _log(
                f"async smoke gate FAILED: async_tt_frac={atf} "
                f"(buffered-async must reach the target loss in <= "
                f"0.8x the synchronous barrier's wall; None means the "
                f"target was never reached)"
            )
            raise SystemExit(1)
        if not extra.get("async_refold_bitexact"):
            _log(
                "async smoke gate FAILED: an emitted version != the "
                "sorted packed_quantized_sum refold of its fold set"
            )
            raise SystemExit(1)
        avs = extra.get("async_versions_per_sec")
        if avs is None or avs < 1.0:
            _log(
                f"async smoke gate FAILED: async_versions_per_sec="
                f"{avs} at N=64 (must be >= 1.0)"
            )
            raise SystemExit(1)
        return

    extra: dict = {}
    record = None

    # Environment fingerprint: cross-round comparisons of the federated
    # (CPU-bound) configs are only interpretable when the host is known —
    # r3→r4's "wire regression" was indistinguishable from a host change.
    import platform as _platform

    extra["env_cpu_count"] = os.cpu_count()
    try:
        extra["env_loadavg_1m"] = round(os.getloadavg()[0], 2)
    except OSError:  # pragma: no cover
        extra["env_loadavg_1m"] = None
    extra["env_platform"] = _platform.machine()
    # Device kind is recorded when the compute section initializes the
    # backend (below).  Deliberately NOT before: once this parent has
    # touched jax.devices() it holds the chip, and the CPU sections
    # spawn children — keep every spawn ahead of the first device touch.
    # The CPU sections therefore run FIRST, accelerator init last.
    extra["env_device_kind"] = "uninitialized (--fed-only)"

    if not compute_only:
        with _section(extra, "pp_bench"):
            _log("1F1B + interleaved pipeline vs DP train step (4-device virtual mesh)...")
            pp_t, ppi_t, dp_t = _one_child("_run_pp_vs_dp", ndev=4)
            extra["pp_step_ms"] = round(pp_t * 1e3, 2)
            extra["pp_interleaved_step_ms"] = round(ppi_t * 1e3, 2)
            extra["dp_step_ms"] = round(dp_t * 1e3, 2)
            extra["pp_vs_dp_step_ratio"] = round(dp_t / pp_t, 3)
            extra["pp_interleaved_vs_dp_step_ratio"] = round(dp_t / ppi_t, 3)
            _log(
                f"  pp(1f1b) {pp_t*1e3:.1f} ms, pp(interleaved v=2) "
                f"{ppi_t*1e3:.1f} ms vs dp {dp_t*1e3:.1f} ms (ratios "
                f"{dp_t/pp_t:.3f} / {dp_t/ppi_t:.3f}; ideal bubble bounds "
                f"0.57 / 0.73 at M=8,S=4)"
            )

    if not compute_only:
        # Federated configs run lightest-first with a settle between
        # them: on the 1-core bench host a predecessor's teardown
        # (socket drain, page-cache churn from 128MB payloads) bleeds
        # into the next child's measurement — the split-FL number was
        # 4x lower when run straight after the push flood.
        def _settle():
            time.sleep(3)

        with _section(extra, "split_fl"):
            _log("split-FL activation push (CPU parties, real transport)...")
            sres = _multi_party("_run_split_party")
            gbps = sum(v["gbps"] for v in sres.values()) / len(sres)
            extra["split_fl_GBps"] = round(gbps, 3)
            extra["split_fl_steps_per_sec"] = round(
                sum(v["steps_per_sec"] for v in sres.values()) / len(sres), 3
            )
            extra["split_fl_bf16_steps_per_sec"] = round(
                sum(v["bf16_steps_per_sec"] for v in sres.values()) / len(sres), 3
            )
            alice = sres.get("alice", next(iter(sres.values())))
            extra["split_fl_wire_read_ms"] = round(alice["wire_read_ms"], 2)
            extra["split_fl_send_path_ms"] = round(alice["send_path_ms"], 2)
            extra["split_fl_other_ms"] = round(alice["other_ms"], 2)
            extra["split_fl_compute_probe_s"] = round(
                sum(v["compute_probe_ms"] for v in sres.values()) / 1e3, 4
            )
            _log(
                f"  split: {gbps:.3f} GB/s; per-step wire-read "
                f"{alice['wire_read_ms']:.1f} ms, send-path "
                f"{alice['send_path_ms']:.1f} ms, compute+sched "
                f"{alice['other_ms']:.1f} ms; bf16 wire "
                f"{extra['split_fl_bf16_steps_per_sec']:.2f} vs f32 "
                f"{extra['split_fl_steps_per_sec']:.2f} steps/s"
            )
            _settle()

        # Push bench AFTER the split section (lightest-first: its 128MB
        # floods would deflate a subsequent split window ~4x via socket
        # drain + page-cache churn) — the split ceiling is derived below
        # once both numbers exist.
        with _section(extra, "push_bench"):
            _log("raw send-proxy push throughput (128MB sharded, loopback)...")
            push, reshard, packed, perleaf, overlap, multirail, onerail = (
                _one_child("_run_push_bench", timeout=900)
            )
            extra["push_GBps"] = round(push, 3)
            extra["push_reshard_GBps"] = round(reshard, 3)
            # Single 128MB payload striped over 4 rails vs pinned to one
            # (wire v4 multi-rail fan-out).
            extra["multirail_GBps"] = round(multirail, 3)
            extra["singlerail_GBps"] = round(onerail, 3)
            extra["multirail_vs_single_rail"] = round(
                multirail / onerail, 3
            ) if onerail > 0 else None
            # End-to-end compressed-tree exchange (compress → wire →
            # decompress): packed single-buffer codec vs per-leaf.
            extra["cross_party_packed_GBps"] = round(packed, 3)
            extra["cross_party_perleaf_GBps"] = round(perleaf, 3)
            extra["packed_codec_speedup"] = round(
                packed / perleaf, 3
            ) if perleaf > 0 else None
            # Fraction of the send path's busy time (prepare+write)
            # hidden by the chunk pipeline's overlap.
            extra["send_overlap_saved_frac"] = round(overlap, 3)
            _log(
                f"  push: {push:.3f} GB/s wire, {reshard:.3f} GB/s with "
                f"re-shard; packed tree {packed:.3f} GB/s vs per-leaf "
                f"{perleaf:.3f} GB/s ({extra['packed_codec_speedup']}x), "
                f"send overlap saves {overlap:.0%} of busy time; "
                f"multirail {multirail:.3f} GB/s vs single-rail "
                f"{onerail:.3f} GB/s "
                f"({extra['multirail_vs_single_rail']}x)"
            )

            # Serialized 1-core model for the split step: every byte
            # crosses the wire once and every FLOP runs once, all on one
            # core — predicted steps/s = 1/(compute_s + bytes/wire_GBps).
            # Both terms measured (alice's serial local-compute probe of
            # both halves + the push bench's wire GB/s), but each under
            # slightly different conditions (the push bench moves 128MB
            # sharded arrays; the split moves 16.8MB ones with cheaper
            # per-byte cost), so the model is a sanity reference, good
            # to ~±15%: a measured number far BELOW it flags a real
            # pathology (r4's 0.056 GB/s would have read ~0.1 of model),
            # slightly above it just means the wire term was
            # conservative.  Reads only `extra` so a failed split
            # section degrades to None fields, not a mislabeled
            # push_bench_error.
            split_compute_s = extra.get("split_fl_compute_probe_s")
            split_sps = extra.get("split_fl_steps_per_sec")
            split_gbps = extra.get("split_fl_GBps")
            extra["split_fl_ceiling_steps_per_sec"] = None
            extra["split_fl_vs_ceiling"] = None
            if push > 0 and split_compute_s and split_sps and split_gbps:
                step_bytes = split_gbps * 1e9 / split_sps
                wire_s = step_bytes / (push * 1e9)
                ceiling_sps = 1.0 / (split_compute_s + wire_s)
                extra["split_fl_ceiling_steps_per_sec"] = round(ceiling_sps, 3)
                extra["split_fl_vs_ceiling"] = round(split_sps / ceiling_sps, 3)
                _log(
                    f"  split serialized model: {ceiling_sps:.2f} steps/s "
                    f"(compute {split_compute_s*1e3:.0f} ms + wire "
                    f"{wire_s*1e3:.0f} ms) -> measured f32 is "
                    f"{extra['split_fl_vs_ceiling']} of it"
                )
        _settle()

        with _section(extra, "send_path"):
            _log("coordinator send-path probe (4-party hub, ResNet-18 "
                 "bundles, arena + multi-rail)...")
            sp = _one_child("_run_send_path_bench", ndev=1, timeout=600)
            _fill_send_path_extra(extra, sp)
            _settle()

        with _section(extra, "stream_agg"):
            _log("streaming FedAvg aggregation (ResNet-18 packed rounds, "
                 "delta cache, 4 parties)...")
            s = _one_child("_run_stream_agg_bench", ndev=1, timeout=600)
            _fill_stream_extra(extra, s)
            _settle()

        with _section(extra, "ring_agg"):
            _log("ring FedAvg aggregation (ResNet-18 packed rounds, "
                 "4-party ring vs hub)...")
            rres = _multi_party(
                "_run_ring_agg_party", parties=RINGB_PARTIES, ndev=1,
                timeout=900,
            )
            _fill_ring_extra(extra, rres)
            _settle()

        with _section(extra, "overlap"):
            _log("pipelined FedAvg rounds (4-party overlap vs sync)...")
            ores = _multi_party(
                "_run_overlap_party", parties=OVERLAPB_PARTIES, ndev=1,
                timeout=900,
            )
            _fill_overlap_extra(extra, ores)
            _settle()

        with _section(extra, "lora_2party"):
            _log("2-party Llama-LoRA federated fine-tune (CPU parties)...")
            lres = _multi_party("_run_lora_party")
            lrps = sum(v[0] for v in lres.values()) / len(lres)
            adapter_mb = next(iter(lres.values()))[1]
            extra["lora_2party_rounds_per_sec"] = round(lrps, 3)
            extra["lora_adapter_MB_per_push"] = round(adapter_mb, 3)
            _log(f"  lora: {lrps:.3f} rounds/s, {adapter_mb:.3f} MB adapters/push")
            _settle()

        with _section(extra, "resnet_fedavg"):
            _log("4-party ResNet-18 FedAvg (CPU parties, real transport)...")
            res = _multi_party(
                "_run_resnet_party", RESNET_PARTIES, ndev=1, use_barrier=True
            )
            rps = sum(v[0] for v in res.values()) / len(res)
            xgbps = sum(v[1] for v in res.values()) / len(res)
            extra["resnet_4party_rounds_per_sec"] = round(rps, 3)
            # Goodput: bundle bytes over the WHOLE round wall — on this
            # CPU bench host the round is ≥95% training compute, so this
            # number tracks the model's step time, not the transport.
            extra["cross_party_goodput_GBps"] = round(xgbps, 3)
            # Coordinator's per-round wire decomposition (alice aggregates).
            coord = res.get("alice", next(iter(res.values())))
            extra["resnet_coord_wire_read_ms"] = round(coord[2], 2)
            extra["resnet_coord_send_path_ms"] = round(coord[3], 2)
            # cross_party_GBps: the coordinator's bytes over its actual
            # wire-session time (read+send) — the rate the cross-party
            # exchange itself sustains.  (Before the packed codec this
            # key recorded the compute-dominated goodput above, which
            # said nothing about the wire; the goodput is preserved
            # under cross_party_goodput_GBps.)
            coord_bytes_per_round = coord[1] * 1e9 * coord[6]
            wire_session_s = (coord[2] + coord[3]) / 1e3
            if wire_session_s > 0:
                extra["cross_party_GBps"] = round(
                    coord_bytes_per_round / wire_session_s / 1e9, 3
                )
                extra["cross_party_wire_GBps"] = extra["cross_party_GBps"]
            # The r05 verdict's gap decomposition, tracked per round:
            # the coordinator's summed send sessions over its summed
            # wire-read sessions (was 2.7×; the send_path section gates
            # the phase-wall form of this at smoke scale).
            if coord[2] > 0:
                extra["resnet_coord_send_vs_read_ratio"] = round(
                    coord[3] / coord[2], 3
                )
            # Full decomposition: step wall (jitted local round incl. fused
            # wire casts), per-party CPU, and idle share.  step/wall ≈ 96%
            # on the 1-core host — the rest is transport CPU + idle.
            step_ms = sum(v[4] for v in res.values()) / len(res)
            cpu_pr = sum(v[5] for v in res.values())
            wall_pr = sum(v[6] for v in res.values()) / len(res)
            extra["resnet_round_step_ms"] = round(step_ms, 1)
            extra["resnet_round_cpu_s_total"] = round(cpu_pr, 2)
            extra["resnet_round_step_wall_frac"] = round(
                step_ms / 1e3 / wall_pr, 3
            )
            # Decompression cost of the wire bundle, measured directly
            # (packed fast path vs per-leaf tree_map).
            decomp_ms = sum(v[9] for v in res.values()) / len(res)
            decomp_perleaf_ms = sum(v[10] for v in res.values()) / len(res)
            extra["resnet_decomp_ms"] = round(decomp_ms, 2)
            extra["resnet_decomp_perleaf_ms"] = round(decomp_perleaf_ms, 2)
            extra["resnet_decomp_speedup"] = round(
                decomp_perleaf_ms / decomp_ms, 3
            ) if decomp_ms > 0 else None
            _log(
                f"  resnet: {rps:.3f} rounds/s, goodput {xgbps:.3f} GB/s, "
                f"wire-session {extra.get('cross_party_GBps')} GB/s; "
                f"coordinator wire-read {coord[2]:.1f} ms + send "
                f"{coord[3]:.1f} ms per round; decomp packed "
                f"{decomp_ms:.1f} ms vs per-leaf {decomp_perleaf_ms:.1f} "
                f"ms; step {step_ms/1e3:.2f}s of {wall_pr:.2f}s wall "
                f"({step_ms/1e3/wall_pr:.0%}), 4-party CPU {cpu_pr:.2f}s"
            )
            _settle()

            # Contention floor: measured inside the same four party
            # processes immediately after the fedavg window (see
            # _run_resnet_party) — bare local rounds, no framework,
            # mp-Barrier-synced per round.  Same processes + same host
            # moment makes fedavg/floor drift-free.
            floor_rps = sum(v[7] for v in res.values()) / len(res)
            floor_cpu = sum(v[8] for v in res.values())
            extra["resnet_compute_floor_rounds_per_sec"] = round(floor_rps, 3)
            extra["resnet_floor_cpu_s_total"] = round(floor_cpu, 2)
            extra["resnet_fedavg_overhead_ratio"] = round(rps / floor_rps, 3)
            _log(
                f"  floor (fed local program, in-process): {floor_rps:.3f} "
                f"rounds/s ({floor_cpu:.2f}s CPU per round across 4 procs); "
                f"fedavg/floor {rps / floor_rps:.3f} (framework share)"
            )

        # North-star ratio (BASELINE.json #3): fedavg vs the single-
        # process data-parallel control at the same total batch.  On a
        # 1-core host floor/dp is the structural cap of the vs_dp ratio:
        # process contention plus the 4×batch-32-vs-batch-128 XLA
        # efficiency gap plus the wire-cast program cost — none of which
        # is framework overhead, and all of which vanish on real
        # hardware where each party owns its chips and the per-device
        # batch matches.
        with _section(extra, "resnet_dp"):
            _log("ResNet-18 single-process DP control (north-star denominator)...")
            dp_rps, dp_cpu = _one_child("_run_resnet_dp_control", ndev=1)
            extra["resnet_dp_control_rounds_per_sec"] = round(dp_rps, 3)
            extra["resnet_dp_cpu_s"] = round(dp_cpu, 2)
            # Cross-section ratios only when the fedavg section produced
            # its numbers — a fedavg failure must not fail the dp
            # control that just measured fine.
            fed_rps = extra.get("resnet_4party_rounds_per_sec")
            fl_rps = extra.get("resnet_compute_floor_rounds_per_sec")
            fl_cpu = extra.get("resnet_floor_cpu_s_total")
            if fed_rps and fl_rps and fl_cpu:
                ratio = fed_rps / dp_rps
                extra["resnet_fedavg_vs_dp_ratio"] = round(ratio, 3)
                extra["resnet_batch_efficiency_ratio"] = round(dp_cpu / fl_cpu, 3)
                # ROADMAP 5a: record the METHOD next to the number —
                # how this ratio is measured, and (below 0.9) the
                # predicted 4-slice model that bounds the shared-chip
                # artifact.
                extra["resnet_vs_dp_method"] = (
                    "4-party pipelined FedAvg rounds/s over the real "
                    "transport divided by the single-process DP "
                    "control at the same total batch, both on this "
                    "host; all parties share the host's cores, so "
                    "process contention + the 4x batch-32-vs-128 XLA "
                    "gap are inside the measured ratio"
                )
                if ratio < 0.9:
                    # Predicted 4-slice model (ROADMAP 5a): on real
                    # hardware each party owns its chip — per-party
                    # round compute = its own CPU-seconds per round
                    # (the contention disappears), and only the
                    # non-overlapped wire is exposed.  Inputs emitted
                    # alongside the prediction so the claim is
                    # auditable from the bench record alone.
                    per_slice_s = fl_cpu / 4.0
                    wire_s = (
                        extra.get("resnet_coord_wire_read_ms", 0.0)
                        + extra.get("resnet_coord_send_path_ms", 0.0)
                    ) / 1e3
                    # Demonstrated comms hiding (the pipelined round
                    # engine's smoke gate floor); 0 = fully exposed
                    # wire, the conservative bound.
                    h = float(extra.get("overlap_hidden_comm_frac", 0.0))
                    extra["resnet_pred_compute_floor_s"] = round(
                        per_slice_s, 3
                    )
                    extra["resnet_pred_wire_s"] = round(wire_s, 3)
                    extra["resnet_pred_overlap_frac"] = round(h, 3)
                    pred_rps = 1.0 / (per_slice_s + (1.0 - h) * wire_s)
                    pred_rps_hidden = 1.0 / max(per_slice_s, wire_s)
                    extra["resnet_pred_4slice_ratio"] = round(
                        pred_rps / dp_rps, 3
                    )
                    extra["resnet_pred_4slice_ratio_full_overlap"] = (
                        round(pred_rps_hidden / dp_rps, 3)
                    )
                    _log(
                        f"  predicted 4-slice model: compute floor "
                        f"{per_slice_s:.2f}s/round per slice + wire "
                        f"{wire_s:.2f}s x (1-{h:.2f} hidden) -> "
                        f"{pred_rps:.3f} rounds/s = "
                        f"{pred_rps / dp_rps:.3f}x dp (the <0.9 "
                        f"residual is the shared-chip artifact)"
                    )
                _log(
                    f"  dp control: {dp_rps:.3f} rounds/s ({dp_cpu:.2f}s CPU) "
                    f"-> fedavg/dp ratio {fed_rps / dp_rps:.3f}; floor/dp "
                    f"{fl_rps / dp_rps:.3f} (structural: dp does the same "
                    f"epoch in {dp_cpu:.1f}s CPU vs the 4 parties' "
                    f"{fl_cpu:.1f}s)"
                )
            else:
                _log(f"  dp control: {dp_rps:.3f} rounds/s ({dp_cpu:.2f}s CPU)")
            _settle()

        with _section(extra, "fedavg_mnist"):
            metric = "fedavg_mnist_2party_rounds_per_sec"
            _log("2-party FedAvg (CPU parties, real transport)...")
            rps = _two_party("_run_fedavg_party")
            prior = _prior_baseline(metric)
            record = {
                "metric": metric,
                "value": round(rps, 3),
                "unit": "rounds/s",
                "vs_baseline": round(rps / prior, 3) if prior else 1.0,
            }
    if not fed_only:
        # No accelerator is a failed run, not a skipped section: the fed
        # metrics already measured are still printed below, and the exit
        # code says the compute half is missing.
        with _section(extra, "compute_bench"):
            device = jax.devices()[0]
            extra["env_device_kind"] = device.device_kind
            if device.platform == "cpu":
                raise RuntimeError(
                    "no accelerator found (JAX fell back to the CPU): the "
                    "compute benches do not run on a CPU; pass --fed-only "
                    "for the CPU-party sections alone"
                )
            # An unknown device_kind fails here, before any timing.
            _peak_flops()
            _peak_hbm_bps()
        fed_only = "compute_bench_error" in extra
    if not fed_only:
        _log(f"compute benches on {extra['env_device_kind']}...")
        with _section(extra, "llama_train"):
            extra.update(bench_llama())
            _log(f"  llama: {extra}")
        with _section(extra, "decode"):
            extra.update(bench_decode())
            _log(f"  decode: {extra}")
        with _section(extra, "flash"):
            extra.update(bench_flash())
            _log(f"  flash: {extra}")
        # The 8B config needs ~11 GB of HBM; a smaller device records
        # the failure (and the run exits non-zero) instead of dying here.
        with _section(extra, "lora_8b"):
            extra.update(bench_lora_8b())
            _log(f"  lora-8b: {extra}")
        with _section(extra, "moe"):
            extra.update(bench_moe())
            _log(f"  moe: {extra}")

    if record is None:
        # compute_only, or the headline federated section failed (its
        # error is in extra) — fall back to the llama headline.
        record = {
            "metric": "llama_tokens_per_sec",
            # None, not 0.0: a section that failed measured nothing.
            "value": extra.get("llama_tokens_per_sec"),
            "unit": "tokens/s",
            "vs_baseline": 1.0,
        }

    record.update(extra)
    # NaN (e.g. a ring-evicted decomposition window) is not valid JSON;
    # map it to null so strict parsers accept every BENCH line.
    record = {
        k: (None if isinstance(v, float) and v != v else v)
        for k, v in record.items()
    }
    print(json.dumps(record), flush=True)
    failed = sorted(k for k in extra if k.endswith("_error"))
    if failed:
        _log(f"bench FAILED: {failed}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
