"""The benchmark harness: one cell, one run, one line of JSON.

Everything that belongs to one cell, one configuration, one family or
one per-layer metric lives in a file of its own, found by name:

- ``workloads/<cell>.json``: parties, placement, the job and the
  ``run_fedavg_rounds`` keyword arguments (the traffic mix);
- ``configs/<config>.json``: the model's sizes as run, and its family;
- ``families/<family>.py``: model, local step, FLOPs, reference check;
- ``layer_metrics/<metric>.py``: one reader per per-layer metric.

A later PR adds files and appends entries to ``BENCHMARK.json``; it
edits nothing here.  See ``README.md``.

The run: check the device, check the family against its plain
reference, then every party (a thread of this process, real loopback
TCP between them) makes one verification-and-warm-up call of
``run_fedavg_rounds`` (compiles every shape; each round's aggregate is
compared with an independent float32 numpy FedAvg) and one measured
call of ``RAMP + R`` rounds.  Neither call passes ``timings=`` or
``on_round=``: round boundaries come from the timestamps the
benchmark's own trainer takes.
"""

from __future__ import annotations

import fnmatch
import importlib.util
import json
import os
import sys
import threading
import time

from benchmark import spans as spans_mod
from benchmark.peaks import peaks_for
from benchmark.reference import fedavg as ref_fedavg

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)
PARTY_NAMES = ("alice", "bob", "carol", "dave", "erin", "frank", "grace",
               "heidi", "ivan", "judy")
VERIFY_ROUNDS = 3  # the verification-and-warm-up call
RAMP = 2  # first rounds of the measured call: ramp, not counted
MIN_ROUNDS = 8
TRACE_ROUNDS = 3  # rounds under jax.profiler in a traced run


class BenchError(RuntimeError):
    """The run cannot produce a valid result."""


def load_cell(name: str, root: str = ROOT) -> dict:
    """A cell's file with its configuration resolved."""
    with open(os.path.join(root, "workloads", f"{name}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "configs", f"{cell['config']}.json")) as f:
        cell["config_data"] = json.load(f)
    cell["name"], cell["root"] = name, root
    return cell


def round_kwargs(cell: dict, parties) -> dict:
    """The cell's ``run_fedavg_rounds`` keyword arguments."""
    kwargs = dict(cell.get("round_kwargs", {}))
    for banned in ("timings", "on_round"):
        if banned in kwargs:
            raise BenchError(
                f"{banned}= turns the pipelined round off; a cell may "
                f"not pass it"
            )
    if cell.get("coordinator") == "last":
        kwargs["coordinator"] = parties[-1]
    return kwargs


def check_device(chips: int, platform: str = "tpu") -> dict:
    """The device as JAX reports it; raises unless it is ``chips`` or
    more devices of ``platform`` whose kind is in the peaks table."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != platform:
        raise BenchError(
            f"JAX found no {platform} (platform {info['platform']!r}); "
            f"the benchmark never measures on another platform"
        )
    if info["count"] < chips:
        raise BenchError(f"need {chips} chips, JAX reports {info['count']}")
    if platform == "tpu":
        peaks_for(info["kind"])
    return info


def phase_log(phase: str, t_start: float) -> None:
    """Where set-up time goes: seconds since the process started."""
    print(json.dumps({"setup_phase": phase,
                      "at_s": round(time.time() - t_start, 3)}),
          file=sys.stderr, flush=True)


def loopback_rx_bytes():
    """Bytes the kernel has received on the loopback interface, or None
    where ``/proc/net/dev`` does not say: the benchmark's own reading of
    the wire, independent of the program's counters."""
    try:
        with open("/proc/net/dev") as f:
            for line in f:
                name, _, rest = line.partition(":")
                if name.strip() == "lo":
                    return int(rest.split()[0])
    except OSError:
        pass
    return None


class CompileClock:
    """Backend compiles of this process (or fetches from the persistent
    cache), counted through ``jax.monitoring`` as ``chip_smoke.py``."""

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.misses += 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.count, self.seconds, self.misses


class Run:
    """State shared by the party threads of one run."""

    def __init__(self, cell, family, seconds, trace, platform):
        self.cell, self.family = cell, family
        self.seconds, self.trace, self.platform = seconds, trace, platform
        n = int(cell["parties"])
        self.parties = list(PARTY_NAMES[:n])
        self.kwargs = round_kwargs(cell, self.parties)
        self.spans = spans_mod.SpanLog()
        self.records: dict = {}  # (party, round) -> dict
        self.lock = threading.Lock()
        self.barrier = threading.Barrier(n)
        self.measured_rounds = None  # R, decided by one thread
        self.compiles = CompileClock()
        self.compiles_at: dict = {}
        self.warm_round_s = None
        self.trace_from = None  # first traced round (traced run only)
        self.recorder = None
        self.profile_dir = None
        self.profile_wall = [None, None]  # time.time() at start / stop
        self.anchor_wall = None
        self._profile_state = "idle"

    # -- tracing (traced run only) -------------------------------------

    def traced(self, round_: int) -> bool:
        return bool(
            self.trace and self.trace_from is not None
            and round_ >= self.trace_from
        )

    def enter_round(self, round_: int) -> None:
        """Called at every entry into ``train``.  A traced run keeps the
        first half of its measured rounds untraced (the base of
        ``trace_overhead``); the first entry into round ``trace_from``
        arms the flight recorder, the first into the round after it
        starts ``jax.profiler``, and ``TRACE_ROUNDS`` rounds later it
        stops."""
        if not self.trace or self.trace_from is None:
            return
        if round_ < self.trace_from or self._profile_state == "done":
            return
        import jax

        from rayfed_tpu import telemetry

        start = self.trace_from + 1
        with self.lock:
            if self.recorder is None:
                self.recorder = telemetry.install(capacity=1 << 18)
            if self._profile_state == "idle" and round_ >= start:
                jax.profiler.start_trace(self.profile_dir)
                self.profile_wall[0] = self.anchor_wall = time.time()
                with jax.profiler.TraceAnnotation("bench_anchor"):
                    time.sleep(0.002)
                self._profile_state = "on"
            elif (self._profile_state == "on"
                  and round_ >= start + TRACE_ROUNDS):
                self.profile_wall[1] = time.time()
                jax.profiler.stop_trace()
                self._profile_state = "done"

    def stop_profile(self) -> None:
        with self.lock:
            if self._profile_state == "on":
                import jax

                self.profile_wall[1] = time.time()
                jax.profiler.stop_trace()
                self._profile_state = "done"


def make_trainer(run: Run):
    """The benchmark's trainer: follows the documented trainer contract
    (``fl.decompress`` -> local steps over different batches ->
    ``fl.compress(..., packed=True)``) and takes the timestamps round
    boundaries and per-layer spans are read from.  In a traced run every
    span ends after the device finished (``block_until_ready``); an
    untraced run blocks nowhere."""
    import jax

    from rayfed_tpu import fl
    from rayfed_tpu.metrics import get_stats

    fam = run.family
    first_measured = VERIFY_ROUNDS + RAMP

    class Trainer:
        def __init__(self, owner: str, index: int):
            self._owner, self._round = owner, 0
            self._state = fam.party_state(index)
            run.records[(owner, "resident")] = fam.resident_arrays(
                self._state
            )

        def train(self, bundle):
            t_in = time.time()
            # Received bytes are counted before a payload reaches its
            # consumer, so at the ENTRY of round r this is exactly what
            # the rounds before r delivered to this party (billing on
            # the send side lags the acknowledgement).
            stats = get_stats()
            r, owner = self._round, self._owner
            self._round += 1
            run.enter_round(r)
            traced = run.traced(r)
            keep = r < VERIFY_ROUNDS  # verification keeps its buffers

            def end(name, t0, value):
                if traced:
                    jax.block_until_ready(value)
                    run.spans.add(owner, name, r, t0)
                return time.time()

            t = t_in
            tree = fl.decompress(bundle)
            t = end("unpack", t, tree)
            carry = fam.begin_round(self._state, tree)
            losses = []
            for k in range(fam.local_steps):
                carry, loss = fam.step(self._state, carry, k)
                losses.append(loss)
                t = end("step", t, loss)
            out = fl.compress(fam.end_round(carry), packed=True)
            t = end("pack", t, out.buf)
            if traced:
                run.spans.add(owner, "train", r, t_in)
            run.records[(owner, r)] = {
                "t_in": t_in,
                "losses": losses,
                "in_devices": sorted(d.id for d in bundle.buf.devices()),
                "in_is_jax": isinstance(bundle.buf, jax.Array),
                "in": bundle.buf if keep else None,
                "out": out.buf if keep else None,
                "received": stats["receive_bytes"],
                "stats": stats if r == first_measured else None,
            }
            return out

    return Trainer


def party_main(run: Run, party: str):
    import jax

    import rayfed_tpu as fed
    from rayfed_tpu.fl.trainer import run_fedavg_rounds
    from rayfed_tpu.metrics import get_stats
    from rayfed_tpu.runtime import get_runtime

    Trainer = fed.remote(make_trainer(run))
    trainers = {
        p: Trainer.party(p).remote(p, i) for i, p in enumerate(run.parties)
    }
    params = run.family.init_global()
    if party == run.parties[0]:
        phase_log("actors_and_model_made", run.t_start)

    def rounds(n: int, start):
        final = run_fedavg_rounds(trainers, start, n, **run.kwargs)
        leaves = jax.tree_util.tree_leaves(final)
        jax.block_until_ready(leaves)
        t_end = time.time()
        # Count the last broadcast too: bytes are billed when ACKed.
        get_runtime().cleanup_manager.wait_sending()
        return final, leaves, t_end

    out = {"init": ref_fedavg.flat_f32(params)}
    final, leaves, out["verify_t_end"] = rounds(VERIFY_ROUNDS, params)
    out["verify_final"] = ref_fedavg.flat_f32(final)
    del final, leaves
    if run.barrier.wait() == 0:
        # One thread decides the measured rounds for all parties, from
        # the warm round the verification call ended with.
        last = VERIFY_ROUNDS - 1
        warm = out["verify_t_end"] - min(
            run.records[(p, last)]["t_in"] for p in run.parties
        )
        run.warm_round_s = warm
        run.measured_rounds = max(
            MIN_ROUNDS, int(round(run.seconds / max(warm, 1e-3)))
        )
        # Traced run: first half plain, second half traced.
        run.trace_from = (
            VERIFY_ROUNDS + RAMP + run.measured_rounds // 2
        )
        run.compiles_at["measure_start"] = run.compiles.snapshot()
        phase_log("verified_and_warm", run.t_start)
    run.barrier.wait()
    final, leaves, out["t_end"] = rounds(RAMP + run.measured_rounds, params)
    if run.barrier.wait() == 0:
        run.compiles_at["measure_end"] = run.compiles.snapshot()
        run.stop_profile()
    out["final"] = ref_fedavg.flat_f32(final)
    out["final_devices"] = sorted(
        {d.id for leaf in leaves for d in leaf.devices()}
    )
    out["final_is_jax"] = all(isinstance(x, jax.Array) for x in leaves)
    out["stats"] = get_stats()
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             *, platform: str = "tpu", t_start: float = None,
             scratch: str = None) -> dict:
    """One run of one cell; returns the result object (see run.py).
    ``t_start``: ``time.time()`` at the start of the process, from
    which set-up is counted."""
    t_start = time.time() if t_start is None else t_start
    from rayfed_tpu.utils import use_compilation_cache

    use_compilation_cache()
    device = check_device(int(cell["chips"]), platform)
    phase_log("device_checked", t_start)
    import jax

    from rayfed_tpu import inprocess, telemetry
    from rayfed_tpu.parallel.mesh import create_mesh

    config = cell["config_data"]
    family_mod = importlib.import_module(
        f"benchmark.families.{config['run']['family']}"
    )
    family = family_mod.build(config, cell["job"], seed)
    run = Run(cell, family, seconds, trace, platform)
    run.loopback_at_start = loopback_rx_bytes()
    reference = family.reference_check()
    phase_log("reference_checked", t_start)
    run.t_start = t_start

    if trace:
        scratch = scratch or os.path.join(REPO, ".bench_scratch")
        run.profile_dir = os.path.join(scratch, f"profile-{cell['name']}")
        import shutil

        shutil.rmtree(run.profile_dir, ignore_errors=True)
        os.makedirs(run.profile_dir, exist_ok=True)
    meshes = None
    if cell.get("placement") == "one_per_chip":
        meshes = {
            p: create_mesh({"dp": 1}, devices=[d])
            for p, d in zip(run.parties, jax.devices())
        }
    try:
        out = inprocess.run_parties(
            lambda p: party_main(run, p),
            inprocess.loopback_cluster(run.parties),
            meshes=meshes, timeout=900, logging_level="warning",
        )
    finally:
        run.stop_profile()
        if trace:
            telemetry.uninstall()
    from benchmark import reduce as reduce_mod

    return reduce_mod.reduce_run(
        run, out, device=device, reference=reference, setup_wall=t_start,
        recorder=run.recorder, meshes=meshes,
    )


def matching_layer_metrics(cell_name: str, root: str = ROOT):
    """Every reader under ``layer_metrics/`` whose ``CELLS`` patterns
    match this cell, as imported modules sorted by name."""
    found = []
    folder = os.path.join(root, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"benchmark_layer_metric_{fname[:-3]}",
            os.path.join(folder, fname),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if any(fnmatch.fnmatch(cell_name, pat) for pat in mod.CELLS):
            found.append(mod)
    return found
