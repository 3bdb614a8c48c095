#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, with
every party's compute and every aggregation kernel on the TPU::

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the paths that exist only across chips

One chip: a whole federated round (local steps -> pack/quantize -> d2h
-> loopback-TCP wire -> h2d + fold kernel -> finalize -> downlink ->
unpack) of ResNet-18 at its full widths, 4 parties as threads of THIS
process (``rayfed_tpu.inprocess`` — a chip belongs to one process at a
time), 3 rounds after warm-up, through ``fed.init`` / ``@fed.remote`` /
``run_fedavg_rounds`` / ``fed.shutdown``, in two wire forms (packed
bf16; uint8 codes folded by ``quantized_accum_kernel``); then two train
steps of a Llama (widths below; depth cut to 2) with the Pallas flash
kernel, against the dense attention reference.

``--chips 4`` runs only: (A) the same ResNet-18 rounds with each party
pinned to its own chip, and (B) ``examples/mesh_fedavg`` as two parties
x a two-chip ``fsdp`` mesh.

Every phase is checked against a plain float32 numpy FedAvg of the same
local updates, computed here with nothing from ``rayfed_tpu.fl``.  One
JSON object per line; the LAST line is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed phase or check, or a platform other than ``tpu``, exits
non-zero with ``"ok": false`` — it never continues on the CPU.  The one
process spawns nothing that needs the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PLATFORM = "tpu"  # what every device must be; the script has no CPU mode
PARTIES = ("alice", "bob", "carol", "dave")
ROUNDS = 3  # measured rounds, after warm-up
BATCH, HW = 32, 32  # per-party CIFAR-shaped batch

# The two wire forms take different device code.  Warm-up rounds compile:
# the quantized form needs two (its first round has no observed delta to
# range the grid, so it runs unquantized — the bootstrap).
WIRE_FORMS = {
    "bf16": dict(
        warmup=1, kwargs=dict(compress_wire=True, packed_wire=True),
    ),
    "uint8": dict(
        warmup=2,
        kwargs=dict(
            compress_wire=True, packed_wire=True, wire_quant="uint8",
            streaming_agg=True,
        ),
    ),
}

# Stated tolerances against the float32 numpy reference.
#  - a bf16 aggregate is the f32 mean rounded once to bf16: half an ulp;
#  - a uint8 aggregate is off by grid steps, so by a fraction of how far
#    the model moved that round, not of the weights: the uplink grid is
#    ranged by QUANT_DELTA_EXPAND = 4 x the previous round's aggregate
#    delta over 255 levels (half a step is 1/64 of that delta), a party's
#    delta that overshoots the range clips and rides the error-feedback
#    residual into the next round, and the downlink recodes once more.
BF16_RTOL = 2.0**-8 * 1.01
QUANT_DELTA_FRAC = 0.1
# Flash vs dense attention, same bf16 step: losses near ln(vocab) = 9.7.
LLAMA_LOSS_ATOL = 0.05
LLAMA_LAYERS = 2  # depth cut from 16 to keep the compile short


class SmokeError(RuntimeError):
    """A phase check failed."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check_device(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is >= ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != PLATFORM:
        raise SmokeError(
            f"JAX found no TPU (platform {info['platform']!r}); "
            f"chip_smoke.py never continues on the CPU"
        )
    if info["count"] < chips:
        raise SmokeError(f"need {chips} chips, JAX reports {info['count']}")
    return info


class CompileClock:
    """Seconds this process spent in XLA backend compiles (or fetching
    them from the persistent cache), and the cache's hits and misses."""

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.seconds, self.hits, self.misses


def numpy_fedavg(updates):
    """Plain float32 FedAvg of flat update buffers — the reference."""
    import numpy as np

    acc = np.zeros(updates[0].shape, np.float32)
    for u in updates:
        acc += u.astype(np.float32)
    return acc / np.float32(len(updates))


def flat_f32(tree):
    """A pytree's float leaves as one flat float32 host buffer, in
    ``tree_leaves`` order (the order the packed wire buffer uses)."""
    import jax
    import numpy as np

    return np.concatenate([
        np.asarray(leaf, np.float32).ravel()
        for leaf in jax.tree_util.tree_leaves(tree)
    ])


def device_ids(array) -> list:
    return sorted(d.id for d in array.devices())


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


# ---------------------------------------------------------------------------
# ResNet-18 x 4 in-process parties (BASELINE.md config #3)
# ---------------------------------------------------------------------------


def run_resnet_rounds(form: str, seed: int, party_devices, coordinator):
    """The federated job itself: ``(per-party results, per-(party,
    round) trainer records)``.  Trainers keep what they saw ON THE
    DEVICE; nothing is copied to the host inside a round."""
    import jax
    import jax.numpy as jnp

    import rayfed_tpu as fed
    from rayfed_tpu import inprocess
    from rayfed_tpu.fl.trainer import run_fedavg_rounds
    from rayfed_tpu.metrics import get_stats
    from rayfed_tpu.models import resnet
    from rayfed_tpu.parallel.mesh import create_mesh
    from rayfed_tpu.runtime import get_runtime

    spec = WIRE_FORMS[form]
    cfg = resnet.resnet18(num_classes=10)
    # ONE jit shared by the party actors.
    fed_step = resnet.make_fed_train_step(cfg, lr=0.05)
    records: dict = {}

    def party_main(party: str):
        @fed.remote
        class Trainer:
            def __init__(self, owner: str, data_seed: int):
                self._owner, self._round = owner, 0
                x = jax.random.normal(
                    jax.random.PRNGKey(data_seed), (BATCH, HW, HW, 3)
                )
                probe = jax.random.normal(
                    jax.random.PRNGKey(seed), (3, cfg.num_classes)
                )
                self._x = x
                self._y = jnp.argmax(jnp.mean(x, axis=(1, 2)) @ probe, -1)

            def train(self, bundle):
                t0 = time.perf_counter()
                out, loss = fed_step(bundle, self._x, self._y)
                loss = float(loss)  # waits for the step
                records[(self._owner, self._round)] = {
                    "t": t0,
                    "step_s": time.perf_counter() - t0,
                    "loss": loss,
                    "in": bundle.buf,
                    "out": out.buf,
                    "x": self._x,
                    "sent": get_stats()["send_bytes"],
                }
                self._round += 1
                return out

        trainers = {
            p: Trainer.party(p).remote(p, 100 * seed + i + 1)
            for i, p in enumerate(PARTIES)
        }
        params = resnet.init_resnet(jax.random.PRNGKey(seed), cfg)
        final = run_fedavg_rounds(
            trainers, params, spec["warmup"] + ROUNDS,
            coordinator=coordinator, **spec["kwargs"],
        )
        leaves = jax.tree_util.tree_leaves(final)
        jax.block_until_ready(leaves)
        # Count the last broadcast too: bytes are billed when ACKed.
        get_runtime().cleanup_manager.wait_sending()
        return {
            "t_end": time.perf_counter(),
            "init": flat_f32(params),
            "final": flat_f32(final),
            "final_leaves": leaves,
            "stats": get_stats(),
        }

    meshes = party_devices and {
        p: create_mesh({"dp": len(d)}, devices=d)
        for p, d in party_devices.items()
    }
    out = inprocess.run_parties(
        party_main, inprocess.loopback_cluster(PARTIES), meshes=meshes,
        timeout=900, trace=True, logging_level="warning",
    )
    return out, records


def check_against_numpy(form: str, out: dict, records: dict) -> dict:
    """Finals byte-identical; every round's aggregate within the stated
    tolerance of the independent numpy FedAvg of that round's updates."""
    import numpy as np

    n_rounds = WIRE_FORMS[form]["warmup"] + ROUNDS
    finals = [out[p]["final"] for p in PARTIES]
    for p, final in zip(PARTIES[1:], finals[1:]):
        require(
            final.tobytes() == finals[0].tobytes(),
            f"{form}: final model of {p} differs from {PARTIES[0]}'s",
        )
    # Round by round: each round's aggregate is what every party trains
    # from next (its bf16 wire form) and, for the last round, the
    # returned model.
    refs = [
        numpy_fedavg([np.asarray(records[(p, r)]["out"]) for p in PARTIES])
        for r in range(n_rounds)
    ]
    moved = [
        float(np.abs(cur - prev).max())
        for prev, cur in zip([out[PARTIES[0]]["init"]] + refs, refs)
    ]
    # Worst error by kind of round: "bf16" rounds met only the bf16 wire
    # (all of form bf16, and the quantized form's bootstrap), "uint8"
    # rounds were aggregated in the compressed domain.
    worst: dict = {}
    for r, ref in enumerate(refs):
        quantized = form == "uint8" and r >= 1  # round 0 is the bootstrap
        last = r == n_rounds - 1
        tol = np.zeros_like(ref)
        if quantized:
            tol += QUANT_DELTA_FRAC * max(moved[r], moved[r - 1])
        if not (last and quantized):  # the aggregate met the bf16 wire
            tol += BF16_RTOL * np.abs(ref) + 1e-12
        gots = finals if last else [
            np.asarray(records[(p, r + 1)]["in"]).astype(np.float32)
            for p in PARTIES
        ]
        kind = worst.setdefault(
            "uint8" if quantized else "bf16", {"frac_of_tol": -1.0}
        )
        for got in gots:
            require(got.shape == ref.shape, f"{form}: layout mismatch")
            err = np.abs(got - ref)
            i = int(np.argmax(err / tol))
            if err[i] / tol[i] > kind["frac_of_tol"]:
                kind.update(
                    err=float(err[i]), tol=float(tol[i]),
                    frac_of_tol=float(err[i] / tol[i]), round=r,
                )
    require(
        all(kind["frac_of_tol"] <= 1.0 for kind in worst.values()),
        f"{form}: aggregate off the numpy FedAvg by {worst}",
    )
    return {
        "model_elems": int(refs[0].size),
        "finals_identical": True,
        "max_abs_err_vs_numpy": worst,
        "moved_per_round": [round(m, 6) for m in moved],
    }


def check_placement(form, out, records, folds, coord, party_devices) -> dict:
    """Everything a party holds is a ``jax.Array`` on a TPU device — its
    own, where parties are pinned — and the quantized fold ran as the
    jitted kernel on the coordinator's device.  Returns ``{party:
    {what: device ids}}``."""
    import jax

    n_rounds = WIRE_FORMS[form]["warmup"] + ROUNDS
    placement = {}
    for p in PARTIES:
        rows = [records[(p, r)] for r in range(n_rounds)]
        held = {
            "trainer_state": [a for r in rows for a in (r["x"], r["out"])],
            "received": [r["in"] for r in rows[1:]],
            "aggregate": out[p]["final_leaves"],
        }
        for what, arrays in held.items():
            require(
                all(isinstance(a, jax.Array) for a in arrays),
                f"{form}: {p}'s {what} is a host array, not a jax.Array",
            )
        placement[p] = {
            what: sorted({i for a in arrays for i in device_ids(a)})
            for what, arrays in held.items()
        }
        placement[p]["accumulator"] = None
    if form == "uint8":
        coded = [f for f in folds if f.detail["codes"] == "uint8"]
        require(
            len(coded) == n_rounds - 1 and all(f.party == coord for f in folds),
            f"uint8: expected {n_rounds - 1} quantized folds at {coord}, "
            f"saw {[(f.party, f.detail) for f in folds]}",
        )
        require(
            all(f.detail["fold"] == "jit" for f in folds),
            "uint8: the aggregator took the numpy fold, not "
            "quantized_accum_kernel",
        )
        placement[coord]["accumulator"] = sorted(
            {i for f in folds for i in f.detail["devices"]}
        )
    platform = {d.id: d.platform for d in jax.devices()}
    for p, where in placement.items():
        mine = party_devices and {d.id for d in party_devices[p]}
        for what, ids in where.items():
            if ids is None:
                continue
            require(
                ids and all(platform[i] == PLATFORM for i in ids),
                f"{form}: {p}'s {what} is not on a TPU: {ids}",
            )
            require(
                not mine or set(ids) <= mine,
                f"{form}: {p}'s {what} sits on devices {ids}, its own "
                f"are {mine}",
            )
    return placement


def check_wire(form: str, out: dict) -> dict:
    """The bytes crossed the real loopback-TCP wire between distinct
    parties: nothing was short-circuited in-process.  Returns each
    party's total bytes sent."""
    sent = {}
    for p in PARTIES:
        stats = out[p]["stats"]
        sent[p] = int(stats["send_bytes"])
        peers = [q for q, n in stats["send_dest_ops"].items() if n and q != p]
        by_backend = stats["send_path_breakdown_by_backend_ms"]
        require(sent[p] > 0 and peers, f"{form}: {p} sent no wire bytes")
        require(
            by_backend["tcp"]["socket_ms"] > 0
            and by_backend["shm"]["socket_ms"] == 0
            and by_backend["uds"]["socket_ms"] == 0,
            f"{form}: {p}'s bytes did not ride loopback TCP: {by_backend}",
        )
    return sent


def resnet_round_phase(form: str, seed: int, party_devices=None) -> dict:
    """Warm-up + ROUNDS FedAvg rounds of ResNet-18 in wire form ``form``.

    ``party_devices``: ``{party: [device, ...]}`` pins each party to its
    own chip(s) (``--chips 4``); None leaves all four on the one chip.
    """
    import numpy as np

    from rayfed_tpu import telemetry

    warmup = WIRE_FORMS[form]["warmup"]
    n_rounds = warmup + ROUNDS
    # The accumulator belongs to the coordinator.  Across chips make it
    # the LAST party: everything landing on chip 0 is the failure to find.
    coordinator = PARTIES[-1] if party_devices else None
    coord = coordinator or min(PARTIES)
    t_phase = time.time()
    out, records = run_resnet_rounds(form, seed, party_devices, coordinator)

    losses = [
        [records[(p, r)]["loss"] for p in PARTIES] for r in range(n_rounds)
    ]
    require(
        all(np.isfinite(v) for row in losses for v in row),
        f"{form}: non-finite loss {losses}",
    )
    accuracy = check_against_numpy(form, out, records)
    folds = [
        rec for rec in telemetry.installed().records()
        if rec.phase == "agg.fold" and rec.t_start >= t_phase
    ]
    placement = check_placement(
        form, out, records, folds, coord, party_devices
    )
    if party_devices:
        for p, where in placement.items():
            emit(phase=f"resnet18_{form}", party=p, device_ids=where)
    sent = check_wire(form, out)

    measured = [
        records[(p, r)] for p in PARTIES for r in range(warmup, n_rounds)
    ]
    t0 = min(records[(p, warmup)]["t"] for p in PARTIES)
    t1 = max(out[p]["t_end"] for p in PARTIES)
    sent_measured = sum(sent[p] - records[(p, warmup)]["sent"] for p in PARTIES)
    return {
        "parties": len(PARTIES),
        "rounds": ROUNDS,
        "warmup_rounds": warmup,
        "coordinator": coord,
        **accuracy,
        "losses": [[round(v, 4) for v in row] for row in losses],
        "fold": sorted({f.detail["fold"] for f in folds}) or None,
        "round_s": round((t1 - t0) / ROUNDS, 4),
        "step_s": round(float(np.median([m["step_s"] for m in measured])), 4),
        "wire_bytes_per_round": sent_measured // ROUNDS,
        "wire_bytes_total": sent,
    }


# ---------------------------------------------------------------------------
# Llama train steps through the Pallas flash kernel (rayfed_tpu/ops)
# ---------------------------------------------------------------------------


def llama_flash_phase(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rayfed_tpu.models import llama
    from rayfed_tpu.ops.attention import dot_product_attention
    from rayfed_tpu.ops.flash_attention import flash_attention

    cfg = llama.LlamaConfig(
        vocab_size=16384, hidden_size=2048, num_layers=LLAMA_LAYERS,
        num_heads=16, num_kv_heads=8, intermediate_size=8192,
        max_seq_len=2048, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat=True, remat_policy="dots",
    )
    batch, seq = 2, 2048
    ids = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, seq), 0, cfg.vocab_size
    )
    result: dict = {
        "layers": LLAMA_LAYERS, "layers_published": 16, "hidden": 2048,
        "heads": 16, "kv_heads": 8, "head_dim": cfg.head_dim, "ffn": 8192,
        "batch": batch, "seq": seq,
    }
    for name, attn_fn in (
        ("flash", flash_attention), ("dense", dot_product_attention),
    ):
        params = llama.init_llama(jax.random.PRNGKey(seed), cfg)
        opt = llama.init_adam(params)
        step = llama.make_train_step(cfg, attn_fn=attn_fn)
        lowered = step.lower(params, opt, ids)
        kernel = "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
        losses, t0 = [], time.perf_counter()
        for _ in range(2):
            params, opt, loss = compiled(params, opt, ids)
            losses.append(float(loss))
        result[name] = {
            "losses": [round(v, 4) for v in losses],
            "tpu_custom_call": kernel,
            "two_steps_s": round(time.perf_counter() - t0, 4),
            "loss_devices": device_ids(loss),
        }
        require(all(np.isfinite(losses)), f"llama {name}: loss {losses}")
        require(
            loss.devices().pop().platform == PLATFORM,
            f"llama {name}: ran on {loss.devices()}",
        )
        del params, opt
    require(
        result["flash"]["tpu_custom_call"],
        "llama: no tpu_custom_call in the lowered flash step — the Pallas "
        "kernel was not compiled (interpreted?)",
    )
    require(
        not result["dense"]["tpu_custom_call"],
        "llama: the dense reference step contains a custom call",
    )
    diff = abs(result["flash"]["losses"][0] - result["dense"]["losses"][0])
    result["step0_loss_diff"] = round(diff, 5)
    result["step0_loss_atol"] = LLAMA_LOSS_ATOL
    require(
        diff <= LLAMA_LOSS_ATOL,
        f"llama: flash vs dense step-0 loss differ by {diff}",
    )
    return result


# ---------------------------------------------------------------------------
# --chips 4: the paths that exist only across chips
# ---------------------------------------------------------------------------


def resnet_per_chip_phase(form: str, seed: int) -> dict:
    """Layout (A): four parties, each pinned to its own chip."""
    import jax

    devices = jax.devices()[: len(PARTIES)]
    return resnet_round_phase(
        form, seed, party_devices={p: [d] for p, d in zip(PARTIES, devices)}
    )


def mesh_fedavg_phase(seed: int) -> dict:
    """Layout (B): examples/mesh_fedavg as two parties x a two-chip
    ``fsdp`` mesh, against the numpy reference of the same updates."""
    del seed  # the program is deterministic: w grows by mean(1, 2) a round
    import jax
    import numpy as np

    from examples import mesh_fedavg
    from rayfed_tpu import inprocess
    from rayfed_tpu.parallel.mesh import create_mesh

    devices = jax.devices()
    parties = mesh_fedavg.PARTIES
    party_devices = {
        p: devices[2 * i: 2 * i + 2] for i, p in enumerate(parties)
    }
    rounds = mesh_fedavg.ROUNDS

    def party_main(party: str):
        params = mesh_fedavg.fedavg_rounds(party, rounds)
        return {
            "w": np.asarray(params["w"]),
            "devices": device_ids(params["w"]),
        }

    out = inprocess.run_parties(
        party_main,
        inprocess.loopback_cluster(parties),
        meshes={
            p: create_mesh({"fsdp": 2}, devices=d)
            for p, d in party_devices.items()
        },
        timeout=600, logging_level="warning",
    )
    w = np.zeros((mesh_fedavg.ROWS, mesh_fedavg.COLS), np.float32)
    for _ in range(rounds):
        w = numpy_fedavg([w + np.float32(i + 1) for i in range(len(parties))])
    err = 0.0
    for p in parties:
        mine = sorted(d.id for d in party_devices[p])
        require(
            out[p]["devices"] == mine,
            f"mesh_fedavg: {p}'s aggregate sits on {out[p]['devices']}, "
            f"its mesh is {mine}",
        )
        require(
            out[p]["w"].tobytes() == out[parties[0]]["w"].tobytes(),
            f"mesh_fedavg: {p}'s result differs from {parties[0]}'s",
        )
        err = max(err, float(np.abs(out[p]["w"] - w).max()))
        # Trainer state and received payloads are asserted inside the
        # program (Trainer.train: the incoming tree's device set IS the
        # party's mesh); the aggregate follows its sharded inputs.
        emit(
            phase="mesh_fedavg", party=p,
            device_ids={
                "trainer_state": mine, "received": mine,
                "aggregate": out[p]["devices"],
            },
        )
    require(err <= 1e-5, f"mesh_fedavg: off the numpy FedAvg by {err}")
    return {
        "parties": len(parties), "chips_per_party": 2, "rounds": rounds,
        "max_abs_err_vs_numpy": err, "finals_identical": True,
    }


PHASES = {
    1: [
        ("resnet18_bf16", lambda seed: resnet_round_phase("bf16", seed)),
        ("resnet18_uint8", lambda seed: resnet_round_phase("uint8", seed)),
        ("llama_flash", llama_flash_phase),
    ],
    4: [
        ("resnet18_bf16", lambda seed: resnet_per_chip_phase("bf16", seed)),
        ("resnet18_uint8", lambda seed: resnet_per_chip_phase("uint8", seed)),
        ("mesh_fedavg", mesh_fedavg_phase),
    ],
}


def environment() -> dict:
    import importlib.metadata
    import shutil

    import jax
    import jaxlib

    from rayfed_tpu import native
    from rayfed_tpu.utils import use_compilation_cache

    def version(dist: str):
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return None

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": version("libtpu"),
        "compile_cache_dir": use_compilation_cache(),
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
        "native_byte_path": native.is_available(),
        "native_status": native.status(),
        "gxx": shutil.which("g++"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=sorted(PHASES), default=1,
        help="4 runs ONLY the multi-chip layouts (A) and (B)",
    )
    parser.add_argument(
        "--seed", type=int, default=0, help="weights and data are made from it",
    )
    args = parser.parse_args(argv)

    device = None
    failed = []
    try:
        env = environment()  # imports rayfed_tpu: fails outside the repo
        device = check_device(args.chips)
        emit(phase="environment", **env, device=device)
        import jax

        clock = CompileClock()
        for name, phase in PHASES[args.chips]:
            c0, t0 = clock.snapshot(), time.perf_counter()
            error = None
            try:
                result = {"ok": True, **phase(args.seed)}
            # Report the failure as this phase's line and go on to the
            # next: one chip call should show every broken phase.
            except Exception as e:  # noqa: BLE001
                import traceback

                traceback.print_exc()
                error = e
                result = {"ok": False, "error": repr(e)[:2000]}
                failed.append(name)
            c1 = clock.snapshot()
            emit(
                phase=name, **result,
                wall_s=round(time.perf_counter() - t0, 3),
                compile_s=round(c1[0] - c0[0], 3),
                compile_cache_hits=c1[1] - c0[1],
                compile_cache_misses=c1[2] - c0[2],
                # fed.init turns it off for multi-chip sub-slice meshes
                # (rayfed_tpu.utils.platform.guard_subslice_mesh).
                compile_cache_on=jax.config.jax_enable_compilation_cache,
            )
            if isinstance(error, TimeoutError):
                break  # its party threads still run and hold the chip
    except Exception as e:  # noqa: BLE001 — the verdict line carries it
        emit(ok=False, error=repr(e)[:2000], device=device)
        return 1
    if failed:
        emit(ok=False, error=f"failed phases: {failed}", device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
