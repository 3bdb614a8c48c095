"""The plain reference for the federated round: float32 numpy FedAvg.

Copied from ``chip_smoke.py`` (``numpy_fedavg``, ``flat_f32`` and the
stated tolerances); uses nothing from ``rayfed_tpu.fl``.
"""

from __future__ import annotations

import numpy as np

# A bf16 aggregate is the f32 mean rounded once to bf16: half an ulp.
BF16_RTOL = 2.0**-8 * 1.01
# A uint8 aggregate is off by grid steps, so by a fraction of how far
# the model moved that round, not of the weights: the uplink grid is
# ranged by 4 x the previous round's aggregate delta over 255 levels, a
# party's delta that overshoots clips and rides the error-feedback
# residual into the next round, and the downlink recodes once more.
QUANT_DELTA_FRAC = 0.1


def numpy_fedavg(updates):
    """Plain float32 mean of flat update buffers."""
    acc = np.zeros(updates[0].shape, np.float32)
    for u in updates:
        acc += np.asarray(u).astype(np.float32)
    return acc / np.float32(len(updates))


def flat_f32(tree):
    """A pytree's leaves as one flat float32 host buffer, in
    ``tree_leaves`` order (the order the packed wire buffer uses)."""
    import jax

    return np.concatenate([
        np.asarray(leaf, np.float32).ravel()
        for leaf in jax.tree_util.tree_leaves(tree)
    ])


def check_rounds(updates, received, init, quantized_from=None):
    """Every round's aggregate against the numpy FedAvg of its updates.

    ``updates[r]``: the parties' flat update buffers of round ``r``;
    ``received[r]``: what each party held after round ``r`` (the next
    round's input in its wire form, or the returned model for the last
    round); ``init``: the flat starting model; ``quantized_from``: first
    round aggregated in the uint8 domain (None: none was).  Returns
    ``(ok, worst)`` with ``worst = {kind: {err, tol, frac_of_tol,
    round}}``.
    """
    refs = [numpy_fedavg(u) for u in updates]
    moved = [
        float(np.abs(cur - prev).max())
        for prev, cur in zip([init] + refs, refs)
    ]
    worst: dict = {}
    n_rounds = len(refs)
    for r, ref in enumerate(refs):
        quantized = quantized_from is not None and r >= quantized_from
        last = r == n_rounds - 1
        tol = np.zeros_like(ref)
        if quantized:
            tol += QUANT_DELTA_FRAC * max(moved[r], moved[r - 1])
        if not (last and quantized):  # the aggregate met the bf16 wire
            tol += BF16_RTOL * np.abs(ref) + 1e-12
        kind = worst.setdefault(
            "uint8" if quantized else "bf16", {"frac_of_tol": -1.0}
        )
        for got in received[r]:
            got = np.asarray(got).astype(np.float32)
            if got.shape != ref.shape:
                return False, {"layout_mismatch": [got.shape, ref.shape]}
            err = np.abs(got - ref)
            i = int(np.argmax(err / tol))
            if err[i] / tol[i] > kind["frac_of_tol"]:
                kind.update(
                    err=float(err[i]), tol=float(tol[i]),
                    frac_of_tol=float(err[i] / tol[i]), round=r,
                )
    ok = all(k["frac_of_tol"] <= 1.0 for k in worst.values())
    return ok, worst
