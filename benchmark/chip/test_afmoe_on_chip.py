"""The expert decoder's timed program against the plain reference ON THE
CHIP, at the published widths: what ``tests/test_afmoe.py`` shows at toy
widths on the CPU (where the grouped product is ``ragged_dot`` or the
kernel's interpreter), here with the compiled megablox ``gmm`` and its
transpose, the flash kernels under the scanned body's ``cond``, the
checkpointed scan and the hand-written backward pass of the chunk loop.

Run it through the chip tool, alone (a chip belongs to one process):
``python -m pytest benchmark/chip/test_afmoe_on_chip.py -q -s``.
Skipped where JAX finds no TPU.  Not under ``benchmark/tests``: that
directory's conftest pins the CPU.
"""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import afmoe_lm
from benchmark.reference import afmoe

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs the chip"
)
CELL = "trinity-mini-ep8.lora-all-linear-2p"
# The step's gradient against the float32 reference's, relative RMS over
# each adapter leaf of each layer.  The step multiplies in bf16 (2^-9 a
# rounding) and a leaf's gradient sums some hundreds to thousands of
# tokens' products, forward roundings carried through the backward pass:
# read 1.15-1.87% over the 28 leaves on the chip (wq and wk highest;
# PERF.md section 6, PR 28).  The limit lies between that and what a
# fault reads: 13% on every expert leaf when one (token, choice) pair in
# a hundred went to another expert than the reference was told (read
# before the step saved its selection across the recomputation); a chunk
# the backward loop skipped, or rows through another expert's adapter,
# move the leaves they touch by their own size.
GRADIENT_REL_RMS_TOL = 0.05


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def test_the_timed_steps_gradients_are_the_references(monkeypatch):
    """What ``jit_decoder_lora_step`` differentiates
    (``decoder.lora_loss``: the step less its Adam update), at the
    published widths on a dense windowed layer, a windowed and a full
    expert layer (one scanned group, both kernels under its ``cond``),
    3,072 tokens (a window and a half).  The chunk is cut to half the
    expected held rows, so that every layer takes two or three, forward
    and backward.  The reference is given the selection this very
    program made (its ``aux``, saved across the recomputation by the
    checkpoint's policy): one that selects again from scores rounded in
    another fusion differs in about one pair in a hundred, which moves
    an expert's adapter gradient (a sum of terms of random sign) by a
    seventh (read on the chip, PR 28: 13%)."""
    from rayfed_tpu.models import decoder, moe

    monkeypatch.setattr(moe, "CHUNK_HEADROOM", 0.5)
    cell = harness.load_cell(CELL)
    config = copy.deepcopy(cell["config_data"])
    config["num_hidden_layers"] = 3
    config["layer_types"] = [
        "sliding_attention", "sliding_attention", "full_attention"
    ]
    job = dict(cell["job"], seq_len=3072)
    fam = afmoe_lm.build(config, job, 20280928)
    cfg = fam.cfg
    assert cfg.remat  # the checkpointed scan, as the cell runs it
    base = fam._make_base(fam.base_key())
    adapters = fam.init_global()
    # B starts at zero, where A has no gradient: give every B a value.
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    adapters = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key != "b"
        else 0.02 * jax.random.normal(next(keys), x.shape),
        adapters,
    )
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, fam.seq), 0,
                             cfg.vocab_size)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda a, b, i: decoder.lora_loss(a, b, i, cfg, attn_fn=fam.attn_fn),
        has_aux=True,
    ))(adapters, base, ids)
    counts = np.asarray(decoder.routing_counts(aux))
    rows, _ = moe._chunk_rows(fam.seq, cfg.experts)
    print("held assignments a layer", counts[:, -1], "chunk rows", rows)
    assert (counts[:, :-1].sum(axis=1) == counts[:, -1]).all()  # none dropped
    assert (counts[:, -1] > rows).all(), "a layer took one chunk only"

    chosen = {i: a["selected"] for i, a in aux.items()}
    kw = fam.reference_kwargs(len(cfg.layers))
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.jit(
            lambda p, l, i, chosen: afmoe.lora_gradients(
                p, l, i, selected=chosen, **kw
            )
        )(decoder.unstack(base, cfg), decoder.unstack(adapters, cfg),
          ids[0], chosen)
    assert abs(float(loss) - float(want_loss)) < 2e-3 * float(want_loss)
    got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
    worst = {}
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want)):
        if path[-1].key == "scale":
            continue
        assert float(jnp.abs(w).max()) > 0, path
        name = "/".join(str(k.key) for k in path[2:])
        worst[name] = max(worst.get(name, 0.0), rel_rms(g, w))
    print("gradient rel rms, worst layer of each leaf:",
          {k: round(v, 4) for k, v in sorted(worst.items())})
    assert max(worst.values()) < GRADIENT_REL_RMS_TOL, worst


def test_an_fp8_forward_fails_the_cells_own_comparison():
    """The control of the comparison that decides ``correct``: the
    reference with fp8 (e4m3) operands in every matrix product, in the
    system's place, must come out not ok, and by the logits' limit and
    the routing's, not by one alone."""
    cell = harness.load_cell(CELL)
    fam = afmoe_lm.build(cell["config_data"], cell["job"], 20280929)
    check = fam.reference_check(round_to=jnp.float8_e4m3fn)
    print(check)
    assert check["ok"] is False
    assert check["rel_rms"] > check["tol"]
    assert check["routing_shortfall"] > check["routing_delta"]
    assert check["routing_exact_share"] < check["routing_exact_min"]
