"""The latent-attention decoder's timed program against the plain
reference ON THE CHIP, at the published widths and the cell's 8,192
tokens: what ``tests/test_kimi_k2.py`` shows at toy widths on the CPU
(the kernels' interpreter, ``ragged_dot``), here with the compiled flash
kernels on a two-part score (128 + 64 wide, one shared rotary key head,
values 128 wide), the megablox ``gmm`` at K = 7,168, the checkpointed
scan and the hand-written backward pass of the chunk loop.

Run it through the chip tool, alone (a chip belongs to one process):
``python -m pytest benchmark/chip/test_kimi_k2_on_chip.py -q -s``.
Skipped where JAX finds no TPU.  Not under ``benchmark/tests``: that
directory's conftest pins the CPU.
"""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import kimi_k2_lm
from benchmark.reference import kimi_k2

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs the chip"
)


@pytest.fixture(autouse=True)
def _free_the_chip():
    """A test's base is 4-7 GB: let it go before the next one makes its
    own."""
    yield
    import gc

    gc.collect()
CELL = "kimi-k2.7-code-ep32.lora-all-linear-2p"
# The step's gradient against the float32 reference's, relative RMS over
# each adapter leaf of each layer.  Two readings of the same program on
# the same weights (my chip run, PR 33; PERF.md section 6): computing in
# bf16 as the cell does, 3.5-6.3% over the 28 leaves of three layers
# (the experts' leaves lowest, the query latent's highest); computing in
# float32, 0.012-0.053%.  So the distance is the precision's, not the
# mathematics': it is three times Trinity's 1.2-1.9% as the logits' is
# twice (no norm after a sub-block, chained latent projections, scores
# sharpened 2.005 times, and the loss's gradient carries the forward's
# error again).  The bf16 limit lies between that and what a fault
# reads: a selection made again in the backward pass moved every expert
# leaf by 13% (PR 28); a rotary key's gradient not summed over its 64
# heads, or a chunk the backward loop skipped, moves the leaves it
# touches by their own size.
GRADIENT_REL_RMS_TOL = 0.10
GRADIENT_REL_RMS_TOL_F32 = 0.01


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def test_the_timed_steps_gradients_are_the_references(monkeypatch):
    """What ``jit_decoder_lora_step`` differentiates
    (``decoder.lora_loss``: the step less its Adam update), at the
    published widths on the dense layer and two expert layers (both
    scanned groups), 8,192 tokens, as the cell computes it (bf16) and
    again in float32 (matrix products at ``highest``; 512 x 512 flash
    blocks and a quarter of the grouped product's tile, since the
    kernels' float32 tiles do not fit VMEM at the timed sizes).  The
    reference is given the selection the program it is compared with
    made (its ``aux``, saved across the recomputation by the
    checkpoint's policy) and recomputes each layer, attention block,
    expert and FFN row block in its backward pass (``remat``: memory,
    not mathematics), so that its float32 activations fit beside the
    base."""
    import dataclasses
    import functools

    from rayfed_tpu.models import decoder, moe
    from rayfed_tpu.ops.flash_attention import flash_attention

    cell = harness.load_cell(CELL)
    config = copy.deepcopy(cell["config_data"])
    config["num_hidden_layers"] = 3
    fam = kimi_k2_lm.build(config, cell["job"], 20290301)
    cfg = fam.cfg
    assert cfg.remat and fam.seq == cell["job"]["seq_len"]  # as the cell runs
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

    def system(cfg, attn_fn):
        """(loss, gradients on the host, the selection it made)."""
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda a, b, i: decoder.lora_loss(a, b, i, cfg, attn_fn=attn_fn),
            has_aux=True,
        ))(adapters, base, ids)
        counts = np.asarray(decoder.routing_counts(aux))
        print("held assignments a layer", counts[:, -1],
              "rows an expert", counts[:, :-1].tolist())
        assert (counts[:, :-1].sum(axis=1) == counts[:, -1]).all()  # none dropped
        chosen = {i: a["selected"] for i, a in aux.items()}
        return float(loss), jax.device_get(grads), chosen

    kw = dict(fam.reference_kwargs(), num_dense_layers=1, block=256, remat=True)

    @jax.jit
    @functools.partial(jax.value_and_grad, argnums=1)
    def reference(p, l, i, chosen):
        # both trees unstacked INSIDE the program: a layer's slice is
        # then no second copy of the base beside the first
        return kimi_k2.loss(
            decoder.unstack(p, cfg), i, lora=decoder.unstack(l, cfg),
            selected=chosen, **kw
        )

    def distance(loss, grads, chosen):
        """Worst layer's relative RMS of each adapter leaf."""
        with jax.default_matmul_precision("highest"):
            want_loss, want = reference(base, adapters, ids[0], chosen)
        print("loss", loss, "reference", float(want_loss))
        assert abs(loss - float(want_loss)) < 2e-3 * float(want_loss)
        # both are in the adapters' stacked layout; layer by layer
        got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
        want = jax.tree_util.tree_leaves(
            decoder.unstack(jax.device_get(want), cfg)
        )
        worst = {}
        for (path, g), w in zip(got, want):
            if path[-1].key == "scale":
                continue
            assert float(np.abs(w).max()) > 0, path
            name = "/".join(str(k.key) for k in path[2:])
            worst[name] = max(worst.get(name, 0.0), rel_rms(g, w))
        return worst

    as_the_cell = system(cfg, fam.attn_fn)
    print("system peak GB",
          jax.local_devices()[0].memory_stats()["peak_bytes_in_use"] / 1e9)
    with jax.default_matmul_precision("highest"), monkeypatch.context() as m:
        m.setattr(moe, "GMM_TILING", (256, 1024, 256))
        in_float32 = system(
            dataclasses.replace(cfg, dtype=jnp.float32),
            functools.partial(flash_attention, block_q=512, block_k=512),
        )
    worst = distance(*as_the_cell)
    print("bf16 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 4) for k, v in sorted(worst.items())})
    worst32 = distance(*in_float32)
    print("float32 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 5) for k, v in sorted(worst32.items())})
    assert max(worst.values()) < GRADIENT_REL_RMS_TOL, worst
    assert max(worst32.values()) < GRADIENT_REL_RMS_TOL_F32, worst32


@pytest.mark.parametrize("seed", [20290302, 20290303])
def test_the_bf16_system_passes_and_an_fp8_forward_fails(seed):
    """The comparison that decides ``correct``, both ways: the system as
    the cell runs it passes every limit; the control (the reference with
    fp8 (e4m3) operands in every matrix product, in the system's place)
    comes out not ok, by the logits' limit and the routing's, not by one
    alone.  Prints both readings: the limits in ``kimi_k2_lm.py`` lie
    between them."""
    cell = harness.load_cell(CELL)
    fam = kimi_k2_lm.build(cell["config_data"], cell["job"], seed)
    check = fam.reference_check()
    print("bf16 system", check)
    print("peak GB",
          jax.local_devices()[0].memory_stats()["peak_bytes_in_use"] / 1e9)
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    print("fp8 control", control)
    assert check["ok"] is True
    assert control["ok"] is False
    assert control["rel_rms"] > control["tol"]
    assert control["routing_shortfall"] > control["routing_delta"]
    assert control["routing_exact_share"] < control["routing_exact_min"]
