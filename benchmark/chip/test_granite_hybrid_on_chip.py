"""The state-space hybrid's timed program against the plain reference ON
THE CHIP, at the published widths and the cell's 8,192 tokens: what
``tests/test_granite_hybrid.py`` shows at toy widths on the CPU, here
with the chunked scan in bf16 products at 64 heads x 64 wide, a state of
128 and 32 chunks of 256, the compiled flash kernels at 32 x 64-wide
heads on 8 K/V heads with the score scale given, the checkpointed scan
of each group and the fused head-and-loss on the tied 100,352-row
embedding.

Run it through the chip tool, alone (a chip belongs to one process):
``python -m pytest benchmark/chip/test_granite_hybrid_on_chip.py -q -s``.
Skipped where JAX finds no TPU.  Not under ``benchmark/tests``: that
directory's conftest pins the CPU.
"""

import copy
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import granite_hybrid_lm
from benchmark.reference import granite_hybrid as ref

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs the chip"
)


@pytest.fixture(autouse=True)
def _free_the_chip():
    """A test's base is 1-3 GB: let it go before the next one makes its
    own."""
    yield
    import gc

    gc.collect()


CELL = "granite-4.0-h-micro-d20.lora-all-linear-2p"
# The scan alone against the float32 token-by-token recurrence, relative
# RMS of the output and of each of the six gradients.  Two readings of
# the same function on the same inputs (my chip run, PR 35): with bf16
# operands as the cell computes, 0.0015 (D) to 0.0038 (B, C), the
# output 0.0021: one bf16 rounding of each product's operands; with
# float32 operands 0 to 9.7e-5 (A, a sum over all 8,192 tokens of a
# head).  So the distance is the precision's, not the chunked form's.  A
# fault reads its own size: the state dropped at a chunk boundary moves
# the output by over 1e-2 already at toy lengths
# (``tests/test_granite_hybrid.py``) and the heads that carry across
# all 32 chunks by far more.
SCAN_REL_RMS_TOL = 0.02
SCAN_REL_RMS_TOL_F32 = 1e-3
# The step's gradient against the float32 reference's, relative RMS over
# each adapter leaf of each layer.  Two readings of the same program on
# the same weights (my chip run, PR 35): computing in bf16 as the cell
# does, 0.86-1.96% over the 18 leaves of six layers (the attention
# layer's `wo` and `wv` lowest, `wk` highest; Trinity's read 1.2-1.9%,
# Kimi's 3.5-6.3%); computing in float32, 0.003-0.004%.  The bf16 limit
# lies between that and what a fault reads: a leaf whose gradient misses
# a chunk, a head or the convolution's shift moves by its own
# size; a selection made again in the backward pass moved Trinity's
# expert leaves by 13% (PR 28).
GRADIENT_REL_RMS_TOL = 0.06
GRADIENT_REL_RMS_TOL_F32 = 0.002


def peak_gb():
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def test_the_chunked_scan_is_the_recurrence_at_the_published_shape():
    """``ssd_scan`` alone at ``[1, 8192, 64, 64]``, state 128, one group,
    chunks of 256, inputs in the regime the mixer gives it (``dt`` the
    softplus of a unit normal about the reference initialisation's
    bias, ``A`` in [-16, -1], ``B`` and ``C`` after a silu): forward and
    all six gradients against the token-by-token recurrence in float32,
    with bf16 operands as the cell computes and with float32 operands."""
    from rayfed_tpu.ops.ssd import ssd_scan

    t, h, p, n = 8192, 64, 64, 128
    k = jax.random.split(jax.random.PRNGKey(20350401), 8)
    dt0 = jnp.exp(jax.random.uniform(
        k[1], (h,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    args = (
        jax.nn.silu(jax.random.normal(k[0], (1, t, h, p))),
        jax.nn.softplus(jax.random.normal(k[2], (1, t, h)) + bias),
        -jax.random.uniform(k[3], (h,), minval=1.0, maxval=16.0),
        jax.nn.silu(jax.random.normal(k[4], (1, t, 1, n))),
        jax.nn.silu(jax.random.normal(k[5], (1, t, 1, n))),
        jnp.ones((h,)),
    )
    w = jax.random.normal(k[6], (1, t, h, p))

    def system(dtype):
        cast = lambda x, dt, a, b, c, d: (
            x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d
        )

        def loss(*v):
            y = ssd_scan(*cast(*v), chunk=256)
            return jnp.sum(y.astype(jnp.float32) * w), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(6), has_aux=True
        ))(*args)
        return y, grads

    @jax.jit
    def reference(*v):
        def loss(x, dt, a, b, c, d):
            y = ref.recurrence(
                x[0], dt[0], a, jnp.repeat(b[0], h, axis=1),
                jnp.repeat(c[0], h, axis=1), d, remat=True,
            )[None]
            return jnp.sum(y * w), y

        return jax.value_and_grad(loss, argnums=range(6), has_aux=True)(*v)

    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = reference(*args)
        y32, grads32 = system(jnp.float32)
    y16, grads16 = system(jnp.bfloat16)
    names = "x dt A B C D".split()
    for label, y, grads, tol in (
        ("bf16", y16, grads16, SCAN_REL_RMS_TOL),
        ("float32", y32, grads32, SCAN_REL_RMS_TOL_F32),
    ):
        read = {"y": rel_rms(y, want)}
        read.update({
            n_: rel_rms(g, r) for n_, g, r in zip(names, grads, want_grads)
        })
        print(label, "scan rel rms", {k_: round(v, 6) for k_, v in read.items()})
        assert max(read.values()) < tol, (label, read)


def test_the_timed_steps_gradients_are_the_references():
    """What ``jit_decoder_lora_step`` differentiates
    (``decoder.lora_loss``: the step less its Adam update), at the
    published widths on layers 0-5 (five Mamba layers and the attention
    layer: two scanned groups), 8,192 tokens, as the cell computes it
    (bf16) and again in float32 (matrix products at ``highest``, 512 x
    512 flash blocks).  The reference recomputes each layer, attention
    block, FFN row block and scan block in its backward pass (``remat``:
    memory, not mathematics)."""
    import dataclasses

    from rayfed_tpu.models import decoder
    from rayfed_tpu.ops.flash_attention import flash_attention

    cell = harness.load_cell(CELL)
    config = copy.deepcopy(cell["config_data"])
    config["num_hidden_layers"] = 6
    fam = granite_hybrid_lm.build(config, cell["job"], 20350301)
    cfg = fam.cfg
    assert cfg.remat and fam.seq == cell["job"]["seq_len"]  # as the cell runs
    assert cfg.groups() == ((0, 5), (5, 6))
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
        loss, grads = jax.jit(jax.value_and_grad(
            lambda a, b, i: decoder.lora_loss(a, b, i, cfg, attn_fn=attn_fn)[0]
        ))(adapters, base, ids)
        return float(loss), jax.device_get(grads)

    kw = dict(fam.reference_kwargs(6), block=256, remat=True,
              logits_scaling=config["logits_scaling"])

    @jax.jit
    @functools.partial(jax.value_and_grad, argnums=1)
    def reference(p, l, i):
        # both trees unstacked INSIDE the program: a layer's slice is
        # then no second copy of the base beside the first
        return ref.loss(
            decoder.unstack(p, cfg), i, lora=decoder.unstack(l, cfg), **kw
        )

    with jax.default_matmul_precision("highest"):
        want_loss, want = reference(base, adapters, ids[0])
    want = jax.tree_util.tree_leaves(
        decoder.unstack(jax.device_get(want), cfg)
    )

    def distance(loss, grads):
        """Worst layer's relative RMS of each adapter leaf."""
        print("loss", loss, "reference", float(want_loss))
        assert abs(loss - float(want_loss)) < 2e-3 * float(want_loss)
        got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
        worst = {}
        for (path, g), w in zip(got, want):
            if path[-1].key == "scale":
                continue
            assert float(np.abs(w).max()) > 0, path
            name = "/".join(str(k.key) for k in path[2:])
            worst[name] = max(worst.get(name, 0.0), rel_rms(g, w))
        return worst

    worst = distance(*system(cfg, fam.attn_fn))
    print("system peak GB", peak_gb())
    print("bf16 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 4) for k, v in sorted(worst.items())})
    with jax.default_matmul_precision("highest"):
        worst32 = distance(*system(
            dataclasses.replace(cfg, dtype=jnp.float32),
            functools.partial(flash_attention, block_q=512, block_k=512),
        ))
    print("float32 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 5) for k, v in sorted(worst32.items())})
    assert max(worst.values()) < GRADIENT_REL_RMS_TOL, worst
    assert max(worst32.values()) < GRADIENT_REL_RMS_TOL_F32, worst32


@pytest.mark.parametrize("seed", [20350302, 20350303])
def test_the_bf16_system_passes_and_an_fp8_forward_fails(seed):
    """The comparison that decides ``correct``, both ways: the system as
    the cell runs it passes every limit; the control (the reference with
    fp8 (e4m3) operands in every matrix product and in the scan's, in
    the system's place) comes out not ok, by the logits' limit.  Prints
    both readings: the limit in ``granite_hybrid_lm.py`` lies between
    them."""
    cell = harness.load_cell(CELL)
    fam = granite_hybrid_lm.build(cell["config_data"], cell["job"], seed)
    check = fam.reference_check()
    print("bf16 system", check)
    print("peak GB", peak_gb())
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    print("fp8 control", control)
    assert check["ok"] is True
    assert control["ok"] is False
    assert control["rel_rms"] > control["tol"]
