"""The ``nemotron_h`` cell's timed program against the plain reference ON
THE CHIP, at the published widths and the cell's 8,192 tokens: what
``tests/test_nemotron_h.py`` shows at toy widths on the CPU, here with
the chunked scan in bf16 products at 128 heads x 64 wide in 8 groups, a
state of 128 and 64 chunks of 128, the grouped products of 64 held
experts in the 1,024-wide latent, the compiled flash kernels at 32 x
128-wide heads on 2 K/V heads, the checkpointed groups, the MTP module
and both fused heads.

Run it on one TPU chip, alone (a chip belongs to one process):
``python -m pytest benchmark/chip/test_nemotron_h_on_chip.py -q -s``.
Skipped where JAX finds no TPU.  Not under ``benchmark/tests``: that
directory's conftest pins the CPU.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import nemotron_h_lm
from benchmark.reference import granite_hybrid as granite_ref
from benchmark.reference import nemotron_h as ref

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs the chip"
)


@pytest.fixture(autouse=True)
def _free_the_chip():
    """A test's base is 6.5 GB: let it go before the next one makes its
    own."""
    yield
    import gc

    gc.collect()


CELL = "nemotron-3-super-120b-a12b-ep8-d11.lora-all-linear-2p"
# The scan alone against the float32 token-by-token recurrence, relative
# RMS of the output and of each of the six gradients: with bf16 operands
# as the cell computes, one bf16 rounding of each product's operands
# (granite's shape read 0.0015-0.0038, PERF.md section 4); with float32
# operands the order of the sums alone (granite's: under 1e-4).  A fault
# reads its own size: a group's B and C given to another group's heads,
# or the state dropped at a chunk boundary, move the output by over 1e-2
# already at toy lengths (``tests/test_nemotron_h.py``).
SCAN_REL_RMS_TOL = 0.02
SCAN_REL_RMS_TOL_F32 = 1e-3
# The step's gradient against the float32 reference's, relative RMS over
# each adapter leaf of each layer, worst layer, the reference given the
# gradient program's own selection: computing in bf16 as the cell does,
# 0.63-2.85% over the 46 leaves of eleven blocks and the MTP module (the
# attention blocks' `wq` and `wk` highest; granite's read 0.86-1.96%,
# Kimi's 3.5-6.3%; read on a TPU v5 lite, PERF.md section 4), at toy
# widths 1.5-11% and float32 1e-6 (``tests/test_nemotron_h.py``'s toy,
# on the CPU).  The
# limit is twice the largest reading; a selection the gradient did not
# make reads 6-32% (another program's, even the same forward compiled
# apart), and a leaf whose gradient misses a chunk, a group or the MTP
# module's shift moves by its own size.
GRADIENT_REL_RMS_TOL = 0.06
# The float32 reference's gradient of all eleven blocks and the MTP
# module at the cell's 8,192 tokens does not fit beside the base (the
# chip's compiler asked 20.8 GB of 15.75), so the comparison runs at
# 2,048 tokens: every block, width and kernel as the cell runs them.
GRADIENT_TOKENS = 2048


def peak_gb():
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def test_the_chunked_scan_with_eight_groups_is_the_recurrence():
    """``ssd_scan`` alone at ``[1, 8192, 128, 64]``, state 128, 8 groups
    (16 heads a group), chunks of 128, inputs in the regime the mixer
    gives it: forward and all six gradients against the token-by-token
    recurrence in float32, with bf16 operands as the cell computes and
    with float32 operands."""
    from rayfed_tpu.ops.ssd import ssd_scan

    t, h, p, g, n = 8192, 128, 64, 8, 128
    k = jax.random.split(jax.random.PRNGKey(20400401), 8)
    dt0 = jnp.exp(jax.random.uniform(
        k[1], (h,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    args = (
        jax.nn.silu(jax.random.normal(k[0], (1, t, h, p))),
        jax.nn.softplus(jax.random.normal(k[2], (1, t, h)) + bias),
        -jax.random.uniform(k[3], (h,), minval=1.0, maxval=16.0),
        jax.nn.silu(jax.random.normal(k[4], (1, t, g, n))),
        jax.nn.silu(jax.random.normal(k[5], (1, t, g, n))),
        jnp.ones((h,)),
    )
    w = jax.random.normal(k[6], (1, t, h, p))

    def system(dtype):
        cast = lambda x, dt, a, b, c, d: (
            x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d
        )

        def loss(*v):
            y = ssd_scan(*cast(*v), chunk=128)
            return jnp.sum(y.astype(jnp.float32) * w), y

        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(6), has_aux=True
        ))(*args)
        return y, grads

    @jax.jit
    def reference(*v):
        def loss(x, dt, a, b, c, d):
            rep = lambda m: jnp.repeat(m[0], h // g, axis=1)
            y = granite_ref.recurrence(
                x[0], dt[0], a, rep(b), rep(c), d, remat=True
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
            n_: rel_rms(g_, r) for n_, g_, r in zip(names, grads, want_grads)
        })
        print(label, "scan rel rms", {k_: round(v, 6) for k_, v in read.items()})
        assert max(read.values()) < tol, (label, read)


@pytest.mark.parametrize("seed", [20400302, 20400303])
def test_the_bf16_system_passes_and_an_fp8_forward_fails(seed):
    """The comparison that decides ``correct``, both ways: the system as
    the cell runs it passes every limit; the control (the reference with
    fp8 (e4m3) operands in every matrix product and in the scan's, in
    the system's place) comes out not ok.  Prints both readings: each
    limit in ``nemotron_h_lm.py`` lies between them."""
    cell = harness.load_cell(CELL)
    fam = nemotron_h_lm.build(cell["config_data"], cell["job"], seed)
    check = fam.reference_check()
    print("bf16 system", check)
    print("peak GB", peak_gb())
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    print("fp8 control", control)
    assert check["ok"] is True
    assert control["ok"] is False


def _gradients(fam, base, adapters, tokens, cfg, attn_fn):
    """(worst layer's relative RMS of each adapter leaf, losses) of the
    step's gradient at ``tokens`` against the float32 reference's, the
    reference given the selection the system's own gradient program made
    in every expert layer (a selection made by another program, even the
    same forward compiled apart, is not the one the gradient used): with
    22 choices of 512 a token, the 1.3% of (token, choice) pairs that
    two roundings select apart touch a quarter of a layer's tokens."""
    from rayfed_tpu.models import decoder

    ids = jax.random.randint(jax.random.PRNGKey(6), (1, tokens), 0,
                             cfg.vocab_size)
    kw = dict(fam.reference_kwargs(), pattern=fam.pattern,
              mtp_pattern=fam.mtp_pattern,
              mtp_loss_weight=decoder.MTP_LOSS_WEIGHT, remat=True)

    def system(a, b, i):
        loss, aux, _ = decoder.lora_loss_terms(a, b, i, cfg, attn_fn=attn_fn)
        return loss, [aux[k]["selected"] for k in sorted(aux)]

    (loss, chosen), grads = jax.jit(jax.value_and_grad(system, has_aux=True))(
        adapters, base, ids
    )
    grads = jax.device_get(grads)

    @jax.jit
    @functools.partial(jax.value_and_grad, argnums=1)
    def reference(p, l, i, chosen):
        # both trees unstacked INSIDE the program: a layer's slice is
        # then no second copy of the base beside the first
        return ref.run(
            decoder.unstack(p, cfg), i, lora=decoder.unstack(l, cfg),
            selected=chosen, **kw,
        )[0]

    with jax.default_matmul_precision("highest"):
        want_loss, want = reference(base, adapters, ids[0], chosen)
    want = jax.tree_util.tree_leaves(
        decoder.unstack(jax.device_get(want), cfg)
    )
    got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
    worst = {}
    for (path, g), w in zip(got, want):
        if path[-1].key == "scale":
            continue
        assert float(np.abs(w).max()) > 0, path
        # a leaf's name without its layer's index
        name = "/".join(
            k for k in (str(getattr(k, "key", k)) for k in path)
            if not k.isdigit()
        )
        worst[name] = max(worst.get(name, 0.0), rel_rms(g, w))
    return worst, (float(loss), float(want_loss))


def test_the_timed_steps_gradients_are_the_references():
    """What ``jit_decoder_lora_step`` differentiates
    (``decoder.lora_loss``: the step less its Adam update, the MTP
    module's loss with its weight included), at the published widths on
    blocks 0-10 and the MTP module, ``GRADIENT_TOKENS`` tokens, as the
    cell computes it (bf16).  The reference recomputes each block,
    attention block, expert and scan block in its backward pass
    (``remat``: memory, not mathematics).  The same program in float32
    agrees with the reference to 1e-5 at toy widths on the CPU
    (``tests/test_nemotron_h.py``); on the chip the kernels refuse a
    float32 step (a float32 operand beside a bf16 one in a Mosaic
    product)."""
    cell = harness.load_cell(CELL)
    fam = nemotron_h_lm.build(cell["config_data"], cell["job"], 20400301)
    cfg = fam.cfg
    assert cfg.remat and fam.seq == cell["job"]["seq_len"]  # as the cell runs
    base = fam._make_base(fam.base_key())
    adapters = fam.init_global()
    # B starts at zero, where A has no gradient: give every B a value.
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 256))
    adapters = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key != "b"
        else 0.02 * jax.random.normal(next(keys), x.shape),
        adapters,
    )
    worst, losses = _gradients(fam, base, adapters, GRADIENT_TOKENS, cfg,
                               fam.attn_fn)
    print("bf16 losses (system, reference)", losses, "peak GB", peak_gb())
    print("bf16 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 4) for k, v in sorted(worst.items())})
    assert abs(losses[0] - losses[1]) < 2e-3 * losses[1]
    assert max(worst.values()) < GRADIENT_REL_RMS_TOL, worst
