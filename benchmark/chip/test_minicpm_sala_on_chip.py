"""MiniCPM-SALA's timed program against the plain reference ON THE CHIP,
at the published widths: what ``tests/test_minicpm_sala.py`` shows at
toy widths on the CPU, here with the compiled block-sparse kernels at 32
query heads of 128 on 2 K/V heads in tiles of 512, the selection over
blocks of 64, the linear attention's scan with one group a head at 32
heads of 128 in chunks of 256, the checkpointed scan of each group and
the fused head-and-loss on the untied 73,448-row head.

Run it on a TPU host, alone (a chip belongs to one process):
``python -m pytest benchmark/chip/test_minicpm_sala_on_chip.py -q -s``.
Skipped where JAX finds no TPU.  Not under ``benchmark/tests``: that
directory's conftest pins the CPU.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import minicpm_sala_lm
from benchmark.reference import minicpm_sala as ref

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs the chip"
)

CELL = "minicpm-sala-d4.lora-all-linear-32k-2p"
# A kernel alone against the float32 reference on the same inputs,
# relative RMS of the output and of each gradient.  With bf16 operands
# as the cell computes, one rounding of each product's operands reads
# some 1e-3 (the flash and scan kernels' 0.0015-0.0038 at their cells'
# shapes); with float32 operands the order of the sums alone.  A fault
# reads its own size: a block attended that was not selected, or one
# left out, moves a query's output by a share of its whole; a state that
# forgets a chunk moves the scan's by more.
KERNEL_REL_RMS_TOL = 0.02
KERNEL_REL_RMS_TOL_F32 = 1e-3
# The step's gradient against the float32 reference's, relative RMS of
# each adapter leaf, worst layer, the reference given the selection the
# gradient program made itself (another program's selection, even the
# same forward compiled apart, need not be the one the gradient used).
# Between two readings on a TPU v5 lite (PERF.md section 4), worst
# leaf: the bf16 step 0.0369 (2.69-3.69% over the 16 leaves; `wq`,
# `wk`, `wv` the highest), and the reference with fp8 (e4m3) operands in
# every forward product, given the same selection, 0.4256 (6.96-42.6%;
# `wv`, `wz`, `wo` the highest).
GRADIENT_REL_RMS_TOL = 0.06
# Above `dense_len` (8,192), so that the selection and the sparse
# kernels run, and whole tiles of 512.  The float32 reference's gradient
# program, base included, asks 15.8 GB of the chip's 16.9 at 10,240
# tokens and 18.6 at 12,288 (XLA's count, compiled for a v5e).
GRADIENT_TOKENS = 10240


@pytest.fixture(autouse=True)
def _free_the_chip():
    yield
    import gc

    gc.collect()


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want**2)))


def _check(label, got, want, tol):
    read = {k: rel_rms(g, w) for k, g, w in zip("y dq dk dv".split(), got, want)}
    print(label, {k: round(v, 6) for k, v in read.items()})
    assert max(read.values()) < tol, (label, read)


def test_the_sparse_kernels_are_the_masked_attention_at_the_published_heads():
    """``sparse_attention`` alone at ``[1, 16384, 32, 128]`` on 2 K/V
    heads, over the selection ``select_blocks`` makes of the same
    inputs (twice ``dense_len``: the selection runs), forward and the
    three gradients against the reference's masked attention over every
    key in float32."""
    from rayfed_tpu.ops import sparse_attention as sa

    cfg = sa.SparseConfig()
    t, h, kv, d = 16384, 32, 2, 128
    k = jax.random.split(jax.random.PRNGKey(20420401), 4)
    q = jax.random.normal(k[0], (1, t, h, d))
    kk = jax.random.normal(k[1], (1, t, kv, d))
    v = jax.random.normal(k[2], (1, t, kv, d))
    w = jax.random.normal(k[3], (1, t, h, d))
    sel = jax.jit(lambda q, k: sa.select_blocks(q, k, cfg))(
        q.astype(jnp.bfloat16), kk.astype(jnp.bfloat16)
    )
    arrays = jax.jit(lambda s: sa.selection_arrays(s, t, cfg))(sel)
    print("mean keys, blocks", [float(x) for x in sa.visit_stats(sel, 64)],
          "pairs", int(arrays[1][2][0]), "of", sa.causal_pairs(t // cfg.tile))

    def system(dtype):
        def loss(q, k, v):
            y = sa.sparse_attention(q.astype(dtype), k.astype(dtype),
                                    v.astype(dtype), arrays, cfg)
            return jnp.sum(y.astype(jnp.float32) * w), y

        (_, y), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, kk, v)
        return (y, *grads)

    @jax.jit
    def reference(q, k, v):
        def loss(q, k, v):
            y = ref.sparse_attention(q[0], k[0], v[0], sel[0], block_size=64,
                                     query_block=256, remat=True)[None]
            return jnp.sum(y * w), y

        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        return (y, *grads)

    with jax.default_matmul_precision("highest"):
        want = reference(q, kk, v)
        got32 = system(jnp.float32)
    _check("float32", got32, want, KERNEL_REL_RMS_TOL_F32)
    _check("bf16", system(jnp.bfloat16), want, KERNEL_REL_RMS_TOL)


def test_the_scan_with_a_group_a_head_is_the_recurrence():
    """``ssd_scan`` alone as the linear attention calls it, at ``[1,
    24576, 32, 128]`` (``dt = 1``, ``A`` minus the first layer's decay
    rates, ``B = k``, ``C = q``, ``D = 0``, chunks of 256): forward and
    the gradients of ``q``, ``k``, ``v`` against the reference's
    token-by-token recurrence in float32."""
    from rayfed_tpu.models.decoder import LightningConfig
    from rayfed_tpu.ops.ssd import ssd_scan

    t, h, d = 24576, 32, 128
    rates = LightningConfig().decay_rates(0, h)
    k = jax.random.split(jax.random.PRNGKey(20420402), 4)
    q, kk, v, w = (jax.random.normal(k[i], (1, t, h, d)) for i in range(4))
    q = q * d ** -0.5

    def system(dtype):
        def loss(q, k, v):
            y = ssd_scan(v.astype(dtype), jnp.ones((1, t, h)), -rates,
                         k.astype(dtype), q.astype(dtype), jnp.zeros((h,)),
                         chunk=256)
            return jnp.sum(y.astype(jnp.float32) * w), y

        (_, y), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, kk, v)
        return (y, *grads)

    @jax.jit
    def reference(q, k, v):
        def loss(q, k, v):
            y = ref.recurrence(q[0], k[0], v[0], jnp.exp(-rates),
                               remat=True)[None]
            return jnp.sum(y * w), y

        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        return (y, *grads)

    with jax.default_matmul_precision("highest"):
        want = reference(q, kk, v)
        got32 = system(jnp.float32)
    _check("float32", got32, want, KERNEL_REL_RMS_TOL_F32)
    _check("bf16", system(jnp.bfloat16), want, KERNEL_REL_RMS_TOL)


@pytest.mark.parametrize("seed", [20420301, 20420302])
def test_the_bf16_system_passes_and_an_fp8_forward_fails(seed):
    """The comparison that decides ``correct``, both ways: the system as
    the cell runs it passes every limit; the control (the reference with
    fp8 (e4m3) operands in every product, in the system's place) comes
    out not ok.  Prints both readings: each limit in
    ``minicpm_sala_lm.py`` lies between them."""
    cell = harness.load_cell(CELL)
    fam = minicpm_sala_lm.build(cell["config_data"], cell["job"], seed)
    check = fam.reference_check()
    print("bf16 system", check)
    stats = jax.local_devices()[0].memory_stats() or {}
    print("peak GB", stats.get("peak_bytes_in_use", 0) / 1e9)
    control = fam.reference_check(round_to=jnp.float8_e4m3fn)
    print("fp8 control", control)
    assert check["ok"] is True
    assert control["ok"] is False


def _flat(tree, cfg):
    """``{layer path/leaf: array}`` of a stacked adapter tree, unstacked,
    its ``scale`` leaves left out."""
    from rayfed_tpu.models import decoder

    return {
        "/".join(str(getattr(k, "key", k)) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            decoder.unstack(jax.device_get(tree), cfg)
        )
        if path[-1].key != "scale"
    }


def _gradients(fam, base, adapters, ids):
    """(loss, :func:`_flat` gradients, the sparse layers' selections) of
    ``decoder.lora_loss`` as the cell computes it."""
    from rayfed_tpu.models import decoder

    def system(a, b, i):
        loss, aux = decoder.lora_loss(a, b, i, fam.cfg, attn_fn=fam.attn_fn)
        return loss, [aux[k]["selected"][0] for k in sorted(aux)
                      if "selected" in aux[k]]

    (loss, chosen), grads = jax.jit(jax.value_and_grad(system, has_aux=True))(
        adapters, base, ids
    )
    return float(loss), _flat(grads, fam.cfg), chosen


def _reference_gradients(fam, base, adapters, ids, chosen, round_to=None):
    """The float32 reference's (loss, :func:`_flat` gradients), given the
    selections ``chosen``; ``round_to``: every product's operands rounded
    to that type.  The reference recomputes each layer, the sparse
    attention's query blocks, the recurrence's blocks and the FFN's row
    blocks in its backward pass: memory, not mathematics."""
    from rayfed_tpu.models import decoder

    cfg = fam.cfg
    kw = dict(fam.reference_kwargs(), logit_scale=cfg.logit_scale,
              remat=True, round_to=round_to)

    @jax.jit
    @functools.partial(jax.value_and_grad, argnums=1)
    def reference(p, a, i, chosen):
        # both trees unstacked INSIDE the program: a layer's slice is
        # then no second copy of the base beside the first
        return ref.loss(decoder.unstack(p, cfg), i,
                        lora=decoder.unstack(a, cfg), selected=chosen, **kw)

    with jax.default_matmul_precision("highest"):
        loss, grads = reference(base, adapters, ids[0], chosen)
    return float(loss), _flat(grads, cfg)

def _worst(got, want):
    """The worst layer's relative RMS of each adapter leaf, by the
    leaf's name without its layer's index."""
    worst = {}
    for path, w in want.items():
        assert float(np.abs(w).max()) > 0, path
        name = "/".join(k for k in path.split("/") if not k.isdigit())
        worst[name] = max(worst.get(name, 0.0), rel_rms(got[path], w))
    return worst


def test_the_timed_steps_gradients_are_the_references():
    """What ``jit_decoder_lora_step`` differentiates
    (``decoder.lora_loss``: the step less its Adam update) at the
    published widths on all four layers, ``GRADIENT_TOKENS`` tokens, as
    the cell computes it (bf16, its kernels, the kept selection), against
    the float32 reference's gradient of every adapter leaf given the
    step's own selection; and the fp8 control, which must read above the
    limit.  The same program in float32 agrees with the reference to
    1e-4 at toy widths on the CPU (``tests/test_minicpm_sala.py``).  Run
    last: a test that fails keeps its base alive in the traceback."""
    cell = harness.load_cell(CELL)
    fam = minicpm_sala_lm.build(cell["config_data"], cell["job"], 20420501)
    cfg = fam.cfg
    assert cfg.remat and GRADIENT_TOKENS > cfg.sparse.dense_len
    base = fam._make_base(fam.base_key())
    adapters = fam.init_global()
    # B starts at zero, where A has no gradient: give every B a value.
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 256))
    adapters = jax.tree_util.tree_map_with_path(
        lambda path, x: x if path[-1].key != "b"
        else 0.02 * jax.random.normal(next(keys), x.shape, x.dtype),
        adapters,
    )
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, GRADIENT_TOKENS), 0,
                             cfg.vocab_size)
    loss, got, chosen = _gradients(fam, base, adapters, ids)
    assert len(chosen) == 1  # the sparse layer selected
    want_loss, want = _reference_gradients(fam, base, adapters, ids, chosen)
    bf16 = _worst(got, want)
    print("bf16 losses (system, reference)", (loss, want_loss),
          "peak GB", jax.local_devices()[0].memory_stats().get(
              "peak_bytes_in_use", 0) / 1e9)
    print("bf16 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 5) for k, v in sorted(bf16.items())})
    fp8_loss, fp8_grads = _reference_gradients(
        fam, base, adapters, ids, chosen, round_to=jnp.float8_e4m3fn
    )
    fp8 = _worst(fp8_grads, want)
    print("fp8 loss", fp8_loss)
    print("fp8 gradient rel rms, worst layer of each leaf:",
          {k: round(v, 5) for k, v in sorted(fp8.items())})
    assert max(bf16.values()) < GRADIENT_REL_RMS_TOL, bf16
    assert max(fp8.values()) > GRADIENT_REL_RMS_TOL, fp8
