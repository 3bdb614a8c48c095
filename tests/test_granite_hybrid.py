"""The decoder's state-space layers (``rayfed_tpu.models.decoder`` mixer
kind ``ssm``: ``models/mamba2.py``, ``ops/ssd.py``; the
``granitemoehybrid`` block) against the plain reference
(``benchmark/reference/granite_hybrid.py``: the recurrence token by
token), at toy widths on the CPU with the published ratios: ``d_inner``
twice the hidden size, one group, a state wider than a head, a chunk
smaller than the sequence, an attention layer without positions between
two runs of Mamba layers (so that groups split), a score scale that is
not ``head_dim ** -0.5``, the three other multipliers, a tied head.

The reference is the benchmark's (the cell's ``correct`` is decided by
the same functions at the published widths on the chip), so a change to
either side is caught here first.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import granite_hybrid as ref
from rayfed_tpu import telemetry
from rayfed_tpu.models import decoder, llama, lora, mamba2
from rayfed_tpu.ops.attention import dot_product_attention
from rayfed_tpu.ops.flash_attention import flash_attention
from rayfed_tpu.ops import ssd
from rayfed_tpu.ops.ssd import scan_bytes, scan_flops, ssd_scan
from tests.test_kimi_k2 import _trained, rel_rms

D, T, VOCAB, FFN = 32, 32, 64, 64
HEADS, KV, DH = 4, 2, 8  # attention
SSM = mamba2.SsmConfig(num_heads=8, head_dim=8, state=16, groups=1,
                       conv_width=4, chunk=8)
KINDS = ("mamba", "mamba", "attention", "mamba", "mamba")
SPECS = tuple(
    decoder.LayerSpec("ssm" if k == "mamba" else "full") for k in KINDS
)
MULTIPLIERS = dict(embed_scale=12.0, residual_scale=0.22, attn_scale=1 / 64,
                   logit_scale=1 / 8)


def toy_config(dtype=jnp.float32, **kw):
    assert SSM.d_inner == 2 * D and SSM.state > SSM.head_dim
    return decoder.DecoderConfig(
        layers=SPECS, vocab_size=VOCAB, hidden_size=D, num_heads=HEADS,
        num_kv_heads=KV, head_dim=DH, intermediate_size=FFN, ssm=SSM,
        qk_norm=False, output_gate=False, post_norms=False,
        tie_embeddings=True, dtype=dtype, param_dtype=jnp.float32,
        **MULTIPLIERS, **kw,
    )


def ref_kwargs(cfg, **kw):
    m = cfg.ssm
    return dict(
        layer_types=KINDS, embedding_multiplier=cfg.embed_scale,
        residual_multiplier=cfg.residual_scale, rms_eps=cfg.rms_eps,
        logits_scaling=1 / cfg.logit_scale,
        ssm=dict(heads=m.num_heads, head_dim=m.head_dim, state=m.state,
                 groups=m.groups, conv_width=m.conv_width, chunk=m.chunk),
        attn=dict(num_heads=HEADS, num_kv_heads=KV, attn_head_dim=DH,
                  attention_multiplier=cfg.attn_scale),
        block=T, **kw,
    )


def make(seed=0, cfg=None):
    """(config, base, adapters with a non-zero B, ids)."""
    cfg = cfg or toy_config()
    base = decoder.init_decoder(jax.random.PRNGKey(seed), cfg)
    adapters = lora.init_lora(
        jax.random.PRNGKey(seed + 1), base,
        lora.LoraConfig(rank=2, alpha=4.0, targets=decoder.ALL_LINEAR),
    )
    ids = jax.random.randint(jax.random.PRNGKey(seed + 3), (1, T), 0, VOCAB)
    return cfg, base, _trained(adapters, seed + 2), ids


# -- the configuration: layer kinds, groups, parameters -----------------

PUBLISHED_LAYER_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4


def test_groups_split_where_the_mixers_parameters_change():
    cfg, base, adapters, _ = make()
    assert cfg.groups() == ((0, 2), (2, 3), (3, 5))
    specs = lambda kinds: tuple(
        decoder.LayerSpec("ssm" if k == "mamba" else "full") for k in kinds
    )
    whole = dataclasses.replace(cfg, layers=specs(PUBLISHED_LAYER_TYPES))
    assert whole.groups() == (
        (0, 5), (5, 6), (6, 15), (15, 16), (16, 25), (25, 26), (26, 35),
        (35, 36), (36, 40),
    )
    cut = dataclasses.replace(cfg, layers=specs(PUBLISHED_LAYER_TYPES[:20]))
    assert cut.groups() == ((0, 5), (5, 6), (6, 15), (15, 16), (16, 20))
    # Trinity's (window and full attention share parameters: the FFN kind
    # alone splits) and Kimi's specs group as they did
    spec = decoder.LayerSpec
    trinity = (spec("window", "dense"),) + (
        spec("window", "moe"), spec("window", "moe"), spec("window", "moe"),
        spec("full", "moe"),
    ) * 2
    grouped = lambda layers: dataclasses.replace(
        cfg, layers=layers, experts=object(), latent=object()
    ).groups()
    assert grouped(trinity) == ((0, 1), (1, 9))
    kimi = (spec("latent", "dense"),) + (spec("latent", "moe"),) * 4
    assert grouped(kimi) == ((0, 1), (1, 5))
    # a state-space layer's parameters, stacked a group
    lp = base["layers"][0]
    m = cfg.ssm
    assert lp["w_in"].shape == (2, D, 2 * m.d_inner + 2 * m.state + m.num_heads)
    assert lp["w_out"].shape == (2, m.d_inner, D)
    assert lp["conv_w"].shape == (2, m.conv_dim, 4)
    assert lp["conv_b"].shape == (2, m.conv_dim)
    for name in ("A_log", "D", "dt_bias"):
        assert lp[name].shape == (2, m.num_heads)
        assert lp[name].dtype == jnp.float32
    assert lp["ssm_norm"].shape == (2, m.d_inner)
    assert not {"wq", "wo", "wz", "lm_head"} & set(lp)
    assert "lm_head" not in base  # tied
    assert sorted(base["layers"][1]) == [
        "attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo",
        "wq", "wv",
    ]
    assert sorted(adapters["layers"]["0"]) == [
        "w_down", "w_gate", "w_in", "w_out", "w_up",
    ]
    assert sorted(adapters["layers"]["1"]) == [
        "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv",
    ]
    # the buffers as the Mamba-2 reference initialises them
    a = np.exp(np.asarray(lp["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.logaddexp(0, np.asarray(lp["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert (np.asarray(lp["D"]) == 1).all()
    assert np.abs(np.asarray(lp["conv_w"])).max() <= 0.5


def test_layer_spec_names_its_mixer_and_still_reads_attention():
    spec = decoder.LayerSpec
    assert spec("full") == spec(mixer="full")
    assert spec("ssm", "dense").attention == spec("ssm").mixer == "ssm"
    assert dataclasses.replace(spec("window"), ffn="moe") == spec("window", "moe")
    # the alias is read-only: it cannot write an old mixer back
    assert dataclasses.replace(spec("window", "moe"), mixer="ssm") == spec(
        "ssm", "moe"
    )
    with pytest.raises(TypeError):
        spec(attention="full")
    with pytest.raises(ValueError, match="window, full, latent, ssm"):
        spec("mamba")
    with pytest.raises(ValueError, match="dense, moe"):
        spec("ssm", "experts")
    with pytest.raises(ValueError, match="needs config.ssm"):
        dataclasses.replace(toy_config(), ssm=None)


def test_adapters_at_the_published_shapes_count_what_the_cell_states():
    """Rank 8 on the published shapes, by hand (ISSUE 35): a Mamba layer's
    two matrices 84,480 + 49,152, an attention layer's four 106,496, every
    FFN 245,760; 18 + 2 layers; the frozen cut 1,698.4 M parameters."""
    cfg = decoder.DecoderConfig(
        layers=tuple(
            decoder.LayerSpec("ssm" if k == "mamba" else "full")
            for k in PUBLISHED_LAYER_TYPES[:20]
        ),
        vocab_size=100352, hidden_size=2048, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=8192, ssm=mamba2.SsmConfig(),
        qk_norm=False, output_gate=False, post_norms=False,
        tie_embeddings=True,
    )
    base = jax.eval_shape(
        lambda: decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    )
    targets = ("/w[qkvo]$", "/w_(in|out)$", "/w_(gate|up|down)$")  # the cell's
    for t in (targets, decoder.ALL_LINEAR):
        adapters = jax.eval_shape(
            lambda b: lora.init_lora(
                jax.random.PRNGKey(0), b, lora.LoraConfig(targets=t)
            ), base,
        )
        assert lora.num_lora_params(adapters) == 7_533_568
    assert 18 * (84_480 + 49_152 + 245_760) + 2 * (106_496 + 245_760) == 7_533_568
    assert base["layers"][0]["w_in"].shape == (5, 2048, 8512)
    frozen = sum(x.size for x in jax.tree_util.tree_leaves(base))
    assert frozen == 1_698_459_520  # the cut: 3.40 GB in bf16


# -- the scan alone against the recurrence ------------------------------


def _scan_inputs(b=2, t=37, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    return (
        jax.random.normal(k[0], (b, t, h, p)),
        jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 1.0),
        -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
        jax.random.normal(k[3], (b, t, g, n)),
        jax.random.normal(k[4], (b, t, g, n)),
        jax.random.normal(k[5], (h,)),
    ), jax.random.normal(k[6], (b, t, h, p))


def _recurrence(x, dt, a, b, c, d, **kw):
    rep = lambda v: jnp.repeat(v, x.shape[2] // v.shape[2], axis=2)
    return jax.vmap(
        lambda x, dt, b, c: ref.recurrence(x, dt, a, b, c, d, **kw)
    )(x, dt, rep(b), rep(c))


@pytest.mark.parametrize("chunk", [
    64,  # one chunk (the sequence is shorter: chunk = T)
    8,   # several, and 37 is no multiple: padded and cut
    16,
    37,
    5,
    1,   # every token a chunk: the carried state alone
])
def test_chunked_scan_is_the_recurrence_forward_and_all_six_gradients(chunk):
    """Float32 both: the distance is the order of the sums."""
    args, w = _scan_inputs()
    scan = lambda *a: ssd_scan(*a, chunk=chunk)
    assert rel_rms(scan(*args), _recurrence(*args)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(scan(*a) * w), argnums=range(6))(*args)
    want = jax.grad(
        lambda *a: jnp.sum(_recurrence(*a) * w), argnums=range(6)
    )(*args)
    for name, g, r in zip("x dt A B C D".split(), got, want):
        assert float(jnp.abs(r).max()) > 0, name
        assert rel_rms(g, r) < 1e-5, name


def test_a_scan_that_drops_the_state_at_a_chunk_boundary_is_noticed():
    """The reference with its state set to zero every ``chunk`` tokens
    is what a chunked form without the carried state computes: far from
    the scan; and the sequence past the first chunk is where."""
    args, _ = _scan_inputs()
    got = ssd_scan(*args, chunk=8)
    dropped = _recurrence(*args, reset_every=8)
    assert rel_rms(got, dropped) > 1e-2
    np.testing.assert_allclose(got[:, :8], dropped[:, :8], atol=1e-4)


def test_scan_refuses_shapes_that_do_not_pair_up():
    (x, dt, a, b, c, d), _ = _scan_inputs()
    with pytest.raises(ValueError, match="groups must divide heads"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, 2), c[:, :, :1].repeat(3, 2),
                 d, chunk=8)
    with pytest.raises(ValueError, match="groups must divide heads"):
        ssd_scan(x, dt[:, :-1], a, b, c, d, chunk=8)


def _gradients(scan, args, w):
    return jax.grad(
        lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * w), argnums=range(6)
    )(*args)


SCAN_CASES = {
    # (inputs, chunk, operand type, limit against the float32 recurrence)
    "two_groups_four_blocks_each": (
        dict(b=1, t=24, h=8, p=8, g=2, n=16), 8, jnp.float32, 1e-5),
    "two_groups_two_heads_a_block": (
        dict(b=1, t=24, h=8, p=8, g=2, n=16), 8, jnp.float32, 1e-5),
    "ragged_length_bf16_operands": (
        dict(b=1, t=37, h=4, p=8, g=1, n=16), 8, jnp.bfloat16, 0.02),
    "batch_of_three": (dict(b=3, t=16, h=4, p=8, g=2, n=16), 8, jnp.float32, 1e-5),
}


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_the_kernels_take_groups_blocks_batches_and_ragged_lengths(
    case, monkeypatch
):
    """Forward and all six gradients against the token-by-token
    recurrence in float32: a group's heads over several grid steps (``dB``
    and ``dC`` summed over them in VMEM), two heads sharing a block's
    lanes, a length that is no multiple of the chunk with bf16 operands
    (the chip test's limit), a batch."""
    shape, chunk, dtype, tol = SCAN_CASES[case]
    if case == "two_groups_four_blocks_each":
        monkeypatch.setattr(ssd, "VMEM_BUDGET_BYTES", 0)  # the smallest
    elif case == "two_groups_two_heads_a_block":
        monkeypatch.setattr(
            ssd, "VMEM_BUDGET_BYTES", ssd.step_vmem_bytes(2, 8, 16, 8, 4)
        )
    args, w = _scan_inputs(**shape)
    h, g = shape["h"], shape["g"]
    block = ssd.head_block(h, g, 8, 16, chunk, jnp.dtype(dtype).itemsize, True)
    assert block == {"two_groups_four_blocks_each": 1,
                     "two_groups_two_heads_a_block": 2}.get(case, h // g)
    cast = lambda x, dt, a, b, c, d: (
        x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d
    )
    scan = lambda *a: ssd_scan(*cast(*a), chunk=chunk)
    assert rel_rms(scan(*args), _recurrence(*args)) < tol
    got, want = _gradients(scan, args, w), _gradients(_recurrence, args, w)
    for name, g_, r in zip("x dt A B C D".split(), got, want):
        assert float(jnp.abs(r).max()) > 0, name
        assert rel_rms(g_, r) < tol, name


def _scan_kernels(jaxpr):
    """Names of the kernels of every ``pallas_call`` a jaxpr holds."""
    from tool.flash_sweep import _sub_jaxprs

    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["jaxpr"].debug_info.func_name)
        for sub in _sub_jaxprs(eqn):
            names.extend(_scan_kernels(sub))
    return names


def test_a_checkpointed_caller_runs_forward_twice_and_backward_once():
    """Under a layer's ``jax.checkpoint`` the gradient's program holds
    the forward kernel twice (the primal, and again for the carried
    states) and the backward kernel once, and the gradients are those
    of the plain call to the bit."""
    args, w = _scan_inputs()
    scan = lambda *a: ssd_scan(*a, chunk=8)
    grad = lambda f: jax.value_and_grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=range(6)
    )
    kernels = _scan_kernels(
        jax.make_jaxpr(grad(jax.checkpoint(scan)))(*args).jaxpr
    )
    assert sorted(kernels) == ["_bwd_kernel", "_fwd_kernel", "_fwd_kernel"]
    (loss, got), (plain_loss, want) = (
        grad(jax.checkpoint(scan))(*args), grad(scan)(*args)
    )
    assert float(loss) == float(plain_loss)
    for g_, r in zip(got, want):
        np.testing.assert_array_equal(g_, r)


# Equations in the forward and backward kernels' jaxprs at the cell's
# shape (`tool/ssd_sweep.py --lowering`: 104 and 233, 4 and 11 matrix
# products), with a tenth of room: the head loop is ONE `fori_loop` body
# however many heads a block holds (unrolled only when Mosaic lowers
# it), and a head of the block adds two equations, its column of `l`.
# What a kernel costs to trace is `setup_s` (ledger, PR 29: 1,779
# equations in three kernels cost two cells their bound; the three flash
# kernels hold 601).
SCAN_MAX_EQUATIONS = (115, 256)
SCAN_PRODUCTS = (4, 11)
# `ssd.head_block` at the cell's shape, chosen by the budget
# (`tool/ssd_sweep.py`, PERF.md section 6)
HEAD_BLOCK_AT_THE_CELL = 16


def test_scan_kernel_programs_stay_within_the_size_budget(monkeypatch):
    from tool.flash_sweep import kernel_counts
    from tool.ssd_sweep import CELL, scan_grad

    monkeypatch.setattr(ssd._flash, "_interpret_default", lambda: False)
    grad, args = scan_grad(CELL)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    fwd, bwd = kernel_counts(jax.make_jaxpr(grad)(*shapes).jaxpr)[-2:]
    for (equations, products), most, dots in zip(
        (fwd, bwd), SCAN_MAX_EQUATIONS, SCAN_PRODUCTS
    ):
        assert equations <= most
        assert products == dots
    # the same program at half the block (8 heads: the smallest that
    # tiles), but for eight columns of `l`
    monkeypatch.setattr(ssd, "VMEM_BUDGET_BYTES", 0)
    grad, _ = scan_grad(CELL)  # traced anew
    small = kernel_counts(jax.make_jaxpr(grad)(*shapes).jaxpr)[-2:]
    assert small == [(n - 2 * 8, dots) for n, dots in (fwd, bwd)]


def test_a_process_traces_each_scan_kernel_once(monkeypatch):
    """The wrappers of the two ``pallas_call``s are jitted on their
    static arguments: a second program that holds the same call (another
    party's step, another scanned group, the forward a checkpoint runs
    again) finds the kernels' jaxprs; another chunk does not.  Under a
    ``jax.checkpoint`` jax 0.9 traces the forward wrapper in two
    contexts (the checkpoint's own trace has no abstract mesh, its
    JVP's an empty one), so a process traces the forward kernel at most
    twice, however many programs and groups hold it."""
    traced = []
    for name in ("_fwd_kernel", "_bwd_kernel"):
        def counting(*a, _kernel=getattr(ssd, name), _name=name, **kw):
            traced.append(_name)
            return _kernel(*a, **kw)

        monkeypatch.setattr(ssd, name, counting)
    args, w = _scan_inputs()

    def program(chunk, layer=lambda f: f):
        scan = layer(lambda *a: ssd_scan(*a, chunk=chunk))
        return jax.jit(jax.grad(
            # two calls, as two scanned groups of one step
            lambda *a: jnp.sum((scan(*a) + scan(*a)) * w), argnums=range(6)
        ))

    jax.clear_caches()
    first = program(8)(*args)
    # once each, though a gradient traces the forward twice (the primal
    # function, then the rule that keeps the carried states)
    assert sorted(traced) == ["_bwd_kernel", "_fwd_kernel"]
    again = program(8)(*args)  # a new program, the same kernels
    assert len(traced) == 2
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    program(8, jax.checkpoint)(*args)
    assert traced.count("_bwd_kernel") == 1 and len(traced) <= 3
    held = len(traced)
    program(8, jax.checkpoint)(*args)
    assert len(traced) == held
    program(16)(*args)
    assert len(traced) == held + 2
    jax.clear_caches()  # the counting kernels leave with the test


@pytest.mark.parametrize("shape, chunk, match", [
    (dict(t=256, h=8, p=64, g=1, n=128), 100, "multiple of 128"),  # chunk
    (dict(t=256, h=8, p=20, g=1, n=128), 128, "multiple of 8"),  # head width
    (dict(t=256, h=8, p=64, g=2, n=64), 128, "groups > 1"),  # state's lanes
    (dict(t=256, h=6, p=32, g=2, n=128), 128, "128-lane tiles"),  # 3 x 32
])
def test_compiled_scan_refuses_shapes_that_do_not_tile(
    shape, chunk, match, monkeypatch
):
    """Interpret mode takes any shape; compiled, a shape the kernels'
    blocks cannot tile is refused before anything is lowered."""
    monkeypatch.setattr(ssd._flash, "_interpret_default", lambda: False)
    args, _ = _scan_inputs(b=1, **shape)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda *a: ssd_scan(*a, chunk=chunk), *args)


def test_ssm_scan_record_when_armed():
    """One ``ssm.scan`` record a call traced while the recorder is
    armed, from static arguments alone; none disarmed.  It says how the
    kernels are laid out: the head block, the grid, a grid step's VMEM
    and what the backward pass is handed."""
    (x, dt, a, b, c, d), _ = _scan_inputs()
    jax.make_jaxpr(lambda *v: ssd_scan(*v, chunk=8))(x, dt, a, b, c, d)
    rec = telemetry.install(capacity=64)
    try:
        jax.make_jaxpr(lambda *v: ssd_scan(*v, chunk=8))(
            x.astype(jnp.bfloat16), dt, a, b, c, d
        )
        (record,) = [r for r in rec.records() if r.phase == "ssm.scan"]
    finally:
        telemetry.uninstall()
    detail = record.detail
    states = 2 * 5 * 4 * 8 * 16 * 4
    assert detail == dict(
        batch=2, tokens=37, chunk=8, chunks=5, heads=4, head_dim=8, state=16,
        groups=2,
        flops_forward=scan_flops(2 * 37, 4, 8, 16, 2, 8),
        bytes_forward=scan_bytes(2, 37, 4, 8, 16, 2, 2),
        state_bytes=states,
        # nothing of chunk x chunk a head is left in HBM: the carried
        # states are the largest array a call makes
        working_set_bytes=states,
        head_block=2, grid=(2, 5, 2),
        vmem_bytes=ssd.step_vmem_bytes(2, 8, 16, 8, 2) + 4 * 8 * 16 * 4,
        residual_bytes=states + 2 * 4 * 8 * 40 * 4,
    )
    # at the cell's shapes: 16 heads a grid step, 67 MB of carried states
    # and 16.8 MB of token rows kept for the backward pass
    assert ssd.head_block(64, 1, 64, 128, 256, 2, False) == HEAD_BLOCK_AT_THE_CELL
    assert 8192 // 256 * 64 * 64 * 128 * 4 == 67_108_864
    # the count of ISSUE 35 at the published shapes: 3.18 MFLOP a token
    # forward (1.05 + 1.05 + 1.05 + 0.03), 140.5 MB a call in and out
    assert scan_flops(1, 64, 64, 128, 1, 256) == pytest.approx(
        4096 * 257 + 2 * 2 * 4096 * 128 + 128 * 257
    )
    assert 3.18e6 < scan_flops(1, 64, 64, 128, 1, 256) < 3.19e6
    assert scan_bytes(1, 8192, 64, 64, 128, 1, 2) == 8192 * (
        2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4
    )


# -- the whole model against the reference ------------------------------


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("attn", ["dense", "flash"])
def test_float32_system_matches_the_reference(attn, remat):
    """Logits, both losses (``lm_loss`` of the logits and the step's
    fused head-and-loss on the tied embedding) and the gradient of every
    adapter leaf, through the dense attention (the CPU path) and the
    flash kernels (interpreter), with and without the checkpointed scan.
    Both sides are float32 and differ in the order of their sums (the
    chunked form against the recurrence): 1e-4 relative, and every
    mistake in the mathematics is of order one (the faults below)."""
    cfg, base, adapters, ids = make(cfg=toy_config(remat=remat))
    attn_fn = {"dense": dot_product_attention, "flash": flash_attention}[attn]
    kw = ref_kwargs(cfg)

    def sys_loss(a):
        logits, _ = decoder.apply_decoder(
            base, ids, cfg, lora=a, attn_fn=attn_fn
        )
        return llama.lm_loss(logits[:, :-1], ids[:, 1:]), logits

    (loss, logits), _ = jax.jit(jax.value_and_grad(sys_loss, has_aux=True))(
        adapters
    )
    step_loss, grads = jax.jit(jax.value_and_grad(
        lambda a: decoder.lora_loss(a, base, ids, cfg, attn_fn=attn_fn)[0]
    ))(adapters)
    last, _ = decoder.apply_decoder(
        base, ids, cfg, lora=adapters, attn_fn=attn_fn, last=5
    )
    np.testing.assert_allclose(last, logits[:, -5:], rtol=1e-5, atol=1e-6)
    plain_base = decoder.unstack(base, cfg)
    plain_adapters = decoder.unstack(adapters, cfg)
    want_logits = ref.forward(plain_base, ids[0], lora=plain_adapters, **kw)
    want_loss, want_grads = ref.lora_gradients(
        plain_base, plain_adapters, ids[0], remat=remat, **kw
    )
    assert rel_rms(logits[0], want_logits) < 1e-4
    for got in (loss, step_loss):
        assert abs(float(got) - float(want_loss)) < 1e-4 * float(want_loss)
    flat_got = jax.tree_util.tree_leaves_with_path(decoder.unstack(grads, cfg))
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) == 4 * 15 + 21
    for (path, got), want in zip(flat_got, flat_want):
        if path[-1].key == "scale":
            continue  # the system holds it constant (stop_gradient)
        assert float(jnp.abs(want).max()) > 0, path
        assert rel_rms(got, want) < 2e-4, path


@pytest.mark.parametrize("piece", ref.PIECES)
def test_the_comparison_notices_every_piece_of_the_mathematics(piece):
    """The reference with one piece left out or broken (the carried
    state dropped at a chunk boundary, the convolution's bias or its
    causal shift, ``D``, the gate outside the norm, ``head_dim ** -0.5``
    for 1/64, a multiplier left out) is another function: the logits
    move by far more than the 1e-4 the agreement is held to."""
    cfg, base, adapters, ids = make()
    logits, _ = decoder.apply_decoder(base, ids, cfg, lora=adapters)
    got = ref.forward(
        decoder.unstack(base, cfg), ids[0],
        lora=decoder.unstack(adapters, cfg), omit=(piece,), **ref_kwargs(cfg),
    )
    assert rel_rms(logits[0], got) > 1e-3, piece


def test_bf16_step_runs_finite_through_the_flash_kernels():
    """The cell's dtypes at toy widths: bf16 compute on float32-kept
    scan buffers, the checkpointed groups, the flash kernels with the
    score scale given; the loss near the float32 reference's."""
    cfg, base, adapters, ids = make(
        cfg=toy_config(dtype=jnp.bfloat16, remat=True)
    )
    step = decoder.make_lora_train_step(cfg, attn_fn=flash_attention)
    new, _, loss, counts = step(adapters, llama.init_adam(adapters), base, ids)
    assert counts is None  # no expert layer
    want = ref.loss(
        decoder.unstack(base, cfg), ids[0],
        lora=decoder.unstack(adapters, cfg), **ref_kwargs(cfg),
    )
    assert abs(float(loss) - float(want)) < 2e-2 * float(want)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), new, adapters
    )
    assert all(np.isfinite(v) for v in jax.tree_util.tree_leaves(moved))
    assert moved["layers"]["0"]["w_in"]["b"] > 0


def test_remat_saved_record_counts_the_groups_of_a_hybrid(monkeypatch):
    """Every layer keeps its input, ``ffn.up`` and the stream between
    its sub-blocks (``layer.mid``), a Mamba layer also ``W_in``'s product
    (``ssm.in``): the record lists all three groups with their bytes."""
    cfg, base, adapters, ids = make(cfg=toy_config(remat=True))
    rec = telemetry.install(capacity=64)
    try:
        jax.make_jaxpr(
            lambda a: decoder.lora_loss(a, base, ids, cfg)[0]
        )(adapters)
        (record,) = [r for r in rec.records() if r.phase == "remat.saved"]
        scans = [r for r in rec.records() if r.phase == "ssm.scan"]
    finally:
        telemetry.uninstall()
    assert record.detail["layers"] == {
        "layers0-1": 2, "layers2-2": 1, "layers3-4": 2,
    }
    every = {"ffn.up": T * FFN * 4, "layer.mid": T * D * 4}
    mamba = dict(every, **{"ssm.in": T * SSM.proj_dim * 4})
    assert record.detail["bytes_per_layer"] == {
        "layers0-1": mamba, "layers2-2": every, "layers3-4": mamba,
    }
    assert record.detail["names"] == list(llama.REMAT_SAVED_NAMES)
    assert {"layer.mid", "ssm.in"} <= set(record.detail["names"])
    assert len(scans) >= 2  # a record a scanned group of Mamba layers
