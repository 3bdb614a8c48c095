"""Wire compression for federated exchanges.

Cross-party pushes ride DCN; at ResNet/Llama scale the parameter payload
is the round's dominant wire cost.  Casting float leaves to bfloat16 for
the wire halves the bytes with ~3 decimal digits kept — the standard FL
compression baseline (more aggressive schemes — top-k sparsification,
int8 — trade convergence; bf16 is numerically safe for parameter
averaging when the accumulate runs in f32, which
:func:`rayfed_tpu.fl.tree_average` does).

Two wire forms:

**Per-leaf** (the original): every float leaf is cast individually via
``tree_map`` — N leaves means N XLA dispatches per direction, and the
wire codec moves N separate buffers with N manifest entries.

**Packed** (:class:`PackedTree`, the fast path): all float leaves are
flattened into ONE contiguous wire-dtype buffer by a single fused
cast+concat kernel (one XLA dispatch for the whole tree), with a static
spec carrying per-leaf ``(offset, size, shape, dtype)`` so decode is one
fused cast (or zero casts, when the consumer wants the wire dtype) plus
per-leaf **zero-copy views** into the buffer.  Non-float leaves ride
alongside untouched.  Because ``PackedTree`` is a registered JAX pytree,
the transport's tensor codec sees exactly one large array leaf — which
crosses the wire as a single zero-copy buffer (shard-streamed and
pipelined above :data:`rayfed_tpu.transport.wire.SHARD_STREAM_THRESHOLD`;
at :data:`~rayfed_tpu.transport.wire.STRIPE_MIN_BYTES` and above its
4 MB chunks additionally fan out round-robin across the per-destination
connection pool, with the device→host fetch and CRC of chunk *k+1*
overlapping the socket write of chunk *k*, and stream sends snapshot
into a reusable page-aligned send arena instead of allocating per round
— see ``docs/source/send_path.rst``) instead of dozens of small ones —
and aggregation arithmetic (:func:`rayfed_tpu.fl.tree_average`) fuses
over the whole model as one elementwise op.

Both :func:`pack_tree` and :func:`unpack_tree` are traceable: inside a
``jit`` (e.g. :func:`rayfed_tpu.models.resnet.make_fed_train_step`) the
cast/slice/concat ops fuse into the surrounding program, so a party's
whole local round — unpack, train, repack — is one compiled call.

Usage (each side of the exchange):

    push:     fed_obj = train.remote(...)  # task returns compress(tree)
    consume:  params = decompress(fed.get(obj), jnp.float32)

``compress(tree, packed=True)`` selects the packed form; ``decompress``
accepts either form transparently.

**Codec/aggregator split.**  This module is pure *codec*: wire forms
(:class:`PackedTree`, and the shared-grid integer form
:class:`~rayfed_tpu.fl.quantize.QuantizedPackedTree` from
:mod:`rayfed_tpu.fl.quantize`, re-exported here) plus the sender-side
residual state that keeps lossy codecs convergent
(:class:`ErrorFeedback` for plain dtype narrowing,
:class:`~rayfed_tpu.fl.quantize.QuantCompressor` for the grid codec).
Nothing here folds: the *aggregator* half — the fold kernels, the
single finalizes, and the per-wire-form kernel selection — lives in
:mod:`rayfed_tpu.fl.fedavg` (``packed_weighted_sum`` /
``packed_quantized_sum`` and their shared finalizes) and
:mod:`rayfed_tpu.fl.streaming` (the streamed/striped folds), which
pick a float or widening-integer accumulate from the codec's wire
dtype.  Decode paths dispatch through ``tree.unpack`` so every wire
form knows how to restore itself.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rayfed_tpu import telemetry


def cast_floats(tree: Any, dtype) -> Any:
    """Cast every floating leaf to ``dtype`` (ints/bools untouched).

    Per-leaf path: one dispatch per leaf when called eagerly.  Inside a
    jit the casts fuse; for eager hot paths prefer the packed form.
    """

    def _cast(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map(_cast, tree)


class PackSpec(NamedTuple):
    """Static description of a packed tree (hashable: jit/aux friendly).

    ``entries`` — one tuple per original leaf, in flatten order:
    ``("f", offset, size, shape, orig_dtype_name)`` for packed float
    leaves (offset/size in *elements* of the wire dtype), or
    ``("p", index)`` for passthrough leaves.  ``treedef`` — the original
    tree structure.  ``wire_dtype`` — dtype name of the packed buffer.
    """

    entries: Tuple
    treedef: Any
    wire_dtype: str


class PackedTree:
    """Wire form of a pytree: one contiguous float buffer + passthrough.

    Registered as a JAX pytree node, so it flows through ``tree_map``,
    ``jit`` and the transport codec like any container; its children are
    ``(buf, *passthrough)`` and the :class:`PackSpec` rides as static
    aux data (pickled with the container skeleton on the wire).
    """

    __slots__ = ("buf", "passthrough", "spec")

    def __init__(self, buf: Any, passthrough: Tuple, spec: PackSpec) -> None:
        self.buf = buf
        self.passthrough = tuple(passthrough)
        self.spec = spec

    @property
    def nbytes(self) -> int:
        total = getattr(self.buf, "nbytes", 0)
        for leaf in self.passthrough:
            total += getattr(leaf, "nbytes", 0)
        return total

    def unpack(self, dtype: Any = None) -> Any:
        """Reconstruct the original tree; see :func:`unpack_tree`."""
        return unpack_tree(self, dtype)

    def __reduce__(self):
        # Explicit reduce: keeps the pickled skeleton stable under
        # __slots__ and admits the class through the restricted
        # unpickler by name (see serialization._INTERNAL_ALLOWED).
        return (PackedTree, (self.buf, self.passthrough, self.spec))

    def __repr__(self) -> str:  # pragma: no cover
        n = sum(1 for e in self.spec.entries if e[0] == "f")
        return (
            f"PackedTree({n} float leaves packed as "
            f"{self.spec.wire_dtype}[{getattr(self.buf, 'shape', '?')}], "
            f"{len(self.passthrough)} passthrough)"
        )


jax.tree_util.register_pytree_node(
    PackedTree,
    lambda pt: ((pt.buf, *pt.passthrough), pt.spec),
    lambda spec, children: PackedTree(children[0], tuple(children[1:]), spec),
)


def _is_float_leaf(leaf: Any) -> bool:
    return hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)


@functools.lru_cache(maxsize=None)
def _jit_packer(wire_dtype: str):
    """ONE fused cast+concat kernel for a whole leaf list (single dispatch)."""
    dt = jnp.dtype(wire_dtype)

    @jax.jit
    def _pack(leaves):
        return jnp.concatenate([l.reshape(-1).astype(dt) for l in leaves])

    return _pack


@functools.partial(jax.jit, static_argnums=(1, 2))
def _jit_unpacker(buf, entries: Tuple, dtype: str):
    """Fused cast + static slices: the whole decode is one XLA program."""
    cast = buf.astype(jnp.dtype(dtype)) if dtype else buf
    return tuple(
        jax.lax.slice(cast, (e[1],), (e[1] + e[2],)).reshape(e[3])
        for e in entries
        if e[0] == "f"
    )


def _is_traced(x: Any) -> bool:
    return isinstance(x, jax.core.Tracer)


def pack_tree(tree: Any, wire_dtype: Any = jnp.bfloat16) -> PackedTree:
    """Pack every float leaf of ``tree`` into one ``wire_dtype`` buffer.

    JAX-array (or traced) leaves go through a single jitted fused
    cast+concat — one dispatch for the whole tree instead of one astype
    per leaf.  Pure-numpy trees are packed host-side with one output
    allocation.  Leaf order is flatten order; offsets are deterministic,
    so two parties packing the same structure produce identical specs
    (required for jit-cache stability across rounds and parties).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    wire_name = np.dtype(wire_dtype).name
    entries = []
    float_leaves = []
    passthrough = []
    offset = 0
    for leaf in leaves:
        if _is_float_leaf(leaf):
            shape = tuple(int(d) for d in leaf.shape)
            size = math.prod(shape) if shape else 1
            entries.append(
                ("f", offset, size, shape, np.dtype(leaf.dtype).name)
            )
            float_leaves.append(leaf)
            offset += size
        else:
            entries.append(("p", len(passthrough)))
            passthrough.append(leaf)
    spec = PackSpec(tuple(entries), treedef, wire_name)

    if not float_leaves:
        buf = np.zeros(0, dtype=np.dtype(wire_name))
    elif any(isinstance(l, jax.Array) or _is_traced(l) for l in float_leaves):
        buf = _jit_packer(wire_name)(float_leaves)
    else:
        # Host path: one allocation, per-leaf vectorized copies (numpy
        # has no dispatch-per-op overhead to amortize).
        buf = np.empty(offset, dtype=np.dtype(wire_name))
        pos = 0
        for leaf in float_leaves:
            n = math.prod(leaf.shape) if leaf.shape else 1
            buf[pos : pos + n] = np.asarray(leaf).reshape(-1)  # casts in-place
            pos += n
    return PackedTree(buf, tuple(passthrough), spec)


def unpack_tree(packed: PackedTree, dtype: Any = None) -> Any:
    """Reconstruct the original tree from a :class:`PackedTree`.

    ``dtype=None`` keeps the wire dtype — on a host buffer the float
    leaves come back as **zero-copy views** into the packed buffer (no
    cast, no allocation).  With a target ``dtype`` the whole buffer is
    cast ONCE (one fused kernel on device, one vectorized pass on host)
    and the per-leaf reshapes are views of that single allocation.
    Traceable: inside jit the slices/casts fuse into the caller.
    """
    entries, treedef, wire_name = packed.spec
    buf = packed.buf
    dtype_name = None if dtype is None else np.dtype(dtype).name
    if dtype_name == wire_name:
        dtype_name = None

    float_views: Tuple = ()
    if any(e[0] == "f" for e in entries):
        if isinstance(buf, jax.Array) or _is_traced(buf):
            float_views = _jit_unpacker(buf, entries, dtype_name)
        else:
            host = np.asarray(buf)
            if dtype_name is not None:
                host = host.astype(np.dtype(dtype_name))
            float_views = tuple(
                host[e[1] : e[1] + e[2]].reshape(e[3])
                for e in entries
                if e[0] == "f"
            )

    leaves = []
    fi = 0
    for entry in entries:
        if entry[0] == "f":
            leaves.append(float_views[fi])
            fi += 1
        else:
            leaves.append(packed.passthrough[entry[1]])
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.lru_cache(maxsize=None)
def _ef_kernel(wire_name: str):
    """Fused error-feedback step over the packed f32 buffer: add the
    carried residual, quantize to the wire dtype, carry the new
    quantization error.  One XLA program for the whole model."""
    dt = jnp.dtype(wire_name)

    @jax.jit
    def _step(buf32, resid):
        corrected = buf32 + resid
        wire_buf = corrected.astype(dt)
        new_resid = corrected - wire_buf.astype(jnp.float32)
        return wire_buf, new_resid

    return _step


class ErrorFeedback:
    """Residual error feedback keeping lossy wire dtypes convergent.

    Each :meth:`compress` call adds the residual quantization error of
    the PREVIOUS round to the outgoing update before casting to the wire
    dtype, then carries the new round's error forward (the EF14/EF-SGD
    scheme: what the wire dropped this round is re-sent next round
    instead of being lost forever).  With bf16 the correction is small;
    with aggressive dtypes (fp8) it is the difference between
    convergence and a noise floor — see the slow convergence test.

    Stateful per sender and per stream: keep one instance per outgoing
    compressed stream (e.g. one per trainer), and :meth:`reset` it when
    the tree structure changes.
    """

    def __init__(self, wire_dtype: Any = jnp.bfloat16) -> None:
        self._wire_name = np.dtype(wire_dtype).name
        self._resid: Any = None

    @property
    def residual(self) -> Any:
        """The carried f32 residual buffer (None before the first round)."""
        return self._resid

    def reset(self) -> None:
        self._resid = None

    def compress(self, tree: Any) -> PackedTree:
        """Pack ``tree`` with error feedback; returns the wire PackedTree."""
        with telemetry.span("fl.pack") as sp:
            packed32 = pack_tree(tree, jnp.float32)
            buf32 = packed32.buf
            if self._resid is None:
                self._resid = jnp.zeros(buf32.shape, jnp.float32)
            elif self._resid.shape != buf32.shape:
                raise ValueError(
                    f"tree structure changed under error feedback "
                    f"({self._resid.shape} residual vs {buf32.shape} buffer) "
                    f"— call reset() when switching models"
                )
            wire_buf, self._resid = _ef_kernel(self._wire_name)(
                buf32, self._resid
            )
            spec = PackSpec(
                packed32.spec.entries, packed32.spec.treedef, self._wire_name
            )
            if sp is not None:
                sp.nbytes = wire_buf.nbytes
            return PackedTree(wire_buf, packed32.passthrough, spec)


def compress(tree: Any, *, packed: bool = False, wire_dtype: Any = jnp.bfloat16):
    """Wire form of a float param tree (half the push bytes at bf16).

    ``packed=True`` selects the fused single-buffer form
    (:class:`PackedTree`): one cast kernel, one wire buffer, zero-copy
    decode — the fast path for whole-model pushes.

    Flight recorder: ``fl.pack`` (the dispatch, not the device's end;
    its parent tells the engine's pack, under ``driver.round``, from a
    trainer's own, under ``task.run``).
    """
    with telemetry.span("fl.pack") as sp:
        if not packed:
            return cast_floats(tree, wire_dtype)
        out = pack_tree(tree, wire_dtype)
        if sp is not None:
            sp.nbytes = out.nbytes
        return out


def decompress(tree: Any, dtype=jnp.float32) -> Any:
    """Restore a wire-compressed tree (any form) to the compute dtype.

    Dispatches through ``tree.unpack`` so subclasses with their own
    decode (the shared-grid integer form dequantizes first) restore
    correctly.  Flight recorder: ``fl.unpack``, as ``fl.pack``.
    """
    with telemetry.span("fl.unpack"):
        if isinstance(tree, PackedTree):
            return tree.unpack(dtype)
        return cast_floats(tree, dtype)


__all__ = [
    "PackSpec",
    "PackedTree",
    "ErrorFeedback",
    "cast_floats",
    "compress",
    "decompress",
    "pack_tree",
    "unpack_tree",
]
