"""Sharding strategies: how a fed task's compute maps onto the party mesh.

A :class:`ShardingStrategy` bundles the mesh with partition rules for
params and batch, and compiles train/eval steps with ``jax.jit`` +
``NamedSharding`` constraints.  DP/FSDP/TP/SP/EP/PP are expressed as which
mesh axes the batch, parameters, sequence, and experts are split over —
XLA inserts the collectives (psum/all-gather/reduce-scatter) from the
sharding annotations; nothing is hand-scheduled.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rayfed_tpu import tree_util
from rayfed_tpu.parallel.mesh import AXIS_DP, AXIS_FSDP


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def shard_params_by_rules(
    mesh: Mesh,
    params: Any,
    rules: Sequence[Tuple[str, P]],
    default: Optional[P] = None,
) -> Any:
    """Build a NamedSharding pytree for ``params`` from (regex, spec) rules.

    First matching rule wins (t5x-style partitioning rules, applied to the
    '/'-joined tree path).  Unmatched leaves use ``default`` (replicated if
    None).  Specs naming axes absent from the mesh degrade to None on that
    dim, so one rule set serves every mesh shape.
    """
    default = default if default is not None else P()
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    axis_names = set(mesh.axis_names)

    def _prune(spec: P) -> P:
        pruned = []
        for entry in spec:
            if entry is None:
                pruned.append(None)
            elif isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a in axis_names)
                pruned.append(kept if kept else None)
            else:
                pruned.append(entry if entry in axis_names else None)
        return P(*pruned)

    def _assign(path, leaf):
        path_s = _path_str(path)
        for pat, spec in compiled:
            if pat.search(path_s):
                return NamedSharding(mesh, _prune(spec))
        return NamedSharding(mesh, _prune(default))

    return jax.tree_util.tree_map_with_path(_assign, params)


@dataclasses.dataclass
class ShardingStrategy:
    """Declarative parallelism plan for a party's compute.

    - ``batch_axes``: mesh axes the leading batch dim is split over (DP).
    - ``param_rules``: (regex, PartitionSpec) rules for model params —
      FSDP ≈ shard large kernels over 'fsdp'; TP ≈ shard feature dims over
      'tp'; EP ≈ shard the expert dim over 'ep'.
    - ``seq_axis``: mesh axis for sequence/context parallelism (ring
      attention / Ulysses) — consumed by the attention ops.
    - ``pp_axis``: mesh axis for pipeline stages — consumed by
      :mod:`rayfed_tpu.parallel.pipeline`.
    """

    mesh: Mesh
    batch_axes: Tuple[str, ...] = (AXIS_DP,)
    param_rules: Tuple[Tuple[str, P], ...] = ()
    param_default: Optional[P] = None
    seq_axis: Optional[str] = None
    pp_axis: Optional[str] = None

    def batch_sharding(self, ndim: int = 2) -> NamedSharding:
        axes = tuple(a for a in self.batch_axes if a in self.mesh.axis_names)
        spec = (axes if axes else None,) + (None,) * (ndim - 1)
        return NamedSharding(self.mesh, P(*spec))

    def param_shardings(self, params: Any) -> Any:
        return shard_params_by_rules(
            self.mesh, params, self.param_rules, self.param_default
        )

    def shard_params(self, params: Any) -> Any:
        return jax.device_put(params, self.param_shardings(params))

    def shard_batch(self, batch: Any) -> Any:
        def _put(x):
            return jax.device_put(x, self.batch_sharding(ndim=max(1, x.ndim)))

        return tree_util.tree_map(_put, batch)

    def replicate(self, tree: Any) -> Any:
        return jax.device_put(tree, replicated(self.mesh))

    def jit_step(
        self,
        step_fn: Callable,
        donate_argnums: Tuple[int, ...] = (),
        **jit_kwargs,
    ) -> Callable:
        """jit ``step_fn`` under this strategy's mesh context.

        Shardings flow from the arguments (params/batch already placed by
        :meth:`shard_params`/:meth:`shard_batch`); XLA derives the rest.
        """
        jitted = jax.jit(step_fn, donate_argnums=donate_argnums, **jit_kwargs)

        def _call(*args, **kwargs):
            with jax.sharding.set_mesh(self.mesh):
                return jitted(*args, **kwargs)

        _call.lower = jitted.lower  # expose for AOT/compile checks
        return _call


def data_parallel(mesh: Mesh) -> ShardingStrategy:
    return ShardingStrategy(mesh=mesh, batch_axes=(AXIS_DP,))


def fsdp(mesh: Mesh, min_shard_dim: int = 2) -> ShardingStrategy:
    """Batch over dp+fsdp; every ≥2-D kernel sharded over 'fsdp' on dim 0."""
    del min_shard_dim
    return ShardingStrategy(
        mesh=mesh,
        batch_axes=(AXIS_DP, AXIS_FSDP),
        param_rules=((r"(kernel|embedding|scale.*|w[0-9]*)$", P(AXIS_FSDP)),),
    )
