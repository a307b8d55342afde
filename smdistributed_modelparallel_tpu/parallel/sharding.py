"""Sharding helpers: batch specs, data axes, replication.

TPU-native core with no single reference counterpart: encodes where the
reference's implicit "each rank gets its own batch shard" placement
(``backend/split.py`` + per-rank data loaders) becomes explicit
PartitionSpecs over the mesh.
"""

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from smdistributed_modelparallel_tpu.backend.topology import (
    CP_AXIS,
    EP_AXIS,
    RDP_AXIS,
    TP_AXIS,
)


def data_axes(cfg):
    """Mesh axes across which distinct batch elements live.

    Parity: reference dp = tp x rdp (``backend/core.py:49-55``) — each GPU
    gets its own batch unless ``prescaled_batch``; ep/cp are TPU extensions
    carved from the data dimension (cp shards sequence, not batch, so it is
    excluded here and applied to the sequence axis).
    """
    axes = [RDP_AXIS, EP_AXIS]
    if cfg.tensor_parallel_degree > 1 and not cfg.prescaled_batch:
        axes.append(TP_AXIS)
    return tuple(axes)


def batch_spec(cfg, ndim, batch_axis=0, stacked=False):
    """PartitionSpec for a batch array: batch dim over data axes, sequence
    dim over cp (if enabled), everything else replicated.

    With ``stacked=True`` the array carries a leading [num_microbatches]
    axis (never sharded) and `batch_axis` refers to the post-stack layout.
    """
    spec = [None] * ndim
    offset = 1 if stacked else 0
    spec_batch = batch_axis + offset
    if spec_batch < ndim:
        spec[spec_batch] = data_axes(cfg)
    if cfg.context_parallel_degree > 1 and spec_batch + 1 < ndim:
        spec[spec_batch + 1] = CP_AXIS
    return P(*spec)


def replicated(mesh):
    return NamedSharding(mesh, P())


def named(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def single_axis_spec(ndim, dim, axis):
    """PartitionSpec naming one mesh axis on one dim of an ndim-rank
    value, everything else replicated — the inverse building block of
    ``strip_axis``. Shared by the tp-overlap ring regions
    (``ops/collective_matmul.py``: sequence/feature block specs) and the
    fused bias+GELU tp wrapper (``nn/utils.py``)."""
    return P(*(axis if d == dim else None for d in range(ndim)))


def manual_axes(*axes):
    """The ``axis_names`` of a partial-manual ``shard_map`` region over
    ``axes`` traced here: those, plus the mesh axes that enclosing ``vmap``s
    gave as ``spmd_axis_name`` (the pipeline executors' ``stage_vmap``
    names pp on the stage axis). ``shard_map``'s batching rule puts the
    vmap's name on the batched dim of the region's specs and traces the
    body at the per-shard size whether or not the region is manual over
    that axis; where it is not, the program fails verification ("operand
    shape ... and region operand shape must match"). So under such a vmap
    the region is manual over its axis as well, and each rank of it runs
    its own rows. JAX's axis environment is where the name is observable
    whatever traces (``scan``, ``checkpoint``, ``vjp``) lie between the
    vmap and the region; ``shard_map`` reads it there for the same
    purpose."""
    from jax._src import core

    return frozenset(axes) | frozenset(core.get_axis_env().spmd_axis_names)


def strip_axis(spec, axis):
    """PartitionSpec with every occurrence of one mesh axis removed —
    the "gathered over that axis" layout of a sharded value. Shared by
    the decode regather (strip pp, ``model.regather_for_decode``) and
    ZeRO-3's just-in-time param gathers (strip rdp,
    ``parallel/zero.strip_rdp``)."""
    def drop(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a != axis)
            return kept if kept else None
        return None if entry == axis else entry

    return P(*(drop(a) for a in spec))
