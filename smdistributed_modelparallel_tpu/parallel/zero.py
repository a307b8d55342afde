"""Optimizer-state sharding (ZeRO-1) and sharded data parallelism (ZeRO-2D /
ZeRO-3).

Parity target: reference ``shard_optimizer_state`` (contiguous buffer +
virtual params, ``torch/model.py:1237-1340``,
``torch/optimizers/optimizer.py:355-391``) and "ZeRO-2D" sharded DP
(DeepSpeed stage-3 fork configured by ``backend/zero_config.py`` —
``sharded_data_parallel_degree`` + the ``sdp_*`` knobs).

TPU-native re-design: all three are PartitionSpecs.
- ZeRO-1: optimizer-state leaves mirror their parameter's pp/tp spec and
  additionally shard a free dimension over rdp. The post-update parameter
  allgather the reference runs by hand (``optimizer.py:379-389``) is
  emitted by XLA from the spec mismatch between sharded state and
  replicated params.
- ZeRO-2D (zero2d): parameters themselves are sharded over rdp (above the
  ``sdp_param_persistence_threshold``); XLA inserts the forward/backward
  allgathers and gradient reduce-scatters that DeepSpeed stage 3 performs
  with explicit collectives, and schedules them (the ``sdp_max_live_
  parameters`` / hierarchical-allgather knobs become advisory).
- ZeRO-3 (``sharded_params: "zero3"``, arXiv 2004.13336): the fully
  explicit form of the same transformation. Parameters >= the persistence
  threshold live sharded over rdp on their LARGEST divisible free dim
  (balanced shards, and the layer axis of scanned stacks stays whole so
  the per-layer dynamic slice is local); the step program all-gathers each
  layer's parameters just-in-time in forward — inside the layer scan's
  while loop, so only one layer (two, double-buffered) is ever gathered —
  and REGATHERS in backward instead of stashing gathered copies
  (``zero3_prefetch_scan``'s custom-vjp layer saves only the sharded
  slice). Gradients are computed as genuine per-rdp-slice partial sums
  (the step engine vmaps the microbatch forward over an rdp-reshaped
  batch axis) and leave through ``zero3_grad_reduce``: bucketed
  ``psum_scatter`` reduce-scatters (``zero3_bucket_mb``) issued inside the
  microbatch scan so they overlap the next microbatch's backward compute.
  Below-threshold ("persistent", DeepSpeed terminology) parameters stay
  replicated and their gradients all-reduce as in plain DP.

Data-parallel contract (same as every DDP/FSDP system, reference
``torch/allreduce/ddp.py``): the explicit-reduce path assumes the
per-microbatch loss is the MEAN of the per-rdp-shard losses — true for
every per-example mean loss — and applies the same averaging to every
SCALAR step output (a sum-semantics scalar reads 1/rdp of its plain
value; return per-example arrays and reduce outside the step). Losses
mixing batch elements across rdp shards should keep
``sharded_params: none``.
"""

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.backend.topology import RDP_AXIS
from smdistributed_modelparallel_tpu.module_manager import path_key
from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()

PREFETCH_ENV = "SMP_ZERO3_PREFETCH"


def _has_rdp(axes):
    if axes is None:
        return False
    return RDP_AXIS in (axes if isinstance(axes, tuple) else (axes,))


def add_rdp_axis(spec, shape, rdp_size, persistence_threshold=0,
                 prefer="first"):
    """Extend `spec` (list of axes per dim, or None) with rdp on a free
    dimension divisible by rdp_size — the first such dim by default,
    the largest (ties -> first) under ``prefer="largest"`` (zero3: balanced
    shards, and a scanned stack's small layer axis loses the tie to the
    weight dims so the per-layer dynamic slice stays local). Specs already
    carrying rdp are returned unchanged (a mesh axis may name only one
    dim). Returns a list or None."""
    if rdp_size <= 1 or not shape:
        return None
    if int(np.prod(shape)) < persistence_threshold:
        return None
    base = list(spec) if spec is not None else [None] * len(shape)
    base += [None] * (len(shape) - len(base))
    if any(_has_rdp(a) for a in base):
        return base
    candidates = [
        (i, dim) for i, dim in enumerate(shape)
        if base[i] is None and dim % rdp_size == 0 and dim > 0
    ]
    if not candidates:
        return None
    if prefer == "largest":
        i, _ = max(candidates, key=lambda c: c[1])
    else:
        i, _ = candidates[0]
    base[i] = RDP_AXIS
    return base


def shard_spec_for_leaf(leaf, rdp_size, persistence_threshold=0,
                        prefer="first"):
    """Spec sharding a tensor over rdp on a divisible dim, or None."""
    out = add_rdp_axis(None, getattr(leaf, "shape", ()), rdp_size,
                       persistence_threshold, prefer=prefer)
    return P(*out) if out is not None else None


def _merged_prior_spec(mm, stop_name, path, leaf):
    """The dimension-wise merge of every provider registered before the
    named one — what the pp/tp layers assigned, so the ZeRO provider only
    claims dims they left free."""
    prior = [None] * getattr(leaf, "ndim", 0)
    for p in mm._spec_providers:
        if getattr(p, "_smp_name", None) == stop_name:
            break
        got = p(path, leaf)
        if got is None:
            continue
        for i, axes in enumerate(got):
            if axes is not None and i < len(prior):
                prior[i] = axes
    return prior


def zero2d_param_provider(model):
    """Spec provider sharding parameters over rdp (ZeRO-2D).

    Composes with pp/tp specs via the module manager's dimension-wise merge:
    this provider only names rdp on dims the earlier providers left free.
    """
    cfg = state.cfg
    mesh = state.mesh
    rdp_size = mesh.shape[RDP_AXIS]
    threshold = cfg.sdp_param_persistence_threshold
    mm = model.module_manager

    def provider(path, leaf):
        # Merge-safe: compute the spec the earlier providers produce, then
        # extend with rdp. Providers are consulted in registration order and
        # this one is registered last, so recursion is bounded by ordering:
        # we re-run only the providers registered before us.
        prior = _merged_prior_spec(mm, "zero2d", path, leaf)
        out = add_rdp_axis(prior, getattr(leaf, "shape", ()), rdp_size, threshold)
        return P(*out) if out is not None else None

    return provider


def zero3_param_provider(model):
    """Spec provider for fully-sharded parameters (``sharded_params:
    zero3``): every parameter >= the persistence threshold is sharded over
    rdp on its largest free divisible dim. Leaves with no divisible free
    dim stay replicated (counted, logged once) rather than unevenly
    padded — exactness over coverage."""
    cfg = state.cfg
    mesh = state.mesh
    rdp_size = mesh.shape[RDP_AXIS]
    threshold = cfg.sdp_param_persistence_threshold
    mm = model.module_manager
    unshardable = []

    def provider(path, leaf):
        prior = _merged_prior_spec(mm, "zero3", path, leaf)
        shape = getattr(leaf, "shape", ())
        out = add_rdp_axis(prior, shape, rdp_size, threshold,
                           prefer="largest")
        if (out is None and shape and
                int(np.prod(shape)) >= threshold and path not in unshardable):
            unshardable.append(path)
            logger.warning(
                "zero3: parameter '%s' %s has no free dim divisible by "
                "rdp=%d; kept replicated.", path, tuple(shape), rdp_size,
            )
        return P(*out) if out is not None else None

    return provider


def maybe_register_zero2d(model):
    """Register whichever ZeRO param-sharding mode the config enables
    (kept under the historical name — the partitioner calls it for both
    the zero2d and zero3 modes)."""
    if state.cfg is None:
        return
    if state.cfg.zero3_enabled:
        model.module_manager.register_spec_provider(
            zero3_param_provider(model), name="zero3"
        )
        logger.info(
            "ZeRO-3 fully-sharded parameters: params >= %d elems sharded "
            "over rdp=%d (largest divisible dim), bucket %d MiB.",
            state.cfg.sdp_param_persistence_threshold,
            state.mesh.shape[RDP_AXIS],
            state.cfg.zero3_bucket_mb,
        )
        return
    if state.cfg.zero2d_enabled:
        model.module_manager.register_spec_provider(
            zero2d_param_provider(model), name="zero2d"
        )
        logger.info(
            "ZeRO sharded data parallelism: parameters >= %d elems sharded "
            "over rdp=%d.",
            state.cfg.sdp_param_persistence_threshold,
            state.mesh.shape[RDP_AXIS],
        )


def describe_state_layout(cfg_like):
    """Compact description of where optimizer/parameter state lives under a
    config — works on a live ``ModelParallelConfig`` or a saved checkpoint's
    plain-dict snapshot, so elastic resume (``resilience/elastic.py``) and
    ``scripts/resilience_probe.py`` can describe the layout transition a
    reshard performs. All modes are PartitionSpec-only in this framework
    (module docstring), which is precisely why a checkpoint's logical
    arrays reshard freely across them: the rdp axis placement is re-derived
    from the resuming config, never read from the files."""
    if hasattr(cfg_like, "get"):
        get = cfg_like.get
    else:
        def get(k, d=None):
            return getattr(cfg_like, k, d)

    rdp = int(get("sharded_data_parallel_degree", 0) or 0)
    sharded_params = str(get("sharded_params", "none") or "none")
    return {
        "zero1": bool(get("shard_optimizer_state", False)),
        "zero2d": rdp > 1,
        "zero3": sharded_params == "zero3",
        "sharded_params": sharded_params,
        "sharded_data_parallel_degree": rdp,
        "pipeline_parallel_degree": int(get("pipeline_parallel_degree", 1) or 1),
        "tensor_parallel_degree": int(get("tensor_parallel_degree", 1) or 1),
    }


def opt_state_shardings(opt_state, model):
    """Shardings for the optimizer-state pytree.

    Moment-like leaves (same shape as a parameter, with the parameter's
    path as a suffix of their pytree path) mirror the parameter's spec;
    under ``shard_optimizer_state``/zero2d/zero3 they are additionally
    sharded over rdp. Returns None when state should stay
    replicated-as-params.
    """
    cfg = state.cfg
    if cfg is None:
        return None
    zero1 = cfg.shard_optimizer_state
    zero2d = cfg.zero2d_enabled
    zero3 = cfg.zero3_enabled
    mesh = state.mesh
    rdp_size = mesh.shape[RDP_AXIS]
    threshold = (
        cfg.sdp_param_persistence_threshold if (zero2d or zero3) else 0
    )

    # Param path -> (shape, spec) for suffix matching.
    param_info = {}
    if model is not None and model.params is not None:
        for path, leaf in jax.tree_util.tree_flatten_with_path(model.params)[0]:
            key = path_key(path)
            spec = model.module_manager.spec_for(key, leaf)
            param_info[key] = (getattr(leaf, "shape", ()), list(spec))

    def leaf_sharding(path, leaf):
        key = path_key(path)
        shape = getattr(leaf, "shape", ())
        base = None
        for pkey, (pshape, pspec) in param_info.items():
            if key.endswith(pkey) and pshape == shape:
                base = list(pspec)
                break
        if zero1 or zero2d or zero3:
            # Under zero2d/zero3 a moment's base spec already carries rdp
            # (mirroring its sharded parameter); add_rdp_axis returns it
            # unchanged then. The extension only fires for moments of
            # replicated params (zero1 semantics).
            extended = add_rdp_axis(
                base, shape, rdp_size, threshold,
                prefer="largest" if zero3 else "first",
            )
            if extended is not None:
                return NamedSharding(mesh, P(*extended))
        if base is not None and any(a is not None for a in base):
            return NamedSharding(mesh, P(*base))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf_sharding, opt_state)


# ----------------------------------------------------------------------
# ZeRO-3: step-engine integration helpers
# ----------------------------------------------------------------------


def zero3_enabled(cfg=None):
    cfg = cfg if cfg is not None else state.cfg
    return bool(cfg is not None and cfg.zero3_enabled)


def zero3_manual_grads_supported(cfg=None):
    """True when the explicit per-slice-grad + bucketed reduce-scatter
    path applies: the rdp axis must be the ONLY nontrivial mesh axis (the
    reduce buckets run in a full-manual shard_map region, which would
    gather the other axes at region entry). Other compositions (pp x zero3, tp x zero3) keep sharded
    params + just-in-time gathers and leave the gradient reduction to
    GSPMD."""
    cfg = cfg if cfg is not None else state.cfg
    if cfg is None or not cfg.zero3_enabled:
        return False
    if (cfg.pipeline_parallel_degree > 1 or cfg.tensor_parallel_degree > 1
            or cfg.context_parallel_degree > 1
            or cfg.expert_parallel_degree > 1):
        return False
    mesh = state.mesh
    return mesh is not None and mesh.shape[RDP_AXIS] > 1


def rdp_size():
    mesh = state.mesh
    return int(mesh.shape[RDP_AXIS]) if mesh is not None else 1


def strip_rdp(spec):
    """PartitionSpec with every rdp entry removed (the gathered/compute
    layout of a zero3-sharded value)."""
    from smdistributed_modelparallel_tpu.parallel.sharding import strip_axis

    return strip_axis(spec, RDP_AXIS)


def zero3_pin_grads(grads, model):
    """Constrain a grads tree onto the parameters' (sharded) placements so
    the compiled program's grad outputs come back rdp-sharded — without
    this GSPMD is free to materialize them replicated, which both wastes
    rdp x memory and trips the X-ray replication detector."""
    if grads is None or model is None or model._param_shardings is None:
        return grads
    return jax.tree_util.tree_map(
        jax.lax.with_sharding_constraint, grads, model._param_shardings
    )


def zero3_slice_batch(leaf, axis, rdp):
    """Split a microbatch leaf's batch dim (at ``axis``) into rdp slices
    and move the slice dim to the FRONT, pinned over rdp: the per-device
    rows become the explicit leading axis the step engine vmaps over, so
    the vmapped forward computes each device's loss shard locally and the
    weight-grad dots never cross rdp — the cross-replica reduction
    happens ONLY in zero3_grad_reduce. The per-slice leaf keeps its batch
    rows at the original ``axis``, exactly what the user fn expects."""
    mesh = state.mesh
    shape = leaf.shape
    new_shape = shape[:axis] + (rdp, shape[axis] // rdp) + shape[axis + 1:]
    leaf = leaf.reshape(new_shape)
    if axis:
        leaf = jnp.moveaxis(leaf, axis, 0)
    spec = [None] * leaf.ndim
    spec[0] = RDP_AXIS
    return jax.lax.with_sharding_constraint(
        leaf, NamedSharding(mesh, P(*spec))
    )


def zero3_sliceable(stacked_leaves, mb_axes, rdp):
    """Every scan leaf's per-microbatch batch dim divisible by rdp (the
    reshape above must be exact). ``stacked_leaves`` carry the leading
    [num_mb] scan axis; ``mb_axes`` are the per-microbatch batch dims."""
    if not stacked_leaves:
        return False
    for leaf, axis in zip(stacked_leaves, mb_axes):
        shape = getattr(leaf, "shape", ())
        if len(shape) <= 1 + axis or shape[1 + axis] % rdp != 0:
            return False
    return True


def _grad_layout(params, model):
    """Per-leaf reduction plan: ``(paths, shard_dims)`` where shard_dims[i]
    is the rdp-sharded dim of leaf i (None -> replicated param, all-reduce
    bucket)."""
    mm = model.module_manager
    rdp = rdp_size()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    paths, dims = [], []
    for path, leaf in flat:
        key = path_key(path)
        spec = list(mm.spec_for(key, leaf))
        spec += [None] * (getattr(leaf, "ndim", 0) - len(spec))
        d = next((i for i, a in enumerate(spec) if _has_rdp(a)), None)
        if d is not None and leaf.shape[d] % rdp != 0:
            d = None
        paths.append(key)
        dims.append(d)
    return paths, dims


def zero3_grad_reduce(pgrads, params, model, name="step"):
    """Reduce per-rdp-slice partial grads into rdp-sharded grads.

    ``pgrads`` leaves carry a leading [rdp] slice axis (vmapped grads of
    the per-slice losses). Sharded params' partials are packed shard-major
    into ``zero3_bucket_mb``-byte buckets and reduced with ONE
    ``psum_scatter`` (a real reduce-scatter instruction) per bucket inside
    a full-manual shard_map region; replicated (persistent) params'
    partials sum over the slice axis (GSPMD lowers the cross-shard sum to
    an all-reduce, exactly DDP's bucketing story). The result is divided
    by rdp — the per-microbatch gradient is the MEAN of the slice
    gradients, matching the plain path's mean-over-batch loss.
    """
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    cfg = state.cfg
    mesh = state.mesh
    rdp = rdp_size()
    bucket_bytes = int(cfg.zero3_bucket_mb) * (1 << 20)

    paths, shard_dims = _grad_layout(params, model)
    g_leaves, g_def = jax.tree_util.tree_flatten(pgrads)
    p_leaves = jax.tree_util.tree_leaves(params)

    # Pin the partials' slice axis over rdp: each device holds exactly its
    # own slice's partial sums, so the shard_map in_specs below are a
    # layout no-op, not a reshard.
    def pin_partial(g):
        spec = [None] * g.ndim
        spec[0] = RDP_AXIS
        return jax.lax.with_sharding_constraint(
            g, NamedSharding(mesh, P(*spec))
        )

    g_leaves = [pin_partial(g) for g in g_leaves]

    rs_idx = [
        i for i, d in enumerate(shard_dims)
        if d is not None and p_leaves[i].size > 0
    ]
    sum_idx = [i for i in range(len(g_leaves)) if i not in rs_idx]

    # Greedy bucket fill, program (layer) order — reverse order would
    # micro-optimize the backward's tail, but grads arrive per-microbatch
    # here, and XLA schedules within the bucket anyway. Sized by the
    # PARTIAL-GRAD dtype (bf16 under half compute), not the fp32 master
    # params — the knob bounds the actual collective payload.
    buckets, cur, cur_bytes = [], [], 0
    for i in rs_idx:
        nbytes = int(p_leaves[i].size) * g_leaves[i].dtype.itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)

    out_leaves = [None] * len(g_leaves)

    for bucket in buckets:
        dims = [shard_dims[i] for i in bucket]
        shapes = [tuple(p_leaves[i].shape) for i in bucket]

        def body(*locals_, _dims=tuple(dims), _shapes=tuple(shapes)):
            # locals_[k]: this device's partial for bucket leaf k, full
            # param shape (the [rdp] slice axis is manual -> local [1,...]).
            flats, meta = [], []
            for g, d, s in zip(locals_, _dims, _shapes):
                gl = jnp.moveaxis(g[0], d, 0)        # shard dim leading
                rest = gl.shape[1:]
                flats.append(gl.reshape(rdp, -1))    # shard-major blocks
                meta.append((d, s[d] // rdp, rest, flats[-1].shape[1]))
            flat = (
                flats[0] if len(flats) == 1
                else jnp.concatenate(flats, axis=1)
            )
            reduced = jax.lax.psum_scatter(
                flat, RDP_AXIS, scatter_dimension=0, tiled=False
            )
            outs, off = [], 0
            for d, rows, rest, width in meta:
                piece = reduced[off:off + width].reshape((rows,) + rest)
                outs.append(jnp.moveaxis(piece, 0, d))
                off += width
            return tuple(outs)

        in_specs = tuple(
            P(*([RDP_AXIS] + [None] * p_leaves[i].ndim)) for i in bucket
        )
        out_specs = tuple(
            P(*(
                [None] * shard_dims[i] + [RDP_AXIS]
                + [None] * (p_leaves[i].ndim - shard_dims[i] - 1)
            ))
            for i in bucket
        )
        reduced = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(*(g_leaves[i] for i in bucket))
        for i, r in zip(bucket, reduced):
            out_leaves[i] = r

    for i in sum_idx:
        # Replicated param: plain cross-slice sum; GSPMD lowers the
        # sharded-axis reduction to an rdp all-reduce.
        out_leaves[i] = jnp.sum(g_leaves[i], axis=0)

    inv = 1.0 / rdp
    out_leaves = [
        (g * jnp.asarray(inv, g.dtype)) for g in out_leaves
    ]

    scatter_bytes = sum(
        int(p_leaves[i].size) * g_leaves[i].dtype.itemsize for i in rs_idx
    )
    telemetry.gauge(
        "smp_zero3_buckets",
        "gradient reduce-scatter buckets per microbatch under zero3",
    ).labels(step=name).set(len(buckets))
    telemetry.gauge(
        "smp_zero3_bucket_bytes",
        "logical gradient bytes entering reduce-scatter buckets per "
        "microbatch under zero3",
    ).labels(step=name).set(scatter_bytes)
    telemetry.gauge(
        "smp_zero3_sharded_params",
        "parameter leaves rdp-sharded under zero3",
    ).labels(step=name).set(len(rs_idx))
    telemetry.gauge(
        "smp_zero3_persistent_params",
        "parameter leaves kept replicated (persistence threshold / no "
        "divisible dim) under zero3",
    ).labels(step=name).set(len(sum_idx))
    return jax.tree_util.tree_unflatten(g_def, out_leaves)


def zero3_outputs_mergeable(plain_out, sliced_out, rdp):
    """Whether the user fn's outputs survive the slice-vmap round trip
    exactly: leaf-wise, the per-slice output must be the per-microbatch
    output with its LEADING dim divided by rdp (merged back losslessly by
    ``zero3_merge_outputs``), or a scalar in both (averaged — the mean
    contract). Anything else — batch on a later axis, shapes that do not
    scale — cannot be reassembled without guessing, so the step engine
    falls back to the GSPMD gradient path where outputs are untouched."""
    p_leaves = jax.tree_util.tree_leaves(plain_out)
    s_leaves = jax.tree_util.tree_leaves(sliced_out)
    if len(p_leaves) != len(s_leaves):
        return False
    for p, s in zip(p_leaves, s_leaves):
        ps = getattr(p, "shape", None)
        ss = getattr(s, "shape", None)
        if ps is None or ss is None:
            if ps != ss:
                return False
            continue
        if ps == () and ss == ():
            continue
        if (len(ps) == len(ss) and ps[1:] == ss[1:] and ss[0] * rdp == ps[0]
                and ps[0] > 0):
            continue
        return False
    return True


def zero3_merge_outputs(out):
    """Undo the vmapped forward's leading [rdp] slice axis on the user's
    per-microbatch outputs. The step engine's output-shape probe
    (``zero3_outputs_mergeable``) already guaranteed every array leaf's
    leading dim scales by rdp under slicing, so the merge is the exact
    inverse of the batch reshape; per-slice scalars (vmapped to [rdp])
    average, matching the mean-loss contract."""
    def merge(leaf):
        if leaf.ndim >= 2:
            return leaf.reshape((-1,) + leaf.shape[2:])
        return jnp.mean(leaf, axis=0) if leaf.ndim == 1 else leaf

    return jax.tree_util.tree_map(merge, out)


# ----------------------------------------------------------------------
# ZeRO-3: double-buffered just-in-time layer gather (PR-5 transfer
# registers, lifted from the pipeline executors' stage-boundary trick)
# ----------------------------------------------------------------------


def prefetch_knob():
    """Normalized SMP_ZERO3_PREFETCH value ("on"/"off") — the prefetch
    and lifted-scan programs differ at identical shapes, so this knob is
    part of the step cache key and the exec-cache knob facts."""
    raw = os.environ.get(PREFETCH_ENV, "1").lower()
    return "off" if raw in ("0", "off", "false") else "on"


def zero3_prefetch_active():
    """Whether scanned-layer models should run the double-buffered gather
    scan: zero3 on, rdp nontrivial, no pipeline (pp executors own the
    layer loop there), and not disabled via SMP_ZERO3_PREFETCH=0."""
    cfg = state.cfg
    if cfg is None or not cfg.zero3_enabled:
        return False
    if cfg.pipeline_parallel_degree > 1:
        return False
    if prefetch_knob() == "off":
        return False
    mesh = state.mesh
    return mesh is not None and mesh.shape[RDP_AXIS] > 1


def gathered_slice_specs(stacked_params, path_prefix):
    """Gather-target specs for one layer's params sliced from a stacked
    [num_layers, ...] tree: the registered spec minus the leading stack
    dim, with rdp stripped (the compute layout — pp/tp axes, were any
    present, survive)."""
    mm = state.module_manager
    mesh = state.mesh

    def spec_of(path, leaf):
        key = path_key(path)
        if path_prefix:
            key = f"{path_prefix}/{key}"
        spec = list(mm.spec_for(key, leaf))
        spec += [None] * (getattr(leaf, "ndim", 0) - len(spec))
        return NamedSharding(mesh, strip_rdp(P(*spec[1:])))

    return jax.tree_util.tree_map_with_path(spec_of, stacked_params)


@jax.custom_vjp
def _issue_before(nxt, h):
    """Optimization barrier tying the NEXT layer's gathered params to the
    current layer's input: XLA cannot sink the prefetch gather below the
    compute that consumes ``h``, so the gather issues while the current
    layer's dots run (the PR-5 'park in transfer registers' ordering).
    Identity on both operands; the barrier stays out of the transpose
    program (the backward re-gathers at use instead)."""
    return jax.lax.optimization_barrier((nxt, h))


def _issue_fwd(nxt, h):
    return _issue_before(nxt, h), None


def _issue_bwd(_, ct):
    return ct


_issue_before.defvjp(_issue_fwd, _issue_bwd)


def zero3_prefetch_scan(apply_layer, h, stacked_params, num_layers,
                        gather_specs):
    """Scan ``apply_layer(h, layer_params) -> h`` over a stacked layer
    tree with the next layer's all-gather double-buffered under the
    current layer's compute.

    Transfer registers in the scan carry hold layer i+1's GATHERED params
    (issued at tick i behind an optimization barrier) next to their
    sharded slice; the backward never sees the gathered register — a
    custom-vjp layer saves only the sharded slice and REGATHERS (plus
    recomputes the layer, standard FSDP-with-remat pairing) in the
    transpose loop, so per-device live gathered params stay at two layers
    in forward and one in backward.
    """
    def gather(tree):
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, gather_specs
        )

    def slice_at(i):
        return jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False),
            stacked_params,
        )

    @jax.custom_vjp
    def run_layer(hh, reg, reg_shard):
        return apply_layer(hh, reg)

    def _run_fwd(hh, reg, reg_shard):
        return apply_layer(hh, reg), (hh, reg_shard)

    def _run_bwd(res, ct):
        hh, reg_shard = res
        w = gather(reg_shard)
        _, vjp = jax.vjp(apply_layer, hh, w)
        dh, dw = vjp(ct)
        # The gathered register's cotangent routes back through the carry
        # chain to the previous tick's gather, whose VJP is the
        # partial-sum -> rdp-sharded reshard of the stacked param grads;
        # the sharded slice itself contributed no forward value.
        return dh, dw, jax.tree_util.tree_map(jnp.zeros_like, reg_shard)

    run_layer.defvjp(_run_fwd, _run_bwd)

    s0 = slice_at(0)
    reg0 = gather(s0)

    def body(carry, i):
        hh, reg, reg_shard = carry
        nxt_shard = slice_at(jnp.minimum(i + 1, num_layers - 1))
        nxt = gather(nxt_shard)
        nxt, hh = _issue_before(nxt, hh)
        hh = run_layer(hh, reg, reg_shard)
        return (hh, nxt, nxt_shard), None

    (h, _, _), _ = jax.lax.scan(
        body, (h, reg0, s0), jnp.arange(num_layers)
    )
    return h
