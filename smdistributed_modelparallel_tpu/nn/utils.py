"""TP utilities: parameter partitioning metadata + activation sharding.

Parity target: reference ``torch/nn/utils.py`` — ``parameter_creation_scope``
(marks params distributed/scaled-batch, ``:120-154``),
``initialize_with_input_partition`` / ``initialize_with_output_partition``
(slice fan-in/fan-out per tp_rank, ``:155-249``), and the autograd
collectives ``NarrowForTP`` / ``AllgatherForTP`` / ``ForwardAllreduceForTP``
/ ``BackwardAllreduceForTP`` / ``ReduceScatterForTP`` /
``ScatterAndMergeForTP`` (``:465-663``).

TPU-native re-design: none of those collectives are written by hand. A
parameter is "input/output partitioned" by carrying a PartitionSpec with the
``tp`` mesh axis on the corresponding dimension (flax ``with_partitioning``
metadata, unboxed by ``DistributedModel``); activations are steered with
``with_sharding_constraint``. GSPMD then inserts exactly the
allgather/reduce-scatter/allreduce pairs the reference implements as
autograd Functions — including their transposes for backward. The explicit
collectives that remain (Ulysses all-to-all, ring permute) live in
``smp.ops``.
"""

import functools

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.backend.topology import (
    CP_AXIS,
    RDP_AXIS,
    EP_AXIS,
    TP_AXIS,
)


def tp_size():
    if state.cfg is None:
        return 1
    return state.cfg.tensor_parallel_degree


def tp_enabled():
    return tp_size() > 1


def _mesh():
    return state.mesh if state.initialized else None


def shard_activation(x, *spec):
    """Constrain an activation to a PartitionSpec over the mesh.

    No-op when the framework is uninitialized or the mesh axes named in the
    spec are all size 1 (e.g. tp_degree=1) — the constraint would be a
    trivial replication and only add noise to the jaxpr.
    """
    mesh = _mesh()
    if mesh is None:
        return x
    sizes = mesh.shape
    if _axes_all_trivial(spec):
        return x
    # Drop axes that don't divide the dim (tiny test shapes).
    fixed = []
    for dim, axes in enumerate(spec):
        if axes is None:
            fixed.append(None)
            continue
        axes_t = axes if isinstance(axes, tuple) else (axes,)
        total = 1
        for a in axes_t:
            total *= sizes.get(a, 1)
        if dim < x.ndim and x.shape[dim] % total == 0:
            fixed.append(axes)
        else:
            fixed.append(None)
    full = fixed + [None] * (x.ndim - len(fixed))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*full))
    )


def batch_seq_spec(extra=()):
    """Leading (batch, seq) axes of an activation: batch over the data axes,
    sequence over cp. ``extra`` appends trailing-dim axes."""
    return (( RDP_AXIS, EP_AXIS), CP_AXIS) + tuple(extra)


def _axes_all_trivial(names):
    """True when every mesh axis named in `names` (entries may be axis
    names, tuples of names, or None) has size 1 on the current mesh — i.e.
    partitioning over them would be a trivial replication."""
    mesh = _mesh()
    if mesh is None:
        return True
    sizes = mesh.shape
    involved = [
        a for n in names if n
        for a in (n if isinstance(n, tuple) else (n,))
    ]
    return all(sizes.get(a, 1) == 1 for a in involved)


def partitioned(init_fn, names):
    """Wrap a flax param init with tp partitioning metadata.

    ``names`` is a tuple with one entry per dim: a mesh axis name or None.
    When tp is disabled the init is returned unwrapped so parameter trees
    are plain arrays in the single-device path.
    """
    if not tp_enabled() or not any(n for n in names):
        return init_fn
    return nn.with_partitioning(init_fn, tuple(names))


def axis_partitioned(init_fn, names):
    """Like ``partitioned`` but gated on ANY named mesh axis being > 1
    (MoE expert params shard over ep, optionally combined with tp)."""
    if not any(n for n in names) or _axes_all_trivial(names):
        return init_fn
    return nn.with_partitioning(init_fn, tuple(names))


# Parameter leaves that a training step leaves as loaded, by the leaf's own
# name (the last part of its path): the module that makes one stops its
# gradient, and ``DistributedOptimizer`` zeroes whatever update the
# transformation still gives it (weight decay). Today: the dropless expert
# layer's ``router/selection_bias``.
FIXED_PARAM_NAMES = frozenset({"selection_bias"})


def is_fixed_param(path):
    """Whether the '/'-joined parameter ``path`` names a fixed leaf."""
    return path.rsplit("/", 1)[-1] in FIXED_PARAM_NAMES


def tp_ring_active():
    """Whether the overlapped-tp ring path applies right now — the one
    lazy wrapper over ``ops.collective_matmul.tp_overlap_active`` the tp
    layer family (nn/linear.py, nn/transformer.py) shares, so gating
    changes cannot silently split between the two."""
    from smdistributed_modelparallel_tpu.ops.collective_matmul import (
        tp_overlap_active,
    )

    return tp_overlap_active()


@functools.lru_cache(maxsize=64)
def _fused_bias_gelu_region(mesh, ndim, interpret, manual):
    from smdistributed_modelparallel_tpu.ops.pallas_gelu import bias_gelu
    from smdistributed_modelparallel_tpu.parallel.sharding import (
        single_axis_spec,
    )

    h_spec = single_axis_spec(ndim, ndim - 1, TP_AXIS)
    b_spec = single_axis_spec(1, 0, TP_AXIS)
    return jax.jit(jax.shard_map(
        lambda h, b: bias_gelu(h, b, interpret),
        mesh=mesh, in_specs=(h_spec, b_spec), out_specs=h_spec,
        axis_names=manual, check_vma=False,
    ))


def fused_bias_gelu(h, b):
    """Dispatch ``gelu(h + b)`` to the fused Pallas kernel
    (``ops/pallas_gelu.py``). Under tensor parallelism the activation's
    feature dim is tp-sharded, so the call runs inside a tp manual
    region handing the kernel its local block (a plain pallas_call on
    the sharded array would force a gather); at tp=1 it is a direct
    call. Callers guard with ``pallas_gelu.bias_gelu_ok``.

    Under ``matmul_precision: fp8`` the epilogue INPUT rounds to the
    e4m3 grid with the ``gelu_in`` slot's delayed scale (straight-
    through gradient) before the kernel — the handoff between the fp8
    matmul and the fused activation carries fp8 information content,
    matching what a fused fp8-epilogue kernel would hand over."""
    from smdistributed_modelparallel_tpu.ops.pallas_gelu import bias_gelu

    from smdistributed_modelparallel_tpu import quant

    if quant.fp8_trace_active():
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_quant_dispatch,
        )

        record_quant_dispatch("gelu_in", "fp8")
        h = quant.fake_quant(h, "gelu_in.x")
    from smdistributed_modelparallel_tpu.parallel.sharding import manual_axes

    interpret = jax.default_backend() != "tpu"
    mesh = _mesh()
    tp = mesh.shape.get(TP_AXIS, 1) if mesh is not None else 1
    if tp <= 1 or h.shape[-1] % tp != 0:
        return bias_gelu(h, b, interpret)
    h = shard_activation(h, *([None] * (h.ndim - 1) + [TP_AXIS]))
    return _fused_bias_gelu_region(
        mesh, h.ndim, interpret, manual_axes(TP_AXIS)
    )(h, b)


def dense_init(scale=None, stddev=0.02):
    if scale is not None:
        return nn.initializers.normal(stddev=scale)
    return nn.initializers.normal(stddev=stddev)


def resolve_deterministic(explicit):
    """Whether dropout should be skipped.

    ``explicit`` is a module's ``deterministic`` field: an explicit bool
    wins; None defers to the wrapping ``DistributedModel``'s train/eval
    mode (parity: the reference's modules are nn.Modules following
    ``model.train()``/``.eval()``; flax needs the flag threaded).
    """
    if explicit is not None:
        return explicit
    model = state.model
    if model is not None:
        return not model.training
    return True


# ----------------------------------------------------------------------
# Sequence sharding helpers (parity: reference torch/nn/utils.py:45-70
# shard_sequence / unshard_sequence).
# ----------------------------------------------------------------------


def shard_sequence(x, axis=1):
    """Constrain the sequence axis over the tp axis (the reference slices
    the sequence per tp_rank; here it is a resharding constraint)."""
    spec = [None] * x.ndim
    spec[axis] = TP_AXIS
    return shard_activation(x, *spec)


def unshard_sequence(x, axis=1):
    spec = [None] * x.ndim
    return shard_activation(x, *spec)


def mask_keep_2d(mask):
    """Boolean [B, T] keep-flags from an attention mask in any accepted
    form ([B, T] or [B, 1, 1, T]; bool / 0-1 int / additive float), or
    None when absent or not reducible to per-key flags."""
    if mask is None:
        return None
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        mask = mask[:, 0, 0, :]
    if mask.ndim != 2:
        return None
    if mask.dtype == jnp.bool_:
        return mask
    if jnp.issubdtype(mask.dtype, jnp.integer):
        return mask != 0
    return mask > -1.0  # additive: 0 keep, large-negative drop


def half_cast(params, half):
    """Cast floating leaves to the half dtype (None = no-op). The ONE
    definition of the training/generation compute-dtype cast — step.py,
    pipeline_1f1b.py, and generation.py all share this predicate."""
    if half is None:
        return params
    return jax.tree_util.tree_map(
        lambda p: p.astype(half)
        if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params,
    )


def pad_row_offset(mask):
    """Per-row position offset ([B] int32, <= 0) for LEFT-padded prompts,
    or None when no mask applies.

    With left padding, pad count = width - sum(keep) at prefill ([B, T]
    prompt mask) and at decode steps ([B, 1, 1, C] mask with generated
    columns kept) alike, so the offset derives statelessly from whatever
    mask arrives. Rows whose keep pattern is NOT a left-pad shape
    (0..0 1..1 monotone) get offset 0 — an arbitrary key-blocking mask
    excludes slots from attention but must not shift positions."""
    keep = mask_keep_2d(mask)
    if keep is None:
        return None
    is_leftpad = jnp.all(keep[:, 1:] >= keep[:, :-1], axis=1)
    off = jnp.sum(keep, axis=1).astype(jnp.int32) - keep.shape[1]
    return jnp.where(is_leftpad, off, 0)


# ----------------------------------------------------------------------
# KV cache for autoregressive decoding (TPU extension, no reference
# counterpart: the reference is a training library; generation support
# makes the switch complete for fine-tune-then-sample users). Used by the
# attention layers under ``decode=True`` and driven by ``smp.generate``.
# ----------------------------------------------------------------------


class DecodeKVCache:
    """Fixed-length per-layer K/V cache held in flax "cache" variables.

    Protocol (see ``generation.py``): the first call on a fresh cache is
    the PREFILL — a whole-prompt chunk attends causally over itself (the
    cache is empty before it, so chunk-causal equals cache semantics, and
    the chunk keeps the flash-attention fast path). Every later call is a
    T=1 DECODE step attending over the written prefix of the cache. Both
    write their K/V into ``cache_len`` fixed slots at ``cache_index``.

    The chunk-size distinction is static (Python ``T > 1``), so prefill
    and decode compile as two separate programs — no traced branching.
    """

    def __init__(self, mod, shape, dtype):
        B, C, H, hd = shape
        if C is None:
            raise ValueError(
                "decode=True requires decode_cache_len (total generation "
                "length) on the module."
            )
        # Static protocol guard state: True iff this apply CREATES the
        # cache (the only call allowed to carry a multi-token chunk).
        self._fresh = not mod.has_variable("cache", "cached_key")
        self._ck = mod.variable(
            "cache", "cached_key", lambda: jnp.zeros((B, C, H, hd), dtype)
        )
        self._cv = mod.variable(
            "cache", "cached_value", lambda: jnp.zeros((B, C, H, hd), dtype)
        )
        self._idx = mod.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        self.cache_len = C

    @property
    def index(self):
        """Positions filled so far (int32 scalar; 0 at prefill)."""
        return self._idx.value

    def append(self, k, v, window=None):
        """Write chunk K/V ([B, T, H, hd]) at the current index.

        Returns ``(k_attend, v_attend, mask)``: for a prefill chunk the
        chunk itself with ``mask=None`` (caller runs plain causal
        attention); for a decode step the full cache plus a
        [1, 1, 1, cache_len] boolean mask selecting positions <= index
        (banded to ``window`` when set).
        """
        T = k.shape[1]
        if T > 1 and not self._fresh:
            raise ValueError(
                "KV-cache protocol violation: a multi-token (prefill) "
                "chunk is only valid on a fresh cache; later calls must "
                "decode one token at a time (the chunk would silently "
                "ignore all previously cached positions)."
            )
        i = self._idx.value
        self._ck.value = jax.lax.dynamic_update_slice(
            self._ck.value, k, (0, i, 0, 0)
        )
        self._cv.value = jax.lax.dynamic_update_slice(
            self._cv.value, v, (0, i, 0, 0)
        )
        self._idx.value = i + T
        if T > 1:
            return k, v, None
        cols = jnp.arange(self.cache_len)
        keep = cols <= i
        if window is not None:
            keep = keep & (i - cols < window)
        return (
            self._ck.value,
            self._cv.value,
            keep[None, None, None, :],
        )


class PagedKVCache:
    """Block-pooled per-layer K/V cache for continuous-batching serving.

    Where ``DecodeKVCache`` gives every sequence a private contiguous
    [B, cache_len, H, hd] buffer, this holds ONE pool of
    ``num_blocks`` fixed-size token blocks ([num_blocks, block_tokens,
    H, hd] per layer) shared by every in-flight sequence. A host-side
    allocator (``serving/kv_cache.BlockAllocator``) hands out blocks and
    builds per-sequence BLOCK TABLES — ordered pool-block ids, logical
    block ``j`` of a sequence living at pool block ``table[j]`` — passed
    into the compiled program as device arrays, so sequences of wildly
    different lengths share the pool and a finished sequence's blocks are
    reusable the moment the host frees them. Pool block 0 is reserved as
    the TRASH block: unused table entries point at it, so writes from
    inactive decode slots and padded prefill tail positions land there
    harmlessly (and are never attended — the mask is position-derived).

    Like ``DecodeKVCache`` the pool shards over tp on the head axis
    (``shard_activation``), so the serving KV footprint per device is
    ``pool_bytes / tp`` and the X-ray's KV replication detector
    (``hlo_audit.serving_kv_findings``) can hold it to that.

    Call protocol (one compiled program each; driven by
    ``serving/engine.py``):

    - decode step: ``k``/``v`` are [S, 1, H, hd] (one token per decode
      slot), ``positions[b]`` is the token's absolute position, and the
      returned attend set is the whole gathered table ([S, T_max, H, hd]
      where ``T_max = max_blocks * block_tokens``) with a
      ``col <= position`` boolean mask.
    - prefill chunk: ``k``/``v`` are [B, C, H, hd] (usually B=1), written
      at ``positions[b] + t``; ``valid[b]`` marks how many of the C
      chunk rows are real (the last chunk of a prompt is padded) — the
      tail's writes are routed to the trash block. The mask is chunk-
      causal against absolute positions: col ``j`` is visible to chunk
      row ``t`` iff ``j <= positions[b] + t``.
    """

    def __init__(self, mod, num_blocks, block_tokens, heads, head_dim,
                 dtype):
        from smdistributed_modelparallel_tpu import quant as _quant

        # SMP_KV_QUANT=int8: the pools store int8 with per-block-per-head
        # scale sidecars ([num_blocks, H] f32 — running block maxima that
        # only grow), halving the pool bytes; decode dequantizes at the
        # gather. The knob is static env config, so the two layouts are
        # different compiled programs (serving keys carry the suffix).
        self._quant = _quant.kv_quant_mode() == "int8"
        self._dtype = dtype
        shape = (num_blocks, block_tokens, heads, head_dim)
        pool_dtype = _quant.kv_pool_dtype(dtype)
        self._pk = mod.variable(
            "cache", "pool_key", lambda: jnp.zeros(shape, pool_dtype)
        )
        self._pv = mod.variable(
            "cache", "pool_value", lambda: jnp.zeros(shape, pool_dtype)
        )
        if self._quant:
            self._sk = mod.variable(
                "cache", "scale_key",
                lambda: jnp.zeros((num_blocks, heads), jnp.float32),
            )
            self._sv = mod.variable(
                "cache", "scale_value",
                lambda: jnp.zeros((num_blocks, heads), jnp.float32),
            )
        self.num_blocks = num_blocks
        self.block_tokens = block_tokens

    def _shard(self, pool):
        # tp shards the head axis, exactly like the activations/contiguous
        # caches; trivial-axis meshes make this a no-op.
        return shard_activation(pool, None, None, TP_AXIS, None)

    def _shard_scale(self, scale):
        # The scale sidecars shard with the pools' head axis.
        return shard_activation(scale, None, TP_AXIS)

    def append(self, k, v, block_tables, positions, valid=None,
               window=None):
        """Write chunk K/V and return ``(k_all, v_all, mask)``.

        Args:
          k, v: [B, T, H, hd] chunk K/V (T=1 decode, T=chunk prefill).
          block_tables: [B, max_blocks] int32 pool-block ids in sequence
            order; unused entries 0 (the trash block).
          positions: [B] int32 absolute position of the chunk's first
            token (number of tokens already cached for that sequence).
          valid: optional [B] int32 — rows ``t >= valid[b]`` of the chunk
            are padding: their writes go to the trash block.
          window: optional local-attention band width.
        """
        B, T = k.shape[:2]
        bt = self.block_tokens
        max_blocks = block_tables.shape[1]
        pos = positions[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        blk = jnp.take_along_axis(
            block_tables, jnp.clip(pos // bt, 0, max_blocks - 1), axis=1
        )
        dest = blk * bt + pos % bt                              # [B, T]
        if valid is not None:
            # Padded chunk tail: route the write into the trash block
            # (offset by t so a wide chunk never scatters twice into one
            # slot of it — the winner would be nondeterministic).
            trash = jnp.arange(T, dtype=jnp.int32)[None, :] % bt
            dest = jnp.where(
                jnp.arange(T)[None, :] < valid[:, None], dest, trash
            )
        flat = dest.reshape(-1)
        H, hd = k.shape[2], k.shape[3]
        if self._quant:
            from smdistributed_modelparallel_tpu import quant as _quant

            # int8 pools: grow the touched blocks' scales by the incoming
            # tokens' per-head amax, requantize the pool under the grown
            # scales, then write the tokens quantized LAST (so they land
            # on the final grid — one rounding, not two).
            blk_flat = flat // bt
            pk8, sk, qk = _quant.kv_quantize_append(
                self._pk.value, self._sk.value, k.reshape(B * T, H, hd),
                blk_flat,
            )
            pv8, sv, qv = _quant.kv_quantize_append(
                self._pv.value, self._sv.value, v.reshape(B * T, H, hd),
                blk_flat,
            )
            pk = pk8.reshape(self.num_blocks * bt, H, hd).at[flat].set(qk)
            pv = pv8.reshape(self.num_blocks * bt, H, hd).at[flat].set(qv)
            self._sk.value = self._shard_scale(sk)
            self._sv.value = self._shard_scale(sv)
        else:
            pk = self._pk.value.reshape(self.num_blocks * bt, H, hd)
            pv = self._pv.value.reshape(self.num_blocks * bt, H, hd)
            pk = pk.at[flat].set(k.reshape(B * T, H, hd))
            pv = pv.at[flat].set(v.reshape(B * T, H, hd))
        self._pk.value = self._shard(
            pk.reshape(self.num_blocks, bt, H, hd)
        )
        self._pv.value = self._shard(
            pv.reshape(self.num_blocks, bt, H, hd)
        )
        # Gather every table slot: logical position of gathered column j
        # IS j (tables list blocks in sequence order).
        slots = (
            block_tables[:, :, None] * bt
            + jnp.arange(bt, dtype=jnp.int32)[None, None, :]
        ).reshape(B, max_blocks * bt)
        pk_flat = self._pk.value.reshape(self.num_blocks * bt, H, hd)
        pv_flat = self._pv.value.reshape(self.num_blocks * bt, H, hd)
        k_all = jnp.take(pk_flat, slots, axis=0)        # [B, S, H, hd]
        v_all = jnp.take(pv_flat, slots, axis=0)
        if self._quant:
            slot_blocks = slots // bt                   # [B, S]
            k_all = _quant.kv_dequantize_gather(
                k_all, self._sk.value, slot_blocks, self._dtype
            )
            v_all = _quant.kv_dequantize_gather(
                v_all, self._sv.value, slot_blocks, self._dtype
            )
        cols = jnp.arange(max_blocks * bt, dtype=jnp.int32)
        # keep[b, t, j]: column j visible to chunk row t of sequence b.
        keep = cols[None, None, :] <= pos[:, :, None]
        if window is not None:
            keep = keep & (pos[:, :, None] - cols[None, None, :] < window)
        return k_all, v_all, keep[:, None, :, :]
