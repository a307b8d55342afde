"""Attention compute core.

Parity target: reference attention math in ``DistributedAttentionLayer``
(``torch/nn/transformer.py:1352-1444``) and the fused softmax kernels it
dispatches to (``torch/nn/softmax.py``, ``can_use_fused_kernel``
``torch/nn/transformer.py:83-112``, SURVEY §2.1 N8).

TPU-native design: one functional entry point ``attention_core`` over
[B, T, H, hd] tensors. Dispatch order:
  1. Pallas flash-attention kernel (TPU, shapes tile, no bias/dropout) —
     never materializes the [T, S] score matrix;
  2. jnp path — XLA fuses scale+mask+softmax into one HBM pass.
Ring-attention context parallelism (M6) wraps this core with a ppermute
loop over KV blocks.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def causal_window_mask(T, S, window=None, dtype=jnp.bool_):
    """[T, S] lower-triangular mask, optionally banded to ``window``.

    Parity: causal-mask buffer + windowed attention
    (``torch/nn/transformer.py:1331-1352``).
    """
    rows = jnp.arange(T)[:, None]
    cols = jnp.arange(S)[None, :]
    offset = S - T
    mask = cols <= rows + offset
    if window is not None:
        mask = mask & (rows + offset - cols < window)
    return mask.astype(dtype)


def block_diffusion_mask(T, block, dtype=jnp.bool_):
    """[T, T] mask of a two-copy stream [noisy ; clean] of T = 2L
    positions cut into blocks of ``block``: position i stands at sequence
    position i mod L, in block (i mod L) // block. A noisy query sees its
    own noisy block, both ways, and the clean blocks before it; a clean
    query the clean blocks up to its own; nothing else (block diffusion:
    the noisy copy of a block is predicted from the clean text before it
    and from its own noisy tokens)."""
    half = T // 2
    idx = jnp.arange(T)
    clean, blk = idx >= half, (idx % half) // block
    rc, cc = clean[:, None], clean[None, :]
    rb, cb = blk[:, None], blk[None, :]
    mask = jnp.where(cc, jnp.where(rc, cb <= rb, cb < rb), ~rc & (rb == cb))
    return mask.astype(dtype)


def _fold_scale_and_seed(q, scale, dropout_rate, dropout_rng):
    """Shared prologue of the Pallas and CP fast paths: fold a traced scale
    into q (their scale arguments are static; keep q's dtype so a traced
    f32 scalar cannot promote bf16 q), and derive the int32 dropout seed
    from the rng — one definition, so the ring/Ulysses/Pallas dropout
    patterns cannot silently diverge."""
    if isinstance(scale, (int, float, np.floating)):
        qq, static_scale = q, float(scale)
    else:
        qq, static_scale = (q * scale).astype(q.dtype), 1.0
    seed = None
    rate = 0.0
    if dropout_rate > 0.0 and dropout_rng is not None:
        rate = float(dropout_rate)
        seed = jax.lax.bitcast_convert_type(
            jax.random.bits(dropout_rng, (), jnp.uint32), jnp.int32
        )
    return qq, static_scale, seed, rate


def attention_core(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
    local_select=None,
    scale: Optional[float] = None,
    extra_scale=None,
    qk_compensation=None,
    bias=None,
    mask=None,
    mask_value: float = -1e4,
    attention_in_fp32: bool = False,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    use_pallas: bool = True,
):
    """Multi-head attention over [B, T, H, hd] q and [B, S, H_kv, hd] k/v.
    H_kv may divide H (grouped KV heads: query head h reads KV head
    h // (H / H_kv)); the flash kernels share the K/V blocks, the jnp path
    repeats them.

    Args:
      causal/window: static masking (window = local attention band).
      block_diffusion: a block length B puts the block-diffusion mask
        (``block_diffusion_mask``) in place of causal and window: q, k, v
        hold a two-copy stream of 2L positions, B dividing L.
      local_select: optional traced bool scalar — when given, the window
        band applies only if True (per-layer local/global selection under
        ``lax.scan``, GPT-Neo ``attention_layers_type``).
      scale: score scale; default 1/sqrt(hd). Applied to q BEFORE the
        matmul so half-precision scores cannot overflow.
      extra_scale: optional traced scalar multiplier on the scale
        (scale_attn_by_layer_idx).
      qk_compensation: optional traced scalar c — q is pre-scaled by 1/c
        before the matmul and the fp32 scores multiplied back by c
        (parity: reference query_key_layer_scaling, a numerics-only
        protection for half-precision score matmuls,
        ``torch/nn/transformer.py:1804-1836``).
      bias: additive [B|1, H|1, T, S] bias (e.g. relative position).
      mask: additive or boolean attention mask broadcastable to
        [B, 1, T, S] (True/0 = keep).
      mask_value: additive value for masked positions (parity: reference
        ``mask_value`` key, default -1e4).
    Returns: [B, T, H, hd].
    """
    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    if extra_scale is not None:
        scale = scale * extra_scale

    # Context parallelism (M6): sequence sharded over the cp mesh axis ->
    # ring / Ulysses manual regions. Real-model features (key-padding
    # masks, attention dropout) are supported in-region; unsupported
    # combinations (windows, rich biases, per-layer local selection) fall
    # through to the GSPMD path (allgather-KV semantics).
    from smdistributed_modelparallel_tpu.ops.context_parallel import cp_size

    if block_diffusion is not None:
        from smdistributed_modelparallel_tpu.ops.pallas_attention import (
            _bd_checked,
        )

        causal, window = _bd_checked(q, k, causal, window, block_diffusion)

    cp_kpad = _as_key_padding_bias(mask, mask_value) if cp_size() > 1 else None
    if (
        cp_size() > 1
        and block_diffusion is None
        and bias is None
        and (mask is None or cp_kpad is not None)
        and local_select is None
        and window is None
        and q.shape[1] == k.shape[1]
        and q.shape[2] == k.shape[2]
        and q.shape[1] % cp_size() == 0
        # The in-region flash kernels share _pallas_ok's mixed-dtype
        # restriction (MXU dots run on the operand dtype).
        and q.dtype == k.dtype == v.dtype
    ):
        from smdistributed_modelparallel_tpu.backend.state import state
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            cp_attention,
        )

        impl = state.cfg.context_parallel_impl
        if impl in ("ring", "ulysses"):
            qq, static_scale, seed, rate = _fold_scale_and_seed(
                q, scale, dropout_rate, dropout_rng
            )
            return cp_attention(
                qq, k, v, scale=static_scale, causal=causal, impl=impl,
                kpad=cp_kpad, dropout_rate=rate, seed=seed,
            )

    kpad = (
        cp_kpad if cp_kpad is not None else _as_key_padding_bias(mask, mask_value)
    )
    if (
        use_pallas
        and _pallas_ok(q, k, v, block_diffusion)
        and bias is None
        and (mask is None or kpad is not None)
        and local_select is None
        # attention_in_fp32 / qk_compensation need no special handling: the
        # kernel's score math is always fp32 (N8 parity, and then some).
    ):
        qq, kernel_scale, seed, rate = _fold_scale_and_seed(
            q, scale, dropout_rate, dropout_rng
        )
        # Block sizes resolve inside the kernel entry (explicit arg ->
        # pallas_attn_block_{q,k} config -> default).
        return _flash_on_mesh(
            qq, k, v, kpad, seed, kernel_scale, causal, window, rate,
            block_diffusion,
        )

    T, S = q.shape[1], k.shape[1]
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    compute_dtype = jnp.float32 if attention_in_fp32 else q.dtype
    # Pre-scale q so the half-precision score matmul cannot overflow
    # (reference applies the norm factor inside the baddbmm alpha).
    pre = jnp.asarray(scale, jnp.float32)
    if qk_compensation is not None:
        pre = pre / qk_compensation
    qc = (q.astype(jnp.float32) * pre).astype(compute_dtype)
    kc = k.astype(compute_dtype)
    scores = jnp.einsum("bthd,bshd->bhts", qc, kc).astype(jnp.float32)
    if qk_compensation is not None:
        scores = scores * qk_compensation

    if causal:
        cmask = causal_window_mask(T, S)
        if window is not None:
            if local_select is not None:
                wmask = causal_window_mask(T, S, window)
                cmask = jnp.where(local_select, wmask, cmask)
            else:
                cmask = causal_window_mask(T, S, window)
        scores = jnp.where(cmask[None, None], scores, mask_value)
    elif block_diffusion is not None:
        scores = jnp.where(
            block_diffusion_mask(T, block_diffusion)[None, None], scores,
            mask_value)
    elif window is not None:
        # Non-causal local attention: symmetric band of width `window`.
        rows = jnp.arange(T)[:, None]
        cols = jnp.arange(S)[None, :]
        band = jnp.abs(rows + (S - T) - cols) < window
        scores = jnp.where(band[None, None], scores, mask_value)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, mask_value)
        else:
            scores = scores + mask.astype(scores.dtype)
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)

    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _flash_on_mesh(q, k, v, kpad, seed, scale, causal, window, rate,
                   block_diffusion=None):
    """Run the flash kernel where the operands live.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"): under a jit over more than one device the bare
    ``pallas_call`` does not lower at all. So on a multi-device mesh the
    kernel runs in a FULL-manual ``shard_map`` region over the mesh (a
    partial-manual region is refused the same way): batch split over the
    data axes and heads over tp, each only when it divides — a dim that
    does not divide stays whole and is computed replicated. Sequence and
    head_dim stay whole. On one device this is the bare call.

    The specs name no axis for pipeline stages, and must not: inside a
    pipeline stage the call is batched by the executors' ``stage_vmap``
    (``parallel/pipeline.py``), whose ``spmd_axis_name`` is pp, and
    ``shard_map``'s batching rule puts that name on the new leading stage
    dim of every spec. Each pp rank then runs the kernels on its own
    stage's rows. Under a vmap that names nothing the stage dim enters
    whole: q, k, v and dO all-gathered over pp, both stages' rows computed
    on every rank, a psum over pp in the backward (PR 28).
    """
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    # Only a call that has the pattern names it: the others' jaxprs, and
    # so their compiled programs, stay what they were.
    pattern = ({} if block_diffusion is None
               else {"block_diffusion": block_diffusion})
    mesh = state.mesh if state.initialized else None
    if mesh is None or mesh.devices.size == 1:
        return flash_attention(
            q, k, v, kpad, seed, None, scale, causal, window, rate,
            **pattern,
        )
    from jax.sharding import PartitionSpec as P

    from smdistributed_modelparallel_tpu.backend.topology import (
        EP_AXIS,
        RDP_AXIS,
        TP_AXIS,
    )

    B, H = q.shape[0], q.shape[2]
    data = tuple(a for a in (RDP_AXIS, EP_AXIS) if mesh.shape[a] > 1)
    n_data = int(np.prod([mesh.shape[a] for a in data])) if data else 1
    b_axes = data if data and B % n_data == 0 else None
    tp = mesh.shape[TP_AXIS]
    h_axis = (
        TP_AXIS if tp > 1 and H % tp == 0 and k.shape[2] % tp == 0 else None
    )
    qkv_spec = P(b_axes, None, h_axis, None)

    operands, specs = [q, k, v], [qkv_spec] * 3
    if kpad is not None:
        operands.append(kpad)
        specs.append(P(b_axes, None))
    if seed is not None:
        operands.append(seed)
        specs.append(P())

    def body(q, k, v, *rest):
        rest = list(rest)
        kpad_l = rest.pop(0) if kpad is not None else None
        seed_l = head0 = None
        if seed is not None:
            # Dropout hashes the GLOBAL head index, and every batch shard
            # draws from its own stream.
            seed_l = rest.pop(0)
            if h_axis is not None:
                head0 = jax.lax.axis_index(h_axis) * q.shape[2]
            if b_axes is not None:
                seed_l = seed_l + jax.lax.axis_index(b_axes).astype(
                    seed_l.dtype
                ) * jnp.asarray(-1640531535, seed_l.dtype)
        return flash_attention(
            q, k, v, kpad_l, seed_l, head0, scale, causal, window, rate,
            head_total=H, **pattern,
        )

    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(specs), out_specs=qkv_spec,
        check_vma=False,
    )(*operands)


def _as_key_padding_bias(mask, mask_value):
    """Reduce a broadcastable attention mask to additive [B, S] form, or
    None if it genuinely varies along T (falls back to the jnp path).

    Accepts [B|1, 1, 1, S] boolean or additive-float masks — the shape of
    HF-style padding masks (reference ``attention_mask`` handling)."""
    if mask is None:
        return None
    if mask.ndim == 2:  # already [B, S]
        reduced = mask
    elif mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        reduced = mask[:, 0, 0, :]
    else:
        return None
    if reduced.dtype == jnp.bool_:
        return jnp.where(reduced, 0.0, mask_value).astype(jnp.float32)
    return reduced.astype(jnp.float32)


def _pallas_ok(q, k, v, block_diffusion=None):
    """Pallas flash kernel preconditions: TPU backend and q/kv sequences
    short enough that K/V (dq pass) or Q/dO (dkv pass) fit VMEM per
    (batch, head) — the kernels pad hd/T/S to tile boundaries themselves
    (``pallas_attention._prep``). Under the default scoped limit that is
    8,192 positions; a call under the block-diffusion mask asks for the
    VMEM its two-copy stream takes, so it passes while that fits a core
    (``pallas_attention.bd_fits_vmem``)."""
    import os

    if os.environ.get("SMP_DISABLE_PALLAS_ATTN", "0") == "1":
        return False
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        return False
    if not (q.dtype == k.dtype == v.dtype):
        # Kernel MXU dots run on the operand dtype (no fp32 upcast), so
        # mixed q/k/v dtypes would fail at trace time — jnp path handles
        # them via its own promotion.
        return False
    T, S, hd = q.shape[1], k.shape[1], q.shape[-1]
    if block_diffusion is None:
        fits = max(T, S) <= 8192
    else:
        from smdistributed_modelparallel_tpu.ops.pallas_attention import (
            bd_fits_vmem,
        )

        fits = bd_fits_vmem(max(T, S), hd, q.dtype.itemsize)
    return T >= 128 and S >= 128 and fits and hd <= 256
