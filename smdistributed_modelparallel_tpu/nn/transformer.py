"""smp.nn Distributed transformer family.

Parity target: reference ``torch/nn/transformer.py``:
- ``DistributedTransformerLMHead`` (``:184-550``) — embeddings + transformer
  + (tied) LM head behind the ``_KEYS`` config surface (``:189-236``); all
  those keys are accepted here with the same names and defaults.
- ``DistributedTransformer`` (``:551-687``) — the layer stack.
- ``DistributedTransformerLayer`` — attention + output (MLP) sublayers with
  pre/post layernorm variants.
- ``DistributedAttentionLayer`` (``:1176-1835``) — dual TP strategies:
  ``optimize="speed"`` head-partitioned QKV (``:1273-1290``),
  ``optimize="memory"`` input-partitioned + scatter/gather (``:1237-1272``);
  rotary embeddings incl. NeoX variant (``:114-183``); causal/windowed
  masks (``:1331-1352``); query-key layer scaling; cross-attention;
  attention-in-fp32.
- ``DistributedTransformerOutputLayer`` (``:965-1175``) — the MLP with the
  same dual strategy.

TPU-native re-design: the hand-written TP collectives become parameter
PartitionSpecs + activation sharding constraints; GSPMD inserts the
allgather/reduce pairs (SURVEY §2.1 N4). ``optimize="speed"`` shards the
head/intermediate dims over tp; ``optimize="memory"`` additionally shards
the residual stream's sequence axis over tp between blocks (Megatron-SP
style reduce-scatter/allgather — the same memory/comm trade the reference's
input-partitioned all-to-all layout makes). Layers are built with
``flax.linen.scan`` so the stack compiles once and pipelines (M2); the
per-layer scan stream carries (layer_idx, is_local) for
query-key-layer-scaling and GPT-Neo-style alternating local/global
attention.
"""

from typing import Any, Optional

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.backend.topology import (
    CP_AXIS,
    EP_AXIS,
    PP_AXIS,
    RDP_AXIS,
    TP_AXIS,
)
from smdistributed_modelparallel_tpu.nn.embedding import DistributedEmbedding
from smdistributed_modelparallel_tpu.nn.layer_norm import DistributedLayerNorm
from smdistributed_modelparallel_tpu.nn.utils import (
    axis_partitioned,
    partitioned,
    resolve_deterministic,
    shard_activation,
    tp_ring_active as _ring_active,
)
from smdistributed_modelparallel_tpu.ops.attention import attention_core
from smdistributed_modelparallel_tpu.parallel.pipeline import PipelineSpec
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

BATCH_AXES = (RDP_AXIS, EP_AXIS)


def _cfg(name, default):
    cfg = state.cfg
    return getattr(cfg, name) if cfg is not None and name in cfg else default


def _activation(name):
    return {
        "gelu": lambda x: nn.gelu(x, approximate=True),
        "gelu_new": lambda x: nn.gelu(x, approximate=True),
        # Exact erf gelu (HF BERT's "gelu"; the tanh approximation above is
        # HF's "gelu_new" and the reference's fused bias_gelu).
        "gelu_erf": lambda x: nn.gelu(x, approximate=False),
        "relu": nn.relu,
        "silu": nn.silu,
        "swish": nn.silu,
    }[name]


def _seq_axes(memory_opt):
    """Sequence-dim mesh axes for the residual stream: cp always; tp too
    under optimize='memory' (sequence-parallel residual)."""
    return (CP_AXIS, TP_AXIS) if memory_opt else CP_AXIS


def _hidden_spec(memory_opt):
    return (BATCH_AXES, _seq_axes(memory_opt), None)


def _seq_parallel(memory_opt):
    """The residual stream is sequence-sharded over tp: explicitly via
    optimize='memory', or implicitly by the overlapped-tp ring."""
    return memory_opt or _ring_active()


def _init(range_, use_normal=True):
    return nn.initializers.normal(stddev=range_)


def _fp8_active():
    """Whether this trace dispatches the fp8 matmul seams (a quant step
    trace is installed — matmul_precision: fp8, training step only)."""
    from smdistributed_modelparallel_tpu import quant

    return quant.fp8_trace_active()


def _fp8_mm(x, w, site, **kw):
    """The fp8 delayed-scaling matmul for one transformer seam, with
    the dispatch decision counted (``smp_quant_dispatch_total``)."""
    from smdistributed_modelparallel_tpu import quant
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        record_quant_dispatch,
    )

    record_quant_dispatch(site, "fp8")
    return quant.fp8_matmul(x, w, site, **kw)


def yarn_inv_freq(rotary_dim, base, factor, original_max_position,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN inverse frequencies [rotary_dim // 2] (float64 numpy, static).

    Dimension i of the rotary half has the plain frequency
    ``base ** (-2i / rotary_dim)`` (extrapolation) or that over ``factor``
    (interpolation), blended by a linear ramp between the dimensions that
    make ``beta_fast`` and ``beta_slow`` rotations over
    ``original_max_position`` positions (floor / ceil, clamped to the
    dimensions there are): ramp 0 keeps the plain frequency, ramp 1 takes
    the interpolated one."""
    half = rotary_dim // 2
    pos_freqs = base ** (np.arange(half, dtype=np.float64) / half)

    def correction_dim(rotations):
        return (rotary_dim * np.log(
            original_max_position / (rotations * 2 * np.pi)
        )) / (2 * np.log(base))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


def apply_rotary(q, k, rotary_dim, base=10000.0, neox_style=False, offset=0,
                 yarn=None, positions=None):
    """Rotary position embedding on the first ``rotary_dim`` channels.

    Where a token stands: ``positions`` ([T] or [B, T], int or float)
    gives each token's own position, for a stream that does not count
    0..T-1 (the two-copy stream of block diffusion: position i stands at
    i mod L); without it, 0..T-1 counted from ``offset``.

    ``yarn``: ``(factor, original_max_position, beta_fast, beta_slow,
    attention_factor)`` switches the frequencies to ``yarn_inv_freq`` and
    multiplies cos and sin by ``attention_factor``.

    Parity: reference ``torch/nn/transformer.py:114-183`` — interleaved
    (GPT-J) vs half-split (``gpt_neox_type_rotary``) variants.
    ``offset`` (int, traced scalar, or per-row [B] array) shifts the
    absolute positions — decode steps rotate the current chunk at its
    cache position; left-padded prompts shift each row by its pad count.
    """

    def rot(x):
        T = x.shape[1]
        d = rotary_dim
        x_rot, x_pass = x[..., :d], x[..., d:]
        half = d // 2
        if yarn is None:
            freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
        else:
            freqs = jnp.asarray(yarn_inv_freq(d, base, *yarn[:4]), jnp.float32)
        if positions is not None:
            t = jnp.asarray(positions, jnp.float32)
        else:
            off = jnp.asarray(offset, jnp.float32)
            t = off[..., None] + jnp.arange(T, dtype=jnp.float32)
        # t: [T] or [B, T]
        angles = t[..., None] * freqs                 # [.., T, half]
        cos = jnp.cos(angles)[..., None, :]
        sin = jnp.sin(angles)[..., None, :]
        if yarn is not None:
            cos, sin = cos * yarn[4], sin * yarn[4]
        if cos.ndim == 3:                             # scalar offset
            cos = cos[None]
            sin = sin[None]
        if neox_style:
            x1, x2 = x_rot[..., :half], x_rot[..., half:]
            rotated = jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
            )
        else:
            x1 = x_rot[..., 0::2]
            x2 = x_rot[..., 1::2]
            r1 = x1 * cos - x2 * sin
            r2 = x2 * cos + x1 * sin
            rotated = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
        rotated = rotated.astype(x.dtype)
        return jnp.concatenate([rotated, x_pass], axis=-1)

    return rot(q), rot(k)


class DistributedAttentionLayer(nn.Module):
    """TP multi-head (self or cross) attention.

    Parity: reference ``DistributedAttentionLayer``
    (``torch/nn/transformer.py:1176-1835``). QKV is one [D, 3, H, hd] kernel
    with the head dim on tp (speed) — the reference's
    ``initialize_with_output_partition`` head split; the output projection
    is input-partitioned ([H, hd, D] with tp on heads) — the reference's
    fan-in slice + allreduce, which GSPMD inserts here.
    """

    num_attention_heads: int
    attention_head_size: int
    hidden_size: int
    attention_dropout_prob: float = 0.1
    hidden_dropout_prob: float = 0.1
    cross_attention: bool = False
    causal_mask_size: Optional[int] = None
    mask_value: float = -1e4
    attention_in_fp32: bool = False
    query_key_layer_scaling: bool = False
    scale_attention_scores: bool = True
    scale_attn_by_layer_idx: bool = False
    initializer_range: float = 0.02
    use_qkv_bias: bool = True
    use_attn_dense_bias: bool = True
    rotary_dim: Optional[int] = None
    rotary_emb_base: Optional[float] = None
    gpt_neox_type_rotary: bool = False
    # (factor, original_max_position, beta_fast, beta_slow,
    # attention_factor): YaRN frequencies for this layer's rotary.
    rotary_yarn: Optional[tuple] = None
    window_size: Optional[int] = None
    # Block diffusion: the block length B. The layer's input is then a
    # two-copy stream [noisy ; clean] of 2L positions, position i stands
    # at i mod L (rotary), and attention runs under
    # ``ops.attention.block_diffusion_mask`` in place of the causal one.
    block_diffusion: Optional[int] = None
    # Grouped KV heads: K and V have this many heads (it divides
    # num_attention_heads); query head h reads KV head h // group. None:
    # as many as query heads, in one fused [D, 3, H, hd] kernel.
    num_key_value_heads: Optional[int] = None
    # Per-head output gate: head h's attention output is multiplied by
    # sigmoid(x W_g)[h] before the output projection.
    head_gate: bool = False
    # RMSNorm over attention_head_size on each query and each key head,
    # with a learned scale shared by the heads, before rotary (epsilon:
    # qk_norm_epsilon).
    qk_norm: bool = False
    qk_norm_epsilon: float = 1e-6
    # KV-cache decoding for smp.generate (nn/utils.DecodeKVCache); only
    # self-attention caches (cross-attention K/V are recomputed from the
    # encoder states passed each step).
    decode: bool = False
    decode_cache_len: Optional[int] = None
    deterministic: Optional[bool] = None
    dtype: Optional[Any] = None

    @nn.nowrap
    def _fused_qkv_wanted(self, D, ring):
        """Whether the fused QKV Pallas kernel should run: the config
        knob, the generic pallas gate, and the kernel's own dispatch
        precondition (at tp > 1 only inside the ring's manual region).
        The ACTUAL path taken is counted per trace by the caller
        (``_record_qkv_dispatch``) — a ring fallback after this gate
        passes still counts as ``fallback``."""
        if not (_cfg("fused_qkv", False)
                and _cfg("use_pallas_kernels", True)):
            return False
        from smdistributed_modelparallel_tpu.nn.utils import tp_size
        from smdistributed_modelparallel_tpu.ops.pallas_qkv import (
            fused_qkv_ok,
        )

        return fused_qkv_ok(D, ring=ring, tp=tp_size())

    @nn.nowrap
    def _record_qkv_dispatch(self, engaged):
        """One ``smp_fused_kernel_dispatch_total`` tick for the qkv
        kernel when the knob requested it, labeled with the path that
        actually ran (the gate can pass and the ring still fall back —
        indivisible sequence — leaving the plain einsum)."""
        if not (_cfg("fused_qkv", False)
                and _cfg("use_pallas_kernels", True)):
            return
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_fused_kernel_dispatch,
        )

        record_fused_kernel_dispatch(
            "qkv", "pallas" if engaged else "fallback"
        )

    @nn.compact
    def __call__(self, hidden, cross_states=None, attention_mask=None, xs=None):
        """The three parts of any attention, each under its scope (whatever
        scope the layer put round the whole): the q/k/v projections, the
        core (rotary, cache, the flash kernels or the plain path) and the
        output projection; the q/k norms keep their own between them."""
        hd = self.attention_head_size
        B = hidden.shape[0]
        with jax.named_scope("smp/attn/qkv"):
            q, k, v = self._project_qkv(hidden, cross_states)

        cache = None
        pos_offset = 0
        if self.decode and not self.cross_attention:
            from smdistributed_modelparallel_tpu.nn.utils import (
                DecodeKVCache,
                pad_row_offset,
            )

            if self.causal_mask_size is None:
                raise SMPValidationError(
                    "decode=True requires causal self-attention "
                    "(causal_mask_size set); BERT-family encoders do not "
                    "decode."
                )
            cache = DecodeKVCache(
                self, (B, self.decode_cache_len, k.shape[2], hd), k.dtype
            )

            # Left-padded prompts: each row's absolute positions shift
            # back by its pad count (see nn/utils.pad_row_offset).
            row_off = pad_row_offset(attention_mask)
            pos_offset = (
                cache.index if row_off is None else cache.index + row_off
            )

        if self.qk_norm:
            head_norm = lambda name: DistributedLayerNorm(  # noqa: E731
                epsilon=self.qk_norm_epsilon, rms=True, use_bias=False,
                name=name,
            )
            with jax.named_scope("smp/attn/qk_norm"):
                q, k = head_norm("q_norm")(q), head_norm("k_norm")(k)

        with jax.named_scope("smp/attn/core"):
            ctx = self._attend(q, k, v, cache, pos_offset, attention_mask, xs)
        with jax.named_scope("smp/attn/out"):
            return self._project_out(ctx, hidden)

    @nn.nowrap
    def _project_qkv(self, hidden, cross_states):
        H, hd, D = self.num_attention_heads, self.attention_head_size, self.hidden_size
        B, T = hidden.shape[0], hidden.shape[1]
        dtype = self.dtype or hidden.dtype
        init = _init(self.initializer_range)

        if self.cross_attention:
            if cross_states is None:
                raise SMPValidationError(
                    "cross_attention=True requires cross_states input."
                )
            q_kernel = self.param(
                "query/kernel", partitioned(init, (None, TP_AXIS, None)), (D, H, hd), dtype
            )
            kv_kernel = self.param(
                "key_value/kernel",
                partitioned(init, (None, None, TP_AXIS, None)),
                (D, 2, H, hd),
                dtype,
            )
            if _fp8_active():
                q = _fp8_mm(hidden, q_kernel.astype(hidden.dtype), "qkv")
            else:
                q = jnp.einsum(
                    "btd,dhk->bthk", hidden, q_kernel.astype(hidden.dtype)
                )
            if self.use_qkv_bias:
                q_bias = self.param(
                    "query/bias", partitioned(nn.initializers.zeros, (TP_AXIS, None)),
                    (H, hd), dtype,
                )
                kv_bias = self.param(
                    "key_value/bias",
                    partitioned(nn.initializers.zeros, (None, TP_AXIS, None)),
                    (2, H, hd), dtype,
                )
                q = q + q_bias.astype(q.dtype)

            def cross_kv():
                kv = jnp.einsum(
                    "bsd,dchk->bcshk", cross_states,
                    kv_kernel.astype(hidden.dtype),
                )
                if self.use_qkv_bias:
                    kv = kv + kv_bias[:, None].astype(kv.dtype)
                return kv

            if self.decode:
                # Encoder K/V are the same every decode step: computed once
                # when the cache variable is created (flax only runs the
                # init closure when the variable is missing), then reused.
                kv = self.variable("cache", "cross_kv", cross_kv).value
            else:
                kv = cross_kv()
            k, v = kv[:, 0], kv[:, 1]
        elif self.num_key_value_heads not in (None, H):
            Hkv = self.num_key_value_heads
            if H % Hkv:
                raise SMPValidationError(
                    f"num_key_value_heads ({Hkv}) must divide "
                    f"num_attention_heads ({H})."
                )
            q_kernel = self.param(
                "query/kernel", partitioned(init, (None, TP_AXIS, None)),
                (D, H, hd), dtype,
            )
            kv_kernel = self.param(
                "key_value/kernel",
                partitioned(init, (None, None, TP_AXIS, None)),
                (D, 2, Hkv, hd), dtype,
            )
            q = jnp.einsum("btd,dhk->bthk", hidden, q_kernel.astype(hidden.dtype))
            kv = jnp.einsum(
                "btd,dchk->bcthk", hidden, kv_kernel.astype(hidden.dtype)
            )
            if self.use_qkv_bias:
                q_bias = self.param(
                    "query/bias",
                    partitioned(nn.initializers.zeros, (TP_AXIS, None)),
                    (H, hd), dtype,
                )
                kv_bias = self.param(
                    "key_value/bias",
                    partitioned(nn.initializers.zeros, (None, TP_AXIS, None)),
                    (2, Hkv, hd), dtype,
                )
                q = q + q_bias.astype(q.dtype)
                kv = kv + kv_bias[:, None].astype(kv.dtype)
            k, v = kv[:, 0], kv[:, 1]
        else:
            qkv_kernel = self.param(
                "qkv/kernel",
                partitioned(init, (None, None, TP_AXIS, None)),
                (D, 3, H, hd),
                dtype,
            )
            qkv_bias = None
            if self.use_qkv_bias:
                qkv_bias = self.param(
                    "qkv/bias",
                    partitioned(nn.initializers.zeros, (None, TP_AXIS, None)),
                    (3, H, hd),
                    dtype,
                )
            ring = not self.decode and _ring_active()
            fused_qkv = self._fused_qkv_wanted(D, ring)
            qkv5 = None
            if ring:
                # Overlapped tp: the column-parallel input all-gather
                # decomposes into a ppermute ring, each hop hidden under
                # the partial matmul on the sequence block in hand
                # (ops/collective_matmul.py); bias folds into the chunk
                # matmuls (the Pallas fused kernel under fused_qkv).
                from smdistributed_modelparallel_tpu.ops.collective_matmul import (  # noqa: E501
                    ring_ag_matmul,
                )

                qkv5 = ring_ag_matmul(
                    hidden, qkv_kernel.astype(hidden.dtype),
                    qkv_bias.astype(hidden.dtype)
                    if qkv_bias is not None else None,
                    w_tp_dim=2, fused=fused_qkv,
                )   # [B, T, 3, H, hd] or None (fall through to GSPMD)
            if qkv5 is None and fused_qkv and not ring:
                # Fused QKV without the ring (tp=1 per fused_qkv_ok):
                # one Pallas matmul against the concatenated [D, 3*H*hd]
                # kernel, bias in the epilogue.
                from smdistributed_modelparallel_tpu.ops.pallas_qkv import (
                    matmul_bias,
                )

                if _fp8_active():
                    # The fp8 rung of the fused-QKV ladder: same tiling,
                    # e4m3 operand refs (pallas_qkv.matmul_bias_fp8),
                    # dequant + bias in the XLA epilogue.
                    qkv5 = _fp8_mm(
                        hidden.reshape(-1, D),
                        qkv_kernel.astype(hidden.dtype).reshape(
                            D, 3 * H * hd
                        ),
                        "qkv",
                        bias=qkv_bias.astype(hidden.dtype)
                        if qkv_bias is not None else None,
                        use_pallas=True,
                        interpret=jax.default_backend() != "tpu",
                    ).reshape(B, T, 3, H, hd)
                else:
                    qkv5 = matmul_bias(
                        hidden.reshape(-1, D),
                        qkv_kernel.astype(hidden.dtype).reshape(
                            D, 3 * H * hd
                        ),
                        qkv_bias.astype(hidden.dtype)
                        if qkv_bias is not None else None,
                        interpret=jax.default_backend() != "tpu",
                    ).reshape(B, T, 3, H, hd)
            self._record_qkv_dispatch(fused_qkv and qkv5 is not None)
            if qkv5 is not None:
                q, k, v = qkv5[:, :, 0], qkv5[:, :, 1], qkv5[:, :, 2]
            elif _fp8_active():
                # [B, T, 3, H, hd] (the fp8 path contracts D in place —
                # the c axis rides behind t instead of in front; the
                # slices below account for the layout).
                qkv = _fp8_mm(
                    hidden, qkv_kernel.astype(hidden.dtype), "qkv"
                )
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                if qkv_bias is not None:
                    q = q + qkv_bias[0].astype(q.dtype)
                    k = k + qkv_bias[1].astype(k.dtype)
                    v = v + qkv_bias[2].astype(v.dtype)
            else:
                qkv = jnp.einsum("btd,dchk->bcthk", hidden, qkv_kernel.astype(hidden.dtype))
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
                if qkv_bias is not None:
                    q = q + qkv_bias[0].astype(q.dtype)
                    k = k + qkv_bias[1].astype(k.dtype)
                    v = v + qkv_bias[2].astype(v.dtype)

        head_spec = (BATCH_AXES, CP_AXIS, TP_AXIS, None)
        return (shard_activation(q, *head_spec),
                shard_activation(k, *head_spec),
                shard_activation(v, *head_spec))

    @nn.nowrap
    def _attend(self, q, k, v, cache, pos_offset, attention_mask, xs):
        hd, T = self.attention_head_size, q.shape[1]
        decode_mask = None
        if self.rotary_dim is not None and not self.cross_attention:
            # The cache stores POST-rotary K: chunk q/k rotate once at
            # their absolute (cache-slot) positions.
            q, k = apply_rotary(
                q, k, self.rotary_dim,
                base=self.rotary_emb_base or 10000.0,
                neox_style=self.gpt_neox_type_rotary,
                offset=pos_offset,
                **({} if self.rotary_yarn is None
                   else {"yarn": tuple(self.rotary_yarn)}),
                positions=(None if self.block_diffusion is None
                           else jnp.arange(T) % (T // 2)),
            )

        if cache is not None:
            k, v, decode_mask = cache.append(k, v, window=self.window_size)
            if decode_mask is not None:
                # Combine with a caller mask (e.g. the T5 relative-position
                # bias, additive [1, H, 1, cache_len] for this step's row).
                if attention_mask is None:
                    attention_mask = decode_mask
                elif attention_mask.dtype == jnp.bool_:
                    attention_mask = attention_mask & decode_mask
                else:
                    attention_mask = attention_mask + jnp.where(
                        decode_mask, 0.0, self.mask_value
                    ).astype(attention_mask.dtype)

        scale = 1.0 / np.sqrt(hd) if self.scale_attention_scores else 1.0
        extra_scale = None
        qk_compensation = None
        layer_idx = None if xs is None else xs.get("layer_idx")
        if self.scale_attn_by_layer_idx and layer_idx is not None:
            # Net scores scaled by 1/(layer_idx+1) (reference
            # torch/nn/transformer.py:1754-1767).
            extra_scale = 1.0 / (layer_idx.astype(jnp.float32) + 1.0)
        if self.query_key_layer_scaling and layer_idx is not None:
            # Numerics-only: protects the half-precision score matmul from
            # overflow; compensated in fp32 before softmax (reference
            # torch/nn/transformer.py:1804-1836).
            qk_compensation = layer_idx.astype(jnp.float32) + 1.0

        local_select = None if xs is None else xs.get("is_local")
        # Causal iff a causal-mask size is configured (reference: GPT-family
        # hooks set causal_mask_size; BERT-family leave it None and mask via
        # attention_mask only). A decode step replaces causal/window with
        # the explicit cache mask (positions <= cache index, banded).
        causal = (
            self.causal_mask_size is not None
            and not self.cross_attention
            and decode_mask is None
        )
        dropout_rng = (
            None
            if resolve_deterministic(self.deterministic)
            or self.attention_dropout_prob == 0.0
            else self.make_rng("dropout")
        )
        if _fp8_active():
            # fp8 handoff precision for the score matmul: q/k round to
            # the e4m3 grid with their slots' delayed scales (straight-
            # through gradient), then the flash/jnp attention runs as
            # built — the values the score dot consumes are exactly the
            # ones a native-f8 kernel would see. A real in-kernel fp8
            # flash pass is the TPU follow-up (its backward would hand
            # f8-dtyped cotangents across the custom_vjp boundary).
            from smdistributed_modelparallel_tpu import quant as _quant

            q = _quant.fake_quant(q, "attn_q.x")
            k = _quant.fake_quant(k, "attn_k.x")
        return attention_core(
            q, k, v,
            causal=causal,
            window=self.window_size if decode_mask is None else None,
            block_diffusion=self.block_diffusion,
            local_select=local_select,
            scale=scale,
            extra_scale=extra_scale,
            qk_compensation=qk_compensation,
            mask=attention_mask,
            mask_value=self.mask_value,
            attention_in_fp32=self.attention_in_fp32,
            dropout_rate=self.attention_dropout_prob,
            dropout_rng=dropout_rng,
            use_pallas=_cfg("use_pallas_kernels", True),
        )

    @nn.nowrap
    def _project_out(self, ctx, hidden):
        H, hd, D = self.num_attention_heads, self.attention_head_size, self.hidden_size
        dtype = self.dtype or hidden.dtype
        memory_opt = _cfg("optimize", "speed") == "memory"
        init = _init(self.initializer_range)
        if self.head_gate:
            gate_kernel = self.param(
                "gate/kernel", partitioned(init, (None, TP_AXIS)), (D, H),
                dtype,
            )
            gate = jax.nn.sigmoid(jnp.einsum(
                "btd,dh->bth", hidden, gate_kernel.astype(hidden.dtype)
            ).astype(jnp.float32))
            ctx = (ctx * gate[..., None]).astype(ctx.dtype)

        proj_kernel = self.param(
            "dense/kernel",
            partitioned(init, (TP_AXIS, None, None)),
            (H, hd, D),
            dtype,
        )
        out = None
        if not self.decode and not self.cross_attention and _ring_active():
            # Overlapped tp: the row-parallel output reduce-scatter
            # decomposes into an accumulator ring (the bias is added
            # once, after the reduction, below).
            from smdistributed_modelparallel_tpu.ops.collective_matmul import (  # noqa: E501
                ring_rs_matmul,
            )

            out = ring_rs_matmul(
                ctx, proj_kernel.astype(ctx.dtype),
                n_contract=2, x_tp_dim=2,
            )
        if out is None:
            if _fp8_active():
                out = _fp8_mm(
                    ctx, proj_kernel.astype(ctx.dtype), "attn_proj",
                    n_contract=2,
                )
            else:
                out = jnp.einsum(
                    "bthk,hkd->btd", ctx, proj_kernel.astype(ctx.dtype)
                )
        out = shard_activation(out, *_hidden_spec(_seq_parallel(memory_opt)))
        if self.use_attn_dense_bias:
            proj_bias = self.param(
                "dense/bias", nn.initializers.zeros, (D,), dtype
            )
            out = out + proj_bias.astype(out.dtype)
        if self.hidden_dropout_prob > 0.0 and not resolve_deterministic(self.deterministic):
            out = nn.Dropout(self.hidden_dropout_prob, deterministic=False)(out)
        return out


class DistributedTransformerOutputLayer(nn.Module):
    """TP MLP block: fc (column-parallel) -> activation -> proj (row-
    parallel). Parity: reference ``DistributedTransformerOutputLayer``
    (``torch/nn/transformer.py:965-1175``), same dual speed/memory strategy.
    """

    hidden_size: int
    intermediate_size: int
    hidden_dropout_prob: float = 0.1
    activation: str = "gelu"
    initializer_range: float = 0.02
    fused_bias_gelu: bool = False
    use_mlp_bias: bool = True
    # Gated MLP (T5 v1.1 / flan-T5, LLaMA-style): out = act(gate(x)) *
    # fc(x) @ proj. Both input projections are column-parallel over tp.
    gated_mlp: bool = False
    deterministic: Optional[bool] = None
    dtype: Optional[Any] = None

    @nn.nowrap
    def _fused_gelu_wanted(self):
        """Whether the fused bias+GELU Pallas kernel should run: the
        module's ``fused_bias_gelu`` flag (the reference's knob, now
        actually dispatching), a bias to fold, the tanh-GELU family, and
        the generic pallas gate. Counted per trace
        (``smp_fused_kernel_dispatch_total``)."""
        if not (self.fused_bias_gelu and self.use_mlp_bias
                and not self.gated_mlp):
            return False
        if not _cfg("use_pallas_kernels", True):
            return False
        from smdistributed_modelparallel_tpu.ops.pallas_gelu import (
            bias_gelu_ok,
        )
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_fused_kernel_dispatch,
        )

        # The kernel sees the LOCAL feature width (nn/utils.fused_bias_gelu
        # hands it the tp shard when the width divides).
        from smdistributed_modelparallel_tpu.nn.utils import tp_size

        F, tp = self.intermediate_size, tp_size()
        ok = bias_gelu_ok(
            self.activation, features=F // tp if F % tp == 0 else F
        )
        record_fused_kernel_dispatch(
            "bias_gelu", "pallas" if ok else "fallback"
        )
        return ok

    @nn.compact
    def __call__(self, hidden):
        D, F = self.hidden_size, self.intermediate_size
        dtype = self.dtype or hidden.dtype
        memory_opt = _cfg("optimize", "speed") == "memory"
        init = _init(self.initializer_range)
        ring = _ring_active()
        fused_gelu = self._fused_gelu_wanted()

        fc_kernel = self.param(
            "fc/kernel", partitioned(init, (None, TP_AXIS)), (D, F), dtype
        )
        fc_bias = None
        if self.use_mlp_bias:
            fc_bias = self.param(
                "fc/bias", partitioned(nn.initializers.zeros, (TP_AXIS,)),
                (F,), dtype,
            )

        def col_matmul(kernel, bias):
            """Column-parallel ``hidden @ kernel (+ bias)``: the
            ring-decomposed overlapped form under tp_overlap, the GSPMD
            einsum otherwise (where XLA fuses the bias into the matmul
            epilogue — parity: fused_bias_gelu, torch/nn/gelu.py — or
            the explicit Pallas kernel takes it below)."""
            y = None
            if ring:
                from smdistributed_modelparallel_tpu.ops.collective_matmul import (  # noqa: E501
                    ring_ag_matmul,
                )

                y = ring_ag_matmul(
                    hidden, kernel.astype(hidden.dtype),
                    bias.astype(hidden.dtype) if bias is not None else None,
                    w_tp_dim=1,
                )
            if y is None:
                if _fp8_active():
                    y = _fp8_mm(
                        hidden, kernel.astype(hidden.dtype), "mlp_fc"
                    )
                else:
                    y = hidden @ kernel.astype(hidden.dtype)
                y = shard_activation(y, BATCH_AXES, CP_AXIS, TP_AXIS)
                if bias is not None:
                    y = y + bias.astype(y.dtype)
            else:
                y = shard_activation(y, BATCH_AXES, CP_AXIS, TP_AXIS)
            return y

        if fused_gelu:
            from smdistributed_modelparallel_tpu.nn.utils import (
                fused_bias_gelu,
            )

            h = col_matmul(fc_kernel, None)
            h = fused_bias_gelu(h, fc_bias.astype(h.dtype))
        else:
            h = col_matmul(fc_kernel, fc_bias)
            if self.gated_mlp:
                gate_kernel = self.param(
                    "gate/kernel", partitioned(init, (None, TP_AXIS)),
                    (D, F), dtype,
                )
                g = col_matmul(gate_kernel, None)
                h = _activation(self.activation)(g) * h
            else:
                h = _activation(self.activation)(h)

        proj_kernel = self.param(
            "proj/kernel", partitioned(init, (TP_AXIS, None)), (F, D), dtype
        )
        out = None
        if ring:
            from smdistributed_modelparallel_tpu.ops.collective_matmul import (  # noqa: E501
                ring_rs_matmul,
            )

            out = ring_rs_matmul(h, proj_kernel.astype(h.dtype),
                                 n_contract=1)
        if out is None:
            if _fp8_active():
                out = _fp8_mm(h, proj_kernel.astype(h.dtype), "mlp_proj")
            else:
                out = h @ proj_kernel.astype(h.dtype)
        out = shard_activation(out, *_hidden_spec(_seq_parallel(memory_opt)))
        if self.use_mlp_bias:
            proj_bias = self.param(
                "proj/bias", nn.initializers.zeros, (D,), dtype
            )
            out = out + proj_bias.astype(out.dtype)
        if self.hidden_dropout_prob > 0.0 and not resolve_deterministic(self.deterministic):
            out = nn.Dropout(self.hidden_dropout_prob, deterministic=False)(out)
        return out


class DistributedTransformerLayer(nn.Module):
    """One transformer block: attention + MLP with pre/post-LN variants.

    Parity: reference ``DistributedTransformerLayer``; layernorm placement
    keys (``pre_layernorm``/``post_layernorm``/``single_pre_layernorm``),
    ``fp32_residual_addition``, optional cross-attention, GPT-J-style
    ``parallel_attn_output``.
    """

    num_attention_heads: int
    attention_head_size: int
    hidden_size: int
    intermediate_size: int
    attention_dropout_prob: float = 0.1
    hidden_dropout_prob: float = 0.1
    activation: str = "gelu"
    layernorm_epsilon: float = 1e-5
    mask_value: float = -1e4
    add_cross_attention: bool = False
    pre_layernorm: bool = False
    post_layernorm: bool = True
    single_pre_layernorm: bool = False
    # A norm on each branch's output before the residual add, the layer's
    # own kind (``<site>/branch_layernorm``): with ``pre_layernorm`` the
    # "sandwich" ``x + N(f(N(x)))``; ``post_layernorm`` norms after the add.
    branch_layernorm: bool = False
    attention_in_fp32: bool = False
    query_key_layer_scaling: bool = False
    scale_attention_scores: bool = True
    scale_attn_by_layer_idx: bool = False
    fp32_residual_addition: bool = False
    fused_bias_gelu: bool = False
    initializer_range: float = 0.02
    use_qkv_bias: bool = True
    use_attn_dense_bias: bool = True
    rotary_dim: Optional[int] = None
    rotary_emb_base: Optional[float] = None
    gpt_neox_type_rotary: bool = False
    window_size: Optional[int] = None
    parallel_attn_output: bool = False
    causal_mask_size: Optional[int] = None
    # T5-compat knobs (TPU extension beyond the reference's layer-level T5
    # hooks): RMS layernorms and bias-free MLP dense layers.
    layernorm_type: str = "layer"
    use_mlp_bias: bool = True
    gated_mlp: bool = False
    # MoE (TPU extension; reference has no MoE — SURVEY §2.6): when
    # num_experts > 0 the MLP block is a DistributedMoE routed over the
    # ep mesh axis instead of a dense DistributedTransformerOutputLayer.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Set per layer kind by a stack with a ``layer_pattern`` (see
    # DistributedTransformer): attention shape of this kind of layer ...
    rotary_yarn: Optional[tuple] = None
    num_key_value_heads: Optional[int] = None
    head_gate: bool = False
    qk_norm: bool = False
    block_diffusion: Optional[int] = None
    # ... or, in place of attention, the kind's mixer: the taps of a gated
    # short convolution (nn/conv.DistributedShortConv; None: attention) ...
    conv_mixer: Optional[int] = None
    # ... or attention through low-rank latents: the fields of
    # nn/latent_attention.DistributedLatentAttentionLayer that the layer
    # does not have itself (the ranks, the three head sizes, the softmax
    # scale), as a dict or its items (None: DistributedAttentionLayer) ...
    latent_attention: Optional[Any] = None
    # ... its residual path: ``{"streams": n, "sinkhorn_iters", "eps",
    # "clamp"}`` (or its items) makes the layer's input and output
    # [B, T, n, D] and puts nn/hyper_connection.DistributedHyperConnection
    # round the attention and the feed-forward; None, or one stream:
    # ``x + f(norm(x))`` on [B, T, D] ...
    hyper_connection: Optional[Any] = None
    # ... its expert layer: dropless (nn/moe.DistributedDroplessMoE) with
    # the ``(first, count)`` range of the ``num_experts`` it holds, a shared
    # expert's width, renormalised top-k weights and their scale, the
    # router's scoring law and whether a bias enters its selection ...
    moe_dropless: bool = False
    moe_held: Optional[tuple] = None
    moe_shared_intermediate_size: int = 0
    moe_norm_topk: bool = True
    moe_routed_scaling: float = 1.0
    moe_score: str = "softmax"
    moe_selection_bias: bool = False
    # ... and the kind's name, which a patterned stack always sets: the
    # layer's ops trace under ``smp/layer/<kind>`` (``smp/layer/block``
    # with no kind), its attention under ``smp/attn/block_diffusion``,
    # ``smp/attn/window`` or ``smp/attn/full`` (inside that the parts
    # ``smp/attn/{qkv,qk_norm,core,out}``, of latent attention
    # ``smp/latent/*``), a convolution mixer under
    # ``smp/conv/{in_proj,core,out_proj}``, a dense feed-forward under
    # ``smp/mlp/dense`` and a hyper-connection under ``smp/mhc/*``; the
    # norms stay charged to their layer.
    kind: Optional[str] = None
    decode: bool = False
    decode_cache_len: Optional[int] = None
    deterministic: Optional[bool] = None
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, hidden, cross_states=None, attention_mask=None, xs=None):
        with jax.named_scope("smp/layer/block" if self.kind is None
                             else f"smp/layer/{self.kind}"):
            return self._block(hidden, cross_states, attention_mask, xs)

    @nn.nowrap
    def _short_conv(self):
        """``conv_mixer``'s mixer, called as the attention is."""
        from smdistributed_modelparallel_tpu.nn.conv import (
            DistributedShortConv,
        )

        if self.decode:
            raise SMPValidationError(
                "a convolution mixer keeps no decode state yet: "
                "conv_mixer does not take decode=True."
            )
        conv = DistributedShortConv(
            hidden_size=self.hidden_size, kernel_size=self.conv_mixer,
            initializer_range=self.initializer_range, dtype=self.dtype,
            name="conv",
        )
        return lambda h, attention_mask=None, xs=None: conv(h)

    @nn.nowrap
    def _latent_attention(self):
        """``latent_attention``'s layer, called as the attention is."""
        from smdistributed_modelparallel_tpu.nn.latent_attention import (
            DistributedLatentAttentionLayer,
        )

        latent = DistributedLatentAttentionLayer(
            num_attention_heads=self.num_attention_heads,
            hidden_size=self.hidden_size,
            rotary_emb_base=self.rotary_emb_base or 10000.0,
            rotary_yarn=self.rotary_yarn,
            layernorm_epsilon=self.layernorm_epsilon,
            mask_value=self.mask_value,
            initializer_range=self.initializer_range,
            decode=self.decode, dtype=self.dtype, name="attention",
            **dict(self.latent_attention),
        )

        def attn(h, attention_mask=None, xs=None):
            with jax.named_scope("smp/attn/full"):
                return latent(h, attention_mask=attention_mask, xs=xs)

        return attn

    @nn.nowrap
    def _connector(self, res_dtype, dtype):
        """``connect(site, x, f)``: one residual site of the block. With
        one stream ``x + f(x)``; with ``hyper_connection``'s streams ``x``
        is [B, T, n, D], ``f`` reads the pre mix of the site's own
        connection and its output is spread over the mixed streams."""
        hc = dict(self.hyper_connection or {})
        if hc.get("streams", 1) == 1:
            def connect(site, x, f):
                out = f(x)
                return (x.astype(res_dtype)
                        + out.astype(res_dtype)).astype(dtype)

            return connect
        from smdistributed_modelparallel_tpu.nn import hyper_connection

        if (self.parallel_attn_output or self.add_cross_attention
                or self.post_layernorm or self.decode):
            raise SMPValidationError(
                "hyper_connection streams take a pre-norm block with no "
                "parallel residual, cross-attention or decode cache."
            )

        def connect(site, x, f):
            u, h_post, h_res = hyper_connection.DistributedHyperConnection(
                hidden_size=self.hidden_size,
                norm_epsilon=self.layernorm_epsilon,
                initializer_range=self.initializer_range, dtype=self.dtype,
                name=f"{site}/hyper_connection", **hc)(x)
            return hyper_connection.post_res(x, f(u), h_post, h_res)

        return connect

    @nn.nowrap
    def _block(self, hidden, cross_states, attention_mask, xs):
        # attention_mask may be a (self_mask, cross_mask) pair: the stack's
        # carry protocol has one mask slot, and T5-style models need both a
        # per-head relative-position bias on self-attention and an encoder
        # key-padding mask on cross-attention.
        cross_attention_mask = None
        if isinstance(attention_mask, tuple):
            attention_mask, cross_attention_mask = attention_mask
        rms = self.layernorm_type == "rms"
        ln = lambda name: DistributedLayerNorm(
            epsilon=self.layernorm_epsilon, rms=rms, use_bias=not rms,
            name=name,
        )
        # The kind's mixer and the name of its norm: attention, or the
        # gated short convolution (which reads no mask and no layer index).
        mixer = "conv" if self.conv_mixer else "attention"
        if self.conv_mixer:
            attn = self._short_conv()
        elif self.latent_attention:
            attn = self._latent_attention()
        else:
            attn = DistributedAttentionLayer(
                num_attention_heads=self.num_attention_heads,
                attention_head_size=self.attention_head_size,
                hidden_size=self.hidden_size,
                attention_dropout_prob=self.attention_dropout_prob,
                hidden_dropout_prob=self.hidden_dropout_prob,
                causal_mask_size=self.causal_mask_size,
                mask_value=self.mask_value,
                attention_in_fp32=self.attention_in_fp32,
                query_key_layer_scaling=self.query_key_layer_scaling,
                scale_attention_scores=self.scale_attention_scores,
                scale_attn_by_layer_idx=self.scale_attn_by_layer_idx,
                initializer_range=self.initializer_range,
                use_qkv_bias=self.use_qkv_bias,
                use_attn_dense_bias=self.use_attn_dense_bias,
                rotary_dim=self.rotary_dim,
                rotary_emb_base=self.rotary_emb_base,
                gpt_neox_type_rotary=self.gpt_neox_type_rotary,
                rotary_yarn=self.rotary_yarn,
                window_size=self.window_size,
                num_key_value_heads=self.num_key_value_heads,
                head_gate=self.head_gate,
                qk_norm=self.qk_norm,
                qk_norm_epsilon=self.layernorm_epsilon,
                block_diffusion=self.block_diffusion,
                decode=self.decode,
                decode_cache_len=self.decode_cache_len,
                deterministic=self.deterministic,
                dtype=self.dtype,
                name="attention",
            )
            attention = attn

            def attn(*args, **kwargs):
                with jax.named_scope(
                        "smp/attn/block_diffusion" if self.block_diffusion
                        else "smp/attn/window" if self.window_size
                        else "smp/attn/full"):
                    return attention(*args, **kwargs)

        if self.num_experts > 0 and self.moe_dropless:
            from smdistributed_modelparallel_tpu.nn.moe import (
                DistributedDroplessMoE,
            )

            mlp = DistributedDroplessMoE(
                hidden_size=self.hidden_size,
                intermediate_size=self.intermediate_size,
                num_experts=self.num_experts,
                top_k=self.moe_top_k,
                held=self.moe_held,
                shared_intermediate_size=self.moe_shared_intermediate_size,
                norm_topk=self.moe_norm_topk,
                routed_scaling=self.moe_routed_scaling,
                score=self.moe_score,
                selection_bias=self.moe_selection_bias,
                activation=self.activation,
                initializer_range=self.initializer_range,
                dtype=self.dtype,
                name="output",
            )
        elif self.num_experts > 0:
            from smdistributed_modelparallel_tpu.nn.moe import DistributedMoE

            mlp = DistributedMoE(
                hidden_size=self.hidden_size,
                intermediate_size=self.intermediate_size,
                num_experts=self.num_experts,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                hidden_dropout_prob=self.hidden_dropout_prob,
                activation=self.activation,
                initializer_range=self.initializer_range,
                deterministic=self.deterministic,
                dtype=self.dtype,
                name="output",
            )
        else:
            mlp = DistributedTransformerOutputLayer(
                hidden_size=self.hidden_size,
                intermediate_size=self.intermediate_size,
                hidden_dropout_prob=self.hidden_dropout_prob,
                activation=self.activation,
                initializer_range=self.initializer_range,
                fused_bias_gelu=self.fused_bias_gelu,
                use_mlp_bias=self.use_mlp_bias,
                gated_mlp=self.gated_mlp,
                deterministic=self.deterministic,
                dtype=self.dtype,
                name="output",
            )
            dense = mlp

            def mlp(h):
                with jax.named_scope("smp/mlp/dense"):
                    return dense(h)

        res_dtype = jnp.float32 if self.fp32_residual_addition else hidden.dtype
        connect = self._connector(res_dtype, hidden.dtype)
        x = hidden

        def branch(site, out):
            """A branch's output as the residual add takes it."""
            if not self.branch_layernorm:
                return out
            with jax.named_scope("smp/layer/branch_norm"):
                return ln(f"{site}/branch_layernorm")(out)

        if self.branch_layernorm and (
                self.parallel_attn_output or self.add_cross_attention):
            raise SMPValidationError(
                "branch_layernorm norms the attention's and the MLP's "
                "output, each before its own residual add: "
                "parallel_attn_output has one add for both, and the norm "
                "of an add_cross_attention branch is not written."
            )
        if self.parallel_attn_output:
            # Parallel residual: GPT-J style shares one LN
            # (single_pre_layernorm); GPT-NeoX style (pre_layernorm, two
            # LNs) feeds the MLP from its own post-attention layernorm.
            h = ln(f"{mixer}/layernorm")(x)
            if self.pre_layernorm and not self.single_pre_layernorm:
                h_mlp = ln("output/layernorm")(x)
            else:
                h_mlp = h
            a = attn(h, attention_mask=attention_mask, xs=xs)
            m = mlp(h_mlp)
            x = (x.astype(res_dtype) + a.astype(res_dtype) + m.astype(res_dtype)).astype(hidden.dtype)
            return x

        pre_ln = self.pre_layernorm or self.single_pre_layernorm
        x = connect(mixer, x, lambda u: branch(mixer, attn(
            ln(f"{mixer}/layernorm")(u) if pre_ln else u,
            attention_mask=attention_mask, xs=xs)))
        if self.post_layernorm:
            x = ln(f"{mixer}/post_layernorm")(x)

        if self.add_cross_attention and cross_states is not None:
            cross = DistributedAttentionLayer(
                num_attention_heads=self.num_attention_heads,
                attention_head_size=self.attention_head_size,
                hidden_size=self.hidden_size,
                attention_dropout_prob=self.attention_dropout_prob,
                hidden_dropout_prob=self.hidden_dropout_prob,
                cross_attention=True,
                mask_value=self.mask_value,
                attention_in_fp32=self.attention_in_fp32,
                scale_attention_scores=self.scale_attention_scores,
                initializer_range=self.initializer_range,
                use_qkv_bias=self.use_qkv_bias,
                use_attn_dense_bias=self.use_attn_dense_bias,
                deterministic=self.deterministic,
                dtype=self.dtype,
                name="crossattention",
            )
            x = connect("crossattention", x, lambda u: cross(
                ln("crossattention/layernorm")(u) if self.pre_layernorm
                else u,
                cross_states=cross_states,
                attention_mask=cross_attention_mask))
            if self.post_layernorm:
                x = ln("crossattention/post_layernorm")(x)

        own_ln = self.pre_layernorm and not self.single_pre_layernorm
        x = connect("output", x, lambda u: branch("output", mlp(
            ln("output/layernorm")(u) if own_ln else u)))
        if self.post_layernorm:
            x = ln("output/post_layernorm")(x)
        return x


class _LayerScanBody(nn.Module):
    """nn.scan body threading per-layer xs (layer_idx, is_local)."""

    layer_kwargs: dict

    @nn.compact
    def __call__(self, carry, xs):
        from smdistributed_modelparallel_tpu.parallel.memory import (
            name_layer_activation,
        )

        x, cross_states, attention_mask = carry
        out = DistributedTransformerLayer(**self.layer_kwargs, name="layer")(
            x, cross_states=cross_states, attention_mask=attention_mask, xs=xs
        )
        out = name_layer_activation(out)
        ys = None
        if _fp8_active():
            # The fp8 seams inside this body recorded amax observations
            # on THIS scan trace; drain them into per-layer ys so they
            # escape the nn.scan — the Python-side pending dict cannot
            # carry tracers across the scan boundary.
            from smdistributed_modelparallel_tpu import quant as _q

            qd = _q.scan_drain()
            if qd:
                ys = qd
        return (out, cross_states, attention_mask), ys


def pattern_segments(pattern):
    """A per-layer pattern of kind names as ``[(repeats, [(kind, count),
    ...]), ...]``: runs of one kind, and a sequence of runs that repeats
    folded into one segment (leading layers, whole periods, a tail).
    ``("lead", "w", "w", "w", "f") * ...`` -> lead once, then (3 w, 1 f)
    as often as it repeats, then what is left."""
    runs = []
    for kind in pattern:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    runs = [tuple(r) for r in runs]
    segments, at = [], 0
    while at < len(runs):
        best = (1, 1)                                  # (repeats, period)
        for period in range(1, (len(runs) - at) // 2 + 1):
            unit, repeats = runs[at:at + period], 1
            while runs[at + repeats * period:
                       at + (repeats + 1) * period] == unit:
                repeats += 1
            if repeats > 1 and repeats * period > best[0] * best[1]:
                best = (repeats, period)
        repeats, period = best
        segments.append((repeats, runs[at:at + period]))
        at += repeats * period
    return segments


def pattern_layer_paths(pattern):
    """Where each layer of a patterned stack keeps its parameters:
    ``[(path under the stack, index into the leading scan axes)]`` in
    layer order. A run outside a period is ``seq_layers_<n>_<kind>/layer``
    indexed ``(j,)``; inside one, ``seq_layers_<n>_period/<i>_<kind>/layer``
    indexed ``(repeat, j)``."""
    where, at = [None] * len(pattern), 0
    for n, (repeats, runs) in enumerate(pattern_segments(pattern)):
        period, start = sum(count for _, count in runs), 0
        for i, (kind, count) in enumerate(runs):
            for r in range(repeats):
                for j in range(count):
                    where[at + r * period + start + j] = (
                        (f"seq_layers_{n}_{kind}/layer", (j,))
                        if repeats == 1 and len(runs) == 1 else
                        (f"seq_layers_{n}_period/{i}_{kind}/layer", (r, j)))
            start += count
        at += repeats * period
    return where


class _PeriodScanBody(nn.Module):
    """One period of a patterned stack: a scan over each of its runs."""

    runs: tuple               # ((kind, count, layer kwargs), ...)
    body: Any

    @nn.compact
    def __call__(self, carry, xs):
        for i, (kind, count, kwargs) in enumerate(self.runs):
            carry, _ = _scan_layers(self.body, count)(
                kwargs, name=f"{i}_{kind}"
            )(carry, {"layer_idx": xs["layer_idx"][i]})
        return carry, None


def _scan_layers(body, length):
    return nn.scan(
        body,
        # intermediates: per-layer sown values (MoE aux losses) stack
        # on the layer axis when applied with mutable=["intermediates"];
        # cache: per-layer decode KV caches (smp.generate).
        variable_axes={"params": 0, "intermediates": 0, "cache": 0},
        split_rngs={"params": True, "dropout": True},
        length=length,
        in_axes=(0,),
        # The scan (layer) axis carries no TP name; its 'pp' sharding is
        # applied by the pipeline's spec provider at partition time.
        metadata_params={nn.meta.PARTITION_NAME: None},
    )


class DistributedTransformer(nn.Module):
    """The scanned transformer stack.

    ``layer_pattern`` (a kind name for each layer) with ``layer_kinds``
    ({kind: overrides of the layer's fields}) builds a stack of layers
    that differ in shape: head counts, window, rotary, dense or expert
    MLP. The pattern is static: consecutive layers of one kind are one
    scan with their parameters stacked (``seq_layers_<segment>_<kind>``),
    and a sequence of runs that repeats is scanned over its repeats with
    the runs' scans inside (``..._period/<run>_<kind>``). Each layer's
    window is a Python constant, so the static-window kernel path holds.
    Without a pattern every layer is alike and the stack is one scan
    (``seq_layers``), as before.

    Parity: reference ``DistributedTransformer`` (``torch/nn/transformer.py:
    551-687``) — ``seq_layers`` of DistributedTransformerLayer. Accepts the
    same per-layer config keys; ``attention_layers_type`` (GPT-Neo) selects
    local/global attention per layer.
    """

    num_layers: int = 12
    num_attention_heads: int = 32
    attention_head_size: int = 32
    hidden_size: int = 1024
    intermediate_size: int = 4096
    attention_dropout_prob: float = 0.1
    hidden_dropout_prob: float = 0.1
    activation: str = "gelu"
    layernorm_epsilon: float = 1e-5
    mask_value: float = -1e4
    add_cross_attention: bool = False
    pre_layernorm: bool = False
    post_layernorm: bool = True
    single_pre_layernorm: bool = False
    branch_layernorm: bool = False
    attention_in_fp32: bool = False
    query_key_layer_scaling: bool = False
    scale_attention_scores: bool = True
    scale_attn_by_layer_idx: bool = False
    fp32_residual_addition: bool = False
    fused_bias_gelu: bool = False
    initializer_range: float = 0.02
    use_qkv_bias: bool = True
    use_attn_dense_bias: bool = True
    rotary_dim: Optional[int] = None
    rotary_emb_base: Optional[float] = None
    gpt_neox_type_rotary: bool = False
    window_size: Optional[int] = None
    parallel_attn_output: bool = False
    causal_mask_size: Optional[int] = None
    layernorm_type: str = "layer"
    use_mlp_bias: bool = True
    gated_mlp: bool = False
    attention_layers_type: Optional[tuple] = None
    layer_pattern: Optional[tuple] = None
    layer_kinds: Optional[Any] = None
    # Passes of the whole stack over its own output with one set of
    # parameters. With n > 1 a norm of the stack's kind (``loop_norm``)
    # follows every pass, its output is the next pass's input, and the
    # stack returns all n normed states, [n, B, T, D]; 1: one pass, no
    # norm, [B, T, D], as before.
    loop_steps: int = 1
    # Every layer's residual path (DistributedTransformerLayer's field of
    # the name): with n > 1 streams the stack copies its input to n
    # streams, its scans carry [B, T, n, D], and it returns their sum.
    hyper_connection: Optional[Any] = None
    activation_checkpointing: bool = False
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    decode: bool = False
    decode_cache_len: Optional[int] = None
    deterministic: Optional[bool] = None
    dtype: Optional[Any] = None

    @nn.nowrap
    def _streams(self):
        return dict(self.hyper_connection or {}).get("streams", 1)

    @nn.nowrap
    def _kind_kwargs(self, kind):
        """The layer kwargs of one kind of a patterned stack."""
        return dict(self._layer_kwargs(), **dict(self.layer_kinds[kind]),
                    kind=kind)

    @nn.nowrap
    def _layer_kwargs(self):
        return dict(
            num_attention_heads=self.num_attention_heads,
            attention_head_size=self.attention_head_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            attention_dropout_prob=self.attention_dropout_prob,
            hidden_dropout_prob=self.hidden_dropout_prob,
            activation=self.activation,
            layernorm_epsilon=self.layernorm_epsilon,
            mask_value=self.mask_value,
            add_cross_attention=self.add_cross_attention,
            pre_layernorm=self.pre_layernorm,
            post_layernorm=self.post_layernorm,
            single_pre_layernorm=self.single_pre_layernorm,
            branch_layernorm=self.branch_layernorm,
            attention_in_fp32=self.attention_in_fp32,
            query_key_layer_scaling=self.query_key_layer_scaling,
            scale_attention_scores=self.scale_attention_scores,
            scale_attn_by_layer_idx=self.scale_attn_by_layer_idx,
            fp32_residual_addition=self.fp32_residual_addition,
            fused_bias_gelu=self.fused_bias_gelu,
            initializer_range=self.initializer_range,
            use_qkv_bias=self.use_qkv_bias,
            use_attn_dense_bias=self.use_attn_dense_bias,
            rotary_dim=self.rotary_dim,
            rotary_emb_base=self.rotary_emb_base,
            gpt_neox_type_rotary=self.gpt_neox_type_rotary,
            window_size=self.window_size,
            parallel_attn_output=self.parallel_attn_output,
            causal_mask_size=self.causal_mask_size,
            layernorm_type=self.layernorm_type,
            use_mlp_bias=self.use_mlp_bias,
            gated_mlp=self.gated_mlp,
            hyper_connection=self.hyper_connection,
            num_experts=self.num_experts,
            moe_top_k=self.moe_top_k,
            moe_capacity_factor=self.moe_capacity_factor,
            decode=self.decode,
            decode_cache_len=self.decode_cache_len,
            deterministic=self.deterministic,
            dtype=self.dtype,
        )

    @nn.nowrap
    def layer_xs(self):
        xs = {"layer_idx": jnp.arange(self.num_layers, dtype=jnp.int32)}
        # is_local only exists for per-layer local/global selection: a
        # traced selector disqualifies the static-window Pallas/CP fast
        # paths, and a homogeneous stack must keep window_size STATIC so
        # (a) windowed attention actually applies without
        # attention_layers_type and (b) the fast paths engage.
        if self.attention_layers_type is not None:
            if len(self.attention_layers_type) != self.num_layers:
                raise SMPValidationError(
                    "attention_layers_type must have num_layers entries."
                )
            xs["is_local"] = jnp.asarray(
                [t == "local" for t in self.attention_layers_type], dtype=bool
            )
        return xs

    def setup(self):
        if self.loop_steps > 1:
            self._setup_loop()
        body = _LayerScanBody
        if self.activation_checkpointing:
            from smdistributed_modelparallel_tpu.parallel.memory import remat_policy

            # Parity: reference set_activation_checkpointing on the layer
            # container (torch/module_manager.py:969-1010) -> per-layer
            # remat, optionally offloading the boundary activation.
            body = nn.remat(body, policy=remat_policy())
        if self.layer_pattern is not None:
            self.segments = self._pattern_segments(body)
            return
        ScanLayers = _scan_layers(body, self.num_layers)
        self.seq_layers = ScanLayers(self._layer_kwargs(), name="seq_layers")

    def _setup_loop(self):
        """What ``loop_steps`` > 1 adds to the stack: the norm after every
        pass. A layer here runs once a pass, not once a forward, which the
        decode cache (one entry a layer), the pipeline executors (a stage
        sees a microbatch once) and the fp8 scales' drain (one scan deep)
        do not know yet."""
        _refuse_loop_under_pipeline(self.loop_steps)
        if self.decode or _fp8_active():
            raise SMPValidationError(
                f"loop_steps={self.loop_steps} runs every layer once a "
                "pass: a decode cache with an entry for every (pass, layer) "
                "and fp8 scales drained through the loop over passes are "
                "not written; it takes neither decode=True nor fp8 matmuls."
            )
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_loop_passes,
        )

        record_loop_passes(self.loop_steps, self.num_layers)
        rms = self.layernorm_type == "rms"
        self.loop_norm = DistributedLayerNorm(
            epsilon=self.layernorm_epsilon, rms=rms, use_bias=not rms,
            name="loop_norm")

    @nn.nowrap
    def _pattern_segments(self, body):
        """``[(module, xs)]`` of a patterned stack, in layer order."""
        pattern = tuple(self.layer_pattern)
        if len(pattern) != self.num_layers:
            raise SMPValidationError(
                "layer_pattern must have num_layers entries."
            )
        if self.attention_layers_type is not None or _fp8_active():
            raise SMPValidationError(
                "layer_pattern gives each layer a static window and shape; "
                "it takes neither attention_layers_type nor fp8 matmuls."
            )
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_attn_latent_layers,
            record_conv_mixers,
            record_mhc_streams,
        )

        for kind in dict.fromkeys(pattern):
            fields = dict(self.layer_kinds[kind])
            if fields.get("conv_mixer"):
                record_conv_mixers(kind, pattern.count(kind))
            if fields.get("latent_attention"):
                record_attn_latent_layers(kind, pattern.count(kind))
            if self._streams() > 1:
                record_mhc_streams(kind, self._streams())
        built, at = [], 0
        for n, (repeats, runs) in enumerate(pattern_segments(pattern)):
            period = sum(count for _, count in runs)
            # layer_idx [repeats, count] of each run: this segment's slice
            # of 0 .. num_layers - 1.
            idx, start = [], at
            for _, count in runs:
                idx.append(jnp.asarray(
                    start + np.arange(count)[None, :]
                    + period * np.arange(repeats)[:, None], jnp.int32))
                start += count
            at += repeats * period
            if repeats == 1 and len(runs) == 1:
                kind, count = runs[0]
                module = _scan_layers(body, count)(
                    self._kind_kwargs(kind), name=f"seq_layers_{n}_{kind}")
                xs = {"layer_idx": idx[0][0]}
            else:
                module = _scan_layers(_PeriodScanBody, repeats)(
                    tuple((kind, count, self._kind_kwargs(kind))
                          for kind, count in runs),
                    body, name=f"seq_layers_{n}_period")
                xs = {"layer_idx": tuple(idx)}
            built.append((module, xs))
        return built

    def __call__(self, hidden, cross_states=None, attention_mask=None):
        if self.loop_steps > 1:
            return self._loop(hidden, cross_states, attention_mask)
        # Innermost on the scans' own work alone: a layer's slice of the
        # stacked parameters, the residuals stacked for the backward pass.
        with jax.named_scope("smp/model/stack"):
            return self._stack(hidden, cross_states, attention_mask)

    def _loop(self, hidden, cross_states, attention_mask):
        """``loop_steps`` passes as one scan with the parameters broadcast
        and the layers' scans inside: one pass in the program's text, and
        the gradient of a weight every pass reads summed in the scan's own
        backward carry. Returns the normed states, [passes, B, T, D]."""

        def one_pass(stack, h, _):
            with jax.named_scope("smp/model/stack"):
                out = stack._stack(h, cross_states, attention_mask)
            # Rematerialized: the backward pass keeps a pass's state as
            # the layers gave it, not the norm's float32 intermediates.
            out = nn.remat(lambda mdl, x: mdl.loop_norm(x),
                           prevent_cse=False)(stack, out)
            return out, out

        # Innermost on the passes' own work: the norm after a pass, the
        # carried state, the states stacked for the head.
        with jax.named_scope("smp/model/loop"):
            _, states = nn.scan(
                one_pass, variable_broadcast="params",
                variable_axes={"intermediates": 0},
                split_rngs={"params": False, "dropout": True},
                length=self.loop_steps)(self, hidden, None)
        return states

    def _stack(self, hidden, cross_states, attention_mask):
        if self._streams() > 1:
            from smdistributed_modelparallel_tpu.nn import hyper_connection

            out = self._layers(
                hyper_connection.expand_streams(hidden, self._streams()),
                cross_states, attention_mask)
            return hyper_connection.collapse_streams(out)
        return self._layers(hidden, cross_states, attention_mask)

    def _layers(self, hidden, cross_states, attention_mask):
        if self.layer_pattern is not None:
            carry = (hidden, cross_states, attention_mask)
            for module, xs in self.segments:
                carry, _ = module(carry, xs)
            return carry[0]
        (out, _, _), ys = self.seq_layers(
            (hidden, cross_states, attention_mask), self.layer_xs()
        )
        if ys is not None:
            # Stacked per-layer amax from the body's quant drain: fold
            # the max over layers back into the enclosing trace level
            # (the microbatch body re-drains it into ITS ys).
            from smdistributed_modelparallel_tpu import quant as _q

            _q.absorb_stacked(ys)
        return out

    # -- pipeline decomposition: identity embed/head carrying the side
    # inputs so attention_mask/cross_states survive pipelining ------------

    def embed(self, hidden, cross_states=None, attention_mask=None):
        return (hidden, cross_states, attention_mask)

    def head(self, carry):
        return carry[0] if isinstance(carry, tuple) else carry

    @nn.nowrap
    def pipeline_spec(self):
        _refuse_loop_under_pipeline(self.loop_steps)
        if self.layer_pattern is not None or self._streams() > 1:
            # Two kinds of layer in a stage, or a carry of several streams
            # (the executors' embed and head see one): not yet.
            return None
        return PipelineSpec(
            layer_path="seq_layers/layer",
            num_layers=self.num_layers,
            layer_module=DistributedTransformerLayer(**self._layer_kwargs()),
            layer_xs=self.layer_xs(),
            carry_is_tuple=True,
        )


def _refuse_loop_under_pipeline(loop_steps):
    if (loop_steps > 1 and state.cfg is not None
            and state.cfg.pipeline_parallel_degree > 1):
        raise SMPValidationError(
            f"loop_steps={loop_steps} with pipeline_parallel_degree > 1: a "
            "pipeline executor whose stages see a microbatch once a pass is "
            "not written; run a looped stack at pp = 1."
        )


def _lm_head_vocab_split(vocab_size):
    """(mesh axes, shards) of the untied LM head's vocabulary dim, read
    from the mesh and ``vocab_size``: over tp and pp where the vocabulary
    divides by both, else over tp, else whole on every chip, (None, 1).

    The head runs once a microbatch on the last stage's output, outside
    the executors' ``stage_vmap``, so pp is free there to hold a share of
    the vocabulary beside tp: unsplit, every chip of a pp x tp mesh
    computes the whole [tokens, V] product, logits and loss, and carries
    the whole kernel through the optimizer. This is the one place a
    layer's module names pp (a region inside a stage may not)."""
    sizes = state.mesh.shape if state.initialized else {}
    tp, pp = sizes.get(TP_AXIS, 1), sizes.get(PP_AXIS, 1)
    for axes, shards in (((TP_AXIS, PP_AXIS), tp * pp), (TP_AXIS, tp)):
        if shards > 1 and vocab_size % shards == 0:
            return axes, shards
    return None, 1


class DistributedTransformerLMHead(nn.Module):
    """Embeddings + DistributedTransformer + LM head.

    Parity: reference ``DistributedTransformerLMHead``
    (``torch/nn/transformer.py:184-550``); the ``_KEYS`` config surface
    (``:189-236``) maps 1:1 onto these fields. ``prescaled_batch`` comes
    from the global smp config, as in the reference.
    """

    num_layers: int = 12
    num_attention_heads: int = 32
    attention_head_size: int = 32
    hidden_size: int = 1024
    intermediate_size: int = 4096
    vocab_size: int = 30522
    num_positions: int = 1024
    attention_dropout_prob: float = 0.1
    hidden_dropout_prob: float = 0.1
    embedding_dropout_prob: float = 0.1
    activation: str = "gelu"
    layernorm_epsilon: float = 1e-5
    mask_value: float = -1e4
    num_token_types: int = 0
    causal_mask_size: Optional[int] = None
    add_cross_attention: bool = False
    add_lm_head: bool = True
    initializer_range: float = 0.02
    use_normal_initialization: bool = False
    pre_layernorm: bool = False
    post_layernorm: bool = True
    attention_in_fp32: bool = False
    query_key_layer_scaling: bool = False
    fp32_residual_addition: bool = False
    fused_softmax: bool = True
    fused_bias_gelu: bool = False
    distribute_embedding: bool = False
    _scale_qkv_fan_out: bool = False
    _precision_test: bool = False
    rotary_dim: Optional[int] = None
    rotary_emb_base: Optional[float] = None
    gpt_neox_type_rotary: bool = False
    use_positional_embedding: bool = True
    # RoBERTa-style pad-aware positions: when set to the pad token id,
    # position ids are cumsum(ids != pad) * (ids != pad) + pad_id (HF
    # create_position_ids_from_input_ids) — pad tokens sit at the pad
    # position and real tokens skip pads (the embedding table carries the
    # pad_id + 1 extra rows).
    position_ids_from_padding: Optional[int] = None
    parallel_attn_output: bool = False
    use_lm_head_bias: bool = False
    attention_layers_type: Optional[tuple] = None
    # A stack of layers that differ in shape: see DistributedTransformer.
    layer_pattern: Optional[tuple] = None
    layer_kinds: Optional[Any] = None
    # The residual path of every layer: see DistributedTransformer.
    hyper_connection: Optional[Any] = None
    # "layer" or "rms" (RMSNorm, no bias), for every norm of the model.
    layernorm_type: str = "layer"
    use_mlp_bias: bool = True
    gated_mlp: bool = False
    use_qkv_bias: bool = True
    use_attn_dense_bias: bool = True
    window_size: Optional[int] = None
    # The share of the positions the stack ran that the head is asked for,
    # in (0, 1]: the final norm, the head and the logits (or losses) are
    # made for that leading part alone (0.5: the noisy half of block
    # diffusion's two-copy stream, whose clean half carries no loss).
    # None: all of them.
    head_positions: Optional[float] = None
    final_layernorm: bool = False
    tie_input_output_embedding: bool = True
    single_pre_layernorm: bool = False
    # The layer's field of the name (a norm on each branch's output).
    branch_layernorm: bool = False
    # Passes of the stack over its own output (DistributedTransformer's
    # field). With n > 1 the final norm is the stack's, after every pass;
    # the head runs on each pass's state, one pass at a time and
    # rematerialized, beside an exit gate (``exit_gate``: one linear layer
    # to a logit a position, the same for every pass); the model returns
    # ``(logits [n, B, T, V], gate logits [n, B, T])``, with ``targets``
    # ``(losses [n, B, T], gate logits)``, both float32, for
    # ``nn.exit_gate.exit_gated_loss``.
    loop_steps: int = 1
    # The most positions of a pass the looped head takes at a time (None:
    # all; the pieces are equal, so the largest divisor of the sequence
    # under it). A piece's logits are what the head keeps alive, [B,
    # positions, V] once in the compute dtype and once in float32, so
    # whoever knows the chip sets it.
    loop_head_positions: Optional[int] = None
    scale_attention_scores: bool = True
    scale_attn_by_layer_idx: bool = False
    activation_checkpointing: bool = False
    use_embedding_layernorm: bool = False  # BERT-family post-embedding LN
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Loss-mode (targets=...) uniform label smoothing, HF/T5 convention.
    label_smoothing: float = 0.0
    # KV-cache decoding for smp.generate (see nn/utils.DecodeKVCache).
    decode: bool = False
    decode_cache_len: Optional[int] = None
    deterministic: Optional[bool] = None
    dtype: Optional[Any] = None

    def setup(self):
        if self.distribute_embedding:
            self.word_embedding = DistributedEmbedding(
                self.vocab_size, self.hidden_size,
                split="vocab",
                init_scale=self.initializer_range,
                name="word_embedding",
            )
        else:
            self.word_embedding = nn.Embed(
                self.vocab_size, self.hidden_size,
                embedding_init=_init(self.initializer_range),
                name="word_embedding",
            )
        if self.use_positional_embedding:
            self.position_embedding = nn.Embed(
                self.num_positions, self.hidden_size,
                embedding_init=_init(self.initializer_range),
                name="position_embedding",
            )
        if self.num_token_types > 0:
            self.token_type_embedding = nn.Embed(
                self.num_token_types, self.hidden_size,
                embedding_init=_init(self.initializer_range),
                name="token_type_embedding",
            )
        if self.use_embedding_layernorm:
            self.embedding_layernorm = DistributedLayerNorm(
                epsilon=self.layernorm_epsilon, name="embedding_layernorm"
            )
        self.transformer = DistributedTransformer(
            **self._transformer_kwargs(), name="transformer"
        )
        if self.loop_steps > 1:
            # In float32 whatever the compute dtype, as a router's product
            # is: its weight's gradient is a sum over every position of
            # terms that nearly cancel.
            self.exit_gate = nn.Dense(
                1, kernel_init=_init(self.initializer_range),
                dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                name="exit_gate")
        elif self.final_layernorm or self.pre_layernorm:
            rms = (
                {"rms": True, "use_bias": False}
                if self.layernorm_type == "rms" else {}
            )
            self.ln_f = DistributedLayerNorm(
                epsilon=self.layernorm_epsilon, name="ln_f", **rms
            )
        if self.add_lm_head and not self.tie_input_output_embedding:
            vocab_axes, _ = _lm_head_vocab_split(self.vocab_size)
            self.lm_head = nn.Dense(
                self.vocab_size, use_bias=self.use_lm_head_bias,
                kernel_init=axis_partitioned(
                    _init(self.initializer_range), (None, vocab_axes)
                ),
                bias_init=axis_partitioned(
                    nn.initializers.zeros_init(), (vocab_axes,)
                ),
                name="lm_head",
            )
        if self.decode:
            # Top-level mirror of the per-layer cache indices (absolute
            # position offset for the learned position embedding).
            self._pos_index = self.variable(
                "cache", "position_index", lambda: jnp.zeros((), jnp.int32)
            )

    @nn.nowrap
    def _transformer_kwargs(self):
        return dict(
            num_layers=self.num_layers,
            num_attention_heads=self.num_attention_heads,
            attention_head_size=self.attention_head_size,
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            attention_dropout_prob=self.attention_dropout_prob,
            hidden_dropout_prob=self.hidden_dropout_prob,
            activation=self.activation,
            layernorm_epsilon=self.layernorm_epsilon,
            mask_value=self.mask_value,
            add_cross_attention=self.add_cross_attention,
            pre_layernorm=self.pre_layernorm,
            post_layernorm=self.post_layernorm,
            single_pre_layernorm=self.single_pre_layernorm,
            branch_layernorm=self.branch_layernorm,
            attention_in_fp32=self.attention_in_fp32,
            query_key_layer_scaling=self.query_key_layer_scaling,
            scale_attention_scores=self.scale_attention_scores,
            scale_attn_by_layer_idx=self.scale_attn_by_layer_idx,
            fp32_residual_addition=self.fp32_residual_addition,
            fused_bias_gelu=self.fused_bias_gelu,
            initializer_range=self.initializer_range,
            use_qkv_bias=self.use_qkv_bias,
            use_attn_dense_bias=self.use_attn_dense_bias,
            rotary_dim=self.rotary_dim,
            rotary_emb_base=self.rotary_emb_base,
            gpt_neox_type_rotary=self.gpt_neox_type_rotary,
            window_size=self.window_size,
            parallel_attn_output=self.parallel_attn_output,
            causal_mask_size=self.causal_mask_size,
            layernorm_type=self.layernorm_type,
            use_mlp_bias=self.use_mlp_bias,
            gated_mlp=self.gated_mlp,
            attention_layers_type=self.attention_layers_type,
            layer_pattern=self.layer_pattern,
            layer_kinds=self.layer_kinds,
            loop_steps=self.loop_steps,
            hyper_connection=self.hyper_connection,
            activation_checkpointing=self.activation_checkpointing,
            num_experts=self.num_experts,
            moe_top_k=self.moe_top_k,
            moe_capacity_factor=self.moe_capacity_factor,
            decode=self.decode,
            decode_cache_len=self.decode_cache_len,
            deterministic=self.deterministic,
            dtype=self.dtype,
        )

    # -- pipeline decomposition (PipelineSpec protocol) -----------------

    def embed(self, input_ids, token_type_ids=None, attention_mask=None):
        with jax.named_scope("smp/model/embed"):
            return self._embed(input_ids, token_type_ids, attention_mask)

    def _embed(self, input_ids, token_type_ids, attention_mask):
        x = self.word_embedding(input_ids)
        if self.use_positional_embedding:
            if self.position_ids_from_padding is not None:
                if self.decode:
                    raise SMPValidationError(
                        "decode=True is unsupported with "
                        "position_ids_from_padding (RoBERTa-style "
                        "pad-aware positions)."
                    )
                ne = (input_ids != self.position_ids_from_padding).astype(jnp.int32)
                pos = jnp.cumsum(ne, axis=-1) * ne + self.position_ids_from_padding
            else:
                start = 0
                if self.decode:
                    # Top-level mirror of the per-layer cache indices:
                    # learned positions need the absolute offset before
                    # the layer stack; left-padded prompts additionally
                    # shift each row by its pad count (see the attention
                    # layers' pos_offset).
                    from smdistributed_modelparallel_tpu.nn.utils import (
                        pad_row_offset,
                    )

                    idx = self._pos_index.value
                    self._pos_index.value = idx + input_ids.shape[-1]
                    row_off = pad_row_offset(attention_mask)
                    start = (
                        idx if row_off is None else (idx + row_off)[:, None]
                    )
                pos = jnp.maximum(
                    start + jnp.arange(input_ids.shape[-1])[None, :], 0
                )
            x = x + self.position_embedding(pos)
        if self.num_token_types > 0 and token_type_ids is not None:
            x = x + self.token_type_embedding(token_type_ids)
        if self.use_embedding_layernorm:
            x = self.embedding_layernorm(x)
        if self.embedding_dropout_prob > 0.0 and not resolve_deterministic(self.deterministic):
            x = nn.Dropout(self.embedding_dropout_prob, deterministic=False)(x)
        memory_opt = _cfg("optimize", "speed") == "memory"
        x = shard_activation(x, *_hidden_spec(_seq_parallel(memory_opt)))
        return (x, None, attention_mask)

    def head(self, carry, targets=None):
        x, _, _ = carry if isinstance(carry, tuple) else (carry, None, None)
        if self.head_positions is not None:
            # The final norm, the head and the logits for the leading
            # positions alone: what follows them in the stream only fed
            # the stack (a two-copy stream's clean half).
            from smdistributed_modelparallel_tpu.utils.telemetry import (
                record_lm_head_positions,
            )

            T = x.shape[-2]
            n = int(round(T * self.head_positions))
            record_lm_head_positions(n, T)
            x = x[..., :n, :]
        if self.loop_steps > 1:
            return self._loop_head(x, targets)
        return self._head(x, targets)

    def _loop_head(self, states, targets):
        """Head and exit gate on each pass's normed state [passes, B, T,
        D], as one scan over the passes with the parameters broadcast and
        its body rematerialized: the backward pass makes a pass's logits
        again from its state, so no two passes' logits are alive
        together."""

        n, B, T, D = states.shape
        pieces = next(c for c in range(1, T + 1) if T % c == 0
                      and T // c <= (self.loop_head_positions or T))
        size = T // pieces

        def one_piece(model, _, piece):
            h, t = piece
            out = model._head(h, t, normed=True)
            with jax.named_scope("smp/head/exit_gate"):
                gate = model.exit_gate(h)[..., 0]
            return None, (out, gate)

        def whole(y):
            """[n * pieces, B, size, ...] -> [n, B, T, ...]"""
            y = jnp.moveaxis(y.reshape(n, pieces, B, size, *y.shape[3:]), 1, 2)
            return y.reshape(n, B, T, *y.shape[4:])

        xs = jnp.moveaxis(states.reshape(n, B, pieces, size, D), 2, 1)
        ts = None
        if targets is not None:
            ts = jnp.tile(
                jnp.moveaxis(targets.reshape(B, pieces, size), 1, 0),
                (n, 1, 1))
        _, out = nn.scan(
            nn.remat(one_piece, prevent_cse=False),
            variable_broadcast="params", split_rngs={"params": False},
            length=n * pieces)(
                self, None, (xs.reshape(n * pieces, B, size, D), ts))
        return jax.tree_util.tree_map(whole, out)

    def _head(self, x, targets, normed=False):
        if not normed and (self.final_layernorm or self.pre_layernorm):
            with jax.named_scope("smp/head/norm"):
                x = self.ln_f(x)
        if not self.add_lm_head:
            return x
        if targets is not None and self.tie_input_output_embedding:
            # Fused LM-head CE (TPU extension): per-token losses without
            # the [.., V] logits intermediate. The dispatcher falls back
            # to the Megatron vocab-parallel path under tp / off-TPU.
            from smdistributed_modelparallel_tpu.nn.cross_entropy import (
                fused_lm_head_cross_entropy,
            )

            return fused_lm_head_cross_entropy(
                x, self.word_embedding.embedding, targets,
                label_smoothing=self.label_smoothing,
            )
        with jax.named_scope("smp/head/logits"):
            logits = self._logits(x)
        if targets is None:
            return logits
        from smdistributed_modelparallel_tpu.nn.cross_entropy import (
            masked_vocab_parallel_cross_entropy,
        )

        return masked_vocab_parallel_cross_entropy(
            logits, targets, label_smoothing=self.label_smoothing
        )

    def _logits(self, x):
        if self.tie_input_output_embedding:
            return self.word_embedding.attend(x)
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_lm_head_vocab_shards,
        )

        logits = self.lm_head(x)
        vocab_axes, shards = _lm_head_vocab_split(self.vocab_size)
        record_lm_head_vocab_shards(shards)
        if vocab_axes is not None:
            logits = shard_activation(
                logits, BATCH_AXES, CP_AXIS, vocab_axes
            )
        return logits

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 targets=None):
        """ids -> logits; with ``targets`` ([B, T] int, -100 = ignored) ->
        per-token fp32 losses via the fused LM-head CE. Loss mode
        requires pp == 1 (the pipeline head protocol carries no
        targets). With ``head_positions`` set, logits (or losses) for the
        leading positions it names only; the stack still runs them all.
        With ``loop_steps`` > 1, a pair: either of those for every pass,
        stacked on a leading axis, and the exit gate's logits."""
        if targets is not None:
            if state.cfg is not None and state.cfg.pipeline_parallel_degree > 1:
                raise SMPValidationError(
                    "model(ids, targets=...) is not available under "
                    "pipeline parallelism; compute the loss from logits."
                )
        carry = self.embed(input_ids, token_type_ids, attention_mask)
        x, cross, amask = carry
        x = self.transformer(x, attention_mask=amask)
        return self.head((x, cross, amask), targets=targets)

    @nn.nowrap
    def pipeline_spec(self):
        _refuse_loop_under_pipeline(self.loop_steps)
        if (self.layer_pattern is not None
                or dict(self.hyper_connection or {}).get("streams", 1) > 1):
            return None     # see DistributedTransformer.pipeline_spec
        return PipelineSpec(
            layer_path="transformer/seq_layers/layer",
            num_layers=self.num_layers,
            layer_module=DistributedTransformerLayer(
                **{
                    k: v
                    for k, v in self._transformer_kwargs().items()
                    if k not in (
                        "num_layers",
                        "attention_layers_type",
                        "layer_pattern",
                        "layer_kinds",
                        "loop_steps",
                        "activation_checkpointing",
                    )
                }
            ),
            layer_xs=DistributedTransformer(
                **self._transformer_kwargs()
            ).layer_xs(),
            carry_is_tuple=True,
        )
