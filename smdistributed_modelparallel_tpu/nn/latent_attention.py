"""smp.nn multi-head latent attention, for training.

Queries and keys / values are not projected straight from the stream but
through low-rank latents, each behind an RMSNorm, and a head's query and
key are two parts side by side: one with no position in it, made from the
latent, and a rotary part, which on the key side is one vector a token
that every head shares (the DeepSeek-V2 / V3 family's attention):

    c_q  = rms(x W_qa)                       [q_lora_rank]
    q_h  = [q_nope_h | q_pe_h] = c_q W_qb    [qk_nope + qk_rope] a head
    [c_kv | k_pe] = x W_kva                  [kv_lora_rank + qk_rope]
    c_kv <- rms(c_kv)
    [k_nope_h | v_h] = c_kv W_kvb            [qk_nope + v_head] a head
    q_pe_h, k_pe <- rotary (halves rotated; YaRN frequencies where given)
    k_h  = [k_nope_h | k_pe]                 the one k_pe under every head
    o_h  = causal softmax(scale q_h . k_h) v_h
    out  = concat_h(o_h) W_o

The query and key heads are ``qk_nope + qk_rope`` wide and the value heads
``v_head``: the flash kernels take the two sizes as they are
(``ops/pallas_attention.py``). ``softmax_scale`` is the whole scale of the
scores (YaRN's ``mscale`` squared is in it, not in the rotary table;
``rotary_yarn``'s fifth entry multiplies cos and sin and is 1 where
``mscale == mscale_all_dim``).

Tensor parallelism: the down-projections ``q_down`` [D, r_q] and
``kv_down`` [D, r_kv + qk_rope] and the latents' norms are replicated
(every rank computes the latents whole: they are what the heads share);
``q_up`` [r_q, H, .], ``kv_up`` [r_kv, H, .] and ``dense`` [H, v_head, D]
split heads over tp, and GSPMD sums the output projection's partial
products, as ``DistributedAttentionLayer``'s.

Training only: the cache of latents and the absorbed form that decoding
wants are not here, and ``decode=True`` raises. The parts trace under
``smp/latent/{q_down,q_up,kv_down,kv_up,rope,out}`` inside whatever
scope the layer put round its attention (``smp/attn/full``, so every
``smp/attn/*`` reader counts them); the kernels or the plain path under
``smp/attn/core``. A scope is ``smp/<subsystem>/<name>``
(``hlo_audit.scopes_of``): a third level would be cut off the name.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.backend.topology import (
    CP_AXIS,
    EP_AXIS,
    RDP_AXIS,
    TP_AXIS,
)
from smdistributed_modelparallel_tpu.nn.layer_norm import DistributedLayerNorm
from smdistributed_modelparallel_tpu.nn.utils import (
    partitioned,
    shard_activation,
)
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

BATCH_AXES = (RDP_AXIS, EP_AXIS)
HEAD_SPEC = (BATCH_AXES, CP_AXIS, TP_AXIS, None)


class DistributedLatentAttentionLayer(nn.Module):
    """Causal self-attention through low-rank latents on hidden
    [B, T, D]."""

    num_attention_heads: int
    hidden_size: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    softmax_scale: float
    rotary_emb_base: float = 10000.0
    # (factor, original_max_position, beta_fast, beta_slow, cos/sin
    # factor): YaRN frequencies for the rotary parts.
    rotary_yarn: Optional[tuple] = None
    layernorm_epsilon: float = 1e-6
    mask_value: float = -1e9
    initializer_range: float = 0.02
    decode: bool = False
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, hidden, attention_mask=None, xs=None):
        from smdistributed_modelparallel_tpu.nn.transformer import (
            _cfg,
            _hidden_spec,
            _init,
            _seq_parallel,
            apply_rotary,
        )
        from smdistributed_modelparallel_tpu.ops.attention import (
            attention_core,
        )

        if self.decode:
            raise SMPValidationError(
                "latent attention keeps no decode cache yet: it does not "
                "take decode=True."
            )
        H, D = self.num_attention_heads, self.hidden_size
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        dtype = self.dtype or hidden.dtype
        init = _init(self.initializer_range)
        heads = lambda: partitioned(init, (None, TP_AXIS, None))  # noqa: E731
        norm = lambda name: DistributedLayerNorm(               # noqa: E731
            epsilon=self.layernorm_epsilon, rms=True, use_bias=False,
            name=name)
        cast = lambda w: w.astype(hidden.dtype)                 # noqa: E731

        with jax.named_scope("smp/latent/q_down"):
            q_down = self.param("q_down/kernel", init, (D, rq), dtype)
            c_q = norm("q_norm")(hidden @ cast(q_down))
        with jax.named_scope("smp/latent/q_up"):
            q_up = self.param("q_up/kernel", heads(), (rq, H, dn + dr), dtype)
            q = shard_activation(
                jnp.einsum("btr,rhk->bthk", c_q, cast(q_up)), *HEAD_SPEC)
        with jax.named_scope("smp/latent/kv_down"):
            kv_down = self.param(
                "kv_down/kernel", init, (D, rkv + dr), dtype)
            latent = hidden @ cast(kv_down)
            c_kv = norm("kv_norm")(latent[..., :rkv])
            k_pe = latent[..., None, rkv:]                      # [B, T, 1, dr]
        with jax.named_scope("smp/latent/kv_up"):
            kv_up = self.param(
                "kv_up/kernel", heads(), (rkv, H, dn + dv), dtype)
            kv = shard_activation(
                jnp.einsum("btr,rhk->bthk", c_kv, cast(kv_up)), *HEAD_SPEC)
        with jax.named_scope("smp/latent/rope"):
            q_pe, k_pe = apply_rotary(
                q[..., dn:], k_pe, dr, base=self.rotary_emb_base,
                neox_style=True,
                **({} if self.rotary_yarn is None
                   else {"yarn": tuple(self.rotary_yarn)}))
            q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_pe, (*kv.shape[:3], dr))], axis=-1)
            q, k, v = (shard_activation(t, *HEAD_SPEC)
                       for t in (q, k, kv[..., dn:]))
        with jax.named_scope("smp/attn/core"):
            ctx = attention_core(
                q, k, v, causal=True, scale=float(self.softmax_scale),
                mask=attention_mask, mask_value=self.mask_value,
                use_pallas=_cfg("use_pallas_kernels", True))
        with jax.named_scope("smp/latent/out"):
            dense = self.param(
                "dense/kernel", partitioned(init, (TP_AXIS, None, None)),
                (H, dv, D), dtype)
            out = jnp.einsum("bthk,hkd->btd", ctx, cast(dense))
            return shard_activation(out, *_hidden_spec(
                _seq_parallel(_cfg("optimize", "speed") == "memory")))
