"""Decoder-only transformer LM — the flagship model family.

Parity target: reference ``torch/nn/transformer.py:184-550``
(``DistributedTransformerLMHead``: embeddings + transformer + tied LM head
behind ~40 config keys) re-designed flax-first:

- layers are built with ``flax.linen.scan`` so parameters carry a leading
  [num_layers] axis — one layer is traced/compiled once, and the stacked
  layout is exactly what the pipeline executor (``parallel/pipeline.py``)
  and per-layer rematerialization need;
- ``embed`` / ``head`` are standalone methods so the pipeline can run them
  around the layer stack (``PipelineSpec`` protocol);
- attention/MLP internals route through ``smp.nn`` functional ops so tensor
  parallelism (M3) applies the Megatron-style sharding without touching
  this file.

Model-zoo configs for GPT-2 sizes are in ``models/gpt2.py``.
"""

from dataclasses import field
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.parallel.pipeline import PipelineSpec


def _gelu(x):
    return nn.gelu(x, approximate=True)


class CausalSelfAttention(nn.Module):
    """Multi-head causal self-attention.

    Parity: reference ``DistributedAttentionLayer``
    (``torch/nn/transformer.py:1176-1835``); TP sharding lands in M3 via
    sharding constraints on the head dimension.

    ``decode=True`` enables the KV-cache path for autoregressive
    generation (TPU extension, ``generation.py``): K/V of every chunk are
    written into fixed-length "cache" variables of ``decode_cache_len``
    slots; a T=1 call attends over the cache (prior positions only), a
    T>1 call is the prefill and attends causally over its own chunk (the
    cache is empty before it, so chunk-causal == cache semantics — and it
    keeps the flash-kernel path for the prompt pass).
    """

    d_model: int
    n_heads: int
    dropout: float = 0.0
    attention_in_fp32: bool = False
    rotary: bool = False
    rotary_dim: Optional[int] = None
    window: Optional[int] = None
    deterministic: bool = True
    decode: bool = False
    decode_cache_len: Optional[int] = None
    # Paged decoding for smp.serving (nn/utils.PagedKVCache): K/V live in
    # a shared block pool; per-call state (block tables, positions)
    # arrives via the ``paged`` argument. Mutually exclusive with decode.
    paged_blocks: Optional[int] = None
    paged_block_tokens: Optional[int] = None

    @nn.compact
    def __call__(self, x, attn_bias=None, paged=None):
        B, T, D = x.shape
        H = self.n_heads
        hd = D // H
        with jax.named_scope("smp/attn/qkv"):
            qkv = nn.Dense(3 * D, name="qkv")(x)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, T, H, hd)
            k = k.reshape(B, T, H, hd)
            v = v.reshape(B, T, H, hd)
        with jax.named_scope("smp/attn/core"):
            out = self._attend(q, k, v, attn_bias, paged)
        with jax.named_scope("smp/attn/out"):
            return nn.Dense(D, name="proj")(out)

    @nn.nowrap
    def _attend(self, q, k, v, attn_bias, paged):
        """Rotary, the cache (decode, paged) and the attention itself."""
        B, T, H, hd = q.shape
        pos_offset = 0
        cache = None
        decode_mask = None
        if self.paged_blocks is not None:
            if paged is None:
                raise ValueError(
                    "paged KV-cache decoding needs the per-call paged "
                    "state (block_tables/positions) — drive this module "
                    "through smp.serving.ServingEngine."
                )
            pos_offset = paged["positions"]
        elif self.decode:
            from smdistributed_modelparallel_tpu.nn.utils import DecodeKVCache

            cache = DecodeKVCache(self, (B, self.decode_cache_len, H, hd),
                                  k.dtype)
            pos_offset = cache.index
        if self.rotary:
            from smdistributed_modelparallel_tpu.nn.transformer import apply_rotary

            rd = self.rotary_dim or hd
            # The cache stores POST-rotary K: chunk q/k rotate at their
            # absolute positions once, on write.
            q, k = apply_rotary(q, k, rd, neox_style=True, offset=pos_offset)
        if self.paged_blocks is not None:
            from smdistributed_modelparallel_tpu.nn.utils import PagedKVCache

            pool = PagedKVCache(
                self, self.paged_blocks, self.paged_block_tokens, H, hd,
                k.dtype,
            )
            k, v, decode_mask = pool.append(
                k, v, paged["block_tables"], paged["positions"],
                valid=paged.get("valid"), window=self.window,
            )
        elif cache is not None:
            k, v, decode_mask = cache.append(k, v, window=self.window)
        from smdistributed_modelparallel_tpu.ops.attention import attention_core

        drop_rng = None
        if self.dropout > 0.0 and not self.deterministic:
            drop_rng = self.make_rng("dropout")
        return attention_core(
            q, k, v,
            causal=decode_mask is None,
            window=self.window if decode_mask is None else None,
            bias=attn_bias,
            mask=decode_mask,
            attention_in_fp32=self.attention_in_fp32,
            dropout_rate=self.dropout if not self.deterministic else 0.0,
            dropout_rng=drop_rng,
        ).reshape(B, T, H * hd)


class TransformerLayer(nn.Module):
    """One pre/post-LN transformer block; applied per pipeline stage."""

    d_model: int
    n_heads: int
    d_ff: int
    dropout: float = 0.0
    pre_layernorm: bool = True
    post_layernorm: bool = False
    attention_in_fp32: bool = False
    rotary: bool = False
    rotary_dim: Optional[int] = None
    window: Optional[int] = None
    parallel_block: bool = False  # GPT-J style parallel attn+mlp
    deterministic: bool = True
    ln_eps: float = 1e-5
    decode: bool = False
    decode_cache_len: Optional[int] = None
    paged_blocks: Optional[int] = None
    paged_block_tokens: Optional[int] = None

    @nn.compact
    def __call__(self, x, paged=None):
        # The scopes ``utils/profiling.SCOPES`` lists: the layer, its
        # attention (``full`` or ``window``) and its feed-forward; the
        # norms stay charged to the layer.
        with jax.named_scope("smp/layer/block"):
            return self._block(x, paged)

    @nn.nowrap
    def _block(self, x, paged):
        attention = CausalSelfAttention(
            self.d_model, self.n_heads, self.dropout, self.attention_in_fp32,
            self.rotary, self.rotary_dim, self.window, self.deterministic,
            self.decode, self.decode_cache_len,
            self.paged_blocks, self.paged_block_tokens,
            name="attn",
        )

        def attn(h, paged):
            with jax.named_scope(
                    "smp/attn/window" if self.window else "smp/attn/full"):
                return attention(h, paged=paged)

        def mlp(h):
            with jax.named_scope("smp/mlp/dense"):
                h = nn.Dense(self.d_ff, name="fc")(h)
                h = _gelu(h)
                return nn.Dense(self.d_model, name="proj")(h)

        if self.parallel_block:
            h = nn.LayerNorm(epsilon=self.ln_eps, name="ln1")(x)
            x = x + attn(h, paged=paged) + mlp(h)
        else:
            h = nn.LayerNorm(epsilon=self.ln_eps, name="ln1")(x) if self.pre_layernorm else x
            x = x + attn(h, paged=paged)
            if self.post_layernorm:
                x = nn.LayerNorm(epsilon=self.ln_eps, name="ln1_post")(x)
            h = nn.LayerNorm(epsilon=self.ln_eps, name="ln2")(x) if self.pre_layernorm else x
            x = x + mlp(h)
            if self.post_layernorm:
                x = nn.LayerNorm(epsilon=self.ln_eps, name="ln2_post")(x)
        if self.dropout > 0.0 and not self.deterministic:
            x = nn.Dropout(self.dropout, deterministic=False)(x)
        return x


class _ScanBody(nn.Module):
    """Carry-protocol wrapper for nn.scan over TransformerLayer. The
    second argument is the scan's xs slot — None in training/decode, the
    (broadcast) paged per-call state under smp.serving."""

    layer_kwargs: dict

    @nn.compact
    def __call__(self, x, paged):
        return (
            TransformerLayer(**self.layer_kwargs, name="block")(
                x, paged=paged
            ),
            None,
        )


class TransformerLM(nn.Module):
    """Embeddings + scanned transformer stack + (tied) LM head."""

    vocab_size: int
    max_len: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: Optional[int] = None
    dropout: float = 0.0
    pos_type: str = "learned"      # learned | rotary | none
    tie_weights: bool = True
    parallel_block: bool = False
    attention_in_fp32: bool = False
    window: Optional[int] = None
    rotary_dim: Optional[int] = None
    deterministic: bool = True
    ln_eps: float = 1e-5
    # Loss-mode (targets=...) uniform label smoothing, HF/T5 convention.
    label_smoothing: float = 0.0
    # KV-cache decoding for smp.generate (see nn/utils.DecodeKVCache).
    decode: bool = False
    decode_cache_len: Optional[int] = None
    # Paged serving decode (smp.serving / nn/utils.PagedKVCache): the
    # block-pool geometry; per-call block tables/positions arrive via the
    # ``paged`` argument of ``__call__``.
    paged_blocks: Optional[int] = None
    paged_block_tokens: Optional[int] = None

    @nn.nowrap
    def _layer_kwargs(self):
        return dict(
            d_model=self.d_model,
            n_heads=self.n_heads,
            d_ff=self.d_ff or 4 * self.d_model,
            dropout=self.dropout,
            attention_in_fp32=self.attention_in_fp32,
            rotary=self.pos_type == "rotary",
            rotary_dim=self.rotary_dim,
            window=self.window,
            parallel_block=self.parallel_block,
            deterministic=self.deterministic,
            ln_eps=self.ln_eps,
            decode=self.decode,
            decode_cache_len=self.decode_cache_len,
            paged_blocks=self.paged_blocks,
            paged_block_tokens=self.paged_block_tokens,
        )

    def setup(self):
        self.wte = nn.Embed(self.vocab_size, self.d_model, name="wte")
        if self.pos_type == "learned":
            self.wpe = nn.Embed(self.max_len, self.d_model, name="wpe")
        scan_kwargs = {}
        if self.paged_blocks is not None:
            # The paged per-call state (block tables, positions) is the
            # same for every layer: broadcast it instead of scanning.
            # Only the paged clone changes its scan signature — the
            # training/decode paths keep the exact pre-serving transform.
            scan_kwargs["in_axes"] = nn.broadcast
        ScanLayers = nn.scan(
            _ScanBody,
            variable_axes={"params": 0, "cache": 0},
            split_rngs={"params": True, "dropout": True},
            length=self.n_layers,
            **scan_kwargs,
        )
        self.layers = ScanLayers(self._layer_kwargs(), name="layers")
        self.ln_f = nn.LayerNorm(epsilon=self.ln_eps, name="ln_f")
        if not self.tie_weights:
            self.lm_head = nn.Dense(self.vocab_size, use_bias=False, name="lm_head")
        if self.decode:
            # Top-level mirror of the per-layer cache indices: learned
            # positions need the absolute offset before the layer stack.
            self._pos_index = self.variable(
                "cache", "position_index", lambda: jnp.zeros((), jnp.int32)
            )

    # -- pipeline decomposition ----------------------------------------

    def embed(self, ids, paged=None):
        with jax.named_scope("smp/model/embed"):
            return self._embed(ids, paged)

    def _embed(self, ids, paged):
        x = self.wte(ids)
        if self.pos_type == "learned":
            if paged is not None:
                # Per-row absolute positions (continuous batching mixes
                # sequences at different depths in one decode batch).
                pos = paged["positions"][:, None] + jnp.arange(
                    ids.shape[-1], dtype=jnp.int32
                )[None, :]
                return x + self.wpe(jnp.clip(pos, 0, self.max_len - 1))
            start = 0
            if self.decode:
                start = self._pos_index.value
                self._pos_index.value = start + ids.shape[-1]
            x = x + self.wpe(start + jnp.arange(ids.shape[-1])[None, :])
        return x

    def head(self, x, targets=None):
        with jax.named_scope("smp/head/norm"):
            x = self.ln_f(x)
        if targets is not None and self.tie_weights:
            # Fused LM-head CE (TPU extension): per-token losses without
            # the [.., V] logits intermediate (nn/cross_entropy.py).
            from smdistributed_modelparallel_tpu.nn.cross_entropy import (
                fused_lm_head_cross_entropy,
            )

            return fused_lm_head_cross_entropy(
                x, self.wte.embedding, targets,
                label_smoothing=self.label_smoothing,
            )
        with jax.named_scope("smp/head/logits"):
            logits = (self.wte.attend(x) if self.tie_weights
                      else self.lm_head(x))
        if targets is None:
            return logits
        from smdistributed_modelparallel_tpu.nn.cross_entropy import (
            masked_vocab_parallel_cross_entropy,
        )

        return masked_vocab_parallel_cross_entropy(
            logits, targets, label_smoothing=self.label_smoothing
        )

    def __call__(self, ids, targets=None, paged=None):
        """ids -> logits; with ``targets`` ([B, T] int, -100 = ignored) ->
        per-token fp32 losses instead, via the fused LM-head CE (the
        logits tensor never materializes on the TPU tied-head path).
        Loss mode requires pp == 1 (the pipeline head protocol carries no
        targets). ``paged`` is the smp.serving per-call decode state
        (block tables / positions / valid), only meaningful on a
        ``paged_blocks`` clone."""
        if targets is not None:
            from smdistributed_modelparallel_tpu.backend.state import state

            if state.cfg is not None and state.cfg.pipeline_parallel_degree > 1:
                raise ValueError(
                    "model(ids, targets=...) is not available under "
                    "pipeline parallelism; compute the loss from logits."
                )
        x = self.embed(ids, paged=paged)
        with jax.named_scope("smp/model/stack"):
            x = self._apply_layers(x, paged=paged)
        return self.head(x, targets)

    def _apply_layers(self, x, paged=None):
        """The layer stack: the lifted ``nn.scan`` normally, or — under
        ``sharded_params: zero3`` at pp=1 — the double-buffered
        just-in-time gather scan (``parallel/zero.zero3_prefetch_scan``):
        each tick all-gathers the NEXT layer's rdp-sharded param slice
        into a transfer register behind an optimization barrier while the
        current layer's matmuls run, and the backward regathers from the
        sharded slice (per-layer remat) instead of stashing gathered
        copies. Decode (mutable KV cache) and non-deterministic dropout
        need the lifted scan's collection/rng plumbing and keep it."""
        if not self.is_initializing() and not self.decode and (
                paged is None) and (
                self.dropout == 0.0 or self.deterministic):
            import jax as _jax

            from smdistributed_modelparallel_tpu.parallel import zero

            stacked = self.layers.variables.get("params", {}).get("block")
            if (stacked and isinstance(x, _jax.core.Tracer)
                    and zero.zero3_prefetch_active()):
                # parent=None: a detached functional module (same trick as
                # PipelineSpec.layer_module), not a registered submodule.
                layer = TransformerLayer(**self._layer_kwargs(), parent=None)
                specs = zero.gathered_slice_specs(stacked, "layers/block")

                def apply_layer(h, p):
                    return layer.apply({"params": p}, h)

                return zero.zero3_prefetch_scan(
                    apply_layer, x, stacked, self.n_layers, specs
                )
        x, _ = self.layers(x, paged)
        return x

    @nn.nowrap
    def pipeline_spec(self):
        return PipelineSpec(
            layer_path="layers/block",
            num_layers=self.n_layers,
            layer_module=TransformerLayer(**self._layer_kwargs()),
        )


