"""DistributedCrossEntropy — vocab-parallel cross-entropy.

Parity target: reference ``torch/nn/cross_entropy.py:28-112``
(Megatron-style): local max -> allreduce-max -> mask local target logits ->
allreduce of target-logit and sum-exp -> loss.

TPU-native re-design: written as a numerically-stable log-softmax over the
(tp-sharded) vocab axis with sharding constraints; GSPMD emits the same
max/sum allreduces the reference codes explicitly. The target-logit gather
is a one-hot contraction (MXU-friendly, partitionable over the sharded
vocab dim).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS
from smdistributed_modelparallel_tpu.nn.utils import shard_activation


#: The scope of the three entry points below (``utils/profiling.SCOPES``):
#: a device trace then says what the loss costs, whoever calls it.
LOSS_SCOPE = "smp/head/loss"


def vocab_parallel_cross_entropy(logits, targets, label_smoothing=0.0):
    """Per-token cross-entropy loss.

    Args:
      logits: [..., vocab] (vocab axis may be tp-sharded).
      targets: [...] int ids.
    Returns:
      [...] per-token losses (fp32).
    """
    with jax.named_scope(LOSS_SCOPE):
        return _cross_entropy(logits, targets, label_smoothing)


def _cross_entropy(logits, targets, label_smoothing):
    vocab = logits.shape[-1]
    spec = [None] * (logits.ndim - 1) + [TP_AXIS]
    logits = shard_activation(logits, *spec)
    logits_f = logits.astype(jnp.float32)
    # Stable logsumexp over the sharded vocab axis: GSPMD lowers max/sum to
    # the reference's allreduce(max)/allreduce(sum) pair
    # (torch/nn/cross_entropy.py:42-71).
    m = jax.lax.stop_gradient(jnp.max(logits_f, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits_f - m), axis=-1)) + m[..., 0]
    one_hot = jax.nn.one_hot(targets, vocab, dtype=logits_f.dtype)
    target_logit = jnp.sum(logits_f * one_hot, axis=-1)
    loss = lse - target_logit
    if label_smoothing > 0.0:
        # mean over vocab of -log_softmax == lse - mean(logits): reuses the
        # lse above instead of a second [.., V] fp32 log-softmax (and its
        # extra allreduce pair under tp).
        smooth = lse - jnp.mean(logits_f, axis=-1)
        loss = (1.0 - label_smoothing) * loss + label_smoothing * smooth
    return loss


def masked_vocab_parallel_cross_entropy(logits, targets, ignore_index=-100,
                                        label_smoothing=0.0):
    """``vocab_parallel_cross_entropy`` with HF-convention ignored labels:
    ``ignore_index`` positions contribute 0 loss and no gradient."""
    with jax.named_scope(LOSS_SCOPE):
        valid = targets != ignore_index
        per = _cross_entropy(
            logits, jnp.where(valid, targets, 0), label_smoothing)
        return jnp.where(valid, per, 0.0)


def _build_tp_fused_ce(mesh, v_global, block_n, block_v, interpret,
                       smoothing):
    """Vocab-parallel fused CE for the tp-sharded table (cached in
    ``pallas_ce.make_vocab_parallel_fused_ce``; partial-manual over tp
    only — dp/cp axes stay GSPMD-automatic)."""
    from smdistributed_modelparallel_tpu.ops.pallas_ce import (
        make_vocab_parallel_fused_ce,
    )

    return make_vocab_parallel_fused_ce(
        mesh, v_global, block_n, block_v, interpret, smoothing, TP_AXIS
    )


def _want_fused_ce(x, embedding_table, tp=1):
    """Policy half of the CE dispatch (capability half: ``pc.fused_ce_ok``).

    The blockwise kernel trades ~5/3 the head matmul flops (the backward
    recomputes logit blocks) for never materializing [N, V]. At transformer
    widths the recompute costs more wall-clock than the saved HBM traffic
    (measured: GPT-2 124M bench 114.5 -> 104.0 ms/step on v5e when switching
    to the logits path), so the kernel is a memory-CAPACITY lever: ``auto``
    engages it only when the logits (at the activation dtype) would be
    large enough to threaten HBM (fused_ce_auto_threshold_mb, default
    2 GB — e.g. 32k tokens x 50k vocab at bf16), where the logits path
    would OOM or evict everything else.
    """
    from smdistributed_modelparallel_tpu.backend.state import state

    mode = getattr(state.cfg, "fused_ce", "auto") if state.initialized else "auto"
    if mode is True:
        return True
    if mode is False:
        return False
    thresh_mb = (
        getattr(state.cfg, "fused_ce_auto_threshold_mb", 2048)
        if state.initialized else 2048
    )
    # Estimate the materialized path's logits at the ACTIVATION dtype
    # (fp32 activations materialize 4-byte logits plus the softmax's fp32
    # copy — underestimating here would defeat the capacity policy).
    # Under tp the vocab axis is sharded, so the per-chip logits are
    # [N, V/tp] — the capacity threshold applies to what one chip holds.
    itemsize = jnp.dtype(x.dtype).itemsize
    logits_mb = (
        x.shape[0] * embedding_table.shape[0] * itemsize / 2**20 / tp
    )
    return logits_mb > thresh_mb


def fused_lm_head_cross_entropy(hidden, embedding_table, targets,
                                ignore_index=-100, label_smoothing=0.0,
                                block_n=None, block_v=None):
    """Tied-LM-head cross-entropy WITHOUT materializing logits.

    TPU extension (no reference counterpart): computes per-token
    ``CE(hidden @ table^T, targets)`` through the blockwise Pallas kernels
    (``ops/pallas_ce.py``) — the [.., V] logits tensor, the single largest
    HBM intermediate of large-vocab LM training, never exists. Block sizes
    default to ``pallas_ce.auto_blocks`` (shrunk to fit VMEM for wide D).
    Under tensor parallelism the kernels run per-shard on the local
    [V/tp, D] table slice inside a tp manual region, combined with the
    same pmax/psum pair the materialized Megatron path uses — at modern
    256k vocabs this is where the capacity win matters most. Falls back
    to the materialized-logits ``vocab_parallel_cross_entropy`` path
    off-TPU; a forced ``fused_ce: True`` that cannot run logs a warning
    at trace time.

    Args:
      hidden: [..., D] final hidden states (post final-layernorm).
      embedding_table: [V, D] tied embedding table.
      targets: [...] int ids; ``ignore_index`` entries contribute 0 loss
        and no gradient.
    Returns: fp32 per-token losses shaped like ``targets``.

    Scopes: the blockwise kernels, which hold the head's product inside
    them, trace under ``smp/head/loss``; on the materialized path the
    product is ``smp/head/logits`` and what follows it the loss.
    """
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.ops import pallas_ce as pc
    from smdistributed_modelparallel_tpu.utils.logger import get_logger

    lead = hidden.shape[:-1]
    D = hidden.shape[-1]
    x = hidden.reshape(-1, D)
    t = targets.reshape(-1)
    with jax.named_scope(LOSS_SCOPE):
        valid = t != ignore_index
        t_safe = jnp.where(valid, t, 0)
    tp = state.mesh.shape.get(TP_AXIS, 1) if state.initialized else 1
    want = _want_fused_ce(x, embedding_table, tp)
    V = embedding_table.shape[0]
    can = pc.fused_ce_ok(x, embedding_table, block_n, block_v) and (
        tp == 1 or V % tp == 0
    )
    if want and can:
        bn, bv = pc.auto_blocks(D, block_n, block_v)
        if tp == 1:
            with jax.named_scope(LOSS_SCOPE):
                per = pc.fused_lm_head_ce(x, embedding_table, t_safe,
                                          bn, bv, False,
                                          float(label_smoothing))
        else:
            # Vocab-parallel: per-shard kernels on the local [V/tp, D]
            # slice, pmax/psum-combined inside a tp manual region — the
            # Megatron composition of vocab_parallel_cross_entropy with
            # the logits never materialized.
            interp = jax.default_backend() != "tpu"
            fn = _build_tp_fused_ce(
                state.mesh, V, bn, bv, interp, float(label_smoothing)
            )
            with jax.named_scope(LOSS_SCOPE):
                per = fn(x, embedding_table, t_safe)
    else:
        if want and not can and state.initialized \
                and getattr(state.cfg, "fused_ce", "auto") is True:
            import os

            if tp > 1 and V % tp != 0:
                why = f"vocab {V} not divisible by tp {tp}"
            elif os.environ.get("SMP_DISABLE_FUSED_CE", "0") == "1":
                why = "SMP_DISABLE_FUSED_CE=1 is set"
            elif jax.default_backend() != "tpu":
                why = "not running on a TPU backend"
            elif (block_n, block_v) != (None, None) \
                    and pc.auto_blocks(D) is not None:
                why = ("explicit block_n=%s/block_v=%s does not fit VMEM "
                       "for D=%d (auto-selected blocks would — drop the "
                       "override)" % (block_n, block_v, D))
            else:
                why = "no block configuration fits VMEM for D=%d" % D
            get_logger().warning(
                "fused_ce: True requested but the kernel cannot run here "
                "(%s) — materializing [%d, %d] logits instead.",
                why, x.shape[0], embedding_table.shape[0],
            )
        with jax.named_scope("smp/head/logits"):
            logits = x @ embedding_table.T.astype(x.dtype)
        per = vocab_parallel_cross_entropy(
            logits, t_safe, label_smoothing=label_smoothing
        )
    with jax.named_scope(LOSS_SCOPE):
        per = jnp.where(valid, per, 0.0)
    return per.reshape(lead)


class DistributedCrossEntropy(nn.Module):
    """Module wrapper matching the reference class surface
    (``torch/nn/cross_entropy.py:28``); reduction over all tokens."""

    reduction: str = "mean"
    label_smoothing: float = 0.0

    def __call__(self, logits, targets):
        loss = vocab_parallel_cross_entropy(logits, targets, self.label_smoothing)
        if self.reduction == "mean":
            return jnp.mean(loss)
        if self.reduction == "sum":
            return jnp.sum(loss)
        return loss
