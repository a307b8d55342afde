"""smp.nn gated short convolution: a sequence mixer that is not attention.

One input projection to three streams of the hidden width, ``B``, ``C`` and
``u``; a first gate ``v = B * u``; a causal depthwise convolution over the
last ``kernel_size`` positions of each channel, zeros before the sequence's
start (``c[t] = sum_j k[j] * v[t - (K - 1 - j)]``: cross-correlation, what
``Conv1d(groups=D, padding=K - 1)[..., :T]`` computes); a second gate
``C * c``; an output projection. No bias, no state beyond the window: in
training the window is read from the sequence itself (LFM2's ``conv``
layers, ``conv_L_cache`` 3).

Tensor parallelism as ``DistributedTransformerOutputLayer``'s: the input
projection is split by channel within each of the three streams
(``in_proj/kernel`` [D, 3, D], tp on the last dim) and the taps with it
(``conv/kernel`` [K, D]), so the gates and the convolution run on a chip's
own channels with no exchange; the output projection is split on its input
(``out_proj/kernel`` [D, D], tp on the first dim) and GSPMD sums its
partial products. The sequence axis is shifted with ``jnp.pad``, so under
context parallelism the compiler brings each shard its ``K - 1``
predecessors.

The three parts trace under ``smp/conv/{in_proj,core,out_proj}``, forward,
recomputed and transposed. ``smp_conv_core_bytes{pass}`` is the least the
gate-conv-gate stage of one call must move: forward reads the three
streams and writes the gated output, backward reads those and the output's
gradient and writes the three streams' gradients (the convolution's own
output is a matter of ``K`` shifted reads, not a tensor that has to pass
through memory).
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
from smdistributed_modelparallel_tpu.nn.utils import (
    partitioned,
    shard_activation,
)

BATCH_AXES = (RDP_AXIS, EP_AXIS)


def conv_core_bytes(tokens, channels, itemsize):
    """``{"fwd", "bwd"}``: bytes one call of the gate-conv-gate stage must
    move over ``tokens`` positions of ``channels`` channels: four
    [tokens, channels] tensors forward (B, C, u in, the gated output out),
    seven backward (B, C, u and the output's gradient in, three gradients
    out); the taps are ``kernel_size x channels`` and not counted."""
    one = tokens * channels * itemsize
    return {"fwd": 4 * one, "bwd": 7 * one}


def causal_depthwise_conv(v, taps):
    """``c[:, t] = sum_j taps[j] * v[:, t - (K - 1 - j)]`` over v [B, T, C]
    with zeros before position 0; ``taps`` [K, C], in whose dtype the sum
    is made (``v`` is shifted as it is stored and widened after)."""
    K, T = taps.shape[0], v.shape[1]
    out = taps[K - 1] * v.astype(taps.dtype)
    for back in range(1, K):
        shifted = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :T]
        out = out + taps[K - 1 - back] * shifted.astype(taps.dtype)
    return out


class DistributedShortConv(nn.Module):
    """The gated short convolution on hidden [B, T, D]."""

    hidden_size: int
    kernel_size: int = 3
    initializer_range: float = 0.02
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, hidden):
        from smdistributed_modelparallel_tpu.nn.transformer import (
            _cfg,
            _hidden_spec,
            _init,
            _seq_parallel,
        )
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_conv_core_bytes,
        )

        D, K = self.hidden_size, self.kernel_size
        dtype = self.dtype or hidden.dtype
        init = _init(self.initializer_range)
        in_kernel = self.param(
            "in_proj/kernel", partitioned(init, (None, None, TP_AXIS)),
            (D, 3, D), dtype)
        taps = self.param(
            "conv/kernel", partitioned(init, (None, TP_AXIS)), (K, D), dtype)
        out_kernel = self.param(
            "out_proj/kernel", partitioned(init, (TP_AXIS, None)), (D, D),
            dtype)
        record_conv_core_bytes(conv_core_bytes(
            hidden.shape[0] * hidden.shape[1], D, hidden.dtype.itemsize))

        with jax.named_scope("smp/conv/in_proj"):
            streams = jnp.einsum(
                "btd,dsc->btsc", hidden, in_kernel.astype(hidden.dtype))
            streams = shard_activation(
                streams, BATCH_AXES, CP_AXIS, None, TP_AXIS)
        with jax.named_scope("smp/conv/core"):
            # Gates and taps in float32 on the chip's registers; what
            # passes through memory keeps the stream's dtype, the first
            # gate's product among it (the compiler keeps it whole for the
            # taps' shifted reads, and for the backward pass its gradient).
            b, c, u = (streams[:, :, s].astype(jnp.float32) for s in range(3))
            v = (b * u).astype(hidden.dtype)
            gated = c * causal_depthwise_conv(v, taps.astype(jnp.float32))
            gated = shard_activation(
                gated.astype(hidden.dtype), BATCH_AXES, CP_AXIS, TP_AXIS)
        with jax.named_scope("smp/conv/out_proj"):
            out = gated @ out_kernel.astype(gated.dtype)
            memory_opt = _cfg("optimize", "speed") == "memory"
            return shard_activation(
                out, *_hidden_spec(_seq_parallel(memory_opt)))
