"""smp.nn manifold-constrained hyper-connections: a residual path of ``n``
streams, mixed round every sub-layer (mHC, arXiv:2512.24880, on
Hyper-Connections, arXiv:2409.19606).

A token carries ``X`` [n, D] where a plain residual path carries [D]. Round
a sub-layer ``F`` (attention, or the feed-forward) with its own
coefficients:

    z      = rms_phi(vec(X))                 one scale vector over n D
    H_pre  = sigmoid(a_pre  (z Phi_pre)  + b_pre)             [n]
    H_post = 2 sigmoid(a_post (z Phi_post) + b_post)          [n]
    H_res  = SK(clip(a_res mat(z Phi_res) + b_res, lo, hi))   [n, n]
    u      = sum_i H_pre[i] X[i]             the sub-layer's input
    y      = F(norm(u))
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

``SK(A)``: ``M = exp(A)``, then ``sinkhorn_iters`` times each column
divided by its sum + ``eps``, then each row by its sum + ``eps``: ``H_res``
is doubly stochastic to the iteration's precision, so the streams' mix
neither grows nor shrinks them (rows are divided last and sum to 1; the
columns follow as the rounds converge: to 1e-6 in 20 on logits a few units
wide, to 1e-3 where a diagonal of 6 makes the matrix almost a
permutation, as at this module's start). The stack copies a token's embedding to
the ``n`` streams and sums them before the last norm
(``DistributedTransformer._stack``). With one stream there is no module:
``DistributedTransformerLayer._block`` writes ``x + F(norm(x))`` as before.

The coefficients are the token's own, so everything here works position by
position and shards as the hidden states do (batch over the data axes, the
sequence over cp or the sequence-parallel axis; the streams and the width
whole); the parameters are replicated, as a norm's are. The streams pass
through memory in the compute dtype; the n x n algebra, the norm's
statistic and the coefficients are float32, laid out with the positions
minor ([.., B, T]: sixteen numbers a token would fill an eighth of a lane
tile the other way round). ``z Phi`` is computed as ``r (X (w Phi))``:
the norm's scale folded into the matrix, its per-token factor ``r`` taken
out of the product, so the streams are read as they are stored.

The parts trace under ``smp/mhc/{coeff,sinkhorn,pre,post_res}``, forward,
recomputed and transposed. ``smp_mhc_bytes{pass}`` is the least one
sub-layer's coefficient read and two mixes must move (``mhc_bytes``).
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.nn.utils import shard_activation


def mhc_bytes(tokens, streams, hidden, itemsize):
    """``{"fwd", "bwd"}``: bytes one hyper-connected sub-layer must move
    over ``tokens`` positions beside the sub-layer itself, in [tokens,
    hidden] tensors of ``itemsize`` bytes, whatever implements it. A
    sub-layer runs between the two mixes, so the streams cannot stay on
    the chip across it. Forward: the streams read once for the
    coefficients and the pre mix (n) and its output written (1); the
    streams read again with the sub-layer's output (n + 1) and the new
    streams written (n): 3 n + 2. Backward: the new streams' gradient read
    and the sub-layer's output's written (n + 1); after the sub-layer's
    own backward its input's gradient, the streams, the new streams'
    gradient and the sub-layer's output read (1 + n + n + 1: the
    coefficients' gradients need all four) and the streams' gradient
    written (n): 4 n + 3. The coefficients themselves are n (n + 2)
    float32 numbers a token and not counted."""
    one = tokens * hidden * itemsize
    return {"fwd": (3 * streams + 2) * one, "bwd": (4 * streams + 3) * one}


def sinkhorn(logits, iters, eps):
    """``SK`` over the two leading axes of ``logits`` [n, n, ...]: rows on
    axis 0, columns on axis 1."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)   # each column
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # each row
    return m


def _hidden():
    """The hidden states' sharding; [B, T, n, D] streams take it too (the
    streams and the width whole)."""
    from smdistributed_modelparallel_tpu.nn.transformer import (
        _cfg,
        _hidden_spec,
        _seq_parallel,
    )

    return _hidden_spec(_seq_parallel(_cfg("optimize", "speed") == "memory"))


def expand_streams(hidden, streams):
    """[B, T, D] -> [B, T, n, D]: a token's embedding copied to every
    stream."""
    B, T, D = hidden.shape
    out = jnp.broadcast_to(hidden[:, :, None, :], (B, T, streams, D))
    return shard_activation(out, *_hidden())


def collapse_streams(x):
    """[B, T, n, D] -> [B, T, D]: the streams summed (in float32)."""
    return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


# The two mixes with their transposes written out: what a mix keeps for
# the backward pass is its operands as they are stored (the streams and
# the sub-layer's output in the compute dtype, the coefficients), and every
# float32 value lives inside one fused pass over them. Differentiated as
# written, each float32 widening of the streams is a residual of its own.

def _f32(x, i):
    return x[:, :, i].astype(jnp.float32)


def _over_width(a, b):
    return jnp.sum(a * b, axis=-1)


@jax.custom_vjp
def pre_mix(x, h_pre):
    """``u = sum_i H_pre[i] X[i]`` on x [B, T, n, D], h_pre [n, B, T]."""
    return sum(h_pre[i][..., None] * _f32(x, i)
               for i in range(x.shape[2])).astype(x.dtype)


def _pre_fwd(x, h_pre):
    return pre_mix(x, h_pre), (x, h_pre)


def _pre_bwd(res, du):
    x, h_pre = res
    n, du = x.shape[2], du.astype(jnp.float32)
    dx = jnp.stack([h_pre[i][..., None] * du for i in range(n)], axis=2)
    dh = jnp.stack([_over_width(du, _f32(x, i)) for i in range(n)])
    return dx.astype(x.dtype), dh


pre_mix.defvjp(_pre_fwd, _pre_bwd)


@jax.custom_vjp
def post_res_mix(x, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` on x [B, T, n, D],
    y [B, T, D], h_post [n, B, T], h_res [n, n, B, T]."""
    n, y32 = x.shape[2], y.astype(jnp.float32)
    return jnp.stack([
        sum(h_res[i, j][..., None] * _f32(x, j) for j in range(n))
        + h_post[i][..., None] * y32
        for i in range(n)], axis=2).astype(x.dtype)


def _post_res_fwd(x, y, h_post, h_res):
    return post_res_mix(x, y, h_post, h_res), (x, y, h_post, h_res)


def _post_res_bwd(res, g):
    x, y, h_post, h_res = res
    n, y32 = x.shape[2], y.astype(jnp.float32)
    gs = [_f32(g, i) for i in range(n)]
    dx = jnp.stack([
        sum(h_res[i, j][..., None] * gs[i] for i in range(n))
        for j in range(n)], axis=2)
    dy = sum(h_post[i][..., None] * gs[i] for i in range(n))
    d_post = jnp.stack([_over_width(gs[i], y32) for i in range(n)])
    d_res = jnp.stack([
        jnp.stack([_over_width(gs[i], _f32(x, j)) for j in range(n)])
        for i in range(n)])
    # One barrier round the four: the compiler would otherwise make ``dy``
    # (a cheap pass over the streams' gradient) again late in a layer's
    # backward pass, for the one reader it schedules last (the sub-layer's
    # last weight gradient), from the scan's carried gradient, which by
    # then holds this layer's own result (read on the chip, PR 44: that
    # one leaf's gradient 11% long, every other within 0.1%).
    return jax.lax.optimization_barrier(
        (dx.astype(x.dtype), dy.astype(y.dtype), d_post, d_res))


post_res_mix.defvjp(_post_res_fwd, _post_res_bwd)


def post_res(x, y, h_post, h_res):
    """The sub-layer's output spread over the mixed streams
    (``post_res_mix``), under its scope and sharded as the streams are."""
    with jax.named_scope("smp/mhc/post_res"):
        return shard_activation(
            post_res_mix(x, y, h_post, h_res), *_hidden())


class DistributedHyperConnection(nn.Module):
    """The coefficients of one sub-layer's connection and its pre mix:
    ``(u [B, T, D], h_post [n, B, T], h_res [n, n, B, T])`` of streams
    [B, T, n, D]; ``post_res`` takes the other two once the sub-layer has
    run."""

    streams: int
    hidden_size: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)
    norm_epsilon: float = 1e-6
    initializer_range: float = 0.02
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        from smdistributed_modelparallel_tpu.nn.transformer import _init
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_mhc_bytes,
        )

        n, D = self.streams, self.hidden_size
        B, T = x.shape[:2]
        dtype = self.dtype or x.dtype
        scale = self.param("norm/scale", nn.initializers.ones, (n, D), dtype)
        phi = self.param(
            "phi", _init(self.initializer_range), (n, D, n * (n + 2)), dtype)
        alpha = self.param(
            "alpha", nn.initializers.constant(0.01), (3,), jnp.float32)
        bias = self.param("bias", _bias_init(n), (n * (n + 2),), jnp.float32)
        record_mhc_bytes(mhc_bytes(B * T, n, D, x.dtype.itemsize))

        with jax.named_scope("smp/mhc/coeff"):
            mean_sq = jnp.mean(
                jnp.square(x.astype(jnp.float32)), axis=(2, 3))    # [B, T]
            r = jax.lax.rsqrt(mean_sq + self.norm_epsilon)
            folded = (scale.astype(jnp.float32)[..., None]
                      * phi.astype(jnp.float32)).astype(x.dtype)
            logits = r[None] * jnp.einsum(
                "btnd,ndc->cbt", x, folded,
                preferred_element_type=jnp.float32)                # [C, B, T]
            alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)
            at = lambda b: b[:, None, None]                    # noqa: E731
            h_pre = jax.nn.sigmoid(alpha[0] * logits[:n] + at(bias[:n]))
            h_post = 2.0 * jax.nn.sigmoid(
                alpha[1] * logits[n:2 * n] + at(bias[n:2 * n]))
            res = jnp.clip(
                alpha[2] * logits[2 * n:] + at(bias[2 * n:]), *self.clamp
            ).reshape(n, n, B, T)
        with jax.named_scope("smp/mhc/sinkhorn"):
            h_res = sinkhorn(res, self.sinkhorn_iters, self.eps)
        with jax.named_scope("smp/mhc/pre"):
            u = shard_activation(pre_mix(x, h_pre), *_hidden())
        return u, h_post, h_res


def _bias_init(n):
    """``b_pre`` so that ``H_pre`` starts at 1 / n a stream (the
    sub-layer reads the streams' mean), ``b_post`` 0 (``H_post`` 1: every
    stream takes the output whole), ``b_res`` 6 on the diagonal (``H_res``
    starts within a hundredth of the identity)."""
    def init(key, shape, dtype=jnp.float32):
        import numpy as np

        pre = np.full((n,), -np.log(max(n - 1, 1)), np.float32)
        return jnp.asarray(np.concatenate(
            [pre, np.zeros((n,), np.float32),
             (6.0 * np.eye(n, dtype=np.float32)).reshape(-1)]), dtype)

    return init
