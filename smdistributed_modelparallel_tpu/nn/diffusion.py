"""Training by block diffusion: what a step needs around the model.

A sequence x0 of L tokens is cut into blocks of B. Block k draws one rate
p_k, and each of its tokens is replaced by the mask id with probability
p_k: xt. The model runs the 2L positions [xt ; x0] under the
block-diffusion mask (a stack whose layers have ``block_diffusion=B``:
``ops.attention.block_diffusion_mask``), so the noisy copy of block k is
predicted from the clean blocks before it and from its own noisy tokens.
The loss reads the noisy half alone, with no shift: a masked position
predicts its own token, weighted by the inverse of its block's rate,

    loss = 1 / (batch L)  sum_{i < L, xt_i = mask}  (1 / p_block(i))
                          (logsumexp(z_i) - z_i[x0_i]).

The noise is data. It comes with the batch (x0, xt and the blocks' rates,
drawn outside the step), so a step is a function of its inputs, two runs
see the same draw, and a reference can be given the same one.

Inside an ``@smp.step`` function::

    logits = model(two_copy_stream(x0, xt))      # head_positions=0.5
    loss, counts = masked_diffusion_loss(logits, x0, xt, rates, mask_id)
    model.backward(loss)
    return loss, counts

and, outside any timed path, ``record_diffusion_stats(counts)``.
"""

import jax
import jax.numpy as jnp
import numpy as np


def two_copy_stream(clean_ids, noisy_ids):
    """[B, 2L] ids: the noisy copy, then the clean one."""
    return jnp.concatenate([noisy_ids, clean_ids], axis=1)


def masked_diffusion_loss(logits, clean_ids, noisy_ids, rates, mask_id):
    """The loss above from the noisy half's ``logits`` [B, L, V] (any
    float dtype; the sums are float32), and its counters ``{"loss_tokens":
    masked positions, "data_tokens": B L}`` as int32 scalars. Traced under
    the scope ``smp/head/loss``."""
    with jax.named_scope("smp/head/loss"):
        return _masked_diffusion_loss(
            logits, clean_ids, noisy_ids, rates, mask_id)


def _masked_diffusion_loss(logits, clean_ids, noisy_ids, rates, mask_id):
    B, L = clean_ids.shape
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, clean_ids[..., None], axis=-1)[..., 0]
    masked = noisy_ids == mask_id
    weight = jnp.where(
        masked, 1.0 / jnp.repeat(rates, L // rates.shape[-1], axis=-1), 0.0)
    loss = jnp.sum(weight * (lse - picked)) / (B * L)
    return loss, {
        "loss_tokens": jnp.sum(masked).astype(jnp.int32),
        "data_tokens": jnp.asarray(B * L, jnp.int32),
    }


def record_diffusion_stats(stats):
    """Read a step's counters back (a host transfer: call it outside a
    timed path) into ``smp_diffusion_loss_tokens`` (positions that carried
    loss, all microbatches of the steps given) and
    ``smp_diffusion_data_tokens`` (data tokens of those steps; the stream
    the stack ran is twice that). ``stats``: what the step function
    returned from ``masked_diffusion_loss`` (arrays, stacked over
    microbatches or steps, or the ``StepOutput`` holding them). Returns
    ``{"loss_tokens", "data_tokens"}``."""
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    if hasattr(stats, "stack"):
        stats = stats.stack()
    out = {k: int(np.asarray(stats[k]).sum())
           for k in ("loss_tokens", "data_tokens")}
    telemetry.gauge(
        "smp_diffusion_loss_tokens",
        "positions of the last recorded steps whose token was masked and "
        "so carried loss",
    ).set(out["loss_tokens"])
    telemetry.gauge(
        "smp_diffusion_data_tokens",
        "data tokens of the last recorded steps (the two-copy stream is "
        "twice as long)",
    ).set(out["data_tokens"])
    return out
