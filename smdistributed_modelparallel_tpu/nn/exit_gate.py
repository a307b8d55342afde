"""Training a looped model with an exit gate: what a step needs after the
model.

A looped model (``DistributedTransformerLMHead(loop_steps=n)``) runs its
stack n times over its own output and, after every pass t, gives each
position a next-token loss l_t and a gate logit z_t (one linear layer on
the pass's normed state, the same for every pass). With
lambda_t = sigmoid(z_t), the probability that a position exits after pass
t is

    p_1 = lambda_1,   p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < n),
    p_n = prod_{j<n} (1 - lambda_j),

which sums to 1 (the last pass takes what is left; its own gate logit is
not read). The loss is the expected loss under p less an entropy bonus
that keeps p from collapsing onto one pass ("Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741, stage I):

    loss = mean_positions [ sum_t p_t l_t  -  beta H(p) ],
    H(p) = - sum_t p_t ln p_t.

Gradients flow through p and through every l_t.

Inside an ``@smp.step`` function::

    targets = next_token_targets(ids)            # the last position: -100
    losses, gates = model(ids, targets=targets)  # [n, B, T] float32 each
    loss, stats = exit_gated_loss(losses, gates, beta, targets != -100)
    model.backward(loss)
    return loss, stats

and, outside any timed path, ``record_exit_stats(stats)``.
"""

import jax
import jax.numpy as jnp
import numpy as np

IGNORED = -100


def next_token_targets(ids):
    """[B, T] targets of next-token prediction: position i predicts id
    i + 1, the last position nothing (``IGNORED``)."""
    last = jnp.full_like(ids[:, :1], IGNORED)
    return jnp.concatenate([ids[:, 1:], last], axis=1)


def exit_log_distribution(gate_logits):
    """ln p [n, ...] of the exit distribution above from the gate logits
    [n, ...] of the n passes, in float32 and through ``log_sigmoid`` (a
    product of n - 1 small numbers is a sum here)."""
    z = gate_logits.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-z[:-1])                 # ln (1 - lambda_j)
    before = jnp.concatenate(
        [jnp.zeros_like(z[:1]), jnp.cumsum(stay, axis=0)], axis=0)
    leave = jnp.concatenate(
        [jax.nn.log_sigmoid(z[:-1]), jnp.zeros_like(z[:1])], axis=0)
    return before + leave


def exit_gated_loss(losses, gate_logits, entropy_weight, valid=None):
    """The loss above from the passes' per-position ``losses`` and
    ``gate_logits`` (both [n, B, T], any float dtype; everything here is
    float32), averaged over the positions ``valid`` [B, T] marks (all, if
    None). Returns ``(loss, stats)``; ``stats`` holds float32 means over
    those positions: ``exit_share`` [n] (p_t by pass), ``entropy`` (H) and
    ``pass_loss`` [n] (l_t by pass). Traced under ``smp/head/exit_gate``."""
    with jax.named_scope("smp/head/exit_gate"):
        return _exit_gated_loss(losses, gate_logits, entropy_weight, valid)


def _exit_gated_loss(losses, gate_logits, entropy_weight, valid):
    losses = losses.astype(jnp.float32)
    log_p = exit_log_distribution(gate_logits)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    weight = (jnp.ones(losses.shape[1:], jnp.float32) if valid is None
              else valid.astype(jnp.float32))
    count = jnp.maximum(jnp.sum(weight), 1.0)
    mean = lambda x: jnp.sum(x * weight, axis=(-2, -1)) / count  # noqa: E731
    expected = jnp.sum(p * losses, axis=0)
    loss = mean(expected - entropy_weight * entropy)
    return loss, {"exit_share": mean(p), "entropy": mean(entropy),
                  "pass_loss": mean(losses)}


def record_exit_stats(stats):
    """Read a step's counters back (a host transfer: call it outside a
    timed path) into ``smp_exit_share{pass}``, ``smp_exit_entropy`` and
    ``smp_exit_pass_loss{pass}``: the means of the steps and microbatches
    given. ``stats``: what the step function returned from
    ``exit_gated_loss`` (arrays, stacked over microbatches or steps, or
    the ``StepOutput`` holding them). Returns them as lists and a float."""
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    if hasattr(stats, "stack"):
        stats = stats.stack()
    by_pass = lambda k: np.asarray(stats[k], np.float64).reshape(  # noqa: E731
        -1, np.shape(stats[k])[-1]).mean(axis=0)
    out = {"exit_share": by_pass("exit_share").tolist(),
           "entropy": float(np.mean(np.asarray(stats["entropy"]))),
           "pass_loss": by_pass("pass_loss").tolist()}
    share = telemetry.gauge(
        "smp_exit_share",
        "mean probability that a position exits after a pass, of the last "
        "recorded steps",
    )
    loss = telemetry.gauge(
        "smp_exit_pass_loss",
        "mean next-token loss after a pass, of the last recorded steps",
    )
    for t, (s, l) in enumerate(zip(out["exit_share"], out["pass_loss"])):
        share.labels(**{"pass": str(t + 1)}).set(s)
        loss.labels(**{"pass": str(t + 1)}).set(l)
    telemetry.gauge(
        "smp_exit_entropy",
        "mean entropy of the exit distribution, of the last recorded steps",
    ).set(out["entropy"])
    return out
