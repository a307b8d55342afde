"""The plain training step: next-token cross-entropy, its gradients row by
row, and AdamW written out. Float32 throughout (``precision`` switches the
matrix products only, for the control)."""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import decoder

# optax.adamw's defaults, which the drivers leave alone.
B1, B2, EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


def row_loss_sum(cfg, w, row, precision):
    """Sum of next-token losses of one sequence [T] (T-1 predictions)."""
    logits = decoder.forward(cfg, w, row[None], precision, remat=True)[0]
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, row[1:, None], axis=-1))


def loss_and_grads(cfg, w, ids, precision):
    """Mean loss over every predicted position of ``ids`` [B, T] and its
    gradient, accumulated one row at a time (so that float32 at the timed
    size fits beside nothing else)."""
    count = ids.shape[0] * (ids.shape[1] - 1)

    def body(acc, row):
        loss, g = jax.value_and_grad(
            lambda w: row_loss_sum(cfg, w, row, precision))(w)
        return jax.tree_util.tree_map(jnp.add, acc, g), loss

    zero = jax.tree_util.tree_map(jnp.zeros_like, w)
    grads, losses = jax.lax.scan(body, zero, ids)
    grads = jax.tree_util.tree_map(lambda g: g / count, grads)
    return jnp.sum(losses) / count, grads


def adamw(w, grads, mu, nu, step, lr):
    """One AdamW update; ``step`` counts from 1."""
    mu = jax.tree_util.tree_map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
    c1, c2 = 1 - B1 ** step, 1 - B2 ** step

    def leaf(p, m, v):
        update = (m / c1) / (jnp.sqrt(v / c2) + EPS) + WEIGHT_DECAY * p
        return p - lr * update

    return jax.tree_util.tree_map(leaf, w, mu, nu), mu, nu


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def follow_steps(cfg_items, w, batches, lr, precision, steps):
    """``steps`` plain steps from ``w`` over ``batches`` [steps, B, T].
    Returns what ``check.py`` compares: each step's loss, the per-leaf norm
    of the first gradient, and the per-leaf norm of the parameters' change
    after the last step."""
    cfg = dict(cfg_items)
    start = w
    mu = jax.tree_util.tree_map(jnp.zeros_like, w)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad = [], None
    for i in range(steps):
        loss, grads = loss_and_grads(cfg, w, batches[i], precision)
        if i == 0:
            first_grad = leaf_norms(grads)
        w, mu, nu = adamw(w, grads, mu, nu, i + 1, lr)
        losses.append(loss)
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, w, start))
    return jnp.stack(losses), first_grad, change


def hashable(cfg):
    """The numbers and strings of a configuration, as ``jit`` static data."""
    return tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool)) or v is None))
