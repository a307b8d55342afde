"""The plain Ouro looped decoder and its training step, against Hugging
Face names (``benchmark/ouro_weights.py``: per-layer tensors stacked).
Float32 at ``Precision.HIGHEST`` (``decoder.product``; ``precision``
switches every matrix product's operands, for the control), a Python loop
over passes, no kernel, no fused head, nothing of the program under test.

``rms_w(x) = w x / sqrt(mean(x^2) + rms_norm_eps)``. One layer:

    a = x + rms_2(Attn(rms_1(x)))          Attn: q, k, v = u Wq, u Wk, u Wv,
    y = a + rms_4(MLP(rms_3(a)))           16 heads of 128, rotary on the
                                           whole head in halves (theta
    MLP(u) = (silu(u Wg) * u Wu) Wd        rope_theta), causal softmax of
                                           q . k / sqrt(128), then Wo

    h_0 = E[ids];  h_t = rms_f(M(h_{t-1})),  t = 1 .. total_ut_steps,
    M the layers in order with the same weights in every pass;
    logits_t = h_t W_head,  l_t(n) = CE(logits_t[n], ids[n + 1]),
    lambda_t(n) = sigmoid(w_g . h_t[n] + b_g);
    p_1 = lambda_1, p_t = lambda_t prod_{j<t} (1 - lambda_j) (t < last),
    p_last = prod_{j<last} (1 - lambda_j);
    loss = mean_n [ sum_t p_t(n) l_t(n) - beta H(p(n)) ],
    H(p) = - sum_t p_t ln p_t,   beta = exit_entropy_weight.

Departures from ISSUE 49's description of this file (a Python loop over
passes and layers, no scan, no remat), each forced by the size it runs at
and found by compiling it for the described chip: the layers of a pass are
a ``lax.scan`` over the stacked tensors with a ``jax.checkpoint`` body, as
in every other family's reference (written out as 20 layer calls at 5 layers,
the compiler kept 15.1 GB of temporaries for one 8,192-token row beside 3.7 GB
of weights and gradient, and 7.5 GB as a scan: float32 activations of that many
layer passes do not fit either way without the checkpoint); the MLP, the
attention (a block of queries at a time) and the head with its loss (a
block of positions at a time) run in blocks that are made again in the
backward pass (``reference/laguna.in_blocks``, as ``reference/xing4.py``
does). The pieces that are any such decoder's are ``reference/laguna.py``'s
own (RMSNorm, rotary in halves, causal attention in blocks, the gated MLP,
AdamW on buffers it may reuse).
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmark import ouro_weights
from benchmark.reference import laguna as shared
from benchmark.reference import train
from benchmark.reference.decoder import product

TOKEN_BLOCK = 1024
LAYER = ouro_weights.LAYER


def attention(cfg, u, lw, precision):
    B, T, _ = u.shape
    hd = cfg["head_dim"]
    a = "self_attn."
    heads = lambda name: product(                           # noqa: E731
        "btd,ed->bte", u, lw[a + name], precision).reshape(B, T, -1, hd)
    cos, sin = shared.rotary_tables(
        T, hd, {"rope_theta": float(cfg["rope_theta"])})
    q = shared.rotate(heads("q_proj.weight"), cos, sin)
    k = shared.rotate(heads("k_proj.weight"), cos, sin)
    out = shared.banded_attention(
        q, k, heads("v_proj.weight"), None, precision)
    return product("bte,de->btd", out.reshape(B, T, -1),
                   lw[a + "o_proj.weight"], precision)


def layer(cfg, x, lw, precision):
    """One layer on x [B, T, D] with its tensors ``lw`` (names without the
    ``model.layers.`` prefix)."""
    eps = cfg["rms_norm_eps"]
    rms = lambda y, name: shared.rms_norm(                  # noqa: E731
        y, lw[name + ".weight"], eps)
    a = x + rms(attention(cfg, rms(x, "input_layernorm"), lw, precision),
                "input_layernorm_2")
    mlp, = shared.in_blocks(
        lambda _, u: (shared.gated_mlp(
            u, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
            lw["mlp.down_proj.weight"], precision),),
        TOKEN_BLOCK, rms(a, "post_attention_layernorm"))
    return a + rms(mlp, "post_attention_layernorm_2")


def pass_states(cfg, w, ids, precision="float32", remat=False):
    """The normed state after every pass, a list of [B, T, D]."""
    stacked = {k[len(LAYER):]: v for k, v in w.items()
               if k.startswith(LAYER)}

    def body(x, lw):
        return layer(cfg, x, lw, precision), None

    h, states = w["model.embed_tokens.weight"][ids], []
    for _ in range(int(cfg["total_ut_steps"])):
        h, _ = jax.lax.scan(
            jax.checkpoint(body) if remat else body, h, stacked)
        h = shared.rms_norm(h, w["model.norm.weight"], cfg["rms_norm_eps"])
        states.append(h)
    return states


def gate_logits(w, h, precision):
    return product("btd,od->bto", h, w["model.early_exit_gate.weight"],
                   precision)[..., 0] + w["model.early_exit_gate.bias"][0]


def forward(cfg, w, ids, precision="float32", remat=False):
    """``(logits [passes, B, T, V], gate logits [passes, B, T])``."""
    states = pass_states(cfg, w, ids, precision, remat)
    return (jnp.stack([product("btd,vd->btv", h, w["lm_head.weight"],
                               precision) for h in states]),
            jnp.stack([gate_logits(w, h, precision) for h in states]))


def exit_distribution(gates):
    """p [passes, ...] from the gate logits, written as the products of
    the definition."""
    lam = jax.nn.sigmoid(gates)
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)       # prod_{j<=t} (1 - l_j)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay], axis=0)
    return before * jnp.concatenate(
        [lam[:-1], jnp.ones_like(lam[:1])], axis=0)


def loss_parts(cfg, w, ids, precision):
    """Over the T - 1 predictions of each row of ``ids`` [B, T], as sums:
    ``(sum_n [sum_t p_t l_t - beta H], sum_n l_t [passes], sum_n p_t
    [passes], sum_n H)``; head, log-softmax and gate a block of positions
    at a time."""
    states = jnp.stack(pass_states(cfg, w, ids, precision, remat=True), 2)
    targets = jnp.roll(ids, -1, axis=1)
    counted = jnp.broadcast_to(
        jnp.arange(ids.shape[1])[None, :] < ids.shape[1] - 1, ids.shape)
    beta = cfg["exit_entropy_weight"]

    def block(_, h, targets, counted):
        h = jnp.moveaxis(h, 2, 0)                        # [passes, B, t, D]
        logp = jax.nn.log_softmax(product(
            "pbtd,vd->pbtv", h, w["lm_head.weight"], precision), axis=-1)
        nll = -jnp.take_along_axis(
            logp, jnp.broadcast_to(targets, h.shape[:3])[..., None],
            axis=-1)[..., 0]
        p = exit_distribution(
            jnp.stack([gate_logits(w, s, precision) for s in h]))
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        total = lambda x: jnp.sum(                           # noqa: E731
            jnp.where(counted, x, 0.0), axis=(-2, -1))
        return (h[0, :, :, :0],
                total(jnp.sum(p * nll, axis=0) - beta * entropy),
                total(nll), total(p), total(entropy))

    _, objective, nll, share, entropy = shared.in_blocks(
        block, TOKEN_BLOCK, states, targets, counted)
    return objective, nll, share, entropy


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def row_gradient(scalars, groups, w, row, precision, count):
    """One sequence's part of the mean loss over ``count`` predictions, its
    gradient, and its parts of the passes' mean losses, the mean exit
    shares and the mean entropy."""
    cfg = shared.unhashable(scalars, groups)

    def part_of_mean(w):
        objective, *stats = loss_parts(cfg, w, row[None], precision)
        return objective / count, [s / count for s in stats]

    (loss, stats), grad = jax.value_and_grad(part_of_mean, has_aux=True)(w)
    return loss, grad, stats


def loss_and_grads(cfg, w, ids, precision):
    """Mean loss over every predicted position of ``ids`` [B, T], its
    gradient summed one row at a time (one compiled program a row: beside
    the float32 training state only one row's gradient and one sum are
    ever alive), and ``[passes' losses, exit shares, entropy]``."""
    count = ids.shape[0] * (ids.shape[1] - 1)
    static = hashable(cfg)
    loss = grads = stats = None
    for row in ids:
        part, grad, stat = row_gradient(*static, w, row, precision, count)
        loss = part if loss is None else loss + part
        stats = stat if stats is None else [a + b for a, b in
                                            zip(stats, stat)]
        grads = grad if grads is None else shared.add_into(grads, grad)
        del grad
        # The host must not run ahead: a row's program is given its
        # buffers when it is enqueued, and two rows' would not fit.
        jax.block_until_ready(grads)
    return loss, grads, stats


@functools.partial(jax.jit, static_argnums=(0, 1))
def change_norms(scalars, groups, w, seed):
    """Per-leaf norm of ``w`` minus the seeded leaf made again from
    ``seed``: no second copy of the start is ever kept."""
    spec = ouro_weights.spec_for(shared.unhashable(scalars, groups))
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        v - ouro_weights.make_leaf(seed, k, *spec[k])))) for k, v in w.items()}


def follow_steps(scalars, groups, w, batches, seed, lr, precision, steps):
    """``steps`` plain steps from ``w`` (given up) over ``batches`` [steps,
    B, T]: each step's loss, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change, and each step's ``[passes'
    losses, exit shares, entropy]``. AdamW's two moments wait on the host
    while a step's rows are summed, as in ``reference/xing4.py``."""
    cfg = shared.unhashable(scalars, groups)
    mu = nu = None
    losses, first_grad, stats = [], None, []
    for i in range(steps):
        loss, grads, stat = loss_and_grads(cfg, w, batches[i], precision)
        if mu is None:
            mu = jax.tree_util.tree_map(jnp.zeros_like, w)
            nu = jax.tree_util.tree_map(jnp.zeros_like, w)
        else:
            mu, nu = jax.device_put((mu, nu), jax.devices()[0])
        w, mu, nu, norms = shared.apply_adamw(
            w, mu, nu, grads, jnp.float32(i + 1), lr)
        del grads       # or the next step's rows would find no room
        if i + 1 < steps:
            mu, nu = jax.device_get((mu, nu))
        if i == 0:
            first_grad = norms
        losses.append(loss)
        stats.append(stat)
    return (jnp.stack(losses), first_grad,
            change_norms(scalars, groups, w, seed), stats)


def hashable(cfg):
    """``(scalars, groups)`` of a configuration as ``jit`` static data:
    its numbers and strings, and the depth that is run."""
    return (train.hashable(cfg),
            (("layer_types", json.dumps(cfg["layer_types"])),))
