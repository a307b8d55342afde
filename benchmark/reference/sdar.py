"""The plain SDAR-MoE decoder trained by block diffusion, against Hugging
Face names (``benchmark/sdar_weights.py``: tensors stacked by kind of
layer, one kind). Float32 at ``Precision.HIGHEST`` (``decoder.product``;
``precision`` switches every matrix product's operands, for the control),
no kernel, no cache, nothing of the program under test: this file imports
the benchmark's own modules alone.

**The objective.** A sequence x0 of L tokens is cut into blocks of B
(``block_length``; assumed 4, the family's released block length). Block k
has one rate p_k = eps + (1 - eps) u_k, u_k ~ U(0, 1), eps 1e-3 (assumed:
the linear schedule of masked diffusion as LLaDA, arXiv:2502.09992, writes
it, drawn per block as BD3-LMs, arXiv:2503.09573, do), and each of its
tokens is replaced by the mask id with probability p_k, independently: xt.
The draw comes with the batch (``clean``, ``noisy``, ``rates``,
``mask_id``).

**The stream.** The stack sees the 2L positions [xt ; x0]. Position i has
sequence position pi(i) = i mod L (rotary uses pi) and block beta(i) =
pi(i) // B. Query i may see key j iff

    i <  L, j <  L  (noisy on noisy):  beta(i) = beta(j)
    i <  L, j >= L  (noisy on clean):  beta(j) <  beta(i)
    i >= L, j >= L  (clean on clean):  beta(j) <= beta(i)
    i >= L, j <  L:                    never.

**A layer**, with H query heads and Hkv KV heads held here, head size hd:

    h  = x + Attn(rms(x))      q = x W_q [H, hd]; k, v = x W_k, x W_v [Hkv, hd]
                               q_h <- rms_hd(q_h) g_q, k_j <- rms_hd(k_j) g_k
                               (assumed, as the Qwen3-MoE class does)
                               rotary on all hd dims, halves rotated, theta
                               rope_theta, at pi(i)
                               query head h reads KV head h // (H / Hkv)
                               softmax(q k / sqrt(hd)) over the live keys
                               out = concat_h(A_h) W_o
    x' = h + MoE(rms(h))       p = softmax(h W_r) over all E, S = top-k,
                               w_e = p_e / sum_S p,
                               sum_{e in S, held} w_e W_down,e(
                                   silu(W_gate,e h) * W_up,e h)

then the final RMSNorm and the untied head, for the noisy half alone.

**The loss**, with no shift (assumed: a masked position predicts its own
token, as LLaDA and BD3-LMs do):

    loss = 1 / (batch L)  sum_{i < L, xt_i = mask}  (1 / p_beta(i))
                          (logsumexp(z_i) - z_i[x0_i]).

What the experts and heads held elsewhere would add is left out, as in the
program. The mask is built from the definition above as a boolean array, a
block of query rows at a time. The pieces that are any such decoder's are
``reference/laguna.py``'s (blocks of positions, each recomputed in the
backward pass; RMSNorm; rotary tables; the gated MLP; the head; AdamW on
buffers it may reuse), and the routed layer's arithmetic is
``reference/mellum.py``'s.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark import sdar_weights
from benchmark.reference import laguna as shared
from benchmark.reference import train
from benchmark.reference.decoder import product
from benchmark.reference.mellum import expert_ffn

QUERY_BLOCK = 512
TOKEN_BLOCK = 1024


def live(rows, cols, half, block):
    """The definition: bool [rows, cols] of absolute stream indices."""
    i, j = rows[:, None], cols[None, :]
    bi, bj = (i % half) // block, (j % half) // block
    return (((i < half) & (j < half) & (bi == bj))
            | ((i < half) & (j >= half) & (bj < bi))
            | ((i >= half) & (j >= half) & (bj <= bi)))


def masked_attention(q, k, v, block, precision):
    """q [B, 2L, H, hd] against k, v [B, 2L, Hkv, hd] under ``live``; a
    block of query rows meets every key and the mask does the rest."""
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    q = q.reshape(B, T, Hkv, H // Hkv, hd)
    size = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    cols = jnp.arange(T)

    def one_block(start, q_blk):
        keep = live(start + jnp.arange(size), cols, T // 2, block)
        scores = product("bqngd,bknd->bngqk", q_blk, k, precision)
        scores = jnp.where(keep[None, None, None],
                           scores / math.sqrt(hd), -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return (product("bngqk,bknd->bqngd", probs, v, precision),)

    out, = shared.in_blocks(one_block, size, q)
    return out.reshape(B, T, H, hd)


def attention(cfg, x, lw, precision):
    B, T, _ = x.shape
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    a = "self_attn."
    split = lambda y: y.reshape(B, T, -1, hd)            # noqa: E731
    q = split(product("btd,ed->bte", x, lw[a + "q_proj.weight"], precision))
    k = split(product("btd,ed->bte", x, lw[a + "k_proj.weight"], precision))
    v = split(product("btd,ed->bte", x, lw[a + "v_proj.weight"], precision))
    q = shared.rms_norm(q, lw[a + "q_norm.weight"], eps)
    k = shared.rms_norm(k, lw[a + "k_norm.weight"], eps)
    # Both copies stand at positions 0 .. L - 1.
    cos, sin = shared.rotary_tables(
        T // 2, hd, {"rope_theta": cfg["rope_theta"]})
    cos, sin = jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))
    q, k = shared.rotate(q, cos, sin), shared.rotate(k, cos, sin)
    out = masked_attention(q, k, v, cfg["block_length"], precision)
    return product("bte,de->btd", out.reshape(B, T, -1),
                   lw[a + "o_proj.weight"], precision)


def layer(cfg, x, lw, precision):
    """One layer on the stream x [B, 2L, D] with its tensors ``lw`` (names
    without the ``model.layers.full.`` prefix): ``(x', loads [held])``."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(
        cfg, shared.rms_norm(x, lw["input_layernorm.weight"], eps), lw,
        precision)
    normed = shared.rms_norm(h, lw["post_attention_layernorm.weight"], eps)
    out, load = shared.in_blocks(
        lambda _, y: expert_ffn(cfg, y, lw, precision), TOKEN_BLOCK, normed)
    return h + out, load


def hidden_states(cfg, w, stream, precision="float32", remat=False):
    """``(hidden states before the last norm [B, 2L, D], loads [layers,
    held])``: a ``lax.scan`` over the one kind's stacked tensors."""
    prefix = "model.layers.full."
    stacked = {k[len(prefix):]: v for k, v in w.items()
               if k.startswith(prefix)}

    def body(x, lw):
        return layer(cfg, x, lw, precision)

    return jax.lax.scan(
        jax.checkpoint(body) if remat else body,
        w["model.embed_tokens.weight"][stream], stacked)


def forward(cfg, w, clean, noisy, precision="float32", remat=False):
    """``(the noisy half's logits [B, L, V], loads [layers, held])``."""
    x, loads = hidden_states(
        cfg, w, jnp.concatenate([noisy, clean], axis=1), precision, remat)
    return shared.logits_of(cfg, w, x[:, :clean.shape[1]], precision), loads


def diffusion_loss_sum(cfg, w, clean, noisy, rates, mask_id, precision):
    """Sum over the masked positions of clean, noisy [B, L] of (1 / their
    block's rate) x (logsumexp - the clean token's logit), the head and
    the log-softmax in blocks of positions; and the loads."""
    L = clean.shape[1]
    x, loads = hidden_states(
        cfg, w, jnp.concatenate([noisy, clean], axis=1), precision,
        remat=True)
    weight = jnp.where(
        noisy == mask_id, 1.0 / jnp.repeat(
            rates, cfg["block_length"], axis=-1), 0.0)

    def block(_, x, targets, weight):
        logp = jax.nn.log_softmax(
            shared.logits_of(cfg, w, x, precision), axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return x[..., :0], -jnp.sum(weight * picked)

    _, total = shared.in_blocks(block, TOKEN_BLOCK, x[:, :L], clean, weight)
    return total, loads


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def row_gradient(scalars, groups, w, row, precision, count):
    """One sequence's part of the loss over ``count`` = batch x L data
    tokens, its gradient, and the held experts' loads. ``row``: the
    sequence's ``clean``, ``noisy``, ``rates`` and the ``mask_id``."""
    cfg = unhashable(scalars, groups)

    def part(w):
        total, loads = diffusion_loss_sum(
            cfg, w, row["clean"][None], row["noisy"][None],
            row["rates"][None], row["mask_id"], precision)
        return total / count, loads

    (loss, loads), grad = jax.value_and_grad(part, has_aux=True)(w)
    return loss, grad, loads


def loss_and_grads(cfg, w, batch, precision):
    """The loss of a batch (a dict, ``sdar_weights.Batches``' item), its
    gradient summed one sequence at a time (one compiled program a
    sequence: beside the float32 training state only one sequence's
    gradient and one sum are ever alive), the loads over the batch."""
    count = batch["clean"].shape[0] * batch["clean"].shape[1]
    static = hashable(cfg)
    loss = grads = loads = None
    for r in range(batch["clean"].shape[0]):
        row = {k: batch[k][r] for k in ("clean", "noisy", "rates")}
        part, grad, load = row_gradient(
            *static, w, dict(row, mask_id=batch["mask_id"]), precision, count)
        loss = part if loss is None else loss + part
        loads = load if loads is None else loads + load
        grads = grad if grads is None else shared.add_into(grads, grad)
        del grad
        # The host must not run ahead: a row's program is given its
        # buffers when it is enqueued, and four rows' would not fit.
        jax.block_until_ready(grads)
    return loss, grads, loads


@functools.partial(jax.jit, static_argnums=(0, 1))
def change_norms(scalars, groups, w, seed):
    """Per-leaf norm of ``w`` minus the seeded leaf made again from
    ``seed``: no second copy of the start is ever kept."""
    cfg = unhashable(scalars, groups)
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        v - sdar_weights.make_leaf(cfg, seed, k)))) for k, v in w.items()}


def follow_steps(scalars, groups, w, batches, seed, lr, precision, steps):
    """``steps`` plain steps of AdamW (``train.adamw``: decoupled decay
    1e-4 x lr x parameter, b1 0.9, b2 0.999, eps 1e-8, bias-corrected)
    from ``w`` (given up) over ``batches``: each step's loss, the per-leaf
    norm of the first gradient, the per-leaf norm of the parameters'
    change, the first step's loads, and the first gradient's sample
    (``sdar_weights.gradient_sample``)."""
    cfg = unhashable(scalars, groups)
    mu = jax.tree_util.tree_map(jnp.zeros_like, w)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad, first_loads, first_sample = [], None, None, None
    for i in range(steps):
        loss, grads, loads = loss_and_grads(cfg, w, batches[i], precision)
        if i == 0:
            first_sample = jax.jit(sdar_weights.gradient_sample)(grads)
        w, mu, nu, norms = shared.apply_adamw(
            w, mu, nu, grads, jnp.float32(i + 1), lr)
        del grads       # or the next step's rows would find no room
        if i == 0:
            first_grad, first_loads = norms, loads
        losses.append(loss)
    return (jnp.stack(losses), first_grad,
            change_norms(scalars, groups, w, seed), first_loads, first_sample)


def hashable(cfg):
    """``(scalars, groups)`` of a configuration as ``jit`` static data:
    its numbers and strings, and as JSON its list of layers and the seeds
    it states for leaves that do not follow the run's."""
    keep = ("layer_types", "routing_seeds")
    return (train.hashable(cfg), tuple(
        (k, json.dumps(cfg[k], sort_keys=True)) for k in keep if k in cfg))


unhashable = shared.unhashable
