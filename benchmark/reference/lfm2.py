"""The plain LFM2-MoE decoder and its training step, against Hugging Face
names (``benchmark/lfm2_weights.py``: tensors stacked by kind of layer).
Float32 at ``Precision.HIGHEST`` (``decoder.product``; ``precision``
switches every matrix product's operands, for the control), no kernel, no
cache, nothing of the program under test.

``rms(x) = w x / sqrt(mean(x^2) + norm_eps)``. Layer l:

    h = x + mixer_l(rms_op(x))
        conv:  [B, C, u] = split3(W_in z); v = B * u
               c[t] = k_0 v[t-2] + k_1 v[t-1] + k_2 v[t], zeros before 0
               (per channel; Conv1d(groups=D, padding=2)[..., :T])
               out = W_out (C * c)
        full_attention, H query heads on Hkv KV heads held here, size hd:
               q, k, v = z W_q, z W_k, z W_v; q_h <- rms_hd(q_h) g_q,
               k_j <- rms_hd(k_j) g_k; rotary on all hd dims, halves
               rotated, theta 1e6; causal softmax scaled 1 / sqrt(hd);
               query head h reads KV head h // (H / Hkv); out = A W_o
    y = h + ffn_l(rms_ffn(h))
        dense (the leading layers): W_2 (silu(W_1 z) * W_3 z)
        routed: s = sigmoid(G z) over all E; S = top-k of s + b;
               w_e = s_e / (sum_S s + 1e-6) * routed_scaling_factor;
               sum_{e in S, held} w_e E_e(z)

and after the last layer ``rms_out`` (``embedding_norm``), then logits
through the transposed input table (tied). ``b`` takes no gradient (it
enters through the selection alone) and no update.

What the experts and heads held elsewhere would add is left out, as in the
program. The pieces that are any such decoder's are ``reference/laguna.py``'s
own (blocks of queries and of positions, each recomputed in the backward
pass; RMSNorm; rotary tables; the causal attention; the gated MLP; AdamW on
buffers it may reuse): this file writes what this family's layers do with
them, and the row-by-row training steps over it.
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmark import lfm2_weights, weights
from benchmark.reference import laguna as shared
from benchmark.reference import train
from benchmark.reference.decoder import product

TOKEN_BLOCK = 1024


def short_conv(x, lw, precision):
    """The gated short convolution on x [B, T, D]."""
    T = x.shape[1]
    gate_in, gate_out, u = jnp.split(product(
        "btd,ed->bte", x, lw["conv.in_proj.weight"], precision), 3, axis=-1)
    v = gate_in * u
    taps = lw["conv.conv.weight"][:, 0, :]                   # [D, K]
    K = taps.shape[-1]
    padded = jnp.concatenate(
        [jnp.zeros_like(v[:, :K - 1]), v], axis=1)           # v[t - (K - 1)]
    c = sum(taps[:, j] * padded[:, j:j + T] for j in range(K))
    return product("bte,de->btd", gate_out * c, lw["conv.out_proj.weight"],
                   precision)


def attention(cfg, x, lw, precision):
    B, T, _ = x.shape
    hd, eps = cfg["head_dim"], cfg["norm_eps"]
    a = "self_attn."
    split = lambda y: y.reshape(B, T, -1, hd)            # noqa: E731
    q = split(product("btd,ed->bte", x, lw[a + "q_proj.weight"], precision))
    k = split(product("btd,ed->bte", x, lw[a + "k_proj.weight"], precision))
    v = split(product("btd,ed->bte", x, lw[a + "v_proj.weight"], precision))
    q = shared.rms_norm(q, lw[a + "q_layernorm.weight"], eps)
    k = shared.rms_norm(k, lw[a + "k_layernorm.weight"], eps)
    cos, sin = shared.rotary_tables(T, hd, cfg["rope_parameters"])
    q, k = shared.rotate(q, cos, sin), shared.rotate(k, cos, sin)
    out = shared.banded_attention(q, k, v, None, precision)
    return product("bte,de->btd", out.reshape(B, T, -1),
                   lw[a + "out_proj.weight"], precision)


def expert_ffn(cfg, x, lw, precision):
    """The held experts' part of the layer's output, and their loads
    [held]."""
    m = "feed_forward."
    first = cfg.get("experts_held_first", 0)
    held = lw[m + "experts.w1.weight"].shape[0]
    scores = jax.nn.sigmoid(
        product("btd,ed->bte", x, lw[m + "gate.weight"], precision))
    chosen_by = scores + lw[m + "expert_bias"] \
        if cfg.get("use_expert_bias") else scores
    _, top_i = jax.lax.top_k(chosen_by, cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6)
    top_s = top_s * cfg.get("routed_scaling_factor", 1.0)
    out, loads = jnp.zeros_like(x), []
    for e in range(held):
        chosen = top_i == first + e                          # [B, T, K]
        weight = jnp.sum(jnp.where(chosen, top_s, 0.0), axis=-1)
        loads.append(jnp.sum(chosen))
        out = out + weight[..., None] * shared.gated_mlp(
            x, lw[m + "experts.w1.weight"][e], lw[m + "experts.w3.weight"][e],
            lw[m + "experts.w2.weight"][e], precision)
    return out, jnp.stack(loads)


def layer_runs(cfg):
    """Consecutive layers of one kind, in layer order: for each run its
    kind, where it starts among the layers of that kind, how many layers,
    its mixer and whether its feed-forward is routed."""
    pattern, kinds = lfm2_weights.plan(cfg)
    seen, runs = {}, []
    for kind in pattern:
        if runs and runs[-1]["kind"] == kind:
            runs[-1]["count"] += 1
        else:
            runs.append({
                "kind": kind, "start": seen.get(kind, 0), "count": 1,
                "conv": bool(kinds[kind].get("conv_mixer")),
                "sparse": kinds[kind]["num_experts"] > 0,
            })
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def layer(cfg, x, lw, run, precision):
    """One layer on x [B, T, D] with its tensors ``lw`` (names without the
    ``model.layers.<kind>.`` prefix): ``(x', loads [held])``."""
    eps = cfg["norm_eps"]
    z = shared.rms_norm(x, lw["operator_norm.weight"], eps)
    h = x + (short_conv(z, lw, precision) if run["conv"]
             else attention(cfg, z, lw, precision))
    normed = shared.rms_norm(h, lw["ffn_norm.weight"], eps)
    if run["sparse"]:
        out, load = shared.in_blocks(
            lambda _, y: expert_ffn(cfg, y, lw, precision), TOKEN_BLOCK,
            normed)
        return h + out, load
    m = "feed_forward."
    out, = shared.in_blocks(lambda _, y: (shared.gated_mlp(
        y, lw[m + "w1.weight"], lw[m + "w3.weight"], lw[m + "w2.weight"],
        precision),), TOKEN_BLOCK, normed)
    return h + out, jnp.zeros((0,), jnp.int32)


def hidden_states(cfg, w, ids, precision="float32", remat=False):
    """``(hidden states before the last norm [B, T, D], loads [routed
    layers, held])``; each run of layers of one kind a ``lax.scan`` over
    its slice of that kind's stacked tensors."""
    x = w["model.embed_tokens.weight"][ids]
    loads = []
    for run in layer_runs(cfg):
        prefix = f"model.layers.{run['kind']}."
        stacked = {
            k[len(prefix):]: v[run["start"]:run["start"] + run["count"]]
            for k, v in w.items() if k.startswith(prefix)}

        def body(x, lw, run=run):
            return layer(cfg, x, lw, run, precision)

        x, load = jax.lax.scan(
            jax.checkpoint(body) if remat else body, x, stacked)
        if run["sparse"]:
            loads.append(load)
    return x, (jnp.concatenate(loads) if loads
               else jnp.zeros((0, 0), jnp.int32))


def logits_of(cfg, w, x, precision):
    """The last norm, then the transposed input table."""
    x = shared.rms_norm(x, w["model.embedding_norm.weight"], cfg["norm_eps"])
    return product("btd,vd->btv", x, w["model.embed_tokens.weight"],
                   precision)


def forward(cfg, w, ids, precision="float32", remat=False):
    """``(logits [B, T, V], loads [routed layers, held])``."""
    x, loads = hidden_states(cfg, w, ids, precision, remat)
    return logits_of(cfg, w, x, precision), loads


def next_token_loss_sum(cfg, w, ids, precision):
    """Sum of the next-token losses of ``ids`` [B, T] (T - 1 predictions a
    row), the head and the log-softmax in blocks of positions; and the
    loads."""
    x, loads = hidden_states(cfg, w, ids, precision, remat=True)
    targets = jnp.roll(ids, -1, axis=1)
    counted = jnp.arange(ids.shape[1])[None, :] < ids.shape[1] - 1

    def block(_, x, targets, counted):
        logp = jax.nn.log_softmax(logits_of(cfg, w, x, precision), axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return x[..., :0], -jnp.sum(jnp.where(counted, picked, 0.0))

    _, total = shared.in_blocks(
        block, TOKEN_BLOCK, x, targets, jnp.broadcast_to(counted, ids.shape))
    return total, loads


@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5))
def row_gradient(scalars, groups, w, row, precision, count):
    """One sequence's part of the mean loss over ``count`` predictions,
    its gradient, and the held experts' loads."""
    cfg = unhashable(scalars, groups)

    def part_of_mean(w):
        total, loads = next_token_loss_sum(cfg, w, row[None], precision)
        return total / count, loads

    (loss, loads), grad = jax.value_and_grad(part_of_mean, has_aux=True)(w)
    return loss, grad, loads


def loss_and_grads(cfg, w, ids, precision):
    """Mean loss over every predicted position of ``ids`` [B, T], its
    gradient summed one row at a time (one compiled program a row: beside
    the float32 training state only one row's gradient and one sum are
    ever alive), the loads over the batch."""
    count = ids.shape[0] * (ids.shape[1] - 1)
    static = hashable(cfg)
    loss = grads = loads = None
    for row in ids:
        part, grad, load = row_gradient(*static, w, row, precision, count)
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
    spec = lfm2_weights.spec_for(unhashable(scalars, groups))
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        v - weights.make_leaf(seed, k, *spec[k])))) for k, v in w.items()}


def follow_steps(scalars, groups, w, batches, seed, lr, precision, steps):
    """``steps`` plain steps from ``w`` (given up) over ``batches`` [steps,
    B, T]: each step's loss, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change, the first step's loads. The
    selection bias is no step's to move: AdamW's decay is taken off it."""
    cfg = unhashable(scalars, groups)
    mu = jax.tree_util.tree_map(jnp.zeros_like, w)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad, first_loads = [], None, None
    for i in range(steps):
        loss, grads, loads = loss_and_grads(cfg, w, batches[i], precision)
        fixed = {k: jnp.copy(v) for k, v in w.items()
                 if k.endswith(".expert_bias")}
        w, mu, nu, norms = shared.apply_adamw(
            w, mu, nu, grads, jnp.float32(i + 1), lr)
        w.update(fixed)
        del grads       # or the next step's rows would find no room
        if i == 0:
            first_grad, first_loads = norms, loads
        losses.append(loss)
    return (jnp.stack(losses), first_grad,
            change_norms(scalars, groups, w, seed), first_loads)


def hashable(cfg):
    """``(scalars, groups)`` of a configuration as ``jit`` static data:
    its numbers and strings, and its lists and rope table as JSON."""
    keep = ("layer_types", "rope_parameters")
    return (train.hashable(cfg),
            tuple((k, json.dumps(cfg[k], sort_keys=True)) for k in keep))


unhashable = shared.unhashable
