"""The plain Mellum decoder and its training step, against Hugging Face
names (``benchmark/mellum_weights.py``: tensors stacked by kind of layer).
Float32 at ``Precision.HIGHEST`` (``decoder.product``; ``precision``
switches every matrix product's operands, for the control), no kernel, no
cache, nothing of the program under test.

Layer l, with H query heads and Hkv KV heads held here, head size hd:

    h  = x + Attn_l(rms(x))    q = x W_q [H, hd]; k, v = x W_k, x W_v [Hkv, hd]
                                q_h <- rms_hd(q_h) g_q, k_j <- rms_hd(k_j) g_k
                                rotary on all hd dims, halves rotated; window
                                layers plain, full layers YaRN
                                query head h reads KV head h // (H / Hkv)
                                causal; window layers: 0 <= i - j < W
                                out = concat_h(A_h) W_o
    x' = h + MoE(rms(h))        p = softmax(h W_r) over all E, S = top-k,
                                w_e = p_e / sum_S p,
                                sum_{e in S, held} w_e E_e(h)

Every layer is routed (``mlp_layer_types`` all ``sparse``, as published).

What the experts and heads held elsewhere would add is left out, as in the
program. The pieces that are any such decoder's are ``reference/laguna.py``'s
own (blocks of queries and of positions, each recomputed in the backward
pass; RMSNorm; rotary tables; the banded attention; the gated MLP; the head;
AdamW on buffers it may reuse): this file writes what this family's layer
does with them, and the row-by-row training steps over it.
"""

import functools
import json

import jax
import jax.numpy as jnp

from benchmark import mellum_weights, weights
from benchmark.reference import laguna as shared
from benchmark.reference import train
from benchmark.reference.decoder import product

TOKEN_BLOCK = 1024


def attention(cfg, x, lw, run, precision):
    B, T, _ = x.shape
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    a = "self_attn."
    split = lambda y: y.reshape(B, T, -1, hd)            # noqa: E731
    q = split(product("btd,ed->bte", x, lw[a + "q_proj.weight"], precision))
    k = split(product("btd,ed->bte", x, lw[a + "k_proj.weight"], precision))
    v = split(product("btd,ed->bte", x, lw[a + "v_proj.weight"], precision))
    q = shared.rms_norm(q, lw[a + "q_norm.weight"], eps)
    k = shared.rms_norm(k, lw[a + "k_norm.weight"], eps)
    cos, sin = shared.rotary_tables(T, hd, run["rope"])
    q, k = shared.rotate(q, cos, sin), shared.rotate(k, cos, sin)
    out = shared.banded_attention(q, k, v, run["window"], precision)
    return product("bte,de->btd", out.reshape(B, T, -1),
                   lw[a + "o_proj.weight"], precision)


def expert_ffn(cfg, x, lw, precision):
    """The held experts' part of the layer's output, and their loads
    [held]."""
    m = "mlp."
    first = cfg.get("experts_held_first", 0)
    held = lw[m + "experts.gate_proj.weight"].shape[0]
    probs = jax.nn.softmax(
        product("btd,ed->bte", x, lw[m + "gate.weight"], precision), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out, loads = jnp.zeros_like(x), []
    for e in range(held):
        chosen = top_i == first + e                          # [B, T, K]
        weight = jnp.sum(jnp.where(chosen, top_p, 0.0), axis=-1)
        loads.append(jnp.sum(chosen))
        out = out + weight[..., None] * shared.gated_mlp(
            x, lw[m + "experts.gate_proj.weight"][e],
            lw[m + "experts.up_proj.weight"][e],
            lw[m + "experts.down_proj.weight"][e], precision)
    return out, jnp.stack(loads)


def layer_runs(cfg):
    """Consecutive layers of one kind, in layer order: for each run its
    kind, where it starts among the layers of that kind, how many layers,
    and what the layer equations need (window, rope entry)."""
    pattern, kinds = mellum_weights.plan(cfg)
    seen, runs = {}, []
    for i, kind in enumerate(pattern):
        window = kinds[kind]["window_size"] is not None
        if runs and runs[-1]["kind"] == kind:
            runs[-1]["count"] += 1
        else:
            runs.append({
                "kind": kind, "start": seen.get(kind, 0), "count": 1,
                "window": cfg["sliding_window"] if window else None,
                "rope": cfg["rope_parameters"][
                    "sliding_attention" if window else "full_attention"],
            })
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def layer(cfg, x, lw, run, precision):
    """One layer on x [B, T, D] with its tensors ``lw`` (names without the
    ``model.layers.<kind>.`` prefix): ``(x', loads [held])``."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(
        cfg, shared.rms_norm(x, lw["input_layernorm.weight"], eps), lw, run,
        precision)
    normed = shared.rms_norm(h, lw["post_attention_layernorm.weight"], eps)
    out, load = shared.in_blocks(
        lambda _, y: expert_ffn(cfg, y, lw, precision), TOKEN_BLOCK, normed)
    return h + out, load


def hidden_states(cfg, w, ids, precision="float32", remat=False):
    """``(hidden states before the last norm [B, T, D], loads [layers,
    held])``; each run of layers of one kind a ``lax.scan`` over
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
        loads.append(load)
    return x, jnp.concatenate(loads)


def forward(cfg, w, ids, precision="float32", remat=False):
    """``(logits [B, T, V], loads [layers, held])``."""
    x, loads = hidden_states(cfg, w, ids, precision, remat)
    return shared.logits_of(cfg, w, x, precision), loads


def next_token_loss_sum(cfg, w, ids, precision):
    """Sum of the next-token losses of ``ids`` [B, T] (T - 1 predictions a
    row), the head and the log-softmax in blocks of positions; and the
    loads."""
    x, loads = hidden_states(cfg, w, ids, precision, remat=True)
    targets = jnp.roll(ids, -1, axis=1)
    counted = jnp.arange(ids.shape[1])[None, :] < ids.shape[1] - 1

    def block(_, x, targets, counted):
        logp = jax.nn.log_softmax(
            shared.logits_of(cfg, w, x, precision), axis=-1)
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
    spec = mellum_weights.spec_for(unhashable(scalars, groups))
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        v - weights.make_leaf(seed, k, *spec[k])))) for k, v in w.items()}


def follow_steps(scalars, groups, w, batches, seed, lr, precision, steps):
    """``steps`` plain steps from ``w`` (given up) over ``batches`` [steps,
    B, T]: each step's loss, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change, the first step's loads."""
    cfg = unhashable(scalars, groups)
    mu = jax.tree_util.tree_map(jnp.zeros_like, w)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first_grad, first_loads = [], None, None
    for i in range(steps):
        loss, grads, loads = loss_and_grads(cfg, w, batches[i], precision)
        w, mu, nu, norms = shared.apply_adamw(
            w, mu, nu, grads, jnp.float32(i + 1), lr)
        del grads       # or the next step's rows would find no room
        if i == 0:
            first_grad, first_loads = norms, loads
        losses.append(loss)
    return (jnp.stack(losses), first_grad,
            change_norms(scalars, groups, w, seed), first_loads)


def hashable(cfg):
    """``(scalars, groups)`` of a configuration as ``jit`` static data:
    its numbers and strings, and its lists and rope table as JSON."""
    keep = ("layer_types", "mlp_layer_types", "rope_parameters")
    return (train.hashable(cfg),
            tuple((k, json.dumps(cfg[k], sort_keys=True)) for k in keep))


unhashable = shared.unhashable
