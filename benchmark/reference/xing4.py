"""The plain Xing4.0 decoder and its training step, against Hugging Face
names (``benchmark/xing4_weights.py``: tensors stacked by kind of layer).
Float32 at ``Precision.HIGHEST`` (``decoder.product``; ``precision``
switches every matrix product's operands, for the control), no kernel, no
cache, nothing of the program under test.

``rms_w(x) = w x / sqrt(mean(x^2) + rms_norm_eps)``. A token carries
``X`` [4, D]: its table row copied to the four streams before layer 0,
the streams summed before ``rms_out`` and the untied head. A sub-layer F
(attention, or the feed-forward) with its own connection ``hc``:

    z     = rms_hc(vec(X))                          over 4 D, one scale
    H_pre = sigmoid(a_pre (Phi_pre z) + b_pre)                  [4]
    H_post = 2 sigmoid(a_post (Phi_post z) + b_post)            [4]
    H_res = SK(clip(a_res mat(Phi_res z) + b_res, lo, hi))      [4, 4]
            SK(A): M = exp(A); hc_sinkhorn_iters times: each column
            divided by its sum + hc_eps, then each row by its sum + hc_eps
    u  = sum_i H_pre[i] X[i];  y = F(rms_F(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

    attention:  c_q = rms(W_qa x); q_h = [q_nope | q_pe] = W_qb c_q
                [c_kv | k_pe] = W_kva x; c_kv <- rms(c_kv)
                [k_nope_h | v_h] = W_kvb c_kv
                rotary on q_pe and on the one k_pe every head shares, in
                interleaved pairs (2j, 2j + 1 turn at frequency j; YaRN);
                causal softmax of q . k over 192 dims scaled
                192^-1/2 m(factor, mscale_all_dim)^2; out = W_o concat(o_h)
    dense ffn:  W_d (silu(W_g z) * W_u z)
    routed ffn: s = sigmoid(G z) over all 64; S = top-4 of s + b;
                w_e = s_e / (sum_S s + 1e-20) * routed_scaling_factor;
                sum_{e in S, held} w_e E_e(z) + E_shared(z)

``b`` takes no gradient (it enters through the selection alone) and no
update. What the experts and heads held elsewhere would add is left out, as
in the program. The pieces that are any such decoder's are
``reference/laguna.py``'s own (blocks of positions, each recomputed in the
backward pass; RMSNorm; the gated MLP; AdamW on buffers it may reuse): this
file writes what this family's layers do with them, and the row-by-row
training steps over it.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark import weights, xing4_weights
from benchmark.reference import laguna as shared
from benchmark.reference import train
from benchmark.reference.decoder import product

TOKEN_BLOCK = 1024
QUERY_BLOCK = 512


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_tables(cfg, seq):
    """cos, sin [seq, rope / 2] of the rotary parts: pair j turns at the
    YaRN frequency j, both times m(factor, mscale) / m(factor,
    mscale_all_dim)."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rope = cfg["rope_scaling"]
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def turns_to_dim(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_to_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
    scale = mscale(factor, rope["mscale"]) / mscale(
        factor, rope["mscale_all_dim"])
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate_pairs(x, cos, sin):
    """Rotary on x [B, T, H, d] in interleaved pairs."""
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape)


def causal_attention(q, k, v, scale, precision):
    """q, k [B, T, H, dqk], v [B, T, H, dv]: causal softmax attention, a
    block of queries at a time against every key."""
    T = q.shape[1]
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    def one_block(start, q_blk):
        rows = start + jnp.arange(block)
        scores = product("bqhd,bkhd->bhqk", q_blk, k, precision) * scale
        keep = jnp.arange(T)[None, :] <= rows[:, None]
        probs = jax.nn.softmax(
            jnp.where(keep[None, None], scores, -jnp.inf), axis=-1)
        return (product("bhqk,bkhd->bqhd", probs, v, precision),)

    out, = shared.in_blocks(one_block, block, q)
    return out


def attention(cfg, x, lw, precision):
    B, T, _ = x.shape
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv, eps, a = cfg["kv_lora_rank"], cfg["rms_norm_eps"], "self_attn."
    c_q = shared.rms_norm(
        product("btd,rd->btr", x, lw[a + "q_a_proj.weight"], precision),
        lw[a + "q_a_layernorm.weight"], eps)
    q = product("btr,er->bte", c_q, lw[a + "q_b_proj.weight"],
                precision).reshape(B, T, -1, dn + dr)
    latent = product(
        "btd,rd->btr", x, lw[a + "kv_a_proj_with_mqa.weight"], precision)
    c_kv = shared.rms_norm(
        latent[..., :rkv], lw[a + "kv_a_layernorm.weight"], eps)
    kv = product("btr,er->bte", c_kv, lw[a + "kv_b_proj.weight"],
                 precision).reshape(B, T, -1, dn + dv)
    cos, sin = rotary_tables(cfg, T)
    q_pe = rotate_pairs(q[..., dn:], cos, sin)
    k_pe = rotate_pairs(latent[..., None, rkv:], cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (*kv.shape[:3], dr))], axis=-1)
    rope = cfg["rope_scaling"]
    scale = (dn + dr) ** -0.5 * mscale(
        rope["factor"], rope["mscale_all_dim"]) ** 2
    out = causal_attention(q, k, kv[..., dn:], scale, precision)
    return product("bte,de->btd", out.reshape(B, T, -1),
                   lw[a + "o_proj.weight"], precision)


def expert_ffn(cfg, x, lw, precision):
    """The held experts' and the shared expert's part of the layer's
    output, and the held experts' loads [held]."""
    m = "mlp."
    first = cfg.get("experts_held_first", 0)
    held = lw[m + "experts.gate_proj.weight"].shape[0]
    scores = jax.nn.sigmoid(
        product("btd,ed->bte", x, lw[m + "gate.weight"], precision))
    _, top_i = jax.lax.top_k(
        scores + lw[m + "gate.e_score_correction_bias"],
        cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    s = m + "shared_experts."
    out = shared.gated_mlp(
        x, lw[s + "gate_proj.weight"], lw[s + "up_proj.weight"],
        lw[s + "down_proj.weight"], precision)
    loads = []
    for e in range(held):
        chosen = top_i == first + e                          # [B, T, K]
        weight = jnp.sum(jnp.where(chosen, top_s, 0.0), axis=-1)
        loads.append(jnp.sum(chosen))
        out = out + weight[..., None] * shared.gated_mlp(
            x, lw[m + "experts.gate_proj.weight"][e],
            lw[m + "experts.up_proj.weight"][e],
            lw[m + "experts.down_proj.weight"][e], precision)
    return out, jnp.stack(loads)


def sinkhorn(cfg, logits):
    """``SK`` on [..., n, n]: rows second to last, columns last."""
    m = jnp.exp(logits)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
    return m


def coefficients(cfg, X, lw, hc, precision):
    """``(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])`` of the
    streams X [B, T, n, D] under the connection named ``hc``."""
    B, T, n, _ = X.shape
    z = shared.rms_norm(X.reshape(B, T, -1), lw[hc + ".norm.weight"],
                        cfg["rms_norm_eps"])
    raw = product("btk,ck->btc", z, lw[hc + ".phi.weight"], precision)
    alpha, bias = lw[hc + ".alpha"], lw[hc + ".bias"]
    pre = jax.nn.sigmoid(alpha[0] * raw[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(
        alpha[1] * raw[..., n:2 * n] + bias[n:2 * n])
    res = jnp.clip(
        alpha[2] * raw[..., 2 * n:] + bias[2 * n:],
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return pre, post, sinkhorn(cfg, res.reshape(B, T, n, n))


def connected(cfg, X, lw, hc, norm, sublayer, precision):
    """One sub-layer round its connection: ``(X', what the sub-layer
    returned beside its output)``."""
    pre, post, res = coefficients(cfg, X, lw, hc, precision)
    n = X.shape[2]
    u = sum(pre[..., i, None] * X[:, :, i] for i in range(n))
    y, *rest = sublayer(shared.rms_norm(u, lw[norm], cfg["rms_norm_eps"]))
    new = [sum(res[..., i, j, None] * X[:, :, j] for j in range(n))
           + post[..., i, None] * y for i in range(n)]
    return jnp.stack(new, axis=2), rest


def layer_runs(cfg):
    """Consecutive layers of one kind, in layer order: for each run its
    kind, where it starts among the layers of that kind, how many layers
    and whether its feed-forward is routed."""
    pattern, kinds = xing4_weights.plan(cfg)
    seen, runs = {}, []
    for kind in pattern:
        if runs and runs[-1]["kind"] == kind:
            runs[-1]["count"] += 1
        else:
            runs.append({"kind": kind, "start": seen.get(kind, 0),
                         "count": 1,
                         "sparse": kinds[kind]["num_experts"] > 0})
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def layer(cfg, X, lw, run, precision):
    """One layer on the streams X [B, T, n, D] with its tensors ``lw``
    (names without the ``model.layers.<kind>.`` prefix): ``(X', loads
    [held])``."""
    X, _ = connected(
        cfg, X, lw, "attn_hc", "input_layernorm.weight",
        lambda z: (attention(cfg, z, lw, precision),), precision)
    if run["sparse"]:
        ffn = lambda z: shared.in_blocks(                    # noqa: E731
            lambda _, y: expert_ffn(cfg, y, lw, precision), TOKEN_BLOCK, z)
    else:
        ffn = lambda z: (*shared.in_blocks(                  # noqa: E731
            lambda _, y: (shared.gated_mlp(
                y, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
                lw["mlp.down_proj.weight"], precision),), TOKEN_BLOCK, z),
            jnp.zeros((0,), jnp.int32))
    X, (load,) = connected(
        cfg, X, lw, "ffn_hc", "post_attention_layernorm.weight", ffn,
        precision)
    return X, load


def hidden_states(cfg, w, ids, precision="float32", remat=False):
    """``(hidden states before the last norm [B, T, D], loads [routed
    layers, held])``; each run of layers of one kind a ``lax.scan`` over
    its slice of that kind's stacked tensors."""
    x = w["model.embed_tokens.weight"][ids]
    X = jnp.broadcast_to(
        x[:, :, None, :], (*x.shape[:2], cfg["hc_mult"], x.shape[-1]))
    loads = []
    for run in layer_runs(cfg):
        prefix = f"model.layers.{run['kind']}."
        stacked = {
            k[len(prefix):]: v[run["start"]:run["start"] + run["count"]]
            for k, v in w.items() if k.startswith(prefix)}

        def body(X, lw, run=run):
            return layer(cfg, X, lw, run, precision)

        X, load = jax.lax.scan(
            jax.checkpoint(body) if remat else body, X, stacked)
        if run["sparse"]:
            loads.append(load)
    return jnp.sum(X, axis=2), (jnp.concatenate(loads) if loads
                                else jnp.zeros((0, 0), jnp.int32))


def logits_of(cfg, w, x, precision):
    x = shared.rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    return product("btd,vd->btv", x, w["lm_head.weight"], precision)


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
        # buffers when it is enqueued, and several rows' would not fit.
        jax.block_until_ready(grads)
    return loss, grads, loads


@functools.partial(jax.jit, static_argnums=(0, 1))
def change_norms(scalars, groups, w, seed):
    """Per-leaf norm of ``w`` minus the seeded leaf made again from
    ``seed``: no second copy of the start is ever kept."""
    spec = xing4_weights.spec_for(unhashable(scalars, groups))
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        v - weights.make_leaf(seed, k, *spec[k])))) for k, v in w.items()}


def follow_steps(scalars, groups, w, batches, seed, lr, precision, steps):
    """``steps`` plain steps from ``w`` (given up) over ``batches`` [steps,
    B, T]: each step's loss, the per-leaf norm of the first gradient, the
    per-leaf norm of the parameters' change, the first step's loads. The
    selection bias is no step's to move: AdamW's decay is taken off it.
    AdamW's two moments wait on the host while a step's rows are summed:
    the parameters, the sum, one row's gradient and that row's float32
    activations at four streams fill the chip without them."""
    cfg = unhashable(scalars, groups)
    mu = nu = None
    losses, first_grad, first_loads = [], None, None
    for i in range(steps):
        loss, grads, loads = loss_and_grads(cfg, w, batches[i], precision)
        fixed = {k: jnp.copy(v) for k, v in w.items()
                 if k.endswith(".e_score_correction_bias")}
        if mu is None:
            mu = jax.tree_util.tree_map(jnp.zeros_like, w)
            nu = jax.tree_util.tree_map(jnp.zeros_like, w)
        else:
            mu, nu = jax.device_put((mu, nu), jax.devices()[0])
        w, mu, nu, norms = shared.apply_adamw(
            w, mu, nu, grads, jnp.float32(i + 1), lr)
        w.update(fixed)
        del grads       # or the next step's rows would find no room
        if i + 1 < steps:
            mu, nu = jax.device_get((mu, nu))
        if i == 0:
            first_grad, first_loads = norms, loads
        losses.append(loss)
    return (jnp.stack(losses), first_grad,
            change_norms(scalars, groups, w, seed), first_loads)


def hashable(cfg):
    """``(scalars, groups)`` of a configuration as ``jit`` static data:
    its numbers and strings, and its lists and rope table as JSON."""
    keep = ("layer_types", "rope_scaling")
    return (train.hashable(cfg),
            tuple((k, json.dumps(cfg[k], sort_keys=True)) for k in keep))


unhashable = shared.unhashable
