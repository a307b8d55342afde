"""The plain Laguna decoder and its training step, against Hugging Face
names (``benchmark/laguna_weights.py``: tensors stacked by kind of layer).
Float32 at ``Precision.HIGHEST`` (``decoder.product``; ``precision``
switches every matrix product's operands, for the control), no kernel, no
cache, nothing of the program under test.

Layer l, with H_l query heads and H_kv KV heads held here, head size hd:

    h  = x + Attn_l(rms(x))         q = x W_q, k = x W_k, v = x W_v
                                    rotary on the first hd x partial dims
                                    (YaRN in full layers), halves rotated
                                    query head h reads KV head h // (H_l / H_kv)
                                    causal; window layers: 0 <= i - j < W
                                    out = concat_h(sigmoid(x W_g)_h A_h) W_o
    x' = h + FFN_l(rms(h))          dense: W_down(silu(W_gate x) * W_up x)
                                    sparse: p = softmax(x W_r) over all E,
                                    S = top-k, w_e = scale p_e / sum_S p,
                                    sum_{e in S, held} w_e E_e(x) + E_shared(x)

What the experts held elsewhere would add is left out, as in the program.
Attention runs over blocks of queries (each against the keys its band can
reach) and what works token by token over blocks of positions, so that
float32 at 8,192 positions fits beside the training state; every layer and
every block is recomputed in the backward pass.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import laguna_weights, weights
from benchmark.reference import train
from benchmark.reference.decoder import product

QUERY_BLOCK = 512
TOKEN_BLOCK = 1024


def in_blocks(fn, size, *arrays):
    """``fn`` on blocks of ``size`` positions of arrays [B, T, ...], one
    block after the other (``lax.map``), each recomputed in the backward
    pass: what works on one token at a time (an MLP, the experts, the head
    and the loss) or on one block of queries never holds a whole
    8,192-token sequence's float32 intermediates. ``fn`` gets the block's
    start and the blocks, and returns a tuple: element 0 [B, size, ...] is
    put together over the blocks, the others are summed over them."""
    B, T = arrays[0].shape[:2]
    size = size if T % size == 0 else T
    n = T // size

    def split(a):
        return jnp.moveaxis(a.reshape(B, n, size, *a.shape[2:]), 1, 0)

    first, *rest = jax.lax.map(
        lambda args: jax.checkpoint(fn)(*args),
        (jnp.arange(n) * size, *map(split, arrays)))
    first = jnp.moveaxis(first, 0, 1).reshape(B, T, *first.shape[3:])
    return (first, *(jnp.sum(r, axis=0) for r in rest))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotary_tables(seq, head_dim, rope):
    """cos, sin [seq, rotary_dim] of one kind of layer, from the
    configuration's ``rope_parameters`` entry. YaRN: the plain frequency
    theta^(-2i/d) and the interpolated one (over ``factor``), blended by a
    linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times in ``original_max_position_embeddings``; cos and
    sin times ``attention_factor``."""
    d = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = rope["rope_theta"]
    exponent = jnp.arange(0, d, 2, dtype=jnp.float32) / d
    inv_freq = 1.0 / theta ** exponent
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor, orig = rope["factor"], rope["original_max_position_embeddings"]

        def turns_to_dim(turns):
            return d * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(turns_to_dim(rope.get("beta_fast", 32))), 0)
        high = min(math.ceil(turns_to_dim(rope.get("beta_slow", 1))), d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
        inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate(x, cos, sin):
    """Rotary on the first ``cos.shape[-1]`` dims of x [B, T, H, hd]."""
    d = cos.shape[-1]
    rot, rest = x[..., :d], x[..., d:]
    turned = jnp.concatenate([-rot[..., d // 2:], rot[..., :d // 2]], axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([rot * cos + turned * sin, rest], axis=-1)


def banded_attention(q, k, v, window, precision):
    """q [B, T, H, hd] against k, v [B, T, Hkv, hd]: causal softmax
    attention, key j visible to query i iff 0 <= i - j (< window). A block
    of queries meets every key (full layers) or the ``window - 1`` keys
    before it and its own (window layers); the mask does the rest."""
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    q = q.reshape(B, T, Hkv, H // Hkv, hd)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    if window is None:
        reach = None
    else:
        reach = block + window - 1
        pad = ((0, 0), (window - 1, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    def one_block(start, q_blk):
        rows = start + jnp.arange(block)
        if reach is None:
            k_blk, v_blk, cols = k, v, jnp.arange(T)
        else:
            # Padded position p holds key p - (window - 1).
            k_blk = jax.lax.dynamic_slice_in_dim(k, start, reach, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, start, reach, axis=1)
            cols = start - (window - 1) + jnp.arange(reach)
        scores = product("bqngd,bknd->bngqk", q_blk, k_blk, precision)
        scores = scores / math.sqrt(hd)
        keep = (cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0)
        if window is not None:
            keep &= rows[:, None] - cols[None, :] < window
        scores = jnp.where(keep[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return (product("bngqk,bknd->bqngd", probs, v_blk, precision),)

    out, = in_blocks(one_block, block, q)
    return out.reshape(B, T, H, hd)


def attention(cfg, x, lw, run, precision):
    B, T, _ = x.shape
    hd = cfg["head_dim"]
    a = "self_attn."
    split = lambda y: y.reshape(B, T, -1, hd)            # noqa: E731
    q = split(product("btd,ed->bte", x, lw[a + "q_proj.weight"], precision))
    k = split(product("btd,ed->bte", x, lw[a + "k_proj.weight"], precision))
    v = split(product("btd,ed->bte", x, lw[a + "v_proj.weight"], precision))
    cos, sin = rotary_tables(T, hd, run["rope"])
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    out = banded_attention(q, k, v, run["window"], precision)
    gate = jax.nn.sigmoid(
        product("btd,hd->bth", x, lw[a + "g_proj.weight"], precision))
    out = (out * gate[..., None]).reshape(B, T, -1)
    return product("bte,de->btd", out, lw[a + "o_proj.weight"], precision)


def gated_mlp(x, gate, up, down, precision):
    h = jax.nn.silu(product("...d,fd->...f", x, gate, precision)) \
        * product("...d,fd->...f", x, up, precision)
    return product("...f,df->...d", h, down, precision)


def expert_ffn(cfg, x, lw, precision):
    """The held experts' and the shared expert's part, and the held
    experts' loads [held]."""
    m = "mlp."
    first = cfg.get("experts_held_first", 0)
    held = lw[m + "experts.gate_proj.weight"].shape[0]
    probs = jax.nn.softmax(
        product("btd,ed->bte", x, lw[m + "gate.weight"], precision), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = top_p * cfg.get("moe_routed_scaling_factor", 1.0)
    out = gated_mlp(x, lw[m + "shared_expert.gate_proj.weight"],
                    lw[m + "shared_expert.up_proj.weight"],
                    lw[m + "shared_expert.down_proj.weight"], precision)
    loads = []
    for e in range(held):
        chosen = top_i == first + e                          # [B, T, K]
        weight = jnp.sum(jnp.where(chosen, top_p, 0.0), axis=-1)
        loads.append(jnp.sum(chosen))
        out = out + weight[..., None] * gated_mlp(
            x, lw[m + "experts.gate_proj.weight"][e],
            lw[m + "experts.up_proj.weight"][e],
            lw[m + "experts.down_proj.weight"][e], precision)
    return out, jnp.stack(loads)


def layer_runs(cfg):
    """Consecutive layers of one kind, in layer order: for each run its
    kind, where it starts among the layers of that kind, how many layers,
    and what the layer equations need (window, rope entry, sparse)."""
    pattern, kinds = laguna_weights.plan(cfg)
    seen, runs = {}, []
    for i, kind in enumerate(pattern):
        window = cfg["layer_types"][i] == "sliding_attention"
        if runs and runs[-1]["kind"] == kind:
            runs[-1]["count"] += 1
        else:
            runs.append({
                "kind": kind, "start": seen.get(kind, 0), "count": 1,
                "window": cfg["sliding_window"] if window else None,
                "rope": cfg["rope_parameters"][
                    "sliding_attention" if window else "full_attention"],
                "sparse": kinds[kind]["num_experts"] > 0,
            })
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def layer(cfg, x, lw, run, precision):
    """One layer on x [B, T, D] with its tensors ``lw`` (names without the
    ``model.layers.<kind>.`` prefix): ``(x', loads [held])``."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(
        cfg, rms_norm(x, lw["input_layernorm.weight"], eps), lw, run,
        precision)
    normed = rms_norm(h, lw["post_attention_layernorm.weight"], eps)
    if run["sparse"]:
        out, load = in_blocks(
            lambda _, y: expert_ffn(cfg, y, lw, precision),
            TOKEN_BLOCK, normed)
        return h + out, load
    out, = in_blocks(lambda _, y: (gated_mlp(
        y, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
        lw["mlp.down_proj.weight"], precision),), TOKEN_BLOCK, normed)
    return h + out, jnp.zeros((0,), jnp.int32)


def hidden_states(cfg, w, ids, precision="float32", remat=False):
    """``(hidden states before the last norm [B, T, D], loads [sparse
    layers, held])``. Each run of layers of one kind is a ``lax.scan``
    over its slice of that kind's stacked tensors (so the gradient of a
    stack is written slice by slice, not summed from padded copies)."""
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
    x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    return product("btd,vd->btv", x, w["lm_head.weight"], precision)


def forward(cfg, w, ids, precision="float32", remat=False):
    """``(logits [B, T, V], loads [sparse layers, held])``."""
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

    _, total = in_blocks(
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


@functools.partial(jax.jit, donate_argnums=(0,))
def add_into(total, part):
    return jax.tree_util.tree_map(jnp.add, total, part)


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def apply_adamw(w, mu, nu, grads, step, lr):
    """``train.adamw`` on buffers it may reuse, and the gradient's per-leaf
    norms; ``step`` counts from 1."""
    norms = train.leaf_norms(grads)
    return (*train.adamw(w, grads, mu, nu, step, lr), norms)


def loss_and_grads(cfg, w, ids, precision):
    """Mean loss over every predicted position of ``ids`` [B, T], its
    gradient summed one row at a time, the loads over the batch. One
    compiled program a row (not a scan over rows): beside the float32
    training state only one row's gradient and one sum are ever alive."""
    count = ids.shape[0] * (ids.shape[1] - 1)
    static = hashable(cfg)
    loss = grads = loads = None
    for row in ids:
        part, grad, load = row_gradient(*static, w, row, precision, count)
        loss = part if loss is None else loss + part
        loads = load if loads is None else loads + load
        grads = grad if grads is None else add_into(grads, grad)
        del grad
        # The host must not run ahead: a row's program is given its
        # buffers when it is enqueued, and four rows' would not fit.
        jax.block_until_ready(grads)
    return loss, grads, loads


@functools.partial(jax.jit, static_argnums=(0, 1))
def change_norms(scalars, groups, w, seed):
    """Per-leaf norm of ``w`` minus the seeded leaf made again from
    ``seed``: no second copy of the start is ever kept."""
    spec = laguna_weights.spec_for(unhashable(scalars, groups))
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
        w, mu, nu, norms = apply_adamw(
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
    import json

    keep = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
            "rope_parameters", "mlp_only_layers")
    return (train.hashable(cfg),
            tuple((k, json.dumps(cfg[k], sort_keys=True)) for k in keep))


def unhashable(scalars, groups):
    import json

    return dict(scalars, **{k: json.loads(v) for k, v in groups})
