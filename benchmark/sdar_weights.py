"""Seeded weights and batches of the SDAR-MoE family (``model_type``
"sdar_moe"). The leaves are Mellum's (``mellum_weights.py``: the Qwen3-MoE
class's matrices under Hugging Face names, stacked by kind of layer, the
routed experts held here over a second axis, per-head q/k norm scales, no
shared expert; all N(0, initializer_range) but the input table's rows,
N(0, embedding_range), for the reason that file gives), with this family's
layer plan: one kind, ``full``. ``spec_for`` and ``make_weights`` are that
file's functions on a copy of it that reads this plan, as
``drivers/train_steps_expert_family.py`` puts a family in Laguna's place.

The batches are the family's own. Training is by block diffusion, and the
noise is data: a batch holds

    clean   [batch, seq] int32    x0: ids with p(k) ~ 1 / (k + offset) over
                                  the data rows (every held row but the
                                  last, which stands for the mask id)
    noisy   [batch, seq] int32    xt: each token of block k replaced by the
                                  mask id with probability rates[k]
    rates   [batch, seq / block]  p_k = eps + (1 - eps) u_k, u_k ~ U(0, 1)
    mask_id scalar int32

all made from the seed, so the program and the reference see one draw and
nothing is drawn inside a step.
"""

import os

from benchmark import laguna_weights, loader, weights
from benchmark.laguna_weights import hf_view, layers_of  # noqa: F401


def plan(cfg):
    """``(pattern, kinds)`` of ``sdar.layer_plan`` for this file."""
    from smdistributed_modelparallel_tpu.nn.huggingface import sdar

    return sdar.layer_plan(hf_view(cfg))


_leaves = loader.load_module(
    os.path.join(loader.HERE, "mellum_weights.py"), "benchmark_sdar_leaves")
_leaves.plan = plan
spec_for = _leaves.spec_for

ROUTER = "mlp.gate.weight"
TABLE = "model.embed_tokens.weight"


def make_leaf(cfg, seed, name):
    """One leaf as this configuration makes it: from ``seed`` (a uint32
    word, traced or not), but for what the configuration's
    ``routing_seeds`` states: each layer's router matrix comes from that
    layer's stated seed and the input table's mask row from its own, the
    same in every run (the file's ``departures`` says why: which of the
    mask token's experts are held here would else be the run's draw)."""
    import jax.numpy as jnp
    import numpy as np

    stated = cfg.get("routing_seeds")
    shape, kind, std = spec_for(cfg)[name]
    if stated is None:
        return weights.make_leaf(seed, name, shape, kind, std)
    if name.endswith(ROUTER):
        return jnp.stack([
            weights.make_leaf(np.uint32(stated["router"][layer]),
                              f"{name}#layer{layer}", shape[1:], kind, std)
            for layer in range(shape[0])])
    leaf = weights.make_leaf(seed, name, shape, kind, std)
    if name == TABLE:
        leaf = leaf.at[cfg["mask_token_id"]].set(weights.make_leaf(
            np.uint32(stated["mask_row"]), name + "#mask_row", shape[1:],
            kind, std))
    return leaf


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word."""
    return {name: make_leaf(cfg, seed, name) for name in spec_for(cfg)}


SAMPLE = 4096


def gradient_sample(named):
    """A fixed sample of each leaf of ``named`` (a gradient under Hugging
    Face names): every n-th element of the leaf laid flat, ``SAMPLE`` of
    them, the same elements on the program's side and on the reference's.
    The driver compares the two samples leaf by leaf as vectors: a
    difference of norms reads what rounding does to a gradient's length,
    which is little; this reads what it does to the gradient."""
    out = {}
    for name, leaf in named.items():
        flat = leaf.reshape(-1)
        out[name] = flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE]
    return out


class Batches:
    """A pool of batches: ``pool[i]`` is batch i as the step and the
    reference take it (a dict), ``pool[:n]`` the first n."""

    def __init__(self, stacked, mask_id):
        self.stacked, self.mask_id = stacked, mask_id

    def __len__(self):
        return len(self.stacked["clean"])

    def __getitem__(self, i):
        part = {k: v[i] for k, v in self.stacked.items()}
        if isinstance(i, slice):
            return Batches(part, self.mask_id)
        return dict(part, mask_id=self.mask_id)


def diffusion_batches(seed, count, batch, seq, data_rows, offset, block,
                      eps, mask_id):
    """The stacked arrays of ``count`` batches, a traceable function of
    the seed word."""
    import jax
    import jax.numpy as jnp

    clean = laguna_weights.token_batches(
        seed, count, batch, seq, data_rows, offset)
    key = jax.random.fold_in(jax.random.key(seed), 0x6E7A)
    k_rate, k_mask = jax.random.split(key)
    rates = eps + (1.0 - eps) * jax.random.uniform(
        k_rate, (count, batch, seq // block), jnp.float32)
    masked = jax.random.uniform(
        k_mask, (count, batch, seq), jnp.float32) < jnp.repeat(
            rates, block, axis=-1)
    return {"clean": clean, "rates": rates,
            "noisy": jnp.where(masked, jnp.int32(mask_id), clean)}


def make_batches(cfg, mix, seed_word):
    """The mix's pool for a configuration: ids from every held row but the
    mask's, the configuration's block length and mask id, the mix's
    schedule."""
    import jax
    import jax.numpy as jnp

    law, noise = mix["token_law"], mix["noise"]
    if law["kind"] != "zipf_mandelbrot" or noise["kind"] != "linear_per_block":
        raise ValueError(f"unknown token_law or noise: {law}, {noise}")
    mask_id = cfg["mask_token_id"]
    if mask_id != cfg["vocab_size"] - 1:
        raise ValueError("the mask id is the last held row")
    stacked = jax.jit(lambda s: diffusion_batches(
        s, mix["batch_pool"], mix["batch"], mix["seq"], mask_id,
        law["offset"], cfg["block_length"], noise["eps"], mask_id))(seed_word)
    return Batches(stacked, jnp.int32(mask_id))
