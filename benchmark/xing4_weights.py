"""Seeded weights of the Xing4.0 family (``model_type`` "xing4_0"), as
``laguna_weights.py`` makes Laguna's: every leaf random from ``--seed``,
made on the device inside one jitted call, under Hugging Face names (the
convention ``nn/huggingface/xing4.py`` assumes: the DeepSeek-V3 class's,
and that file's own for the hyper-connections' leaves), per-layer tensors
stacked **by kind of layer** (``plan(cfg)``'s names: ``lead_dense``,
``full``) and the routed experts held here over a second axis. All
matrices N(0, initializer_range), the input table, the connections' ``phi``
and the selection bias among them; RMSNorm scales, the latents' and the
connections' among them, 1 + N(0, initializer_range); linear weights
[out, in], as torch keeps them.

Three kinds of leaf are the seeded normal plus a constant, put on in
``make_weights`` (``OFFSETS``): a connection's ``alpha`` (0.01 + N(0,
0.002)) and ``bias`` (pre -ln(n - 1), post 0, res 6 on the diagonal, each +
N(0, 0.02): the sub-layer starts on the streams' mean, every stream takes
its output whole, the streams start almost unmixed), and the selection bias,
whose values of the experts held here are repeated over every group of as
many experts (``held_bias_everywhere``, as ``lfm2_weights.py`` says why).
The per-leaf norms of the parameters' change
(``train_steps_experts.Trainer.leaf_norms``, ``reference/xing4.change_norms``)
subtract the leaf as ``weights.make_leaf`` makes it, so for these leaves
they read the change plus that constant, the same in the program and the
reference: their gap there says little, and their first gradients are
compared like every other leaf's.

The configuration's file holds the chip's share (``n_routed_experts`` held
of ``n_routed_experts_published``, ``vocab_size`` the slice, the heads
held, ``layer_types`` the layers kept and ``first_k_dense_replace`` those
of them that lead).
"""

import math

from benchmark import weights
from benchmark.laguna_weights import (  # noqa: F401  (this family's too)
    layers_of,
    token_batches,
)

HC_SITES = ("attn_hc", "ffn_hc")


def hf_view(cfg):
    """The configuration as ``nn/huggingface/xing4.py`` reads it: the
    depth that is run, the router at its published width with the range
    of experts held here."""
    view = dict(cfg)
    view["num_hidden_layers"] = len(cfg["layer_types"])
    view["n_routed_experts"] = cfg["n_routed_experts_published"]
    view["experts_held"] = (cfg.get("experts_held_first", 0),
                            cfg["n_routed_experts"])
    return view


def plan(cfg):
    """``(pattern, kinds)`` of ``xing4.layer_plan`` for this file."""
    from smdistributed_modelparallel_tpu.nn.huggingface import xing4

    return xing4.layer_plan(hf_view(cfg))


def spec_for(cfg):
    """``{name: (shape, kind, std)}``."""
    pattern, kinds = plan(cfg)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    n_s = cfg["hc_mult"]
    C = n_s * (n_s + 2)
    std = cfg.get("initializer_range", 0.02)
    spec = {
        "model.embed_tokens.weight": ((V, D), "normal", std),
        "model.norm.weight": ((D,), "scale", std),
        "lm_head.weight": ((V, D), "normal", std),
    }
    for kind, layers in layers_of(pattern).items():
        n, kw = len(layers), kinds[kind]
        H = kw["num_attention_heads"]
        p = f"model.layers.{kind}."
        a = p + "self_attn."
        spec.update({
            p + "input_layernorm.weight": ((n, D), "scale", std),
            p + "post_attention_layernorm.weight": ((n, D), "scale", std),
            a + "q_a_proj.weight": ((n, rq, D), "normal", std),
            a + "q_a_layernorm.weight": ((n, rq), "scale", std),
            a + "q_b_proj.weight": ((n, H * (dn + dr), rq), "normal", std),
            a + "kv_a_proj_with_mqa.weight": (
                (n, rkv + dr, D), "normal", std),
            a + "kv_a_layernorm.weight": ((n, rkv), "scale", std),
            a + "kv_b_proj.weight": ((n, H * (dn + dv), rkv), "normal", std),
            a + "o_proj.weight": ((n, D, H * dv), "normal", std),
        })
        for site in HC_SITES:
            h = f"{p}{site}."
            spec.update({
                h + "norm.weight": ((n, n_s * D), "scale", std),
                h + "phi.weight": ((n, C, n_s * D), "normal", std),
                h + "alpha": ((n, 3), "normal", 0.1 * std),
                h + "bias": ((n, C), "normal", std),
            })
        F, m = kw["intermediate_size"], p + "mlp."
        if not kw["num_experts"]:
            lead = (n,)
        else:
            E = kw["num_experts"]
            spec[m + "gate.weight"] = ((n, E, D), "normal", std)
            spec[m + "gate.e_score_correction_bias"] = (
                (n, E), "normal", std)
            Fs, s = kw["moe_shared_intermediate_size"], m + "shared_experts."
            spec[s + "gate_proj.weight"] = ((n, Fs, D), "normal", std)
            spec[s + "up_proj.weight"] = ((n, Fs, D), "normal", std)
            spec[s + "down_proj.weight"] = ((n, D, Fs), "normal", std)
            lead, m = (n, kw["moe_held"][1]), m + "experts."
        spec[m + "gate_proj.weight"] = (lead + (F, D), "normal", std)
        spec[m + "up_proj.weight"] = (lead + (F, D), "normal", std)
        spec[m + "down_proj.weight"] = (lead + (D, F), "normal", std)
    return spec


def held_bias_everywhere(cfg, bias):
    """``bias`` [layers, experts] with the values of the experts held
    here repeated over every group of as many experts."""
    import jax.numpy as jnp

    first, held = cfg.get("experts_held_first", 0), cfg["n_routed_experts"]
    groups, rest = divmod(bias.shape[-1], held)
    if rest:
        return bias
    return jnp.tile(bias[:, first:first + held], (1, groups))


def connection_bias_offset(streams):
    """What a connection's seeded ``bias`` stands on: pre -ln(n - 1), post
    0, res 6 on the diagonal."""
    import numpy as np

    return np.concatenate([
        np.full((streams,), -math.log(max(streams - 1, 1)), np.float32),
        np.zeros((streams,), np.float32),
        (6.0 * np.eye(streams, dtype=np.float32)).reshape(-1)])


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word."""
    out = {}
    for name, entry in spec_for(cfg).items():
        leaf = weights.make_leaf(seed, name, *entry)
        if name.endswith(".e_score_correction_bias"):
            leaf = held_bias_everywhere(cfg, leaf)
        elif name.endswith("_hc.alpha"):
            leaf = 0.01 + leaf
        elif name.endswith("_hc.bias"):
            leaf = leaf + connection_bias_offset(cfg["hc_mult"])
        out[name] = leaf
    return out
