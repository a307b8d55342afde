"""Seeded weights of the LFM2-MoE family (``model_type`` "lfm2_moe"), as
``laguna_weights.py`` makes Laguna's: every leaf random from ``--seed``,
made on the device inside one jitted call, under Hugging Face names (the
convention ``nn/huggingface/lfm2_moe.py`` assumes), per-layer tensors
stacked **by kind of layer** (``plan(cfg)``'s names: ``lead_dense_conv``,
``full``, ``conv``) and the routed experts held here over a second axis.
All matrices N(0, initializer_range), the convolution's taps, the selection
bias and the input table among them (``embedding_range``, where a file
states one, is the table's own; this family's file states 0.02 and says
why not Mellum's 1.0: the table is also the head's, tied); RMSNorm
scales, the per-head q/k norms' among them, 1 + N(0,
initializer_range); linear weights [out, in], the depthwise convolution's
[channels, 1, taps], as torch keeps them.

The selection bias is seeded and not zero so that a program which leaves
it out of the selection, or adds it to the weights, fails the comparison;
no step moves it (``reference/lfm2.py`` holds it as the program does).
**Each layer's seeded values of the experts held here are repeated over
every group of as many experts** (``held_bias_everywhere``): a bias of
0.02 moves an expert's load by about 28% (the k largest of 64 sigmoid
scores lie where a score's density is steep), so with 64 independent
values the rows that land on the 8 held follow the seed by 5% and the
step's time with them; with every chip of the group holding the same
eight values the held experts' expected load is the deployment's, by
symmetry, whatever the values (``PERF.md``, PR 42). The per-leaf norms of
the parameters' change (``train_steps_experts.Trainer.leaf_norms``,
``reference/lfm2.change_norms``) subtract the leaf as ``weights.make_leaf``
makes it, so they read this leaf's constant distance from that, the same
number in the program and the reference, and its gap reads 0 while no step
moves it.

The configuration's file holds the chip's share as Laguna's does
(``num_experts`` held of ``num_experts_published``, ``vocab_size`` the
slice, the heads held, ``layer_types`` the layers kept and
``num_dense_layers`` those of them that lead), so the view of it that the
translator reads, the layers by kind and the token batches are
``laguna_weights``' own.
"""

from benchmark import weights
from benchmark.laguna_weights import (  # noqa: F401  (this family's too)
    hf_view,
    layers_of,
    token_batches,
)


def plan(cfg):
    """``(pattern, kinds)`` of ``lfm2_moe.layer_plan`` for this file."""
    from smdistributed_modelparallel_tpu.nn.huggingface import lfm2_moe

    return lfm2_moe.layer_plan(hf_view(cfg))


def spec_for(cfg):
    """``{name: (shape, kind, std)}``."""
    pattern, kinds = plan(cfg)
    D, hd, V = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    spec = {
        "model.embed_tokens.weight": (
            (V, D), "normal", cfg.get("embedding_range", std)),
        "model.embedding_norm.weight": ((D,), "scale", std),
    }
    for kind, layers in layers_of(pattern).items():
        n, kw = len(layers), kinds[kind]
        p = f"model.layers.{kind}."
        spec[p + "operator_norm.weight"] = ((n, D), "scale", std)
        spec[p + "ffn_norm.weight"] = ((n, D), "scale", std)
        if kw.get("conv_mixer"):
            K = kw["conv_mixer"]
            spec[p + "conv.in_proj.weight"] = ((n, 3 * D, D), "normal", std)
            spec[p + "conv.conv.weight"] = ((n, D, 1, K), "normal", std)
            spec[p + "conv.out_proj.weight"] = ((n, D, D), "normal", std)
        else:
            H, Hkv = kw["num_attention_heads"], kw["num_key_value_heads"]
            a = p + "self_attn."
            spec[a + "q_proj.weight"] = ((n, H * hd, D), "normal", std)
            spec[a + "k_proj.weight"] = ((n, Hkv * hd, D), "normal", std)
            spec[a + "v_proj.weight"] = ((n, Hkv * hd, D), "normal", std)
            spec[a + "out_proj.weight"] = ((n, D, H * hd), "normal", std)
            spec[a + "q_layernorm.weight"] = ((n, hd), "scale", std)
            spec[a + "k_layernorm.weight"] = ((n, hd), "scale", std)
        F, m = kw["intermediate_size"], p + "feed_forward."
        if not kw["num_experts"]:
            lead = (n,)
        else:
            E = kw["num_experts"]
            spec[m + "gate.weight"] = ((n, E, D), "normal", std)
            spec[m + "expert_bias"] = ((n, E), "normal", std)
            lead, m = (n, kw["moe_held"][1]), m + "experts."
        spec[m + "w1.weight"] = (lead + (F, D), "normal", std)
        spec[m + "w3.weight"] = (lead + (F, D), "normal", std)
        spec[m + "w2.weight"] = (lead + (D, F), "normal", std)
    return spec


def held_bias_everywhere(cfg, bias):
    """``bias`` [layers, experts] with the values of the experts held
    here repeated over every group of as many experts."""
    import jax.numpy as jnp

    first, held = cfg.get("experts_held_first", 0), cfg["num_experts"]
    groups, rest = divmod(bias.shape[-1], held)
    if rest:
        return bias
    return jnp.tile(bias[:, first:first + held], (1, groups))


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word."""
    made = {name: weights.make_leaf(seed, name, *entry)
            for name, entry in spec_for(cfg).items()}
    return {name: held_bias_everywhere(cfg, leaf)
            if name.endswith(".expert_bias") else leaf
            for name, leaf in made.items()}
