"""Seeded weights of the Ouro family (``model_type`` "ouro"), as
``weights.py`` makes GPT-2's: every leaf random from ``--seed``, made on
the device inside one jitted call, under Hugging Face names (the convention
``nn/huggingface/ouro.py`` assumes), per-layer tensors stacked over a
leading [layers] axis and named without the layer index
(``model.layers.self_attn.q_proj.weight`` holds every layer's). All
matrices N(0, initializer_range), the table, the head and the exit gate's
weight among them; RMSNorm scales 1 + N(0, initializer_range); linear
weights [out, in], as torch keeps them.

One leaf is the seeded normal plus a constant: the exit gate's bias stands
on the configuration's ``exit_gate_bias`` (kind ``gate_bias`` in the spec;
``make_leaf`` puts it on, so the parameters' change is taken against the
leaf as it was loaded). The configuration's file says why it is not 0.

This module stands where ``drivers/train_steps.py`` names ``weights``
(``drivers/train_steps_looped.py`` puts it there).
"""

import math

from benchmark import weights
from benchmark.laguna_weights import token_batches  # noqa: F401
from benchmark.weights import seed_word  # noqa: F401

LAYER = "model.layers."


def depth(cfg):
    return len(cfg["layer_types"])


def hf_view(cfg):
    """The configuration as ``nn/huggingface/ouro.py`` reads it: the depth
    that is run."""
    return dict(cfg, num_hidden_layers=depth(cfg))


def spec_for(cfg):
    """``{name: (shape, kind, std)}``."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    E = cfg["num_attention_heads"] * cfg["head_dim"]
    L, std = depth(cfg), cfg.get("initializer_range", 0.02)
    spec = {
        "model.embed_tokens.weight": ((V, D), "normal", std),
        "model.norm.weight": ((D,), "scale", std),
        "model.early_exit_gate.weight": ((1, D), "normal", std),
        "model.early_exit_gate.bias": (
            (1,), ("gate_bias", cfg.get("exit_gate_bias", 0.0)), std),
        "lm_head.weight": ((V, D), "normal", std),
    }
    for name in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2"):
        spec[f"{LAYER}{name}.weight"] = ((L, D), "scale", std)
    for name in "qkv":
        spec[f"{LAYER}self_attn.{name}_proj.weight"] = (
            (L, E, D), "normal", std)
    spec[LAYER + "self_attn.o_proj.weight"] = ((L, D, E), "normal", std)
    spec[LAYER + "mlp.gate_proj.weight"] = ((L, F, D), "normal", std)
    spec[LAYER + "mlp.up_proj.weight"] = ((L, F, D), "normal", std)
    spec[LAYER + "mlp.down_proj.weight"] = ((L, D, F), "normal", std)
    return spec


def make_leaf(seed, name, shape, kind, std):
    """One leaf; ``seed`` is a uint32 scalar, traced or not."""
    if isinstance(kind, tuple):
        return kind[1] + weights.make_leaf(seed, name, shape, "normal", std)
    return weights.make_leaf(seed, name, shape, kind, std)


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word."""
    return {name: make_leaf(seed, name, *entry)
            for name, entry in spec_for(cfg).items()}


def parameters(cfg):
    return sum(math.prod(shape) for shape, _, _ in spec_for(cfg).values())
