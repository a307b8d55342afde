"""Seeded weights of the Mellum family (``model_type`` "mellum"), as
``laguna_weights.py`` makes Laguna's: every leaf random from ``--seed``,
made on the device inside one jitted call, under Hugging Face names (the
convention ``nn/huggingface/mellum.py`` assumes), per-layer tensors stacked
**by kind of layer** (``plan(cfg)``'s names: ``window``, ``full``) and the
routed experts held here over a second axis. All matrices N(0,
initializer_range) but the input table, whose rows are N(0,
embedding_range); RMSNorm scales, the per-head q/k norms' among them,
1 + N(0, initializer_range); linear weights [out, in].

**Why the input table has a range of its own** (the file states 1.0: token
vectors of unit rms). With rows of 0.02 a token's vector is as small as
what attention adds to it, the mean of the values before it, which all
tokens of a context share: half of the stream is then common to them, the
router (published width, no balancing term in the config) sends most
tokens to the same few experts, and how many of those are among the 16
held is a matter of the seed: 1.81-2.37 held rows a token a layer over six
seeds at the seeded weights, max / mean load 1.5-5.4, where every expert is
present in the deployment and a trained router is balanced. With rows of
unit rms the stream is the token's own: 1.989-2.002 over four seeds, max /
mean 1.07-1.22 (counted on the CPU with the plain reference at the
published widths; ``PERF.md``, PR 31). The rate of a step follows the rows,
so the first choice made the cell's rate a draw of the seed.

What differs from Laguna's leaves: ``self_attn.{q,k}_norm.weight``
[layers, head_dim] and no ``g_proj``; every layer routed, with no shared
expert. The configuration's file holds the chip's share as Laguna's does
(``num_experts`` held of ``num_experts_published``, ``vocab_size`` the
slice, the heads held, ``layer_types`` the layers kept), so the view of it
that the translator reads, the layers by kind and the token batches are
``laguna_weights``' own.
"""

from benchmark import weights
from benchmark.laguna_weights import (  # noqa: F401  (this family's too)
    hf_view,
    layers_of,
    token_batches,
)


def plan(cfg):
    """``(pattern, kinds)`` of ``mellum.layer_plan`` for this file."""
    from smdistributed_modelparallel_tpu.nn.huggingface import mellum

    return mellum.layer_plan(hf_view(cfg))


def spec_for(cfg):
    """``{name: (shape, kind, std)}``."""
    pattern, kinds = plan(cfg)
    D, hd, V = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    spec = {
        "model.embed_tokens.weight": (
            (V, D), "normal", cfg.get("embedding_range", std)),
        "model.norm.weight": ((D,), "scale", std),
        "lm_head.weight": ((V, D), "normal", std),
    }
    for kind, layers in layers_of(pattern).items():
        n, kw = len(layers), kinds[kind]
        H, Hkv = kw["num_attention_heads"], kw["num_key_value_heads"]
        E, F = kw["num_experts"], kw["intermediate_size"]
        held = kw["moe_held"][1]
        p = f"model.layers.{kind}."
        spec.update({
            p + "input_layernorm.weight": ((n, D), "scale", std),
            p + "post_attention_layernorm.weight": ((n, D), "scale", std),
            p + "self_attn.q_proj.weight": ((n, H * hd, D), "normal", std),
            p + "self_attn.k_proj.weight": ((n, Hkv * hd, D), "normal", std),
            p + "self_attn.v_proj.weight": ((n, Hkv * hd, D), "normal", std),
            p + "self_attn.o_proj.weight": ((n, D, H * hd), "normal", std),
            p + "self_attn.q_norm.weight": ((n, hd), "scale", std),
            p + "self_attn.k_norm.weight": ((n, hd), "scale", std),
            p + "mlp.gate.weight": ((n, E, D), "normal", std),
            p + "mlp.experts.gate_proj.weight": (
                (n, held, F, D), "normal", std),
            p + "mlp.experts.up_proj.weight": ((n, held, F, D), "normal", std),
            p + "mlp.experts.down_proj.weight": (
                (n, held, D, F), "normal", std),
        })
    return spec


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word."""
    return {name: weights.make_leaf(seed, name, *entry)
            for name, entry in spec_for(cfg).items()}
