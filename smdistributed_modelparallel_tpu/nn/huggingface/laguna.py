"""HF Laguna translation (poolside Laguna-S / Laguna-XS: ``model_type``
"laguna").

Structure, from the published ``config.json``: RMSNorm pre-norm decoder, no
biases, untied head; grouped KV heads with a per-layer count of query heads
(``num_attention_heads_per_layer``), a sigmoid gate on each head's
attention output (``gating``), full-attention and sliding-window layers
(``layer_types``, ``sliding_window``) with their own rotary
(``rope_parameters``: YaRN on part of the head in full layers, plain on the
whole head in window layers); a dense gated MLP in ``mlp_only_layers`` and
routed experts elsewhere (``num_experts`` at ``num_experts_per_tok``,
``norm_topk_prob``, ``moe_routed_scaling_factor``) beside one shared expert.

The stack is built from a static per-layer pattern
(``DistributedTransformer.layer_pattern``); each layer's parameters live
where ``pattern_layer_paths`` says.

Assumed (no network here, and the modelling code is not in the config):
the state-dict names follow the Llama / Qwen2-MoE convention the config's
key names come from — ``self_attn.{q,k,v,o}_proj``, the head gate as
``self_attn.g_proj`` [H, D], ``mlp.{gate,up,down}_proj`` in a dense layer,
``mlp.gate`` [E, D] for the router, ``mlp.experts.{e}.*`` and
``mlp.shared_expert.*`` in a sparse one; rotary rotates halves (HF
``rotate_half``). A chip's share: ``config.experts_held = (first, count)``
(not an HF key) keeps only those experts' tensors, under their published
indices.

What a sibling family with a patterned stack shares lives here too
(``nn/huggingface/mellum.py`` imports it): the rotary of a layer type, the
attention and expert fields of a layer kind, the decoder's kwargs, the
tensor functions (a head gate and per-head q/k norm scales are taken where
the state dict has them) and both translators, which take the family's
``layer_plan``.
"""

import numpy as np

from smdistributed_modelparallel_tpu.nn.huggingface import common as c
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

HF_ARCHITECTURES = ("LagunaForCausalLM", "LagunaModel")

STACK = "transformer"


def _get(config, key, default=None):
    if isinstance(config, dict):
        return config.get(key, default)
    return getattr(config, key, default)


def _rope(config, layer_type):
    rope = dict(_get(config, "rope_parameters")[layer_type])
    hd = _get(config, "head_dim")
    out = {
        "rotary_dim": int(hd * rope.get("partial_rotary_factor", 1)),
        "rotary_emb_base": float(rope["rope_theta"]),
        "rotary_yarn": None,
    }
    if rope.get("rope_type", "default") == "yarn":
        factor = float(rope["factor"])
        out["rotary_yarn"] = (
            factor, int(rope["original_max_position_embeddings"]),
            float(rope.get("beta_fast", 32)), float(rope.get("beta_slow", 1)),
            float(rope.get("attention_factor") or 0.1 * np.log(factor) + 1.0),
        )
    elif rope.get("rope_type", "default") != "default":
        raise SMPValidationError(
            f"laguna: rope_type {rope['rope_type']!r} is not supported."
        )
    return out


def attention_kind(config, heads, window, **fields):
    """A layer kind's attention fields: ``heads`` query heads on the
    config's KV heads, the window and the rotary of its layer type, and
    what the family adds (``head_gate``, ``qk_norm``)."""
    Hkv = _get(config, "num_key_value_heads")
    if heads == Hkv:
        raise SMPValidationError(
            "as many KV heads as query heads is not a shape the patterned "
            "families have; the translator expects grouped KV heads."
        )
    return dict(
        num_attention_heads=heads, num_key_value_heads=Hkv,
        window_size=_get(config, "sliding_window") if window else None,
        **_rope(config, "sliding_attention" if window else "full_attention"),
        **fields,
    )


def experts_kind(config):
    """A sparse layer kind's MLP fields: the dropless expert layer at the
    router's published width, told the ``experts_held`` range."""
    held = _get(config, "experts_held")
    return dict(
        intermediate_size=_get(config, "moe_intermediate_size"),
        num_experts=_get(config, "num_experts"),
        moe_top_k=_get(config, "num_experts_per_tok"),
        moe_dropless=True,
        moe_held=tuple(held) if held is not None else None,
        moe_shared_intermediate_size=_get(
            config, "shared_expert_intermediate_size", 0) or 0,
        moe_norm_topk=bool(_get(config, "norm_topk_prob", True)),
        moe_routed_scaling=float(
            _get(config, "moe_routed_scaling_factor", 1.0)),
    )


def layer_plan(config):
    """``(pattern, kinds)``: a kind name for each layer and each kind's
    overrides of ``DistributedTransformerLayer``'s fields. Sparse layers
    are ``full`` / ``window``; dense-MLP layers before the first sparse one
    ``lead_dense`` (``lead_dense_window`` with a window), later ones
    ``<attention>_dense``; two layers of one name that still differ (head
    count) get ``_h<heads>``."""
    L = _get(config, "num_hidden_layers")
    types = list(_get(config, "layer_types"))[:L]
    heads = list(_get(config, "num_attention_heads_per_layer")
                 or [_get(config, "num_attention_heads")] * L)[:L]
    dense = set(_get(config, "mlp_only_layers") or [])
    mlp_types = _get(config, "mlp_layer_types")
    if mlp_types is not None:
        dense = {i for i, t in enumerate(list(mlp_types)[:L]) if t == "dense"}
    gating = _get(config, "gating")
    if gating not in (True, "per-head", "per_head"):
        raise SMPValidationError(
            f"laguna: gating {gating!r} is not the per-head output gate."
        )
    first_sparse = min((i for i in range(L) if i not in dense), default=L)
    pattern, kinds = [], {}
    for i in range(L):
        window = types[i] == "sliding_attention"
        attn = "window" if window else "full"
        kw = attention_kind(config, heads[i], window, head_gate=True)
        if i in dense:
            name = ("lead_dense" + ("_window" if window else "")
                    if i < first_sparse else f"{attn}_dense")
            kw.update(intermediate_size=_get(config, "intermediate_size"),
                      num_experts=0)
        else:
            name = attn
            kw.update(experts_kind(config))
        if kinds.get(name, kw) != kw:
            name = f"{name}_h{heads[i]}"
        if kinds.setdefault(name, kw) != kw:
            raise SMPValidationError(
                f"laguna: layer {i} differs from layer kind {name!r} in "
                "more than its head count."
            )
        pattern.append(name)
    return tuple(pattern), kinds


def config_to_smp(config):
    """Laguna config -> ``DistributedTransformerLMHead`` kwargs."""
    if _get(config, "attention_bias", False):
        raise SMPValidationError("laguna: attention_bias is not supported.")
    if _get(config, "moe_router_logit_softcapping", 0):
        raise SMPValidationError(
            "laguna: router logit soft-capping is not supported.")
    return decoder_kwargs(config, *layer_plan(config))


def decoder_kwargs(config, pattern, kinds):
    """``DistributedTransformerLMHead`` kwargs of an RMSNorm pre-norm
    decoder with no biases, a gated MLP and rotary on halves, its stack
    built from ``pattern`` and ``kinds``."""
    return {
        "num_layers": _get(config, "num_hidden_layers"),
        "num_attention_heads": _get(config, "num_attention_heads"),
        "attention_head_size": _get(config, "head_dim"),
        "hidden_size": _get(config, "hidden_size"),
        "intermediate_size": _get(config, "intermediate_size"),
        "vocab_size": _get(config, "vocab_size"),
        "layer_pattern": pattern,
        "layer_kinds": kinds,
        "layernorm_type": "rms",
        "layernorm_epsilon": _get(config, "rms_norm_eps", 1e-6),
        "activation": _get(config, "hidden_act", "silu"),
        "gated_mlp": True,
        "use_mlp_bias": False,
        "use_qkv_bias": False,
        "use_attn_dense_bias": False,
        "use_lm_head_bias": False,
        "gpt_neox_type_rotary": True,
        "use_positional_embedding": False,
        "tie_input_output_embedding": bool(
            _get(config, "tie_word_embeddings", False)),
        "final_layernorm": True,
        "pre_layernorm": True,
        "post_layernorm": False,
        "add_lm_head": True,
        "mask_value": -1e9,
        "causal_mask_size": _get(config, "max_position_embeddings"),
        "num_positions": _get(config, "max_position_embeddings"),
        "attention_dropout_prob": 0.0,
        "hidden_dropout_prob": 0.0,
        "embedding_dropout_prob": 0.0,
        "initializer_range": _get(config, "initializer_range", 0.02),
        "scale_attention_scores": True,
    }


# ----------------------------------------------------------------------
# One layer's tensors, HF names <-> the layer module's names. Written over
# an array namespace (numpy here, jax.numpy for a jitted builder) on
# tensors that may carry leading stack axes.
# ----------------------------------------------------------------------


def _t(x):
    """Swap the last two axes ([out, in] <-> [in, out])."""
    return x.swapaxes(-1, -2)


def attention_from_hf(q, k, v, o, g, hd, xp=np):
    """``q_proj`` [.., H*hd, D], ``k_proj`` / ``v_proj`` [.., Hkv*hd, D],
    ``o_proj`` [.., D, H*hd], ``g_proj`` [.., H, D] or ``None`` -> the
    attention layer's ``query``, ``key_value``, ``dense`` and (with a
    ``g_proj``) ``gate`` kernels."""
    lead, D = q.shape[:-2], q.shape[-1]
    heads = lambda w: _t(w).reshape(*lead, D, -1, hd)   # noqa: E731
    out = {
        "attention/query/kernel": heads(q),
        "attention/key_value/kernel": xp.stack(
            [heads(k), heads(v)], axis=len(lead) + 1),
        "attention/dense/kernel": _t(o).reshape(*lead, -1, hd, D),
    }
    if g is not None:
        out["attention/gate/kernel"] = _t(g)
    return out


def attention_to_hf(layer):
    """Inverse of ``attention_from_hf``: ``(q, k, v, o, g)``, ``g`` ``None``
    for a layer without a head gate."""
    query, kv = layer["attention/query/kernel"], layer["attention/key_value/kernel"]
    dense = layer["attention/dense/kernel"]
    lead, D = query.shape[:-3], query.shape[-3]
    flat = lambda w: _t(w.reshape(*lead, D, -1))        # noqa: E731
    n = len(lead)
    k, v = kv[(slice(None),) * (n + 1) + (0,)], kv[(slice(None),) * (n + 1) + (1,)]
    gate = layer.get("attention/gate/kernel")
    return (flat(query), flat(k), flat(v),
            _t(dense.reshape(*lead, -1, D)),
            None if gate is None else _t(gate))


# A layer's vectors: Hugging Face name under the layer -> the module's.
LAYER_VECTORS = {
    "input_layernorm.weight": "attention/layernorm/scale",
    "post_attention_layernorm.weight": "output/layernorm/scale",
    # per-head q/k RMSNorm scales [hd] of a family that has them
    "self_attn.q_norm.weight": "attention/q_norm/scale",
    "self_attn.k_norm.weight": "attention/k_norm/scale",
}


def gated_mlp_from_hf(gate, up, down, prefix):
    """``gate_proj`` / ``up_proj`` [.., F, D], ``down_proj`` [.., D, F]."""
    return {f"{prefix}/gate/kernel": _t(gate), f"{prefix}/fc/kernel": _t(up),
            f"{prefix}/proj/kernel": _t(down)}


def experts_from_hf(gate, up, down, xp=np):
    """Stacked experts ``gate_proj`` / ``up_proj`` [.., n, F, D] and
    ``down_proj`` [.., n, D, F] -> ``experts/gate_up`` [.., n, D, 2, F] and
    ``experts/down`` [.., n, F, D]."""
    return {
        "output/experts/gate_up/kernel": xp.stack(
            [_t(gate), _t(up)], axis=gate.ndim - 1),
        "output/experts/down/kernel": _t(down),
    }


def experts_to_hf(layer):
    gate_up = layer["output/experts/gate_up/kernel"]
    gate, up = gate_up[..., 0, :], gate_up[..., 1, :]
    return _t(gate), _t(up), _t(layer["output/experts/down/kernel"])


def _layer_from_hf(sd, p, config, sparse):
    hd = _get(config, "head_dim")
    a = f"{p}.self_attn."
    out = attention_from_hf(
        sd[a + "q_proj.weight"], sd[a + "k_proj.weight"],
        sd[a + "v_proj.weight"], sd[a + "o_proj.weight"],
        sd.get(a + "g_proj.weight"), hd)
    for theirs, ours in LAYER_VECTORS.items():
        if f"{p}.{theirs}" in sd:
            out[ours] = sd[f"{p}.{theirs}"]
    m = f"{p}.mlp."
    if not sparse:
        out.update(gated_mlp_from_hf(
            sd[m + "gate_proj.weight"], sd[m + "up_proj.weight"],
            sd[m + "down_proj.weight"], "output"))
        return out
    first, count = (_get(config, "experts_held")
                    or (0, _get(config, "num_experts")))
    stack = lambda name: np.stack([                     # noqa: E731
        sd[f"{m}experts.{e}.{name}.weight"]
        for e in range(first, first + count)])
    out["output/router/kernel"] = _t(sd[m + "gate.weight"])
    out.update(experts_from_hf(
        stack("gate_proj"), stack("up_proj"), stack("down_proj")))
    if _get(config, "shared_expert_intermediate_size", 0):
        s = m + "shared_expert."
        out.update(gated_mlp_from_hf(
            sd[s + "gate_proj.weight"], sd[s + "up_proj.weight"],
            sd[s + "down_proj.weight"], "output/shared"))
    return out


def _stack_by_path(per_layer, pattern):
    """Per-layer dicts -> ``{flat key: array stacked over the scan axes}``
    at each layer's place in the patterned stack."""
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    where = pattern_layer_paths(pattern)
    shapes = {}
    for path, index in where:
        lead = shapes.setdefault(path, [0] * len(index))
        for axis, i in enumerate(index):
            lead[axis] = max(lead[axis], i + 1)
    out = {}
    for (path, index), layer in zip(where, per_layer):
        for key, value in layer.items():
            flat = f"{STACK}/{path}/{key}"
            if flat not in out:
                out[flat] = np.zeros(
                    tuple(shapes[path]) + value.shape, value.dtype)
            out[flat][index] = value
    return out


def translate_hf_state_dict(sd, config=None, plan=layer_plan):
    """HF state dict -> flat '/'-keyed smp param dict; ``plan`` is the
    family's ``layer_plan``."""
    if config is None:
        raise SMPValidationError("config required for the layer pattern.")
    sd = {k: c.to_np(v) for k, v in sd.items()}
    pattern, kinds = plan(config)
    per_layer = [
        _layer_from_hf(sd, f"model.layers.{i}", config,
                       kinds[kind]["num_experts"] > 0)
        for i, kind in enumerate(pattern)
    ]
    out = _stack_by_path(per_layer, pattern)
    out[c.WTE] = sd["model.embed_tokens.weight"]
    out[f"{c.LN_F}/scale"] = sd["model.norm.weight"]
    if "lm_head.weight" in sd:
        out[c.LM_HEAD] = sd["lm_head.weight"].T
    return out


def translate_state_dict_to_hf(flat, config=None, plan=layer_plan):
    """Flat smp param dict -> HF naming ([out, in] weights); ``plan`` is
    the family's ``layer_plan``."""
    if config is None:
        raise SMPValidationError("config required for the layer pattern.")
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    pattern, kinds = plan(config)
    out = {
        "model.embed_tokens.weight": np.asarray(flat[c.WTE]),
        "model.norm.weight": np.asarray(flat[f"{c.LN_F}/scale"]),
    }
    if c.LM_HEAD in flat:
        out["lm_head.weight"] = np.asarray(flat[c.LM_HEAD]).T
    first = (_get(config, "experts_held") or (0, 0))[0]
    for i, (path, index) in enumerate(pattern_layer_paths(pattern)):
        prefix = f"{STACK}/{path}/"
        layer = {k[len(prefix):]: np.asarray(v)[index]
                 for k, v in flat.items() if k.startswith(prefix)}
        p = f"model.layers.{i}"
        for name, w in zip("qkvog", attention_to_hf(layer)):
            if w is not None:
                out[f"{p}.self_attn.{name}_proj.weight"] = w
        for theirs, ours in LAYER_VECTORS.items():
            if ours in layer:
                out[f"{p}.{theirs}"] = layer[ours]
        m = f"{p}.mlp."

        def gated(prefix_ours, prefix_hf):
            out[prefix_hf + "gate_proj.weight"] = \
                layer[f"{prefix_ours}/gate/kernel"].T
            out[prefix_hf + "up_proj.weight"] = \
                layer[f"{prefix_ours}/fc/kernel"].T
            out[prefix_hf + "down_proj.weight"] = \
                layer[f"{prefix_ours}/proj/kernel"].T

        if kinds[pattern[i]]["num_experts"] == 0:
            gated("output", m)
            continue
        out[m + "gate.weight"] = layer["output/router/kernel"].T
        gate, up, down = experts_to_hf(layer)
        for e in range(gate.shape[0]):
            out[f"{m}experts.{first + e}.gate_proj.weight"] = gate[e]
            out[f"{m}experts.{first + e}.up_proj.weight"] = up[e]
            out[f"{m}experts.{first + e}.down_proj.weight"] = down[e]
        if "output/shared/fc/kernel" in layer:
            gated("output/shared", m + "shared_expert.")
    return out
