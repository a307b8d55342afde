"""HF LFM2-MoE translation (LiquidAI LFM2-8B-A1B / LFM2-24B-A2B:
``model_type`` "lfm2_moe").

Structure, from the published ``config.json``: RMSNorm pre-norm decoder
(``norm_eps``), no biases, the head tied to the input table behind a last
norm (``embedding_norm``); each layer's mixer by ``layer_types``: ``conv``,
a gated short convolution over ``conv_L_cache`` positions
(``nn/conv.DistributedShortConv``), or ``full_attention``, grouped KV heads
with an RMSNorm on each query and key head before plain rotary on the whole
head (``rope_parameters``); the first ``num_dense_layers`` layers carry a
dense gated MLP ``intermediate_size`` wide, the others ``num_experts``
routed experts ``moe_intermediate_size`` wide at ``num_experts_per_tok`` a
token and no shared expert, scored by a sigmoid with a per-expert bias
that enters the selection and not the weights (``use_expert_bias``), the
weights renormalised (``norm_topk_prob``) and scaled
(``routed_scaling_factor``).

Assumed (no network here; names as the ``Lfm2Moe`` class gives them):
``operator_norm`` / ``ffn_norm`` for a layer's two norms,
``conv.{in_proj,conv,out_proj}`` (``in_proj`` [3 D, D] with the streams B,
C, x in that order; the depthwise ``conv.weight`` [D, 1, K]),
``self_attn.{q,k,v,out}_proj`` and ``self_attn.{q,k}_layernorm`` [head_dim],
``feed_forward.{w1,w3,w2}`` (gate, up, down) in a dense layer,
``feed_forward.gate`` [E, D], ``feed_forward.expert_bias`` [E] and
``feed_forward.experts.{e}.{w1,w3,w2}`` in a routed one,
``model.embed_tokens``, ``model.embedding_norm``; ``tie_word_embeddings``
true where the config does not say; ``head_dim`` = ``hidden_size`` /
``num_attention_heads`` where it gives none. The stack is a patterned one
(``DistributedTransformer.layer_pattern``) and a chip's share is
``config.experts_held = (first, count)``, as Laguna's
(``nn/huggingface/laguna.py``, whose tensor functions this file uses).
"""

import numpy as np

from smdistributed_modelparallel_tpu.nn.huggingface import common as c
from smdistributed_modelparallel_tpu.nn.huggingface import laguna
from smdistributed_modelparallel_tpu.nn.huggingface.laguna import _get, _t
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

HF_ARCHITECTURES = ("Lfm2MoeForCausalLM", "Lfm2MoeModel")

STACK = laguna.STACK


def head_dim(config):
    return _get(config, "head_dim") or (
        _get(config, "hidden_size") // _get(config, "num_attention_heads"))


def _attention_view(config):
    """What Laguna's ``attention_kind`` and ``decoder_kwargs`` read of a
    config, under the keys they read it by."""
    rope = dict(_get(config, "rope_parameters") or {
        "rope_type": "default",
        "rope_theta": _get(config, "rope_theta", 1000000.0)})
    view = {k: _get(config, k) for k in (
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "hidden_size", "intermediate_size", "vocab_size",
        "max_position_embeddings", "initializer_range")
        if _get(config, k) is not None}
    view.update(
        head_dim=head_dim(config), rope_parameters={"full_attention": rope},
        rms_norm_eps=_get(config, "norm_eps", 1e-5), hidden_act="silu",
        tie_word_embeddings=_get(config, "tie_word_embeddings", True)
        is not False)
    return view


def layer_plan(config):
    """``(pattern, kinds)``. Routed layers are ``conv`` / ``full`` by their
    mixer; the leading dense-MLP layers ``lead_dense_conv`` /
    ``lead_dense``."""
    L = _get(config, "num_hidden_layers")
    types = list(_get(config, "layer_types"))[:L]
    dense = int(_get(config, "num_dense_layers", 0) or 0)
    if _get(config, "conv_bias", False):
        raise SMPValidationError("lfm2_moe: conv_bias is not supported.")
    view = _attention_view(config)
    experts = dict(
        laguna.experts_kind(config),
        moe_routed_scaling=float(_get(config, "routed_scaling_factor", 1.0)),
        moe_score="sigmoid",
        moe_selection_bias=bool(_get(config, "use_expert_bias", False)))
    pattern, kinds = [], {}
    for i in range(L):
        if types[i] == "conv":
            name = "conv"
            kw = {"conv_mixer": int(_get(config, "conv_L_cache", 3))}
        elif types[i] == "full_attention":
            name = "full"
            kw = laguna.attention_kind(
                view, _get(config, "num_attention_heads"), False,
                qk_norm=True)
        else:
            raise SMPValidationError(
                f"lfm2_moe: layer type {types[i]!r} is neither 'conv' nor "
                "'full_attention'.")
        if i < dense:
            name = "lead_dense" + ("_conv" if name == "conv" else "")
            kw.update(intermediate_size=_get(config, "intermediate_size"),
                      num_experts=0)
        else:
            kw.update(experts)
        kinds.setdefault(name, kw)
        pattern.append(name)
    return tuple(pattern), kinds


def config_to_smp(config):
    """LFM2-MoE config -> ``DistributedTransformerLMHead`` kwargs."""
    return laguna.decoder_kwargs(_attention_view(config),
                                 *layer_plan(config))


# ----------------------------------------------------------------------
# One layer's tensors, HF names <-> the layer module's names, over an
# array namespace on tensors that may carry leading stack axes.
# ----------------------------------------------------------------------

# A layer's vectors: Hugging Face name under the layer -> the module's,
# the first with the mixer's name ("conv" or "attention") put in.
NORMS = {"operator_norm.weight": "{mixer}/layernorm/scale",
         "ffn_norm.weight": "output/layernorm/scale"}
QK_NORMS = {"self_attn.q_layernorm.weight": "attention/q_norm/scale",
            "self_attn.k_layernorm.weight": "attention/k_norm/scale"}


def conv_from_hf(in_proj, taps, out_proj):
    """``conv.in_proj`` [.., 3 D, D], ``conv.conv`` [.., D, 1, K],
    ``conv.out_proj`` [.., D, D] -> the mixer's kernels."""
    D = in_proj.shape[-1]
    return {
        "conv/in_proj/kernel": _t(in_proj).reshape(
            *in_proj.shape[:-2], D, 3, D),
        "conv/conv/kernel": _t(taps[..., 0, :]),
        "conv/out_proj/kernel": _t(out_proj),
    }


def conv_to_hf(layer):
    """Inverse of ``conv_from_hf``: ``(in_proj, conv, out_proj)``."""
    kernel = layer["conv/in_proj/kernel"]
    D = kernel.shape[-1]
    return (_t(kernel.reshape(*kernel.shape[:-3], D, 3 * D)),
            _t(layer["conv/conv/kernel"])[..., None, :],
            _t(layer["conv/out_proj/kernel"]))


def _layer_from_hf(sd, p, config, kw):
    conv = bool(kw.get("conv_mixer"))
    mixer = "conv" if conv else "attention"
    out = {ours.format(mixer=mixer): sd[f"{p}.{theirs}"]
           for theirs, ours in NORMS.items()}
    if conv:
        out.update(conv_from_hf(
            sd[f"{p}.conv.in_proj.weight"], sd[f"{p}.conv.conv.weight"],
            sd[f"{p}.conv.out_proj.weight"]))
    else:
        a = f"{p}.self_attn."
        out.update(laguna.attention_from_hf(
            sd[a + "q_proj.weight"], sd[a + "k_proj.weight"],
            sd[a + "v_proj.weight"], sd[a + "out_proj.weight"], None,
            head_dim(config)))
        out.update({ours: sd[f"{p}.{theirs}"]
                    for theirs, ours in QK_NORMS.items()})
    m = f"{p}.feed_forward."
    if not kw["num_experts"]:
        out.update(laguna.gated_mlp_from_hf(
            sd[m + "w1.weight"], sd[m + "w3.weight"], sd[m + "w2.weight"],
            "output"))
        return out
    first, count = kw["moe_held"] or (0, kw["num_experts"])
    stack = lambda name: np.stack([                     # noqa: E731
        sd[f"{m}experts.{e}.{name}.weight"]
        for e in range(first, first + count)])
    out["output/router/kernel"] = _t(sd[m + "gate.weight"])
    if kw["moe_selection_bias"]:
        out["output/router/selection_bias"] = sd[m + "expert_bias"]
    out.update(laguna.experts_from_hf(stack("w1"), stack("w3"), stack("w2")))
    return out


def translate_hf_state_dict(sd, config=None):
    """HF state dict -> flat '/'-keyed smp param dict."""
    if config is None:
        raise SMPValidationError("config required for the layer pattern.")
    sd = {k: c.to_np(v) for k, v in sd.items()}
    pattern, kinds = layer_plan(config)
    per_layer = [_layer_from_hf(sd, f"model.layers.{i}", config, kinds[kind])
                 for i, kind in enumerate(pattern)]
    out = laguna._stack_by_path(per_layer, pattern)
    out[c.WTE] = sd["model.embed_tokens.weight"]
    out[f"{c.LN_F}/scale"] = sd["model.embedding_norm.weight"]
    if "lm_head.weight" in sd and not _attention_view(config)[
            "tie_word_embeddings"]:
        out[c.LM_HEAD] = sd["lm_head.weight"].T
    return out


def translate_state_dict_to_hf(flat, config=None):
    """Flat smp param dict -> HF naming ([out, in] weights)."""
    if config is None:
        raise SMPValidationError("config required for the layer pattern.")
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    pattern, kinds = layer_plan(config)
    out = {
        "model.embed_tokens.weight": np.asarray(flat[c.WTE]),
        "model.embedding_norm.weight": np.asarray(flat[f"{c.LN_F}/scale"]),
    }
    if c.LM_HEAD in flat:
        out["lm_head.weight"] = np.asarray(flat[c.LM_HEAD]).T
    for i, (path, index) in enumerate(pattern_layer_paths(pattern)):
        prefix = f"{STACK}/{path}/"
        layer = {k[len(prefix):]: np.asarray(v)[index]
                 for k, v in flat.items() if k.startswith(prefix)}
        kw, p = kinds[pattern[i]], f"model.layers.{i}"
        mixer = "conv" if kw.get("conv_mixer") else "attention"
        for theirs, ours in NORMS.items():
            out[f"{p}.{theirs}"] = layer[ours.format(mixer=mixer)]
        if mixer == "conv":
            for name, w in zip(("in_proj", "conv", "out_proj"),
                               conv_to_hf(layer)):
                out[f"{p}.conv.{name}.weight"] = w
        else:
            q, k, v, o, _ = laguna.attention_to_hf(layer)
            for name, w in (("q", q), ("k", k), ("v", v), ("out", o)):
                out[f"{p}.self_attn.{name}_proj.weight"] = w
            for theirs, ours in QK_NORMS.items():
                out[f"{p}.{theirs}"] = layer[ours]
        m = f"{p}.feed_forward."
        if not kw["num_experts"]:
            out[m + "w1.weight"] = layer["output/gate/kernel"].T
            out[m + "w3.weight"] = layer["output/fc/kernel"].T
            out[m + "w2.weight"] = layer["output/proj/kernel"].T
            continue
        out[m + "gate.weight"] = layer["output/router/kernel"].T
        if "output/router/selection_bias" in layer:
            out[m + "expert_bias"] = layer["output/router/selection_bias"]
        first = (kw["moe_held"] or (0, 0))[0]
        for name, w in zip(("w1", "w3", "w2"), laguna.experts_to_hf(layer)):
            for e in range(w.shape[0]):
                out[f"{m}experts.{first + e}.{name}.weight"] = w[e]
    return out
