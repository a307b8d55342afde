"""HF Mellum translation (JetBrains Mellum 2: ``model_type`` "mellum").

Structure, from the published ``config.json``: RMSNorm pre-norm decoder, no
biases, untied head; grouped KV heads (``num_attention_heads`` on
``num_key_value_heads`` of ``head_dim``) with an RMSNorm over the head
size on each query and key head before rotary; full-attention and
sliding-window layers (``layer_types``, ``sliding_window``,
``use_sliding_window``) with their own rotary on the whole head
(``rope_parameters``: YaRN in full layers, plain in window layers); routed
experts in every layer (``mlp_layer_types`` all ``sparse``; ``num_experts``
at ``num_experts_per_tok``, ``norm_topk_prob``) and no shared expert;
``intermediate_size`` is used by no layer.

Assumed (no network here, and the modelling code is not in the config):
the per-head q/k norm and the state-dict names are those of the
Qwen3-MoE config class, whose keys the config uses (``norm_topk_prob``,
``moe_intermediate_size``, ``max_window_layers``, ``use_sliding_window``):
``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm.weight`` [head_dim],
``mlp.gate`` [E, D] for the router, ``mlp.experts.{e}.{gate,up,down}_proj``.
The stack, the tensor functions and the translators are Laguna's
(``nn/huggingface/laguna.py``), given this family's layer plan; a chip's
share is ``config.experts_held = (first, count)`` as there.
"""

import functools

from smdistributed_modelparallel_tpu.nn.huggingface import laguna
from smdistributed_modelparallel_tpu.nn.huggingface.laguna import _get
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

HF_ARCHITECTURES = ("MellumForCausalLM", "MellumModel")


def layer_plan(config):
    """``(pattern, kinds)``: ``window`` / ``full``; every kind normalises
    q and k per head and routes its MLP."""
    L = _get(config, "num_hidden_layers")
    types = list(_get(config, "layer_types")
                 or ["full_attention"] * L)[:L]
    if "dense" in list(_get(config, "mlp_layer_types") or [])[:L]:
        raise SMPValidationError(
            "mellum: a dense entry in mlp_layer_types is not a layer this "
            "family has; every layer is routed."
        )
    windowed = _get(config, "use_sliding_window", True) is not False
    pattern, kinds = [], {}
    for i in range(L):
        window = windowed and types[i] == "sliding_attention"
        name = "window" if window else "full"
        kinds.setdefault(name, dict(
            laguna.attention_kind(
                config, _get(config, "num_attention_heads"), window,
                qk_norm=True),
            **laguna.experts_kind(config)))
        pattern.append(name)
    return tuple(pattern), kinds


def config_to_smp(config):
    """Mellum config -> ``DistributedTransformerLMHead`` kwargs."""
    if _get(config, "attention_bias", False):
        raise SMPValidationError("mellum: attention_bias is not supported.")
    return laguna.decoder_kwargs(config, *layer_plan(config))


translate_hf_state_dict = functools.partial(
    laguna.translate_hf_state_dict, plan=layer_plan)
translate_state_dict_to_hf = functools.partial(
    laguna.translate_state_dict_to_hf, plan=layer_plan)
