"""HF SDAR-MoE translation (JetLM SDAR-30B-A3B: ``model_type`` "sdar_moe").

Structure, from the published ``config.json``: RMSNorm pre-norm decoder, no
biases, untied head; grouped KV heads (``num_attention_heads`` on
``num_key_value_heads`` of ``head_dim``) with an RMSNorm over the head size
on each query and key head before rotary; plain rotary on the whole head
(``rope_theta``, ``rope_scaling`` null); routed experts in every layer
(``mlp_only_layers`` empty, ``decoder_sparse_step`` 1; ``num_experts`` at
``num_experts_per_tok``, ``norm_topk_prob``) and no shared expert;
``intermediate_size`` is used by no layer. The matrices are those of the
Qwen3-MoE class, as Mellum's are. What the family computes in training is
not next-token prediction: every layer's attention runs under the
block-diffusion mask over a two-copy stream (``nn/diffusion.py``), so the
one layer kind, ``full``, carries ``block_diffusion`` = the block length.

Assumed (no network here, and the modelling code is not in the config):
the block length (``block_length``, not an HF key; 4, the family's released
one, where the config does not give it), the per-head q/k norm and the
state-dict names of the Qwen3-MoE convention: ``self_attn.{q,k,v,o}_proj``,
``self_attn.{q,k}_norm.weight`` [head_dim], ``mlp.gate`` [E, D],
``mlp.experts.{e}.{gate,up,down}_proj``. The stack, the tensor functions
and the translators are Laguna's (``nn/huggingface/laguna.py``), given this
family's layer plan; a chip's share is ``config.experts_held = (first,
count)`` as there.
"""

import functools

from smdistributed_modelparallel_tpu.nn.huggingface import laguna
from smdistributed_modelparallel_tpu.nn.huggingface.laguna import _get
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

HF_ARCHITECTURES = ("SDARMoeForCausalLM", "SDARMoeModel")

BLOCK_LENGTH = 4


def _attention_view(config):
    """What Laguna's ``attention_kind`` reads of a config, with the rotary
    table it expects (one entry, plain, at ``rope_theta``) where this
    family's config keeps a scalar."""
    return {
        "head_dim": _get(config, "head_dim"),
        "num_key_value_heads": _get(config, "num_key_value_heads"),
        "rope_parameters": {"full_attention": {
            "rope_type": "default",
            "rope_theta": _get(config, "rope_theta", 10000.0)}},
    }


def layer_plan(config):
    """``(pattern, kinds)``: every layer ``full``, its q and k normalised
    per head, its MLP routed, its attention under the block-diffusion
    mask."""
    if _get(config, "mlp_only_layers"):
        raise SMPValidationError(
            "sdar_moe: a non-empty mlp_only_layers is not a layer this "
            "family has; every layer is routed."
        )
    if _get(config, "use_sliding_window", False):
        raise SMPValidationError(
            "sdar_moe: use_sliding_window is not supported (the "
            "block-diffusion mask has no band)."
        )
    if _get(config, "rope_scaling"):
        raise SMPValidationError("sdar_moe: rope_scaling is not supported.")
    kinds = {"full": dict(
        laguna.attention_kind(
            _attention_view(config), _get(config, "num_attention_heads"),
            False, qk_norm=True,
            block_diffusion=int(_get(config, "block_length", BLOCK_LENGTH))),
        **laguna.experts_kind(config))}
    return ("full",) * _get(config, "num_hidden_layers"), kinds


def config_to_smp(config):
    """SDAR-MoE config -> ``DistributedTransformerLMHead`` kwargs. The
    model takes a two-copy stream (``nn.diffusion.two_copy_stream``) and
    gives logits for its noisy half: the head is asked for the leading
    half of the positions, the only ones that carry loss."""
    if _get(config, "attention_bias", False):
        raise SMPValidationError("sdar_moe: attention_bias is not supported.")
    return dict(laguna.decoder_kwargs(config, *layer_plan(config)),
                head_positions=0.5)


translate_hf_state_dict = functools.partial(
    laguna.translate_hf_state_dict, plan=layer_plan)
translate_state_dict_to_hf = functools.partial(
    laguna.translate_state_dict_to_hf, plan=layer_plan)
