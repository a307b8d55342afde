"""HF Ouro translation (ByteDance Ouro looped language models:
``model_type`` "ouro").

Structure, from the published ``config.json`` and "Scaling Latent
Reasoning via Looped Language Models" (arXiv:2510.25741): an RMSNorm
decoder with no biases and an untied head; ``num_attention_heads`` heads of
``head_dim`` on as many key-value heads, rotary on the whole head in halves
(``rope_theta``, ``rope_scaling`` null); a gated SiLU MLP; **four norms a
layer**, one before and one after each branch ("sandwich"): ``x + N2(Attn(
N1(x)))``, then ``a + N4(MLP(N3(a)))``; and **the whole stack run
``total_ut_steps`` times over its own output with the same weights**, the
model's one final norm after every pass, the head and an exit gate (one
linear layer to a logit a position) on every pass's normed state. The
kwargs are ``DistributedTransformerLMHead``'s ``loop_steps`` and
``branch_layernorm``; training's loss is ``nn/exit_gate.exit_gated_loss``.

Assumed (no network here, and the modelling code is not in the config): the
state-dict names, those of the Llama convention with the second norm of a
pair named ``<first>_2``: ``model.layers.{i}.self_attn.{q,k,v,o}_proj``,
``mlp.{gate,up,down}_proj``, ``input_layernorm`` (N1), ``input_layernorm_2``
(N2), ``post_attention_layernorm`` (N3), ``post_attention_layernorm_2``
(N4), ``model.norm``, ``model.early_exit_gate.{weight [1, D], bias [1]}``,
``lm_head``; linear weights [out, in]. Not written: grouped key-value heads
(the family's released models have none), a sliding window, rotary scaling,
and ``early_exit_threshold`` (generation stops a sequence's passes by it;
``ROADMAP.md`` Queue 2).
"""

import numpy as np

from smdistributed_modelparallel_tpu.nn.huggingface import common as c
from smdistributed_modelparallel_tpu.nn.huggingface.laguna import (
    _get,
    _t,
    gated_mlp_from_hf,
)
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

HF_ARCHITECTURES = ("OuroForCausalLM", "OuroModel")

LOOP_NORM = "transformer/loop_norm/scale"
GATE_W, GATE_B = "exit_gate/kernel", "exit_gate/bias"

#: A layer's four norms, HF name -> the layer module's.
NORMS = {
    "input_layernorm.weight": "attention/layernorm/scale",
    "input_layernorm_2.weight": "attention/branch_layernorm/scale",
    "post_attention_layernorm.weight": "output/layernorm/scale",
    "post_attention_layernorm_2.weight": "output/branch_layernorm/scale",
}
A, M = "self_attn.", "mlp."
MLP = ("gate_proj", "up_proj", "down_proj")


def config_to_smp(config):
    """Ouro config -> ``DistributedTransformerLMHead`` kwargs."""
    H, hd = _get(config, "num_attention_heads"), _get(config, "head_dim")
    refused = {
        "num_key_value_heads": _get(config, "num_key_value_heads", H) != H,
        "use_sliding_window": bool(_get(config, "use_sliding_window", False)),
        "rope_scaling": bool(_get(config, "rope_scaling")),
        "attention_bias": bool(_get(config, "attention_bias", False)),
    }
    if any(refused.values()):
        raise SMPValidationError(
            "ouro: not supported: "
            + ", ".join(k for k, bad in refused.items() if bad) + "."
        )
    return {
        "num_layers": _get(config, "num_hidden_layers"),
        "num_attention_heads": H,
        "attention_head_size": hd,
        "hidden_size": _get(config, "hidden_size"),
        "intermediate_size": _get(config, "intermediate_size"),
        "vocab_size": _get(config, "vocab_size"),
        "loop_steps": int(_get(config, "total_ut_steps", 1)),
        "layernorm_type": "rms",
        "layernorm_epsilon": _get(config, "rms_norm_eps", 1e-6),
        "pre_layernorm": True,
        "post_layernorm": False,
        "branch_layernorm": True,
        "final_layernorm": True,
        "activation": _get(config, "hidden_act", "silu"),
        "gated_mlp": True,
        "use_mlp_bias": False,
        "use_qkv_bias": False,
        "use_attn_dense_bias": False,
        "use_lm_head_bias": False,
        "rotary_dim": hd,
        "rotary_emb_base": float(_get(config, "rope_theta", 10000.0)),
        "gpt_neox_type_rotary": True,
        "use_positional_embedding": False,
        "tie_input_output_embedding": bool(
            _get(config, "tie_word_embeddings", False)),
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
# One layer's tensors, HF names <-> the layer module's names, over an array
# namespace (numpy here, jax.numpy for a jitted builder) on tensors that
# may carry leading stack axes.
# ----------------------------------------------------------------------


def attention_from_hf(q, k, v, o, hd, xp=np):
    """``{q,k,v}_proj`` [.., H*hd, D] and ``o_proj`` [.., D, H*hd] -> the
    attention layer's fused ``qkv`` [.., D, 3, H, hd] and ``dense`` [.., H,
    hd, D] kernels."""
    lead, D = q.shape[:-2], q.shape[-1]
    heads = lambda w: _t(w).reshape(*lead, D, -1, hd)   # noqa: E731
    return {
        "attention/qkv/kernel": xp.stack(
            [heads(q), heads(k), heads(v)], axis=len(lead) + 1),
        "attention/dense/kernel": _t(o).reshape(*lead, -1, hd, D),
    }


def attention_to_hf(layer):
    """The inverse: ``(q, k, v, o)`` in HF's layout."""
    qkv, dense = layer["attention/qkv/kernel"], layer["attention/dense/kernel"]
    lead, D = qkv.shape[:-4], qkv.shape[-4]
    flat = lambda w: _t(w.reshape(*lead, D, -1))        # noqa: E731
    return (*(flat(qkv[..., :, j, :, :]) for j in range(3)),
            _t(dense.reshape(*lead, -1, D)))


def layer_from_hf(take, hd, xp=np):
    """A layer's (or a stack of layers') module-named tensors from
    ``take(HF name under the layer's prefix)``."""
    layer = attention_from_hf(
        *(take(f"{A}{n}_proj.weight") for n in "qkvo"), hd, xp=xp)
    layer.update(gated_mlp_from_hf(
        *(take(f"{M}{n}.weight") for n in MLP), "output"))
    layer.update({ours: take(theirs) for theirs, ours in NORMS.items()})
    return layer


def layer_to_hf(layer):
    """The inverse: ``{HF name under the layer's prefix: tensor}``."""
    named = {f"{A}{n}_proj.weight": w
             for n, w in zip("qkvo", attention_to_hf(layer))}
    named.update({
        f"{M}gate_proj.weight": _t(layer["output/gate/kernel"]),
        f"{M}up_proj.weight": _t(layer["output/fc/kernel"]),
        f"{M}down_proj.weight": _t(layer["output/proj/kernel"]),
    })
    named.update({theirs: layer[ours] for theirs, ours in NORMS.items()})
    return named


def globals_from_hf(take):
    """The tensors outside the layers: table, the norm after every pass,
    the exit gate, the head."""
    return {
        c.WTE: take("model.embed_tokens.weight"),
        LOOP_NORM: take("model.norm.weight"),
        GATE_W: _t(take("model.early_exit_gate.weight")),
        GATE_B: take("model.early_exit_gate.bias"),
        c.LM_HEAD: _t(take("lm_head.weight")),
    }


def globals_to_hf(flat):
    return {
        "model.embed_tokens.weight": flat[c.WTE],
        "model.norm.weight": flat[LOOP_NORM],
        "model.early_exit_gate.weight": _t(flat[GATE_W]),
        "model.early_exit_gate.bias": flat[GATE_B],
        "lm_head.weight": _t(flat[c.LM_HEAD]),
    }


def translate_hf_state_dict(sd, config=None):
    """HF Ouro state dict -> flat '/'-keyed smp param dict."""
    if config is None:
        raise SMPValidationError("ouro: config required (head size).")
    sd = {k: c.to_np(v) for k, v in sd.items()}
    if "lm_head.weight" not in sd:          # a tied checkpoint keeps one
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    hd = _get(config, "head_dim")
    out = globals_from_hf(sd.__getitem__)
    layers = [
        layer_from_hf(lambda name, i=i: sd[f"model.layers.{i}.{name}"], hd)
        for i in range(c.num_layers_in(sd, "model.layers.", 2))]
    for key, value in c.stack_layers(layers).items():
        out[f"{c.L}/{key}"] = value
    return out


def translate_state_dict_to_hf(flat, config=None):
    """Flat smp param dict -> HF Ouro naming (torch tensor layout)."""
    flat = {k: np.asarray(v) for k, v in flat.items()}
    out = globals_to_hf(flat)
    prefix = c.L + "/"
    stacked = layer_to_hf({k[len(prefix):]: v for k, v in flat.items()
                           if k.startswith(prefix)})
    for name, value in stacked.items():
        for i, one in enumerate(value):
            out[f"model.layers.{i}.{name}"] = one
    return out
