"""GPT-NeoX through the tensor-parallel stack:
``nn/transformer.DistributedTransformerLMHead`` with the kwargs of
``nn/huggingface/gptneox.config_to_smp``. The Hugging Face names of
``benchmark/weights.py`` (per-layer tensors stacked) are translated to the
module's flat parameter names here, in ``jax.numpy`` on stacked tensors so
that it runs inside the one jitted call that makes the weights; the repo's
own per-layer numpy translator (``gptneox.translate_hf_state_dict``) is what
``tests/benchmark`` holds this one against."""

import types

L = "transformer/seq_layers/layer"
P = "gpt_neox.layers."


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import gptneox
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    hf = types.SimpleNamespace(**{
        k: v for k, v in cfg.items() if not isinstance(v, dict)})
    return DistributedTransformerLMHead(**gptneox.config_to_smp(hf))


def train_step(smp):
    """The user's step function for this module: logits out of the model,
    next-token cross-entropy in float32, mean over the predictions."""
    import jax
    import jax.numpy as jnp

    @smp.step
    def step(model, ids):
        logits = model(ids)[:, :-1].astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
        loss = jnp.mean(lse - tgt)
        model.backward(loss)
        return loss

    return step


def flat_from_hf(cfg, w):
    """HF-named state dict (stacked) -> the module's '/'-keyed flat dict."""
    qkv_w = w[P + "attention.query_key_value.weight"]       # [L, 3D, D]
    n, _, D = qkv_w.shape
    dense_w = w[P + "attention.dense.weight"]               # [L, D, D]
    qkv_b = w[P + "attention.query_key_value.bias"]
    H = cfg["num_attention_heads"]
    hd = D // H
    return {
        "word_embedding/embedding": w["gpt_neox.embed_in.weight"],
        "ln_f/scale": w["gpt_neox.final_layer_norm.weight"],
        "ln_f/bias": w["gpt_neox.final_layer_norm.bias"],
        "lm_head/kernel": w["embed_out.weight"].T,
        f"{L}/attention/layernorm/scale": w[P + "input_layernorm.weight"],
        f"{L}/attention/layernorm/bias": w[P + "input_layernorm.bias"],
        f"{L}/output/layernorm/scale":
            w[P + "post_attention_layernorm.weight"],
        f"{L}/output/layernorm/bias": w[P + "post_attention_layernorm.bias"],
        # out dim [H, 3, hd]-interleaved -> [D, 3, H, hd]
        f"{L}/attention/qkv/kernel":
            qkv_w.reshape(n, H, 3, hd, D).transpose(0, 4, 2, 1, 3),
        f"{L}/attention/qkv/bias":
            qkv_b.reshape(n, H, 3, hd).transpose(0, 2, 1, 3),
        # [out, in] -> [in = H x hd, out]
        f"{L}/attention/dense/kernel":
            dense_w.transpose(0, 2, 1).reshape(n, H, hd, D),
        f"{L}/attention/dense/bias": w[P + "attention.dense.bias"],
        f"{L}/output/fc/kernel":
            w[P + "mlp.dense_h_to_4h.weight"].transpose(0, 2, 1),
        f"{L}/output/fc/bias": w[P + "mlp.dense_h_to_4h.bias"],
        f"{L}/output/proj/kernel":
            w[P + "mlp.dense_4h_to_h.weight"].transpose(0, 2, 1),
        f"{L}/output/proj/bias": w[P + "mlp.dense_4h_to_h.bias"],
    }


def hf_from_flat(cfg, flat):
    """The module's flat dict (or one shaped like it) -> HF names."""
    qkv = flat[f"{L}/attention/qkv/kernel"]                 # [L, D, 3, H, hd]
    n, D = qkv.shape[:2]
    return {
        "gpt_neox.embed_in.weight": flat["word_embedding/embedding"],
        "gpt_neox.final_layer_norm.weight": flat["ln_f/scale"],
        "gpt_neox.final_layer_norm.bias": flat["ln_f/bias"],
        "embed_out.weight": flat["lm_head/kernel"].T,
        P + "input_layernorm.weight": flat[f"{L}/attention/layernorm/scale"],
        P + "input_layernorm.bias": flat[f"{L}/attention/layernorm/bias"],
        P + "post_attention_layernorm.weight":
            flat[f"{L}/output/layernorm/scale"],
        P + "post_attention_layernorm.bias":
            flat[f"{L}/output/layernorm/bias"],
        P + "attention.query_key_value.weight":
            qkv.transpose(0, 3, 2, 4, 1).reshape(n, 3 * D, D),
        P + "attention.query_key_value.bias":
            flat[f"{L}/attention/qkv/bias"].transpose(0, 2, 1, 3)
            .reshape(n, 3 * D),
        P + "attention.dense.weight":
            flat[f"{L}/attention/dense/kernel"].reshape(n, D, D)
            .transpose(0, 2, 1),
        P + "attention.dense.bias": flat[f"{L}/attention/dense/bias"],
        P + "mlp.dense_h_to_4h.weight":
            flat[f"{L}/output/fc/kernel"].transpose(0, 2, 1),
        P + "mlp.dense_h_to_4h.bias": flat[f"{L}/output/fc/bias"],
        P + "mlp.dense_4h_to_h.weight":
            flat[f"{L}/output/proj/kernel"].transpose(0, 2, 1),
        P + "mlp.dense_4h_to_h.bias": flat[f"{L}/output/proj/bias"],
    }
