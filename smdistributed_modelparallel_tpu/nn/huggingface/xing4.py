"""HF Xing4.0 translation (XingChen-AGI Xing4.0-29B-A4B: ``model_type``
"xing4_0").

Structure, from the published ``config.json``, whose attention, router and
MTP keys are the DeepSeek-V3 class's: RMSNorm pre-norm decoder
(``rms_norm_eps``), no biases, untied head. Every layer's mixer is
multi-head latent attention (``nn/latent_attention.py``): ``q_lora_rank``
and ``kv_lora_rank`` latents behind RMSNorms, query / key heads of
``qk_nope_head_dim + qk_rope_head_dim``, value heads of ``v_head_dim``,
rotary on the rope part alone (one key part shared by the heads) at
``rope_theta`` under YaRN (``rope_scaling``), whose ``mscale`` enters the
softmax scale: ``(nope + rope)^-1/2 m(factor, mscale_all_dim)^2`` with
``m(s, a) = 0.1 a ln s + 1``, while cos and sin are multiplied by
``m(factor, mscale) / m(factor, mscale_all_dim)``. The first
``first_k_dense_replace`` layers carry a dense gated MLP
``intermediate_size`` wide; the others ``n_routed_experts`` experts
``moe_intermediate_size`` wide at ``num_experts_per_tok`` a token beside
``n_shared_experts`` shared ones, scored by a sigmoid
(``scoring_func``), a per-expert bias entering the selection and not the
weights (``topk_method`` "noaux_tc"), the weights renormalised
(``norm_topk_prob``) and scaled (``routed_scaling_factor``). The residual
path carries ``hc_mult`` streams, mixed round every sub-layer by
manifold-constrained hyper-connections (``nn/hyper_connection.py``:
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``).

Not run: group-limited selection (``n_group`` / ``topk_group`` over 1),
more than one shared expert, and the multi-token-prediction module
(``num_nextn_predict_layers``: its tensors, ``model.layers.<L>.*``, are
left where they are; continued training without it is the family's plain
forward).

Assumed (no network here, and the family's modelling file is not
published with the config): the state-dict names of the DeepSeek-V3 class
(``self_attn.{q_a_proj,q_a_layernorm,q_b_proj,kv_a_proj_with_mqa,
kv_a_layernorm,kv_b_proj,o_proj}``, ``mlp.{gate,up,down}_proj``,
``mlp.gate.weight`` [E, D], ``mlp.gate.e_score_correction_bias`` [E],
``mlp.experts.{e}.*``, ``mlp.shared_experts.*``, ``input_layernorm``,
``post_attention_layernorm``, ``model.embed_tokens``, ``model.norm``,
``lm_head``) and its interleaved-pair rotary (this translator permutes
the rope columns of ``q_b_proj`` and ``kv_a_proj_with_mqa`` once, so the
program rotates halves); names of this file's own for the connections'
leaves, ``attn_hc`` round the attention and ``ffn_hc`` round the
feed-forward: ``<hc>.norm.weight`` [n D], ``<hc>.phi.weight``
[n (n + 2), n D] (rows: pre n, post n, res n x n row-major),
``<hc>.alpha`` [3] (pre, post, res), ``<hc>.bias`` [n (n + 2)]. A chip's
share is ``config.experts_held = (first, count)``, as Laguna's
(``nn/huggingface/laguna.py``, whose tensor functions this file uses).
"""

import numpy as np

from smdistributed_modelparallel_tpu.nn.huggingface import common as c
from smdistributed_modelparallel_tpu.nn.huggingface import laguna
from smdistributed_modelparallel_tpu.nn.huggingface.laguna import _get, _t
from smdistributed_modelparallel_tpu.utils.exceptions import SMPValidationError

HF_ARCHITECTURES = ("Xing4ForCausalLM", "Xing4Model")

STACK = laguna.STACK


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 else 1.0


def latent_fields(config):
    """``(latent_attention, rotary)``: the latent-attention layer's own
    fields and the layer's rotary fields."""
    dn, dr = _get(config, "qk_nope_head_dim"), _get(config, "qk_rope_head_dim")
    if not _get(config, "q_lora_rank"):
        raise SMPValidationError(
            "xing4: a query projected without a latent (q_lora_rank null) "
            "is not supported.")
    scale, yarn = float(dn + dr) ** -0.5, None
    rope = _get(config, "rope_scaling")
    if rope:
        if rope.get("type", rope.get("rope_type")) != "yarn":
            raise SMPValidationError(
                f"xing4: rope_scaling {rope!r} is not YaRN.")
        factor = float(rope["factor"])
        all_dim = float(rope.get("mscale_all_dim", 0) or 0)
        if all_dim:
            scale *= float(yarn_mscale(factor, all_dim)) ** 2
        yarn = (factor, int(rope["original_max_position_embeddings"]),
                float(rope.get("beta_fast", 32)),
                float(rope.get("beta_slow", 1)),
                float(yarn_mscale(factor, float(rope.get("mscale", 1)))
                      / yarn_mscale(factor, all_dim)))
    latent = {
        "q_lora_rank": _get(config, "q_lora_rank"),
        "kv_lora_rank": _get(config, "kv_lora_rank"),
        "qk_nope_head_dim": dn, "qk_rope_head_dim": dr,
        "v_head_dim": _get(config, "v_head_dim"),
        "softmax_scale": scale,
    }
    return latent, {
        "rotary_emb_base": float(_get(config, "rope_theta", 10000.0)),
        "rotary_yarn": yarn}


def hyper_connection_fields(config):
    """The stack's ``hyper_connection``; ``None`` for one stream."""
    streams = int(_get(config, "hc_mult", 1) or 1)
    if streams == 1:
        return None
    return {
        "streams": streams,
        "sinkhorn_iters": int(_get(config, "hc_sinkhorn_iters", 20)),
        "eps": float(_get(config, "hc_eps", 1e-6)),
        "clamp": (float(_get(config, "mhc_h_res_clamp_min", -30)),
                  float(_get(config, "mhc_h_res_clamp_max", 30))),
    }


def layer_plan(config):
    """``(pattern, kinds)``: ``lead_dense`` for the leading dense-MLP
    layers, ``full`` for the routed ones."""
    if (_get(config, "n_group", 1) or 1) > 1 or (
            _get(config, "topk_group", 1) or 1) > 1:
        raise SMPValidationError(
            "xing4: group-limited expert selection (n_group, topk_group "
            "over 1) is not supported.")
    if _get(config, "scoring_func", "sigmoid") != "sigmoid":
        raise SMPValidationError(
            f"xing4: scoring_func {_get(config, 'scoring_func')!r} is not "
            "the sigmoid law.")
    shared = int(_get(config, "n_shared_experts", 0) or 0)
    if shared > 1:
        raise SMPValidationError(
            "xing4: more than one shared expert is not supported.")
    if (_get(config, "moe_layer_freq", 1) or 1) != 1:
        raise SMPValidationError("xing4: moe_layer_freq must be 1.")
    latent, rotary = latent_fields(config)
    attention = dict(
        num_attention_heads=_get(config, "num_attention_heads"),
        latent_attention=latent, **rotary)
    held = _get(config, "experts_held")
    F = _get(config, "moe_intermediate_size")
    kinds = {
        "lead_dense": dict(
            attention, intermediate_size=_get(config, "intermediate_size"),
            num_experts=0),
        "full": dict(
            attention, intermediate_size=F,
            num_experts=_get(config, "n_routed_experts"),
            moe_top_k=_get(config, "num_experts_per_tok"),
            moe_dropless=True,
            moe_held=tuple(held) if held is not None else None,
            moe_shared_intermediate_size=shared * F,
            moe_norm_topk=bool(_get(config, "norm_topk_prob", True)),
            moe_routed_scaling=float(
                _get(config, "routed_scaling_factor", 1.0)),
            moe_score="sigmoid",
            moe_selection_bias=_get(config, "topk_method") == "noaux_tc"),
    }
    L = _get(config, "num_hidden_layers")
    dense = int(_get(config, "first_k_dense_replace", 0) or 0)
    pattern = tuple("lead_dense" if i < dense else "full" for i in range(L))
    return pattern, {k: kinds[k] for k in dict.fromkeys(pattern)}


def config_to_smp(config):
    """Xing4.0 config -> ``DistributedTransformerLMHead`` kwargs."""
    if _get(config, "attention_bias", False):
        raise SMPValidationError("xing4: attention_bias is not supported.")
    view = {k: _get(config, k) for k in (
        "num_hidden_layers", "num_attention_heads", "hidden_size",
        "intermediate_size", "vocab_size", "max_position_embeddings",
        "rms_norm_eps", "hidden_act", "tie_word_embeddings",
        "initializer_range") if _get(config, k) is not None}
    view["head_dim"] = (_get(config, "qk_nope_head_dim")
                        + _get(config, "qk_rope_head_dim"))
    return dict(laguna.decoder_kwargs(view, *layer_plan(config)),
                hyper_connection=hyper_connection_fields(config))


# ----------------------------------------------------------------------
# One layer's tensors, HF names <-> the layer module's names, over an
# array namespace on tensors that may carry leading stack axes.
# ----------------------------------------------------------------------

A = "self_attn."
NORMS = {"input_layernorm.weight": "attention/layernorm/scale",
         "post_attention_layernorm.weight": "output/layernorm/scale",
         A + "q_a_layernorm.weight": "attention/q_norm/scale",
         A + "kv_a_layernorm.weight": "attention/kv_norm/scale"}
# A connection's leaves: HF name under the layer -> the sub-layer's site.
CONNECTIONS = {"attn_hc": "attention", "ffn_hc": "output"}


def rope_halves(dr):
    """Where each rotate-half column reads the interleaved-pair
    convention's: the even ones, then the odd ones."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def attention_from_hf(q_a, q_b, kv_a, kv_b, o, config, xp=np):
    """``q_a_proj`` [.., r_q, D], ``q_b_proj`` [.., H (nope + rope), r_q],
    ``kv_a_proj_with_mqa`` [.., r_kv + rope, D], ``kv_b_proj``
    [.., H (nope + v), r_kv], ``o_proj`` [.., D, H v] -> the latent
    attention layer's kernels, the rope columns put in halves."""
    dn, dr = _get(config, "qk_nope_head_dim"), _get(config, "qk_rope_head_dim")
    dv, rkv = _get(config, "v_head_dim"), _get(config, "kv_lora_rank")
    lead, halves = q_a.shape[:-2], rope_halves(dr)
    q_up = _t(q_b).reshape(*lead, q_b.shape[-1], -1, dn + dr)
    q_up = xp.concatenate(
        [q_up[..., :dn], q_up[..., dn:][..., halves]], axis=-1)
    kv_down = _t(kv_a)
    kv_down = xp.concatenate(
        [kv_down[..., :rkv], kv_down[..., rkv:][..., halves]], axis=-1)
    return {
        "attention/q_down/kernel": _t(q_a),
        "attention/q_up/kernel": q_up,
        "attention/kv_down/kernel": kv_down,
        "attention/kv_up/kernel": _t(kv_b).reshape(
            *lead, kv_b.shape[-1], -1, dn + dv),
        "attention/dense/kernel": _t(o).reshape(*lead, -1, dv, o.shape[-2]),
    }


def attention_to_hf(layer, config, xp=np):
    """Inverse of ``attention_from_hf``: ``(q_a, q_b, kv_a, kv_b, o)``."""
    dn, dr = _get(config, "qk_nope_head_dim"), _get(config, "qk_rope_head_dim")
    rkv = _get(config, "kv_lora_rank")
    pairs = np.argsort(rope_halves(dr))
    q_up, kv_down = (layer["attention/q_up/kernel"],
                     layer["attention/kv_down/kernel"])
    kv_up, dense = (layer["attention/kv_up/kernel"],
                    layer["attention/dense/kernel"])
    lead = q_up.shape[:-3]
    q_up = xp.concatenate(
        [q_up[..., :dn], q_up[..., dn:][..., pairs]], axis=-1)
    kv_down = xp.concatenate(
        [kv_down[..., :rkv], kv_down[..., rkv:][..., pairs]], axis=-1)
    return (_t(layer["attention/q_down/kernel"]),
            _t(q_up.reshape(*lead, q_up.shape[-3], -1)),
            _t(kv_down),
            _t(kv_up.reshape(*lead, kv_up.shape[-3], -1)),
            _t(dense.reshape(*lead, -1, dense.shape[-1])))


def connection_from_hf(norm, phi, alpha, bias, site, streams):
    """``<hc>.norm.weight`` [.., n D], ``<hc>.phi.weight`` [.., C, n D],
    ``<hc>.alpha`` [.., 3], ``<hc>.bias`` [.., C] -> the connection's
    leaves under ``<site>/hyper_connection``."""
    p = f"{site}/hyper_connection/"
    lead = norm.shape[:-1]
    return {
        p + "norm/scale": norm.reshape(*lead, streams, -1),
        p + "phi": _t(phi).reshape(*lead, streams, -1, phi.shape[-2]),
        p + "alpha": alpha, p + "bias": bias,
    }


def connection_to_hf(layer, site):
    """Inverse of ``connection_from_hf``: ``(norm, phi, alpha, bias)``."""
    p = f"{site}/hyper_connection/"
    norm, phi = layer[p + "norm/scale"], layer[p + "phi"]
    lead = norm.shape[:-2]
    return (norm.reshape(*lead, -1),
            _t(phi.reshape(*lead, -1, phi.shape[-1])),
            layer[p + "alpha"], layer[p + "bias"])


CONNECTION_LEAVES = ("norm.weight", "phi.weight", "alpha", "bias")


def _layer_from_hf(sd, p, config, kw):
    a = f"{p}.{A}"
    out = attention_from_hf(
        sd[a + "q_a_proj.weight"], sd[a + "q_b_proj.weight"],
        sd[a + "kv_a_proj_with_mqa.weight"], sd[a + "kv_b_proj.weight"],
        sd[a + "o_proj.weight"], config)
    out.update({ours: sd[f"{p}.{theirs}"] for theirs, ours in NORMS.items()})
    hc = hyper_connection_fields(config)
    if hc:
        for theirs, site in CONNECTIONS.items():
            out.update(connection_from_hf(
                *(sd[f"{p}.{theirs}.{leaf}"] for leaf in CONNECTION_LEAVES),
                site, hc["streams"]))
    m = f"{p}.mlp."
    if not kw["num_experts"]:
        out.update(laguna.gated_mlp_from_hf(
            sd[m + "gate_proj.weight"], sd[m + "up_proj.weight"],
            sd[m + "down_proj.weight"], "output"))
        return out
    first, count = kw["moe_held"] or (0, kw["num_experts"])
    stack = lambda name: np.stack([                     # noqa: E731
        sd[f"{m}experts.{e}.{name}.weight"]
        for e in range(first, first + count)])
    out["output/router/kernel"] = _t(sd[m + "gate.weight"])
    if kw["moe_selection_bias"]:
        out["output/router/selection_bias"] = sd[
            m + "gate.e_score_correction_bias"]
    out.update(laguna.experts_from_hf(
        stack("gate_proj"), stack("up_proj"), stack("down_proj")))
    if kw["moe_shared_intermediate_size"]:
        s = m + "shared_experts."
        out.update(laguna.gated_mlp_from_hf(
            sd[s + "gate_proj.weight"], sd[s + "up_proj.weight"],
            sd[s + "down_proj.weight"], "output/shared"))
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
    out[f"{c.LN_F}/scale"] = sd["model.norm.weight"]
    if "lm_head.weight" in sd:
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
        "model.norm.weight": np.asarray(flat[f"{c.LN_F}/scale"]),
    }
    if c.LM_HEAD in flat:
        out["lm_head.weight"] = np.asarray(flat[c.LM_HEAD]).T
    for i, (path, index) in enumerate(pattern_layer_paths(pattern)):
        prefix = f"{STACK}/{path}/"
        layer = {k[len(prefix):]: np.asarray(v)[index]
                 for k, v in flat.items() if k.startswith(prefix)}
        kw, p = kinds[pattern[i]], f"model.layers.{i}"
        for name, w in zip(
                ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj",
                 "o_proj"), attention_to_hf(layer, config)):
            out[f"{p}.{A}{name}.weight"] = w
        for theirs, ours in NORMS.items():
            out[f"{p}.{theirs}"] = layer[ours]
        for theirs, site in CONNECTIONS.items():
            if f"{site}/hyper_connection/phi" in layer:
                for leaf, w in zip(CONNECTION_LEAVES,
                                   connection_to_hf(layer, site)):
                    out[f"{p}.{theirs}.{leaf}"] = w
        m = f"{p}.mlp."

        def gated(ours, theirs):
            out[theirs + "gate_proj.weight"] = layer[f"{ours}/gate/kernel"].T
            out[theirs + "up_proj.weight"] = layer[f"{ours}/fc/kernel"].T
            out[theirs + "down_proj.weight"] = layer[f"{ours}/proj/kernel"].T

        if not kw["num_experts"]:
            gated("output", m)
            continue
        out[m + "gate.weight"] = layer["output/router/kernel"].T
        if "output/router/selection_bias" in layer:
            out[m + "gate.e_score_correction_bias"] = layer[
                "output/router/selection_bias"]
        first = (kw["moe_held"] or (0, 0))[0]
        for name, w in zip(("gate_proj", "up_proj", "down_proj"),
                           laguna.experts_to_hf(layer)):
            for e in range(w.shape[0]):
                out[f"{m}experts.{first + e}.{name}.weight"] = w[e]
        if "output/shared/fc/kernel" in layer:
            gated("output/shared", m + "shared_experts.")
    return out
