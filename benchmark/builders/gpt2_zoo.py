"""GPT-2 through the zoo: ``models/gpt2.gpt2`` -> ``TransformerLM``, the
class ``ServingEngine`` accepts. The Hugging Face names of
``benchmark/weights.py`` map one to one onto the module's parameter paths:
no tensor is reshaped or transposed (HF's Conv1D kernels are [in, out], as
flax's are)."""

# Hugging Face name (stacked over layers where it starts with "h.") ->
# '/'-joined path in the TransformerLM parameter tree.
HF_TO_PATH = {
    "wte.weight": "wte/embedding",
    "wpe.weight": "wpe/embedding",
    "h.ln_1.weight": "layers/block/ln1/scale",
    "h.ln_1.bias": "layers/block/ln1/bias",
    "h.attn.c_attn.weight": "layers/block/attn/qkv/kernel",
    "h.attn.c_attn.bias": "layers/block/attn/qkv/bias",
    "h.attn.c_proj.weight": "layers/block/attn/proj/kernel",
    "h.attn.c_proj.bias": "layers/block/attn/proj/bias",
    "h.ln_2.weight": "layers/block/ln2/scale",
    "h.ln_2.bias": "layers/block/ln2/bias",
    "h.mlp.c_fc.weight": "layers/block/fc/kernel",
    "h.mlp.c_fc.bias": "layers/block/fc/bias",
    "h.mlp.c_proj.weight": "layers/block/proj/kernel",
    "h.mlp.c_proj.bias": "layers/block/proj/bias",
    "ln_f.weight": "ln_f/scale",
    "ln_f.bias": "ln_f/bias",
}


def module(cfg):
    from smdistributed_modelparallel_tpu.models.gpt2 import gpt2

    # The zoo's entry sets the family's fixed choices (learned positions,
    # tied head); every size comes from the configuration file.
    return gpt2("gpt2_1p5b", d_model=cfg["n_embd"], n_heads=cfg["n_head"],
                n_layers=cfg["n_layer"], d_ff=cfg.get("n_inner"),
                vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
                ln_eps=cfg["layer_norm_epsilon"])


def train_step(smp):
    """The user's step function for this module: next-token loss from the
    model's loss mode (``model(ids, targets=...)``), mean over the
    predicted positions."""
    import jax.numpy as jnp

    @smp.step
    def step(model, ids):
        tgt = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
        per = model(ids, targets=tgt)
        loss = jnp.sum(per) / (per.shape[0] * (per.shape[1] - 1))
        model.backward(loss)
        return loss

    return step


def flat_from_hf(cfg, weights):
    """HF-named state dict -> the '/'-keyed flat dict ``load_state_dict``
    takes."""
    return {HF_TO_PATH[name]: value for name, value in weights.items()}


def tree_from_hf(cfg, weights):
    """HF-named state dict -> the module's nested parameter tree."""
    tree = {}
    for name, value in weights.items():
        node = tree
        *parents, last = HF_TO_PATH[name].split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = value
    return tree


def hf_from_flat(cfg, flat):
    """A '/'-keyed flat dict of the module's parameters (or of a tree
    shaped like them) -> HF names."""
    return {name: flat[path] for name, path in HF_TO_PATH.items()}
