"""Fine-tune a HuggingFace T5 under pipeline x tensor parallelism.

Loads HF T5 weights via smp.from_hf (full encoder-decoder translation —
RMSNorm, relative-position buckets, tied-head rescale), trains under
pp2 x tp2 with activation checkpointing + offload (BASELINE config #5's
shape, scaled down), and exports the fine-tuned weights back to HF
naming (loadable by transformers).
    python examples/finetune_hf_t5.py
Runs on the devices JAX finds. With no accelerator (or fewer devices than
the degrees below need), run it on eight virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/finetune_hf_t5.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
import torch
import transformers

import smdistributed_modelparallel_tpu as smp


def main():
    smp.init({
        "pipeline_parallel_degree": 2,
        "tensor_parallel_degree": 2,
        "ddp": True,
        "microbatches": 2,
        "offload_activations": True,
    })

    # A tiny random-weight T5 stands in for a pretrained one; with real
    # weights this is transformers.T5ForConditionalGeneration
    # .from_pretrained("t5-3b") (or a gated/untied v1.1 such as
    # "google/flan-t5-base" — both dialects translate).
    config = transformers.T5Config(
        vocab_size=256, d_model=64, d_kv=16, num_heads=4, num_layers=2,
        num_decoder_layers=4, d_ff=128, dropout_rate=0.0,
        feed_forward_proj="relu",
    )
    torch.manual_seed(0)
    hf = transformers.T5ForConditionalGeneration(config).eval()

    # translate -> DistributedModel; the encoder runs inside the pipeline's
    # embed phase (tp/dp-parallel), the decoder stack is pipelined.
    model = smp.from_hf(hf, deterministic=True,
                        activation_checkpointing=True)
    opt = smp.DistributedOptimizer(optax.adamw(3e-4), model)

    @smp.step
    def train_step(model, enc, dec):
        logits = model(enc, dec)
        lg = logits[:, :-1]
        tgt = jnp.take_along_axis(lg, dec[:, 1:, None], axis=-1)[..., 0]
        lse = jax.scipy.special.logsumexp(lg.astype(jnp.float32), axis=-1)
        loss = jnp.mean(lse - tgt.astype(jnp.float32))
        model.backward(loss)
        return loss

    rng = np.random.RandomState(0)
    enc = jnp.asarray(rng.randint(0, 256, (4, 16)))
    dec = jnp.asarray(rng.randint(0, 256, (4, 8)))
    for step in range(5):
        out = train_step(model, enc, dec)
        opt.step()
        print(f"step {step}: loss {float(out.reduce_mean()):.4f}")

    # Export back to HF naming and reload into a fresh transformers model.
    from smdistributed_modelparallel_tpu.module_manager import path_key
    from smdistributed_modelparallel_tpu.nn.huggingface import t5 as t5mod

    flat = {
        path_key(path): np.asarray(jax.device_get(leaf))
        for path, leaf in
        jax.tree_util.tree_flatten_with_path(model.params)[0]
    }
    sd = t5mod.translate_state_dict_to_hf(flat, config=config)
    fresh = transformers.T5ForConditionalGeneration(config)
    missing, unexpected = fresh.load_state_dict(
        {k: torch.tensor(v) for k, v in sd.items()}, strict=False
    )
    assert not missing and not unexpected, (missing, unexpected)
    print("fine-tuned weights reloaded into transformers — OK")


if __name__ == "__main__":
    main()
