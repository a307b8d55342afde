"""Fine-tune a HuggingFace GPT-2 under tensor parallelism.

Loads HF weights via smp.from_hf, trains under tp, saves a full
checkpoint back in HF naming (loadable by transformers).
    python examples/finetune_hf_gpt2.py
Runs on the devices JAX finds. With no accelerator (or fewer devices than
the degrees below need), run it on eight virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/finetune_hf_gpt2.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
import transformers

import smdistributed_modelparallel_tpu as smp


def main():
    smp.init({"tensor_parallel_degree": 4, "ddp": True, "microbatches": 2})

    # A tiny random-weight GPT-2 stands in for a pretrained one; with real
    # weights this is transformers.GPT2LMHeadModel.from_pretrained("gpt2").
    config = transformers.GPT2Config(
        n_embd=64, n_layer=2, n_head=4, vocab_size=256, n_positions=32,
        attn_pdrop=0.0, resid_pdrop=0.0, embd_pdrop=0.0,
    )
    hf_model = transformers.GPT2LMHeadModel(config)

    model = smp.from_hf(hf_model)
    optimizer = smp.DistributedOptimizer(optax.adamw(1e-4), model)

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        lg = logits[:, :-1]
        tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        lse = jax.scipy.special.logsumexp(lg.astype(jnp.float32), axis=-1)
        loss = jnp.mean(lse - tgt.astype(jnp.float32))
        model.backward(loss)
        return loss

    rng = np.random.RandomState(0)
    for step in range(4):
        ids = jnp.asarray(rng.randint(0, 256, (8, 32)))
        out = train_step(model, ids)
        optimizer.step()
        print(f"step {step}: loss={float(out.reduce_mean()):.4f}")

    # Full checkpoint in HF naming; reloadable by transformers.
    smp.save_checkpoint("/tmp/smp_example_hf", tag="tuned", model=model,
                        partial=False, translate_if_full=True)
    import pickle

    with open("/tmp/smp_example_hf/tuned", "rb") as fh:
        sd = pickle.load(fh)["model"]
    import torch

    hf_model.load_state_dict(
        {k: torch.tensor(np.asarray(v)) for k, v in sd.items()}
    )
    print("tuned weights loaded back into the HF model; done.")

    # Sample from the tuned model in-framework (KV-cache decode on the
    # same tp mesh that trained it) and check token-exact agreement with
    # the exported HF model's own generate.
    prompts = jax.random.randint(jax.random.key(1), (2, 6), 1, 64)
    ours = np.asarray(model.generate(prompts, 6))
    hf_model.eval()
    with torch.no_grad():
        t_ids = torch.tensor(np.asarray(prompts))
        theirs = hf_model.generate(
            t_ids, attention_mask=torch.ones_like(t_ids),
            max_new_tokens=6, do_sample=False, pad_token_id=0,
        ).numpy()
    assert np.array_equal(ours, theirs), "in-framework vs exported-HF generate"
    print("generation: in-framework == exported-HF, tokens", ours[0, 6:].tolist())


if __name__ == "__main__":
    main()
