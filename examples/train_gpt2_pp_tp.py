"""Train GPT-2 with pipeline + tensor parallelism on synthetic data.

    python examples/train_gpt2_pp_tp.py
Runs on the devices JAX finds. With no accelerator (or fewer devices than
the degrees below need), run it on eight virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_gpt2_pp_tp.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.models.gpt2 import gpt2


def main():
    smp.init({
        "pipeline_parallel_degree": 2,
        "tensor_parallel_degree": 2,
        "ddp": True,
        "microbatches": 4,
    })
    print(f"mesh: {dict(smp.get_mesh().shape)}")

    model = smp.DistributedModel(
        gpt2("gpt2_124m", vocab_size=256, max_len=32,
             d_model=32, n_layers=4, n_heads=2)
    )
    optimizer = smp.DistributedOptimizer(
        optax.adamw(3e-4), model, grad_clip_norm=1.0
    )

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        lg = logits[:, :-1]
        tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        lse = jax.scipy.special.logsumexp(lg.astype(jnp.float32), axis=-1)
        loss = jnp.mean(lse - tgt.astype(jnp.float32))
        model.backward(loss)
        return loss

    def synthetic_batches(n, B=8, T=32):
        rng = np.random.RandomState(0)
        for _ in range(n):
            yield {"ids": rng.randint(0, 256, (B, T))}

    for step, batch in enumerate(smp.dataloader(synthetic_batches(4))):
        out = train_step(model, jnp.asarray(batch["ids"]))
        optimizer.step()
        print(f"step {step}: loss={float(out.reduce_mean()):.4f}")

    smp.save_checkpoint("/tmp/smp_example_ckpt", tag="final",
                        model=model, optimizer=optimizer, blocking=False)
    smp.wait_for_checkpoints()
    print("checkpoint saved; done.")


if __name__ == "__main__":
    main()
