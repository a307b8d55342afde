"""Long-context ring attention + Mixture-of-Experts in one model.

The sequence axis shards over the cp mesh axis (zigzag ring attention with
causal load balancing); MoE experts shard over ep. Both are TPU-native
capabilities beyond the reference framework.
    python examples/long_context_moe.py
Runs on the devices JAX finds. With no accelerator (or fewer devices than
the degrees below need), run it on eight virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_moe.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import smdistributed_modelparallel_tpu as smp


def main():
    smp.init({
        "context_parallel_degree": 2,
        "expert_parallel_degree": 2,
        "context_parallel_impl": "ring",
        "ddp": True,
        "microbatches": 2,
    })
    print(f"mesh: {dict(smp.get_mesh().shape)}")

    model = smp.DistributedModel(smp.nn.DistributedTransformerLMHead(
        num_layers=2, num_attention_heads=4, attention_head_size=8,
        hidden_size=32, intermediate_size=64, vocab_size=256,
        num_positions=128, causal_mask_size=128,
        pre_layernorm=True, post_layernorm=False, final_layernorm=True,
        attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
        embedding_dropout_prob=0.0,
        num_experts=4,            # MoE over ep
        deterministic=True,
    ))
    optimizer = smp.DistributedOptimizer(optax.adamw(3e-4), model)

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
    for step in range(3):
        ids = jnp.asarray(rng.randint(0, 256, (4, 128)))  # T=128 over cp=2
        out = train_step(model, ids)
        optimizer.step()
        print(f"step {step}: loss={float(out.reduce_mean()):.4f}")
    print("ring-attention + MoE training done.")


if __name__ == "__main__":
    main()
