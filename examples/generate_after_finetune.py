"""Train-then-sample: fine-tune a GPT-2-style LM under tp2, then generate
continuations with the KV-cache decode path (``smp.generate``).

Generation is a TPU extension beyond the reference (a training library):
prefill + every decode step compile into ONE program (no per-token host
round trips), and the same tensor-parallel sharding that trained the
weights serves them.
    python examples/generate_after_finetune.py
Runs on the devices JAX finds. With no accelerator (or fewer devices than
the degrees below need), run it on eight virtual CPU devices:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/generate_after_finetune.py
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
    smp.init({"tensor_parallel_degree": 2, "ddp": True, "microbatches": 2})
    print(f"mesh: {dict(smp.get_mesh().shape)}")

    vocab, seq = 257, 32
    model = smp.DistributedModel(
        gpt2(vocab_size=vocab, max_len=64, d_model=64, n_layers=2, n_heads=4)
    )
    optimizer = smp.DistributedOptimizer(optax.adamw(3e-3), model)

    # A toy skill the model can learn quickly: a fixed set of 4-token
    # motifs, each row one motif repeated. The transition statistics are
    # memorizable in tens of steps; continuation = keep the cycle.
    rng = np.random.default_rng(0)
    motifs = rng.integers(0, vocab, size=(6, 4))

    def batch(n=8):
        rows = motifs[rng.integers(0, len(motifs), size=n)]
        return np.tile(rows, (1, seq // 4))

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        tgt = ids[:, 1:]
        lse = jax.scipy.special.logsumexp(
            logits[:, :-1].astype(jnp.float32), axis=-1
        )
        picked = jnp.take_along_axis(
            logits[:, :-1], tgt[:, :, None], axis=-1
        )[..., 0].astype(jnp.float32)
        loss = jnp.mean(lse - picked)
        model.backward(loss)
        return loss

    for it in range(100):
        loss = train_step(model, jnp.asarray(batch())).reduce_mean()
        optimizer.step()
        if it % 20 == 0:
            print(f"step {it:3d}  loss {float(loss):.4f}")

    # Greedy continuation of fresh periodic prompts.
    full = batch(4)
    prompts = jnp.asarray(full[:, :8])
    out = np.asarray(model.generate(prompts, 8))
    correct = sum(
        int(np.array_equal(out[row, 8:], full[row, 8:16])) for row in range(4)
    )
    print(f"greedy continuations correct for {correct}/4 prompts")
    print("sampled:", np.asarray(
        model.generate(prompts, 8, temperature=0.8, top_k=20,
                       rng=jax.random.key(0))
    )[0, 8:])

    # ---- The same workflow under PIPELINE parallelism -----------------
    # Decode never runs the pipeline schedule: smp.generate regathers the
    # pp-stage-sharded layer stacks onto the full mesh automatically
    # (model.regather_for_decode, cached between calls), so training at
    # pp x tp and sampling need no topology change.
    trained = model.state_dict()
    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": 2})
    print(f"\npp x tp mesh: {dict(smp.get_mesh().shape)}")
    model = smp.DistributedModel(
        gpt2(vocab_size=vocab, max_len=64, d_model=64, n_layers=2, n_heads=4)
    )
    optimizer = smp.DistributedOptimizer(optax.adamw(3e-3), model)
    loss = train_step(model, jnp.asarray(batch())).reduce_mean()
    optimizer.step()
    print(f"pp step loss {float(loss):.4f} (fresh init; now loading the "
          "tp-phase weights)")
    model.load_state_dict(trained)  # reuse the tp-phase weights
    out_pp = np.asarray(model.generate(prompts, 8))
    assert np.array_equal(out_pp, out), "pp decode must match tp decode"
    print("pp2 x tp2 generation matches the tp2 run token for token")


if __name__ == "__main__":
    main()
