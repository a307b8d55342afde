"""On-chip kernel microbenchmarks for the bench hot paths.

Times fwd+bwd of the two CE implementations and the two attention
implementations at the exact shapes `bench.py` runs (GPT-2 124M, per-
microbatch B=2, T=1024, H=12, Dh=64, V=50257), so a regression in either
Pallas kernel vs the XLA path is attributable with one script. Not part of
the test suite; run manually on TPU.

Usage: python scripts/kernel_probe.py [ce|attn|all]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def _time(fn, *args, inner=32, reps=3):
    """Per-call device time of ``fn(*args)``.

    Per-dispatch latency from the host is far larger than the kernels
    being measured, so the op is iterated
    ``inner`` times inside ONE jitted ``lax.scan`` with a forced data
    dependency (carry perturbed by the output) to stop XLA from hoisting
    or deduplicating the loop body; one dispatch + one readback per rep.
    """
    import numpy as np

    def once(a0, args):
        out = fn(a0, *args[1:])
        leaf = jax.tree_util.tree_leaves(out)[0]
        bump = (leaf.ravel()[0] * 1e-30).astype(a0.dtype)
        return a0 + bump, leaf.ravel()[0]

    @jax.jit
    def loop(args):
        def body(a0, _):
            return once(a0, args)

        a_final, outs = jax.lax.scan(body, args[0], None, length=inner)
        return outs[-1]

    out = loop(args)
    np.asarray(out)  # warmup compile + sync
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = loop(args)
        np.asarray(out)
        times.append((time.perf_counter() - t0) / inner)
    return sorted(times)[reps // 2]


def probe_ce():
    from smdistributed_modelparallel_tpu.ops.pallas_ce import fused_lm_head_ce

    N, D, V = 2048, 768, 50257
    x = jax.random.normal(jax.random.key(0), (N, D), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (V, D), jnp.bfloat16) * 0.02
    t = jax.random.randint(jax.random.key(2), (N,), 0, V)

    def xla_ce(x, w, t):
        logits = x @ w.T
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - tgt.astype(jnp.float32))

    def fused(x, w, t):
        return jnp.mean(fused_lm_head_ce(x, w, t))

    for name, f in [("xla_logits", xla_ce), ("pallas_fused", fused)]:
        g = jax.jit(jax.grad(lambda x, w, t=t, f=f: f(x, w, t), argnums=(0, 1)))
        dt = _time(g, x, w)
        print(f"ce   {name:14s} fwd+bwd {dt * 1e3:8.3f} ms")


def probe_attn():
    from smdistributed_modelparallel_tpu.ops.attention import attention_core

    B, T, H, Dh = 2, 1024, 12, 64
    q = jax.random.normal(jax.random.key(0), (B, T, H, Dh), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, T, H, Dh), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, T, H, Dh), jnp.bfloat16)

    def timed(use_pallas):
        def f(q, k, v):
            o = attention_core(q, k, v, causal=True, use_pallas=use_pallas)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    for name, flag in (("xla", False), ("pallas", True)):
        try:
            dt = _time(timed(flag), q, k, v)
            print(f"attn {name:14s} fwd+bwd {dt * 1e3:8.3f} ms")
        except Exception as e:
            print(f"attn {name:14s} FAILED: {e!r}")


def probe_attn_blocks():
    """Sweep flash-attention block sizes at the bench shape."""
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    B, T, H, Dh = 2, 1024, 12, 64
    q = jax.random.normal(jax.random.key(0), (B, T, H, Dh), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, T, H, Dh), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, T, H, Dh), jnp.bfloat16)

    for bq, bk in ((128, 128), (256, 256), (512, 512), (256, 512),
                   (512, 256), (1024, 256), (256, 1024), (1024, 512)):
        def f(q, k, v, bq=bq, bk=bk):
            o = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        try:
            dt = _time(jax.jit(jax.grad(f, argnums=(0, 1, 2))), q, k, v)
            print(f"attn flash bq={bq:4d} bk={bk:4d} fwd+bwd {dt*1e3:8.3f} ms")
        except Exception as e:
            print(f"attn flash bq={bq:4d} bk={bk:4d} FAILED: {type(e).__name__}")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    print(f"devices: {jax.devices()}")
    if which in ("ce", "all"):
        probe_ce()
    if which in ("attn", "all"):
        probe_attn()
    if which in ("blocks", "all"):
        probe_attn_blocks()
