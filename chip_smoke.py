#!/usr/bin/env python3
"""Smoke run of the train and serve main paths on the TPU chip.

    python chip_smoke.py            # one chip: train, fused-CE step, serve
    python chip_smoke.py --chips 4  # four chips: the pp x tp sharded trainer

One process from start to end: the plain-JAX reference, the trainer and the
server share it (a chip belongs to one process). It finds the device the way
JAX does and never sets ``JAX_PLATFORMS`` itself; with no TPU it exits
non-zero and prints no result. On the CPU the Pallas kernels run in the
interpreter and would "pass" — that is what this script refuses to report.

One chip (``gpt2_124m`` at its published width and depth, bf16 compute,
8 x 1024 tokens, 4 microbatches):

- *train*: ``smp.init`` -> ``smp.DistributedModel`` -> ``@smp.step`` ->
  ``smp.DistributedOptimizer.step()`` on a fixed seeded batch. Every loss is
  finite, the step-0 loss is within ``LOSS_TOLERANCE`` of a plain
  ``jax.numpy`` / optax step of the same module and parameters, the loss is
  lower after the steps than before, and the compiled step holds the flash
  attention kernels.
- *fused_ce*: one further step under ``fused_ce: True`` — ``auto`` keeps the
  materialised logits at this shape — whose loss agrees with the
  materialised path's on the same parameters and whose compiled step holds
  the CE kernels.
- *serve*: ``smp.serving.ServingEngine`` over the trained parameters answers
  greedy requests of mixed prompt lengths; the tokens equal what
  ``smp.generate`` returns for the same prompts (the engine's parity
  contract). In bf16 a near-tie may flip: a first divergence is allowed only
  where the reference's top-two logit margin is under ``TIE_MARGIN``, and is
  printed.

Four chips (``--chips 4``; ``gpt2_350m`` widths and depth, pp=2 x tp=2): the
sharded trainer against the plain step of the same module, seed and batch on
one of the four devices, with parameter shards and live memory on every
device and pp and tp collectives in the compiled step.

Every line but the last is one JSON object of information for whoever reads
the log (timings here are not benchmark metrics). The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed; a phase that fails raises, and the process exits non-zero.
"""

import argparse
import dataclasses
import functools
import json
import logging
import math
import os
import sys
import time

# bf16 compute, loss ~ ln(50257) = 10.8: the two programs fuse and round
# differently but average over 8k tokens. 2e-2 absolute is ~0.2% of the loss.
LOSS_TOLERANCE = 2e-2
# Logits are fp32 casts of bf16 values of magnitude 8-16, whose spacing is
# 2**-4: two such steps is the closest call bf16 can make either way.
TIE_MARGIN = 0.125
# Sized for this chip (16 GB); another device has not been sized or run.
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")
# Switches that take a kernel out from outside, and peaks that stand in for
# the device: the smoke refuses to run under any of them.
FORBIDDEN_ENV = (
    "SMP_DISABLE_PALLAS_ATTN", "SMP_DISABLE_FUSED_CE",
    "SMP_PEAK_TFLOPS", "SMP_PEAK_GBPS", "SMP_EXEC_CACHE",
)

FLASH_KERNELS = ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv")
CE_KERNELS = ("smp_ce_fwd", "smp_ce_bwd_dx", "smp_ce_bwd_dw")


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


@dataclasses.dataclass(frozen=True)
class OneChipSize:
    """The one-chip run: a ``models.gpt2`` size and its traffic."""

    model: str = "gpt2_124m"
    overrides: tuple = ()          # (key, value) pairs over the published size
    batch: int = 8
    seq: int = 1024
    microbatches: int = 4
    steps: int = 5
    lr: float = 1e-4
    prompt_lens: tuple = (16, 64, 64, 200, 512, 512)
    new_tokens: int = 32


@dataclasses.dataclass(frozen=True)
class FourChipSize:
    """The four-chip run: GPT-2 350M through the tp-capable stack."""

    layers: int = 24
    heads: int = 16
    d_model: int = 1024
    vocab: int = 50257
    batch: int = 8
    seq: int = 1024
    microbatches: int = 4
    steps: int = 3
    lr: float = 1e-4


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


# ----------------------------------------------------------------------
# What a compiled program holds, read from the executable itself.
# ----------------------------------------------------------------------

def kernels_in(compiled):
    """``{kernel name: count}`` of the Mosaic kernels (``tpu_custom_call``)
    in a compiled executable. The repo's Pallas kernels carry stable names
    (``smp_flash_fwd`` ...), which land in each call's ``op_name``."""
    import re

    found = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        names = re.findall(r"smp_[a-z0-9_]+", m.group(1)) if m else []
        name = names[-1] if names else "unnamed"
        found[name] = found.get(name, 0) + 1
    return found


def compiled_step(train_step):
    """The AOT executable the step engine runs for ``train_step``."""
    runners = list(train_step._cache.values())
    check(len(runners) == 1,
          f"expected one compiled step program, found {len(runners)}")
    compiled = runners[0].holder.get("compiled")
    check(compiled is not None,
          "the step ran through jit dispatch, not its AOT executable")
    return compiled


class WarningWatch(logging.Handler):
    """Fails the run on the step engine's AOT-fallback warning: on the chip
    that warning is a second full compile."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record):
        msg = record.getMessage()
        if "AOT" in msg or "materializing" in msg:
            self.messages.append(msg)

    def check(self):
        check(not self.messages,
              f"fallback warnings during the run: {self.messages}")


def paths_taken(kernels):
    """Which attention and CE path a program took, from its kernels."""
    return {
        "attention_path": "pallas_flash" if all(
            k in kernels for k in FLASH_KERNELS) else "xla_jnp",
        "ce_path": "pallas_fused" if all(
            k in kernels for k in CE_KERNELS) else "materialized_logits",
    }


def first_step(train_step, model, ids, counter):
    """The first call of a step function — trace, compile, run — with the
    compile seconds and the compile-cache hits and misses it caused.
    Returns ``(loss, fields for the phase's line)``."""
    from smdistributed_modelparallel_tpu.utils import exec_cache

    cache0, mark = counter.counts(), exec_cache.compile_event_mark()
    t0 = time.perf_counter()
    loss = float(train_step(model, ids).reduce_mean())
    return loss, {
        "first_call_seconds": round(time.perf_counter() - t0, 3),
        "compile_seconds": compile_seconds(mark),
        "compile_cache": cache_delta(counter, cache0),
    }


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def compile_seconds(mark):
    from smdistributed_modelparallel_tpu.utils import exec_cache

    return round(sum(
        e["seconds"] for e in exec_cache.compile_events_since(mark)
    ), 3)


def cache_delta(counter, before):
    now = counter.counts()
    return {k: now[k] - before[k] for k in now}


# ----------------------------------------------------------------------
# The plain reference: jax.numpy + optax, nothing of the framework.
# ----------------------------------------------------------------------

def to_bf16(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)


def plain_ce_loss(logits, ids):
    import jax
    import jax.numpy as jnp

    lg = logits[:, :-1]
    tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    lse = jax.scipy.special.logsumexp(lg.astype(jnp.float32), axis=-1)
    return jnp.mean(lse - tgt.astype(jnp.float32))


def plain_train_step(apply_fn, tx, microbatches):
    """The "without the framework" step: bf16 parameters, a scan over the
    microbatches accumulating fp32 gradients, one optax update."""
    import jax
    import jax.numpy as jnp
    import optax

    def loss_fn(params, mb):
        return plain_ce_loss(apply_fn(to_bf16(params), mb), mb)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, ids):
        mbs = ids.reshape(microbatches, ids.shape[0] // microbatches, -1)

        def body(acc, mb):
            loss, g = jax.value_and_grad(loss_fn)(params, mb)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss

        acc0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        grads, losses = jax.lax.scan(body, acc0, mbs)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jnp.mean(losses))

    return step


def run_plain_reference(apply_fn, params, ids, size, device=None):
    """``size.steps`` plain steps from ``params``; returns the losses."""
    import jax
    import jax.numpy as jnp
    import optax

    tx = optax.adamw(size.lr)
    step = plain_train_step(apply_fn, tx, size.microbatches)
    if device is not None:
        params, ids = jax.device_put((params, ids), device)
    else:
        params = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = jax.jit(tx.init)(params)
    losses = []
    for _ in range(size.steps):
        params, opt_state, loss = step(params, opt_state, ids)
        losses.append(float(loss))
    return losses


def lm_loss_step(smp):
    """The user's step function: next-token loss from the model's loss mode
    (``model(ids, targets=...)``), mean over the predicted positions."""
    import jax.numpy as jnp

    @smp.step
    def train_step(model, ids):
        tgt = jnp.concatenate(
            [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
        per = model(ids, targets=tgt)
        loss = jnp.sum(per) / (per.shape[0] * (per.shape[1] - 1))
        model.backward(loss)
        return loss

    return train_step


# ----------------------------------------------------------------------
# One chip: train, the fused-CE step, serve.
# ----------------------------------------------------------------------

def train_phase(size, counter, expect_kernels=True):
    """Train ``size.steps`` steps through the framework and the same steps
    through the plain reference. Returns what the later phases need."""
    import jax
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.gpt2 import gpt2

    module = gpt2(size.model, max_len=size.seq, **dict(size.overrides))
    ids = jax.random.randint(
        jax.random.key(0), (size.batch, size.seq), 0, module.vocab_size)

    smp.reset()
    smp.init({"microbatches": size.microbatches, "bf16": True},
             devices=jax.devices()[:1])     # one chip, whatever the host has
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.adamw(size.lr), model)
    train_step = lm_loss_step(smp)

    loss0, first = first_step(train_step, model, ids, counter)
    losses = [loss0]

    # The reference starts from the framework's own initial parameters
    # (the step above left them untouched; optimizer.step() installs).
    t0 = time.perf_counter()
    ref_losses = run_plain_reference(
        lambda p, mb: module.apply({"params": p}, mb), model.params, ids,
        size)
    reference_s = time.perf_counter() - t0

    step_seconds = []
    optimizer.step()
    for _ in range(size.steps - 1):
        t0 = time.perf_counter()
        losses.append(float(train_step(model, ids).reduce_mean()))
        optimizer.step()
        step_seconds.append(time.perf_counter() - t0)

    compiled = compiled_step(train_step)
    kernels = kernels_in(compiled)
    emit(
        "train", model=size.model, tokens_per_step=size.batch * size.seq,
        losses=losses, reference_losses=ref_losses,
        step0_abs_diff=abs(losses[0] - ref_losses[0]),
        tolerance=LOSS_TOLERANCE, **first,
        step_seconds=[round(s, 4) for s in step_seconds],
        reference_seconds=round(reference_s, 3),
        kernels=kernels, **paths_taken(kernels),
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
    )
    check(all(math.isfinite(l) for l in losses + ref_losses),
          f"non-finite loss: {losses} / reference {ref_losses}")
    check(abs(losses[0] - ref_losses[0]) <= LOSS_TOLERANCE,
          f"step-0 loss {losses[0]} is not within {LOSS_TOLERANCE} of the "
          f"plain reference's {ref_losses[0]}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {size.steps} steps: {losses}")
    if expect_kernels:
        missing = [k for k in FLASH_KERNELS if k not in kernels]
        check(not missing,
              f"flash attention kernels missing from the compiled step: "
              f"{missing} (found {kernels})")

    # The materialised path's loss on the TRAINED parameters: what the
    # fused-CE step is compared with.
    trained_loss = float(train_step(model, ids).reduce_mean())
    return {"module": module, "ids": ids, "state_dict": model.state_dict(),
            "trained_loss": trained_loss, "init_loss": losses[0]}


def fused_ce_phase(size, trained, counter, expect_kernels=True):
    """One step under ``fused_ce: True`` on the trained parameters."""
    import jax
    import optax

    import smdistributed_modelparallel_tpu as smp

    smp.reset()
    smp.init({"microbatches": size.microbatches, "bf16": True,
              "fused_ce": True}, devices=jax.devices()[:1])
    model = smp.DistributedModel(trained["module"])
    smp.DistributedOptimizer(optax.adamw(size.lr), model)
    train_step = lm_loss_step(smp)

    # Same seed, same initial parameters as the train phase's step 0.
    init_loss, first = first_step(train_step, model, trained["ids"], counter)
    model.load_state_dict(trained["state_dict"])
    t0 = time.perf_counter()
    loss = float(train_step(model, trained["ids"]).reduce_mean())
    step_s = time.perf_counter() - t0

    kernels = kernels_in(compiled_step(train_step))
    emit(
        "fused_ce", loss=loss, materialized_loss=trained["trained_loss"],
        abs_diff=abs(loss - trained["trained_loss"]),
        init_loss=init_loss, materialized_init_loss=trained["init_loss"],
        tolerance=LOSS_TOLERANCE, **first, step_seconds=round(step_s, 4),
        kernels=kernels, **paths_taken(kernels),
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
    )
    for got, want, what in (
        (init_loss, trained["init_loss"], "initial"),
        (loss, trained["trained_loss"], "trained"),
    ):
        check(math.isfinite(got) and abs(got - want) <= LOSS_TOLERANCE,
              f"fused-CE loss on the {what} parameters ({got}) is not "
              f"within {LOSS_TOLERANCE} of the materialised path's ({want})")
    if expect_kernels:
        missing = [k for k in CE_KERNELS if k not in kernels]
        check(not missing,
              f"fused CE kernels missing from the compiled step: {missing} "
              f"(found {kernels})")
    return model


def top_two_margin(module, params, tokens):
    """Top-two logit margin of the next-token distribution after
    ``tokens``, from a plain bf16 forward of the module."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def last_logits(params, padded, n):
        logits = module.apply({"params": to_bf16(params)}, padded)
        row = jnp.take(logits[0], n - 1, axis=0).astype(jnp.float32)
        top = jax.lax.top_k(row, 2)[0]
        return top[0] - top[1]

    padded = jnp.zeros((1, module.max_len), jnp.int32)
    padded = padded.at[0, :len(tokens)].set(jnp.asarray(tokens, jnp.int32))
    return float(last_logits(params, padded, len(tokens)))


def serve_phase(size, model, ids, counter):
    """Greedy requests through ``ServingEngine`` against ``smp.generate``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.utils import exec_cache

    # Prompts are prefixes of the rows the model was trained on.
    rows = np.asarray(ids)
    prompts = [
        [int(t) for t in rows[i % rows.shape[0], :n]]
        for i, n in enumerate(size.prompt_lens)
    ]

    cache0, mark = counter.counts(), exec_cache.compile_event_mark()
    engine = smp.serving.ServingEngine(model)
    t0 = time.perf_counter()
    engine.run([smp.serving.ServeRequest(
        "warmup", prompts[0], 2)], timeout_s=600)
    warmup_s = time.perf_counter() - t0
    serve_compile_s = compile_seconds(mark)
    programs = sorted(engine._programs)
    serve_cache = cache_delta(counter, cache0)

    t0 = time.perf_counter()
    results = engine.run([
        smp.serving.ServeRequest(f"r{i}", p, size.new_tokens)
        for i, p in enumerate(prompts)
    ], timeout_s=600)
    run_s = time.perf_counter() - t0
    engine.close()

    t0 = time.perf_counter()
    divergences = []
    for i, p in enumerate(prompts):
        got = [int(t) for t in results[f"r{i}"]]
        ref = np.asarray(smp.generate(
            model, jnp.asarray(p, jnp.int32)[None, :], size.new_tokens))
        want = [int(t) for t in ref[0, len(p):]]
        check(len(got) == size.new_tokens,
              f"request r{i} answered {len(got)} of {size.new_tokens} tokens")
        if got == want:
            continue
        at = next(j for j in range(size.new_tokens) if got[j] != want[j])
        margin = top_two_margin(
            model.module, model.params, p + want[:at])
        divergences.append({
            "request": f"r{i}", "prompt_len": len(p), "at": at,
            "engine": got[at], "generate": want[at], "margin": margin,
        })
    reference_s = time.perf_counter() - t0

    emit(
        "serve", requests=len(prompts), prompt_lens=list(size.prompt_lens),
        new_tokens=size.new_tokens, programs=programs,
        warmup_seconds=round(warmup_s, 3), compile_seconds=serve_compile_s,
        run_seconds=round(run_s, 3),
        generate_reference_seconds=round(reference_s, 3),
        decode_steps=engine.stats["decode_steps"],
        prefill_chunks=engine.stats["prefill_chunks"],
        exact_matches=len(prompts) - len(divergences),
        divergences=divergences, tie_margin=TIE_MARGIN,
        compile_cache=serve_cache,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
    )
    check(programs == ["decode", "prefill"],
          f"expected the prefill and decode programs, found {programs}")
    loud = [d for d in divergences if not d["margin"] < TIE_MARGIN]
    check(not loud,
          f"engine tokens differ from smp.generate where the reference was "
          f"not at a near-tie (margin >= {TIE_MARGIN}): {loud}")


def one_chip(size, counter, expect_kernels=True):
    import smdistributed_modelparallel_tpu as smp

    trained = train_phase(size, counter, expect_kernels)
    model = fused_ce_phase(size, trained, counter, expect_kernels)
    serve_phase(size, model, trained["ids"], counter)
    smp.reset()


# ----------------------------------------------------------------------
# Four chips: pp=2 x tp=2.
# ----------------------------------------------------------------------

def four_chip_module(size):
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        num_layers=size.layers, num_attention_heads=size.heads,
        attention_head_size=size.d_model // size.heads,
        hidden_size=size.d_model, intermediate_size=4 * size.d_model,
        vocab_size=size.vocab, num_positions=size.seq,
        causal_mask_size=size.seq, pre_layernorm=True, post_layernorm=False,
        final_layernorm=True, attention_dropout_prob=0.0,
        hidden_dropout_prob=0.0, embedding_dropout_prob=0.0,
    )


def sharded_phase(size, counter, expect_kernels=True):
    """The pp=2 x tp=2 trainer against the plain step on one device."""
    import jax
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.backend.topology import (
        PP_AXIS, TP_AXIS,
    )
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 found {len(devices)} devices")
    ids = jax.random.randint(
        jax.random.key(0), (size.batch, size.seq), 0, size.vocab)

    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": size.microbatches, "bf16": True},
             devices=devices[:4])
    module = four_chip_module(size)
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.adamw(size.lr), model)

    @smp.step
    def train_step(model, ids):
        loss = plain_ce_loss(model(ids), ids)
        model.backward(loss)
        return loss

    loss0, first = first_step(train_step, model, ids, counter)
    losses = [loss0]

    # Placement: every mesh device holds parameter shards and live memory.
    mesh_devices = list(state.mesh.devices.flat)
    shard_bytes = {d.id: 0 for d in mesh_devices}
    for leaf in jax.tree_util.tree_leaves(model.params):
        for s in leaf.addressable_shards:
            shard_bytes[s.device.id] += s.data.size * s.data.dtype.itemsize
    in_use = {
        d.id: (d.memory_stats() or {}).get("bytes_in_use")
        for d in mesh_devices
    }
    total_param_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(model.params))

    # The plain step of the same module and parameters, on one device. The
    # smp.nn layers place their activations on the framework's mesh while
    # it is initialized, so the reference runs after smp.shutdown().
    start_params = jax.device_get(model.params)
    step_seconds = []
    optimizer.step()
    for _ in range(size.steps - 1):
        t0 = time.perf_counter()
        losses.append(float(train_step(model, ids).reduce_mean()))
        optimizer.step()
        step_seconds.append(time.perf_counter() - t0)

    compiled = compiled_step(train_step)
    kernels = kernels_in(compiled)
    census = hlo_audit.collective_census(compiled.as_text(), state.mesh)
    by_axis = {}
    for op, ent in census.items():
        for axis, n in ent["axes"].items():
            by_axis.setdefault(axis, {})[op] = n["count"]
    mesh_shape = dict(state.mesh.shape)
    mesh_ids = [int(d.id) for d in mesh_devices]
    # Where each mesh position sits on the 2 x 2 interconnect (the mesh is
    # built from the device list in order).
    mesh_coords = [list(getattr(d, "coords", ())) for d in mesh_devices]
    peaks = {d.id: peak_bytes(d) for d in mesh_devices}
    del model, optimizer
    smp.shutdown()

    t0 = time.perf_counter()
    ref_losses = run_plain_reference(
        lambda p, mb: module.apply({"params": p}, mb), start_params,
        jax.device_get(ids), size, device=devices[0])
    reference_s = time.perf_counter() - t0

    emit(
        "sharded_train", model="gpt2_350m", mesh=mesh_shape,
        mesh_device_ids=mesh_ids, mesh_device_coords=mesh_coords,
        tokens_per_step=size.batch * size.seq,
        losses=losses, reference_losses=ref_losses,
        step0_abs_diff=abs(losses[0] - ref_losses[0]),
        tolerance=LOSS_TOLERANCE, **first,
        step_seconds=[round(s, 4) for s in step_seconds],
        reference_seconds=round(reference_s, 3),
        param_bytes_total=total_param_bytes,
        param_shard_bytes_by_device=shard_bytes,
        bytes_in_use_by_device=in_use, peak_bytes_in_use_by_device=peaks,
        collectives_by_axis=by_axis, kernels=kernels,
    )
    check(all(math.isfinite(l) for l in losses + ref_losses),
          f"non-finite loss: {losses} / reference {ref_losses}")
    check(abs(losses[0] - ref_losses[0]) <= LOSS_TOLERANCE,
          f"step-0 loss {losses[0]} is not within {LOSS_TOLERANCE} of the "
          f"one-device reference's {ref_losses[0]}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {size.steps} steps: {losses}")
    check(len(mesh_devices) == 4 and all(
        0 < b < total_param_bytes for b in shard_bytes.values()),
        f"not every device holds a proper share of the parameters: "
        f"{shard_bytes} of {total_param_bytes}")
    if expect_kernels:
        # The CPU backend reports no memory statistics.
        check(all(b for b in in_use.values()),
              f"a device reports no live memory: {in_use}")
    for axis in (PP_AXIS, TP_AXIS):
        check(by_axis.get(axis),
              f"no collective over the {axis!r} axis in the compiled step: "
              f"{by_axis}")


# ----------------------------------------------------------------------

def describe_environment(chips):
    """Refuse anything that is not the chip; print what was found."""
    bad = [k for k in FORBIDDEN_ENV if os.environ.get(k)]
    if bad:
        sys.exit(f"chip_smoke: refusing to run with {bad} set: they switch "
                 "off or stand in for what this run is there to check.")
    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: found no TPU (JAX reports platform "
                 f"{devices[0].platform!r}, {len(devices)} device(s)); this "
                 "run means nothing off the chip.")
    kind = devices[0].device_kind
    if kind not in KNOWN_DEVICE_KINDS:
        sys.exit(f"chip_smoke: device kind {kind!r} is not one this run was "
                 f"sized for ({KNOWN_DEVICE_KINDS}).")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX finds {len(devices)}.")

    from smdistributed_modelparallel_tpu.backend import native
    from smdistributed_modelparallel_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except (ImportError, AttributeError):
        libtpu_version = None
    emit(
        "environment", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version, platform=devices[0].platform,
        device_kind=kind, device_count=len(devices),
        compile_cache_dir=cache_dir,
        compile_cache_entries_at_start=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        native_library="built" if native.available() else "pure_python",
    )
    return devices, compile_cache.CacheCounter()


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: train, fused-CE step and serve on one chip (default). "
             "4: the pp=2 x tp=2 trainer and its one-device reference only.")
    args = parser.parse_args(argv)

    t_start = time.perf_counter()
    devices, counter = describe_environment(args.chips)

    from smdistributed_modelparallel_tpu.utils.logger import get_logger

    watch = WarningWatch()
    get_logger().addHandler(watch)
    if args.chips == 4:
        sharded_phase(FourChipSize(), counter)
    else:
        one_chip(OneChipSize(), counter)
    watch.check()

    emit("total", seconds=round(time.perf_counter() - t_start, 1),
         compile_cache=counter.counts())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
