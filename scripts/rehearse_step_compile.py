#!/usr/bin/env python3
"""Compile the framework's own train step for a described v5e — no chip.

    JAX_PLATFORMS=cpu python scripts/rehearse_step_compile.py one
    JAX_PLATFORMS=cpu python scripts/rehearse_step_compile.py one_fused
    JAX_PLATFORMS=cpu python scripts/rehearse_step_compile.py four

The third rehearsal before a chip call (after the tiny CPU run and the run on
virtual devices): the TPU compiler is installed in the sandbox and compiles
for a chip that is described, not attached. Nothing runs and nothing is
timed; what it shows is what the compiler refuses — PR 21 found here that a
bare ``pallas_call`` does not lower under a multi-device jit.

The step engine places arrays on devices in three spots; a described device
holds no arrays, so this script swaps those spots for ``ShapeDtypeStruct``s
with the same shardings, fakes ``jax.default_backend()`` to "tpu" so the
dispatchers take their chip branch, and lets ``@smp.step`` lower and compile.
The call after the compile fails (nothing can run); the executable is read
from ``train_step._cache``. Shapes and configs are ``chip_smoke.py``'s.
"""

import importlib
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke as cs
import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils import hlo_audit


class _Shape(jax.ShapeDtypeStruct):
    nbytes = property(lambda s: s.size * s.dtype.itemsize)


def _shape(x, sharding):
    return _Shape(x.shape, x.dtype, sharding=sharding)


def _patch_placement():
    """Swap the three places that put arrays on devices for shapes."""
    step_mod = importlib.import_module("smdistributed_modelparallel_tpu.step")
    model_mod = importlib.import_module("smdistributed_modelparallel_tpu.model")
    opt_mod = importlib.import_module(
        "smdistributed_modelparallel_tpu.optimizer")
    from smdistributed_modelparallel_tpu.parallel.zero import (
        opt_state_shardings,
    )

    step_mod._place = _shape

    def eager_init(self, args, kwargs):
        rngs = self._init_rngs()       # eager ops: outside the TPU mesh
        with jax.set_mesh(state.mesh):
            variables = jax.eval_shape(self.module.init, rngs, *args, **kwargs)
        self._set_params(variables["params"])

    def apply_shardings(self):
        self._param_shardings = self.module_manager.param_shardings(
            state.mesh, self._params)
        self._params = jax.tree_util.tree_map(
            _shape, self._params, self._param_shardings)
        self._decode_params_cache = None

    def ensure_state(self):
        if self._opt_state is not None:
            return
        shapes = jax.eval_shape(self.tx.init, self.model.params)
        shardings = opt_state_shardings(shapes, self.model)
        if shardings is None:
            replicated = NamedSharding(state.mesh, P())
            shardings = jax.tree_util.tree_map(lambda _: replicated, shapes)
        self._opt_state = jax.tree_util.tree_map(_shape, shapes, shardings)
        self._update = self.build_update_fn()

    model_mod.DistributedModel._eager_init = eager_init
    model_mod.DistributedModel._apply_shardings = apply_shardings
    opt_mod.DistributedOptimizer._ensure_state = ensure_state


def _compile(train_step, model, ids, microbatch):
    t0 = time.time()
    model._eager_init((ids[:microbatch],), {})
    try:
        train_step(model, ids)
    except Exception as e:  # the call after the compile cannot run
        print(f"after the compile (expected): {type(e).__name__}: "
              f"{str(e)[:200]}")
    compiled = cs.compiled_step(train_step)
    mem = compiled.memory_analysis()
    print(f"compiled in {time.time() - t0:.1f} s; per device: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    print("kernels:", cs.kernels_in(compiled))
    return compiled


def main(which):
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"
    _patch_placement()

    if which in ("one", "one_fused"):
        size = cs.OneChipSize()
        from smdistributed_modelparallel_tpu.models.gpt2 import gpt2

        cfg = {"microbatches": size.microbatches, "bf16": True}
        if which == "one_fused":
            cfg["fused_ce"] = True
        smp.init(cfg, devices=topo.devices[:1])
        model = smp.DistributedModel(gpt2(size.model, max_len=size.seq))
        smp.DistributedOptimizer(optax.adamw(size.lr), model)
        train_step = cs.lm_loss_step(smp)
    else:
        size = cs.FourChipSize()
        smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
                  "ddp": True, "microbatches": size.microbatches,
                  "bf16": True}, devices=topo.devices[:4])
        model = smp.DistributedModel(cs.four_chip_module(size))
        smp.DistributedOptimizer(optax.adamw(size.lr), model)

        @smp.step
        def train_step(model, ids):
            loss = cs.plain_ce_loss(model(ids), ids)
            model.backward(loss)
            return loss

    ids = jnp.zeros((size.batch, size.seq), jnp.int32)
    compiled = _compile(
        train_step, model, ids, size.batch // size.microbatches)
    if which == "four":
        census = hlo_audit.collective_census(compiled.as_text(), state.mesh)
        print("collectives:", {
            op: {axis: n["count"] for axis, n in ent["axes"].items()}
            for op, ent in census.items()
        })


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "one")
