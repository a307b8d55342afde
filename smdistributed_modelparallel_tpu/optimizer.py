"""DistributedOptimizer: optax-backed optimizer with smp semantics.

Parity target: reference ``torch/optimizers/optimizer.py:437-549``
(``DistributedOptimizer``): wraps the user optimizer, makes ``step()``
distribution-aware (sharded update + allgather under
``shard_optimizer_state``), and provides TP/shard-aware state_dicts. Here
the user optimizer is an ``optax.GradientTransformation``; ``step()``
consumes the gradients stashed by the last ``@smp.step`` call and applies a
jit-compiled donated update. Under ``shard_optimizer_state`` (M4) the
optimizer state carries rdp-sharded PartitionSpecs — the reference's
contiguous-buffer/virtual-parameter machinery (``torch/model.py:1237-1340``)
reduces to sharding annotations, and XLA emits the reduce-scatter/allgather
pair of a sharded update.
"""

import numpy as np

import jax
import jax.numpy as jnp
import optax

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.module_manager import path_key
from smdistributed_modelparallel_tpu.utils import health
from smdistributed_modelparallel_tpu.utils import profiling
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPValidationError,
    StepUsageError,
)
from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()


_OPTIMIZER_SERIAL = [0]


def _hold_fixed(updates):
    """``updates`` with a zero for every leaf the model keeps as loaded
    (``nn/utils.is_fixed_param``: no gradient reaches one, and this takes
    the transformation's own terms, weight decay, off it). A tree with no
    such leaf is returned as it is."""
    from smdistributed_modelparallel_tpu.nn.utils import is_fixed_param

    return jax.tree_util.tree_map_with_path(
        lambda path, u: jnp.zeros_like(u)
        if is_fixed_param(path_key(path)) else u, updates)


class DistributedOptimizer:
    def __init__(self, tx, model=None, grad_clip_norm=None):
        # Monotonic serial for step-cache keys: id() can be reused by the
        # allocator after a replaced optimizer is collected, which would let
        # a new optimizer silently hit the old optimizer's cached fused
        # update.
        _OPTIMIZER_SERIAL[0] += 1
        self._serial = _OPTIMIZER_SERIAL[0]
        if not isinstance(tx, optax.GradientTransformation):
            raise SMPValidationError(
                "DistributedOptimizer expects an optax.GradientTransformation "
                f"(got {type(tx).__name__})."
            )
        self.tx = tx
        self.model = model if model is not None else state.model
        if self.model is None:
            raise SMPValidationError("Create smp.DistributedModel before the optimizer.")
        self.grad_clip_norm = grad_clip_norm
        self._opt_state = None
        self._update = None
        state.optimizer = self

    # ------------------------------------------------------------------

    def _ensure_state(self):
        if self._opt_state is not None:
            return
        if self.model.params is None:
            raise StepUsageError(
                "Optimizer state is created lazily from model parameters; run a "
                "step (or initialize the model) before optimizer.step()."
            )
        from smdistributed_modelparallel_tpu.parallel.zero import opt_state_shardings

        self._opt_state = jax.jit(self.tx.init)(self.model.params)
        opt_shardings = opt_state_shardings(self._opt_state, self.model)
        if opt_shardings is not None:
            self._opt_state = jax.device_put(self._opt_state, opt_shardings)
        if state.loaded_optimizer_state is not None:
            # Deferred resume payload (parity: reference
            # torch/optimizers/optimizer.py:545-547).
            from smdistributed_modelparallel_tpu.shard_io import ShardCatalog

            logger.info("Applying deferred checkpoint state to optimizer.")
            payload = state.loaded_optimizer_state
            state.loaded_optimizer_state = None
            if isinstance(payload, ShardCatalog):
                self.load_sharded(payload)
            else:
                self.load_state_dict(payload)

        update = self.build_update_fn()

        # Pin output shardings: without them GSPMD may return params
        # resharded to whatever layout the update program preferred (e.g. a
        # tp-sharded embedding coming back from tp-sharded grads), after
        # which the step's AOT executable rejects its inputs and every
        # subsequent step pays jit-dispatch. Parity: the reference's
        # post-step param allgather restores the canonical placement
        # (torch/optimizers/optimizer.py:355-391); here the canonical
        # placement is the partitioner's _param_shardings.
        param_pin = self.model._param_shardings
        opt_pin = opt_shardings if opt_shardings is not None else (
            jax.tree_util.tree_map(lambda l: l.sharding, self._opt_state)
        )
        out_shardings = None
        if param_pin is not None:
            out_shardings = (param_pin, opt_pin)
        self._update = jax.jit(
            update, donate_argnums=(0, 1), out_shardings=out_shardings
        )

    def build_update_fn(self):
        """Pure (params, opt_state, grads) -> (new_params, new_opt_state)
        update, shared between the standalone jitted update and the fused
        in-step update (``fused_optimizer_step``)."""
        clip = self.grad_clip_norm
        tx = self.tx

        def update(params, opt_state, grads):
            # In-graph profiler region: the optimizer's ops carry this
            # scope in HLO op metadata, so an XLA trace of the fused step
            # shows where the update ends and the model compute begins.
            with profiling.named_region("smp/optimizer/update"):
                if clip is not None:
                    gnorm = optax.global_norm(grads)
                    scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
                updates, new_opt_state = tx.update(grads, opt_state, params)
                updates = _hold_fixed(updates)
                new_params = optax.apply_updates(params, updates)
            return new_params, new_opt_state

        return update

    # ------------------------------------------------------------------

    def step(self):
        """Apply the gradients stashed by the last @smp.step call.

        Parity: reference patched ``step()``
        (``torch/optimizers/optimizer.py:355-391``) — sharded update then
        param allgather; under XLA both emerge from the sharding specs.
        """
        # It belongs to the step whose call has just returned.
        with profiling.region("optimizer/step", step=state.step_count - 1):
            self._step_impl()

    def _step_impl(self):
        if self.model._grads_store is None:
            raise StepUsageError(
                "No gradients available: run an @smp.step function with "
                "model.backward(loss) before optimizer.step()."
            )
        # Fused path (``fused_optimizer_step``): the step program already
        # computed (new_params, new_opt_state) in the same launch; installing
        # them is a host-side pointer swap. Guarded by grads identity so a
        # user who replaced model._grads (custom grad processing) falls back
        # to the real update below. The identity check deliberately avoids
        # reading model._grads (that would force the lazy average).
        pending = getattr(self.model, "_pending_update", None)
        self.model._dropped_updates = 0  # the loop does call optimizer.step()
        if pending is not None:
            self.model._pending_update = None
            if (
                pending[0] is not None
                and self.model._grads_token_is(pending[0])
                and self.model._params is pending[3]
                and self._opt_state is pending[4]
            ):
                self.model.params = pending[1]
                self._opt_state = pending[2]
                if health.enabled():
                    # Grad-norm / update-ratio gauges (before the grads
                    # store is cleared). Under fused_step_donation the
                    # pending tuple is self-referential (old params gone)
                    # — the ratio is skipped there.
                    old = pending[3] if pending[3] is not pending[1] else None
                    health.record_update_stats(self.model, old, pending[1])
                self.model._grads = None
                self.model._grads_finite = None
                return
        grads = self.model._grads
        self._ensure_state()
        scaler = state.loss_scaler
        finite = self.model._grads_finite
        if finite is not None and not bool(finite):
            # Overflow under fp16 loss scaling: skip the update, back the
            # scale off (reference Bit16_Optimizer skip path; agreement
            # across ranks is implicit — the flag is one SPMD value).
            if scaler is not None:
                scaler.update(True)
            self.model._grads = None
            self.model._grads_finite = None
            return
        with jax.set_mesh(state.mesh):
            new_params, self._opt_state = self._update(
                self.model.params, self._opt_state, grads
            )
        self.model.params = new_params
        if health.enabled():
            # The pre-update params were donated into _update, so only the
            # grad/param norms are recorded here; the update ratio comes
            # from the fused path, which retains the old tree.
            health.record_update_stats(self.model, None, new_params)
        self.model._grads = None
        self.model._grads_finite = None
        if scaler is not None:
            scaler.update(False)

    def zero_grad(self):
        self.model._grads = None

    # ------------------------------------------------------------------

    @property
    def opt_state(self):
        return self._opt_state

    def state_dict(self):
        """Gathered optimizer state as numpy arrays keyed by pytree path."""
        self._ensure_state()
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self._opt_state)[0]:
            key = path_key(path)
            flat[key] = np.asarray(jax.device_get(leaf)) if isinstance(
                leaf, jax.Array
            ) else leaf
        return flat

    def local_state_dict(self):
        """Per-process shard payload (parity: reference ``local_state_dict``;
        r2 weak item: this used to gather the full state). Round-trips
        through ``load_state_dict``."""
        from smdistributed_modelparallel_tpu.shard_io import shard_payload

        self._ensure_state()
        return shard_payload(self._opt_state, dedupe_global=False)

    def load_sharded(self, catalog):
        """Load a sharded optimizer checkpoint (``shard_io`` catalog)."""
        self._ensure_state()
        shardings = jax.tree_util.tree_map(
            lambda l: l.sharding if isinstance(l, jax.Array) else None,
            self._opt_state,
        )
        try:
            self._opt_state = catalog.load_tree(self._opt_state, shardings)
        finally:
            catalog.close()

    def load_state_dict(self, flat_dict):
        from smdistributed_modelparallel_tpu.shard_io import (
            InMemoryCatalog,
            is_shard_payload,
        )

        if is_shard_payload(flat_dict):
            self.load_sharded(InMemoryCatalog(flat_dict))
            return
        self._ensure_state()
        leaves, _ = jax.tree_util.tree_flatten_with_path(self._opt_state)
        new = []
        for path, old in leaves:
            key = path_key(path)
            if key in flat_dict and isinstance(old, jax.Array):
                arr = jnp.asarray(flat_dict[key], dtype=old.dtype)
                new.append(jax.device_put(arr, old.sharding))
            else:
                new.append(old)
        self._opt_state = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._opt_state), new
        )

