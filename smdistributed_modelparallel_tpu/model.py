"""DistributedModel: the central model wrapper.

Parity target: reference ``torch/model.py:110-1608`` (``DistributedModel``).
The reference wraps an ``nn.Module`` tree, re-instantiates TP-marked modules,
wraps in a DDP fork, patches forwards to route cross-partition calls through
the module-server, and manages parameter placement after partitioning.

TPU-native re-design: the wrapped module is a Flax module; parameters are an
explicit pytree initialized lazily on the first ``@smp.step`` call (the
reference's first-step trace/partition moment, ``torch/server.py:345-352``).
Instead of moving parameters between processes, partitioning produces a
``NamedSharding`` per parameter over the mesh (pp stage assignment -> pp
axis specs in M2, TP specs in M3, ZeRO/rdp specs in M4); XLA moves the data.
``model(...)`` inside a step function applies the module with the parameters
of the current trace, and ``model.backward(loss)`` records the loss tracer
so the step engine can differentiate — the SPMD replacement for the
reference's autograd-graph-driven distributed backward
(``torch/patches/execution.py:400-441``).
"""

import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.module_manager import path_key
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPValidationError,
    StepUsageError,
)
from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()


class DistributedModel:
    """Wraps a Flax module for distributed execution under @smp.step.

    Args:
      module: a ``flax.linen.Module`` (including ``smp.nn`` modules).
      loss_scale / dtype policy are handled by the step engine via config
      (fp16/bf16 keys), not here.
      rngs: names of RNG streams the module needs besides "params"
        (e.g. ("dropout",)).
      trace_device: device used for the one-time eager init run.
    """

    def __init__(self, module, rngs=("dropout",), name="main",
                 translate_functions=None):
        if state.cfg is None:
            raise SMPValidationError("Call smp.init(config) before DistributedModel().")
        self.module = module
        self.name = name
        self.rng_streams = tuple(rngs)
        # (to_hf, from_hf) state-dict translators for this instance (set by
        # smp.from_hf); checkpoint translate_if_full prefers these over the
        # class-keyed registry entry (several HF families share one
        # distributed class).
        self._translate_functions = translate_functions
        self._params = None               # materialized param pytree (jax.Arrays)
        self._param_shardings = None      # pytree of NamedSharding
        self._grads_store = None          # ("avg", tree) | ("raw", tree, divisor, avg_cache)
        self._grads_finite = None         # device bool under fp16 loss scaling
        self._pending_update = None       # fused-step (grads_token, params, opt_state)
        self._tls = threading.local()     # per-trace bound params / backward loss
        self._partition_result = None     # set by the pipeline partitioner (M2)
        self._pipeline_spec = None        # PipelineSpec when pp > 1 (M2)
        self._output_aval = None          # output shapes of the model call
        self._input_aval = None
        self._post_partition_hooks = []
        self._train = True
        state.model = self

        from smdistributed_modelparallel_tpu.module_manager import ModuleManager

        # Annotations (set_partition / set_tensor_parallelism / ...) may have
        # been made before DistributedModel construction; adopt the existing
        # manager rather than dropping them.
        if state.module_manager is not None and state.module_manager.root_module is None:
            self.module_manager = state.module_manager
            self.module_manager.root_module = module
        else:
            self.module_manager = ModuleManager(module)
        state.module_manager = self.module_manager

        # Re-instantiate tp-marked registered modules as their smp.nn
        # counterparts (parity: reference _replace_tp_counterparts,
        # torch/model.py:285-333).
        from smdistributed_modelparallel_tpu.nn.auto_distribute import distribute_tree

        self.module, self._tp_replaced = distribute_tree(
            module, self.module_manager, state.tp_registry
        )
        self.module_manager.root_module = self.module

    # ------------------------------------------------------------------
    # Tracing-time interface (used inside @smp.step user functions)
    # ------------------------------------------------------------------

    def __call__(self, *args, **kwargs):
        # Pipeline capture/force modes (pp > 1, see step.py): the step engine
        # traces the user fn with the model call intercepted — 'capture'
        # records the inputs and returns a dummy of the right shape; 'force'
        # substitutes the pipelined output.
        mode = getattr(self._tls, "call_mode", None)
        if mode is not None:
            kind, payload = mode
            self._tls.captured_calls.append((args, kwargs))
            if kind == "capture":
                return jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), payload
                )
            return payload  # force

        params = getattr(self._tls, "bound_params", None)
        if params is None:
            # Eager call outside a step: use materialized params (init first).
            if self._params is None:
                self._eager_init(args, kwargs)
            params = self._params
        rngs = getattr(self._tls, "rngs", None)
        variables = {"params": params}
        # Run with intermediates mutable so MoE router load-balancing losses
        # (sown under "moe_aux_loss", nn/moe.py) reach the step engine; they
        # are folded into the differentiated loss in _end_step_trace.
        from smdistributed_modelparallel_tpu.nn.moe import (
            collect_moe_aux,
            collect_moe_stats,
        )

        out, mut = self.module.apply(
            variables, *args, rngs=rngs, mutable=["intermediates"], **kwargs
        )
        if getattr(self._tls, "in_step", False):
            aux = collect_moe_aux(mut.get("intermediates"))
            if aux is not None:
                prev = getattr(self._tls, "aux_loss", None)
                self._tls.aux_loss = aux if prev is None else prev + aux
            self._tls.moe_stats = collect_moe_stats(mut.get("intermediates"))
        self._output_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), out
        )
        self._input_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") else a,
            (args, kwargs),
        )
        return out

    def moe_stats(self):
        """Inside an @smp.step function, after the model call: the
        dropless expert layers' counters for this microbatch, ``{layer
        path: int32 [..., held + 1]}`` (held experts' loads, then the
        dropped count; ``{}`` for a model without such layers). Return it
        from the step function beside the loss, and hand the step's output
        to ``smp.nn.record_moe_stats`` outside any timed path."""
        return dict(getattr(self._tls, "moe_stats", None) or {})

    def backward(self, loss):
        """Record the scalar to differentiate for this microbatch.

        Parity: reference ``model.backward(loss)`` inside @smp.step
        (``torch/model.py:1113-1146``). Under the functional design this
        marks the loss; actual differentiation happens in the step engine.
        """
        if getattr(self._tls, "in_step", False):
            if getattr(self._tls, "backward_loss", None) is not None:
                raise StepUsageError("model.backward() called twice in one microbatch.")
            self._tls.backward_loss = loss
        else:
            # Outside a step: reference raises; we record for forward-only use.
            raise StepUsageError("model.backward() must be called inside an @smp.step function.")
        return loss

    # -- step-engine hooks ---------------------------------------------

    def _begin_step_trace(self, params, rngs):
        self._tls.bound_params = params
        self._tls.rngs = rngs
        self._tls.backward_loss = None
        self._tls.in_step = True
        self._tls.call_mode = None
        self._tls.captured_calls = []
        self._tls.aux_loss = None
        self._tls.moe_stats = None

    def _begin_capture(self, out_aval):
        """Intercept the model call: record inputs, return zeros(out_aval)."""
        self._begin_step_trace(None, None)
        self._tls.call_mode = ("capture", out_aval)

    def _begin_force(self, params, rngs, value):
        """Intercept the model call: record inputs, return `value`."""
        self._begin_step_trace(params, rngs)
        self._tls.call_mode = ("force", value)

    def _end_step_trace(self):
        loss = getattr(self._tls, "backward_loss", None)
        aux = getattr(self._tls, "aux_loss", None)
        self._tls.captured = getattr(self._tls, "captured_calls", [])
        self._tls.bound_params = None
        self._tls.rngs = None
        self._tls.backward_loss = None
        self._tls.in_step = False
        self._tls.call_mode = None
        self._tls.captured_calls = []
        self._tls.aux_loss = None
        if loss is not None and aux is not None:
            weight = getattr(state.cfg, "moe_aux_loss_weight", 1.0)
            if weight:
                loss = loss + jnp.asarray(weight, loss.dtype) * aux.astype(
                    loss.dtype
                )
        return loss

    @property
    def _last_captured(self):
        return getattr(self._tls, "captured", [])

    # ------------------------------------------------------------------
    # Initialization / partitioning
    # ------------------------------------------------------------------

    @property
    def initialized(self):
        return self._params is not None

    def _init_rngs(self):
        mgr = state.rng_manager
        rngs = {"params": mgr.next_key("params")}
        for s in self.rng_streams:
            rngs[s] = mgr.next_key(s)
        return rngs

    def _eager_init(self, args, kwargs):
        """Materialize parameters from example inputs (first model call).

        Parity note: this is the reference's first-step tracing moment
        (``torch/worker.py:248-278``); here it both creates params and
        gives the partitioner concrete shapes. Under
        ``delayed_parameter_initialization`` parameters are born sharded
        (never materialized whole on one device).
        """
        if state.cfg is not None and state.cfg.delayed_parameter_initialization:
            self._sharded_init(args, kwargs)
            return
        logger.info("Initializing model parameters from first batch shapes.")
        # set_mesh: partial-manual shard_map regions (context parallelism)
        # inside the init need the mesh bound at the jit call site.
        with jax.set_mesh(state.mesh):
            variables = jax.jit(self.module.init)(
                self._init_rngs(), *args, **kwargs
            )
        params = variables["params"]
        self._set_params(params)

    def _sharded_init(self, args, kwargs):
        """Delayed (sharded) parameter initialization.

        Parity: reference ``delay_param_initialization``
        (``torch/parameter.py:24-123`` + ``torch/model.py:511-584``,
        torchdistx deferred init: parameters materialize only on their
        owning rank after partitioning). TPU-native: ``jax.eval_shape`` the
        init to learn shapes + sharding metadata, build the NamedShardings
        from the registered specs, then compile the init with
        ``out_shardings`` so every parameter materializes directly in its
        sharded placement — per-device init memory is the shard, not the
        tree.
        """
        from flax.core import meta as flax_meta

        logger.info("Delayed init: materializing parameters directly sharded.")
        rngs = self._init_rngs()
        aval_vars = jax.eval_shape(
            lambda r, a, kw: self.module.init(r, *a, **kw), rngs, args, kwargs
        )
        aval_params = self._adopt_param_metadata(aval_vars["params"])
        self.module_manager.record_param_tree(aval_params)
        mesh = state.mesh
        shardings = self.module_manager.param_shardings(mesh, aval_params)

        def init_unboxed(r, a, kw):
            return flax_meta.unbox(self.module.init(r, *a, **kw)["params"])

        with jax.set_mesh(mesh):
            compiled = (
                jax.jit(init_unboxed, out_shardings=shardings)
                .lower(rngs, args, kwargs)
                .compile()
            )
            try:
                self._init_memory_analysis = compiled.memory_analysis()
            except Exception:  # pragma: no cover - backend-specific
                self._init_memory_analysis = None
            params = compiled(rngs, args, kwargs)
        self._set_params(params)

    def _set_params(self, params):
        params = self._adopt_param_metadata(params)
        self._params = params
        self.module_manager.record_param_tree(params)
        self._apply_shardings()
        if state.loaded_model_state is not None:
            # Deferred resume_from_checkpoint payload (parity: reference
            # torch/model.py:245-251).
            from smdistributed_modelparallel_tpu.shard_io import ShardCatalog

            logger.info("Applying deferred checkpoint state to model.")
            payload = state.loaded_model_state
            state.loaded_model_state = None
            if isinstance(payload, ShardCatalog):
                self.load_sharded(payload)
            else:
                self.load_state_dict(payload)
        for hook in self._post_partition_hooks:
            hook(self)

    def _adopt_param_metadata(self, params):
        """Unbox flax ``Partitioned`` metadata (smp.nn modules attach tp axis
        names via ``nn.with_partitioning``) and register the resulting specs
        with the module manager.

        TPU-native counterpart of the reference's ``parameter_creation_scope``
        distribution-axis registry (``torch/nn/utils.py:120-154``,
        ``torch/module_manager.py:240-277``): where the reference records
        which dim of each param is sliced across tp_ranks, here the record is
        the param's PartitionSpec, consumed during ``_apply_shardings``.
        """
        import flax.linen as fnn
        from flax.core import meta as flax_meta

        boxed = [
            leaf for leaf in jax.tree_util.tree_leaves(
                params, is_leaf=lambda x: isinstance(x, flax_meta.AxisMetadata)
            )
            if isinstance(leaf, flax_meta.AxisMetadata)
        ]
        if not boxed:
            return params
        spec_tree = fnn.get_partition_spec(params)
        flat_specs = {}
        for path, spec in jax.tree_util.tree_flatten_with_path(
            spec_tree, is_leaf=lambda x: isinstance(x, P)
        )[0]:
            if any(axis is not None for axis in spec):
                flat_specs[path_key(path)] = spec

        def provider(path, leaf):
            return flat_specs.get(path)

        self.module_manager.register_spec_provider(provider, name="tp_params")
        return flax_meta.unbox(params)

    def _apply_shardings(self):
        """Compute and apply parameter shardings.

        M1: replicate everything (DP only). M2/M3/M4 refine this with
        pp-stage, tp, and ZeRO specs via the module_manager's partition
        and the nn modules' sharding metadata.
        """
        mesh = state.mesh
        self._param_shardings = self.module_manager.param_shardings(mesh, self._params)
        self._params = jax.device_put(self._params, self._param_shardings)
        # The identity-keyed regather_for_decode cache can never serve the
        # replaced tree, but the superseded full-size gathered copy would
        # stay pinned in HBM until the next params-setter call — drop it
        # with the tree it was built from (ADVICE round 5).
        self._decode_params_cache = None

    def post_partition(self, partition_result):
        """Install a pipeline-partition result (M2)."""
        self._partition_result = partition_result
        if self._params is not None:
            self._apply_shardings()

    def register_post_partition_hook(self, hook):
        """Parity: reference ``smp.register_post_partition_hook``."""
        self._post_partition_hooks.append(hook)
        return hook

    # ------------------------------------------------------------------
    # Parameter access / state_dict
    # ------------------------------------------------------------------

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, new_params):
        self._params = new_params
        # The pp-regathered decode copy (regather_for_decode) is keyed to
        # the old tree; dropping it here frees the full-size gathered
        # params as soon as they go stale instead of pinning them in HBM
        # across the following training steps.
        self._decode_params_cache = None

    @property
    def grads(self):
        return self._grads

    # _grads backs onto a store that can hold the RAW microbatch-sum tree
    # from a fused step (averaging folds into the optimizer update, so the
    # mean is only computed if someone actually reads the grads).
    @property
    def _grads(self):
        store = self._grads_store
        if store is None:
            return None
        if store[0] == "avg":
            return store[1]
        _, raw, divisor, avg = store
        if avg is None:
            avg = jax.tree_util.tree_map(
                lambda g, p: (g / divisor).astype(p.dtype), raw, self._params
            )
            self._grads_store = ("raw", raw, divisor, avg)
        return avg

    @_grads.setter
    def _grads(self, value):
        self._grads_store = None if value is None else ("avg", value)

    def _set_raw_grads(self, raw, divisor):
        self._grads_store = ("raw", raw, divisor, None)

    def _grads_token_is(self, token):
        """Identity check against the step's grads output without forcing
        the lazy average."""
        store = self._grads_store
        if store is None:
            return False
        return (store[1] is token)

    def parameters(self):
        """Flat list of parameter arrays (reference-compat-ish)."""
        return jax.tree_util.tree_leaves(self._params)

    def local_parameters(self):
        """Parity: reference ``local_parameters`` — params owned by this
        rank's partition. Under SPMD all params are mesh-sharded; the local
        view is the addressable shards."""
        return jax.tree_util.tree_leaves(self._params)

    def num_parameters(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    def state_dict(self):
        """Full (gathered) state dict of numpy arrays, keyed by '/'-joined
        paths. Parity: reference ``torch/model.py:863-932``."""
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self._params)[0]:
            key = path_key(path)
            flat[key] = np.asarray(jax.device_get(leaf))
        return flat

    def local_state_dict(self):
        """Per-process shard payload. Parity: reference ``local_state_dict``
        (``torch/model.py:1482+``); the replica-0 shards addressable from
        this process, round-trippable through ``load_state_dict``."""
        from smdistributed_modelparallel_tpu.shard_io import shard_payload

        return shard_payload(self._params, dedupe_global=False)

    def load_state_dict(self, flat_dict):
        """Load a '/'-keyed flat dict into the param tree (resharding as
        needed). Shard payloads (``local_state_dict`` output) load
        shard-wise."""
        from smdistributed_modelparallel_tpu.shard_io import (
            InMemoryCatalog,
            is_shard_payload,
        )

        if is_shard_payload(flat_dict):
            self.load_sharded(InMemoryCatalog(flat_dict))
            return
        if self._params is None:
            raise SMPValidationError(
                "Model parameters are not initialized; run a step or call "
                "init_from_state_dict with example inputs first."
            )
        leaves, treedef = jax.tree_util.tree_flatten_with_path(self._params)
        new_leaves = []
        for path, old in leaves:
            key = path_key(path)
            if key not in flat_dict:
                raise SMPValidationError(f"Missing parameter '{key}' in state dict.")
            arr = jnp.asarray(flat_dict[key], dtype=old.dtype)
            if arr.shape != old.shape:
                raise SMPValidationError(
                    f"Shape mismatch for '{key}': {arr.shape} vs {old.shape}"
                )
            new_leaves.append(arr)
        params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._params), new_leaves
        )
        self._params = jax.device_put(params, self._param_shardings)
        self._decode_params_cache = None

    def load_sharded(self, catalog):
        """Load a sharded checkpoint (``shard_io`` catalog): each process
        reads only the pieces its addressable shards need — no full-tree
        materialization anywhere. Parity: reference per-rank partial load
        (``torch/checkpoint.py:42-122``)."""
        if self._params is None:
            raise SMPValidationError(
                "Model parameters are not initialized; run a step first."
            )
        try:
            self._params = catalog.load_tree(
                self._params, self._param_shardings
            )
            self._decode_params_cache = None
        finally:
            catalog.close()

    # ------------------------------------------------------------------
    # train / eval mode (dropout etc. is explicit in flax; kept for parity)
    # ------------------------------------------------------------------

    def generate(self, input_ids, max_new_tokens, **kwargs):
        """Autoregressive sampling via the KV-cache decode path; see
        ``smp.generate`` (``generation.py``)."""
        from smdistributed_modelparallel_tpu.generation import generate

        return generate(self, input_ids, max_new_tokens, **kwargs)

    def regather_for_decode(self):
        """Decode-ready view of the parameters under pipeline parallelism.

        Training at pp > 1 shards stacked layer parameters over the 'pp'
        mesh axis (one stage's layers per submesh). The decode path is a
        plain forward — no pipeline schedule — so it wants those stacks
        whole: this re-places the parameter tree onto shardings with the
        pp axis stripped (an all-gather along pp over ICI), leaving
        tp/ZeRO axes in place. Training state is untouched: the original
        pp-sharded ``self.params`` remain installed, and the regathered
        tree is cached until the next optimizer step replaces the params.

        Enables the train-at-pp-then-sample workflow the reference
        supports by exporting to HF (SURVEY §2.3; the reference has no
        in-framework decode at all).
        """
        from jax.sharding import NamedSharding

        from smdistributed_modelparallel_tpu.backend.topology import PP_AXIS
        from smdistributed_modelparallel_tpu.parallel.sharding import (
            strip_axis,
        )

        if self._params is None:
            raise SMPValidationError(
                "Model parameters are not initialized; run a step first."
            )
        cached = getattr(self, "_decode_params_cache", None)
        if cached is not None and cached[0] is self._params:
            return cached[1]

        def strip_pp(sharding):
            return NamedSharding(
                sharding.mesh, strip_axis(sharding.spec, PP_AXIS)
            )

        shardings = jax.tree_util.tree_map(strip_pp, self._param_shardings)
        gathered = jax.device_put(self._params, shardings)
        self._decode_params_cache = (self._params, gathered)
        return gathered

    def train(self):
        self._train = True
        return self

    def eval(self):
        self._train = False
        return self

    @property
    def training(self):
        return self._train

