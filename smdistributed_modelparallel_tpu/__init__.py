"""smdistributed_modelparallel_tpu — TPU-native model-parallelism framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of AWS SageMaker's
``smdistributed.modelparallel`` (reference surveyed in /root/repo/SURVEY.md):
pipeline, tensor, data, context and sharded-data parallelism behind the
``smp.init`` / ``@smp.step`` / ``smp.DistributedModel`` /
``smp.DistributedOptimizer`` API, lowered to a single SPMD program over a
``jax.sharding.Mesh`` instead of the reference's MPMD module-server runtime.

Typical use::

    import smdistributed_modelparallel_tpu as smp

    smp.init({"pipeline_parallel_degree": 4, "microbatches": 8, "ddp": True})
    model = smp.DistributedModel(module, loss_fn=...)
    optimizer = smp.DistributedOptimizer(optax.adamw(1e-4), model)

    @smp.step
    def train_step(model, batch):
        loss = model(batch)
        model.backward(loss)
        return loss

    losses = train_step(model, batch)   # StepOutput
    optimizer.step()
"""

from smdistributed_modelparallel_tpu.backend.config import ModelParallelConfig
from smdistributed_modelparallel_tpu.backend.collectives import (
    CollectiveCommunicator,
    CommGroup,
    RankType,
)
from smdistributed_modelparallel_tpu.backend.split import StepOutput, TensorSplitter
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils import exceptions
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPError,
    SMPRuntimeError,
    SMPUnsupportedError,
    SMPValidationError,
)
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry, watchdog
from smdistributed_modelparallel_tpu.utils.flight_recorder import flight_recorder
from smdistributed_modelparallel_tpu.utils import health
from smdistributed_modelparallel_tpu.utils import hlo_audit as xray
from smdistributed_modelparallel_tpu.utils import exec_cache
from smdistributed_modelparallel_tpu.utils import profiling
from smdistributed_modelparallel_tpu import resilience
from smdistributed_modelparallel_tpu.resilience.supervisor import supervisor
from smdistributed_modelparallel_tpu.utils.fleet import fleet
from smdistributed_modelparallel_tpu.utils.goodput import goodput
from smdistributed_modelparallel_tpu.model import DistributedModel
from smdistributed_modelparallel_tpu.optimizer import DistributedOptimizer
from smdistributed_modelparallel_tpu.step import step
from smdistributed_modelparallel_tpu.checkpoint import (
    load,
    resume_from_checkpoint,
    save,
    save_checkpoint,
    wait_for_checkpoints,
)
from smdistributed_modelparallel_tpu.nn.tp_registry import (
    tp_register,
    tp_register_with_module,
)
from smdistributed_modelparallel_tpu.nn.huggingface import from_hf
from smdistributed_modelparallel_tpu.generation import generate
from smdistributed_modelparallel_tpu import serving
from smdistributed_modelparallel_tpu.utils.data import (
    dataloader,
    prefetch_to_device,
    shard_batches,
)
from smdistributed_modelparallel_tpu import amp
from smdistributed_modelparallel_tpu import nn

__version__ = "0.1.0"

WORLD = CommGroup.WORLD
PP_GROUP = CommGroup.PP_GROUP
TP_GROUP = CommGroup.TP_GROUP
DP_GROUP = CommGroup.DP_GROUP
RDP_GROUP = CommGroup.RDP_GROUP
MP_GROUP = CommGroup.MP_GROUP


def init(config=None, devices=None):
    """Initialize the framework.

    Parity: reference ``torch/__init__.py:88-176`` (``smp.init``) — config
    validation, backend init, topology construction. The reference also
    launches a C++ listener thread and patches ``nn.Module``; neither has a
    TPU counterpart (there are no in-flight requests, and module recording
    happens at DistributedModel construction).
    """
    cfg = config if isinstance(config, ModelParallelConfig) else ModelParallelConfig(config)
    state.initialize(cfg, devices=devices)
    return cfg


def is_initialized():
    return state.initialized


def shutdown():
    # Decode the last step's pending health word before the session dies:
    # cheap mode is one step behind by design, and a run whose FINAL step
    # went non-finite should still say so (utils/health.py).
    try:
        health.monitor.flush()
    except Exception:
        pass
    state.core.shutdown()
    state.reset()


def reset():
    """Testing hook: drop model/optimizer/step registrations."""
    from smdistributed_modelparallel_tpu.generation import _COMPILED

    _COMPILED.clear()
    state.reset()


# -- rank / size / group queries (parity: backend/core.py:434-489) ------

def rank():
    return state.core.rank()


def size():
    return state.core.size()


def local_rank():
    return state.core.local_rank()


def local_size():
    return state.core.local_size()


def pp_rank():
    return state.core.pp_rank()


def tp_rank():
    return state.core.tp_rank()


def rdp_rank():
    return state.core.rdp_rank()


def dp_rank():
    return state.core.dp_rank()


def mp_rank():
    return state.core.mp_rank()


def cp_rank():
    return state.core.cp_rank()


def pp_size():
    return state.core.pp_size()


def tp_size():
    return state.core.tp_size()


def rdp_size():
    return state.core.rdp_size()


def dp_size():
    return state.core.dp_size()


def mp_size():
    return state.core.mp_size()


def cp_size():
    return state.core.cp_size()


def num_microbatches():
    return state.cfg.microbatches if state.cfg else 1


def get_pp_group():
    return state.core.get_pp_group()


def get_tp_group():
    return state.core.get_tp_group()


def get_dp_group():
    return state.core.get_dp_group()


def get_rdp_group():
    return state.core.get_rdp_group()


def get_mp_group():
    return state.core.get_mp_group()


def get_world_group():
    return state.core.get_world_group()


def get_mesh():
    """The jax.sharding.Mesh for the current topology (TPU-native addition)."""
    return state.mesh


def barrier(group=CommGroup.WORLD):
    """Barrier over the host processes of `group` (subgroup barriers ride
    the native message bus; see backend/collectives.py)."""
    state.comm.barrier(group=group)


def mp_barrier():
    barrier(CommGroup.MP_GROUP)


def pp_barrier():
    barrier(CommGroup.PP_GROUP)


def dp_barrier():
    barrier(CommGroup.DP_GROUP)


def tp_barrier():
    barrier(CommGroup.TP_GROUP)


def rdp_barrier():
    barrier(CommGroup.RDP_GROUP)


def broadcast(obj, group=CommGroup.WORLD, src=0):
    """Broadcast a picklable object across the processes of `group`.
    Parity: reference ``smp.broadcast`` (``backend/collectives.py``)."""
    return state.comm.broadcast(obj, group=group, src=src)


def allgather(obj, group=CommGroup.WORLD):
    """Gather a picklable object from every process of `group`."""
    return state.comm.allgather(obj, group=group)


def send(obj, dest, group=CommGroup.WORLD):
    """Async-send a picklable object to process `dest` of `group` over the
    native message bus. Parity: reference ``smp.send``."""
    state.comm.send(obj, dest, group=group)


def recv_from(src, group=CommGroup.WORLD):
    """Receive the next in-order object from process `src` of `group`.
    Parity: reference ``smp.recv_from``."""
    return state.comm.recv_from(src, group=group)


def is_tracing():
    """True inside the first-step init/trace pass (parity: reference
    ``smp.is_tracing`` — the module-server trace phase; here the eager
    microbatch-0 run that materializes params and discovers backward)."""
    return bool(getattr(state, "_tracing", False))


def process_index():
    return state.core.process_index()


def process_count():
    return state.core.process_count()


def pp_rank_to_rank(pp_rank):
    """World rank of pipeline stage ``pp_rank`` in this rank's tp x rdp
    group. Parity: reference ``backend/core.py:439-446``."""
    return state.core.pp_rank_to_rank(pp_rank)


def tp_rank_to_rank(tp_rank):
    return state.core.tp_rank_to_rank(tp_rank)


def rdp_rank_to_rank(rdp_rank):
    return state.core.rdp_rank_to_rank(rdp_rank)


def dp_rank_to_rank(dp_rank):
    return state.core.dp_rank_to_rank(dp_rank)


def mp_rank_to_rank(mp_rank):
    return state.core.mp_rank_to_rank(mp_rank)


def instance_id(rank=None):
    """Host (instance) id of device ``rank`` (default: this process's).
    Parity: reference ``smp.instance_id`` (backend/core.py:486-489)."""
    return state.core.instance_id(rank)


def is_in_same_instance(rank):
    """Whether device ``rank`` is on this process's host. Parity:
    reference ``smp.is_in_same_instance`` (backend/core.py:479-481)."""
    return state.core.is_in_same_instance(rank)


def is_multi_node():
    """Parity: reference ``smp.is_multi_node`` (backend/core.py:483-485)."""
    return state.core.is_multi_node()


# Process-group aliases (reference naming: get_*_process_group).
get_pp_process_group = get_pp_group
get_tp_process_group = get_tp_group
get_dp_process_group = get_dp_group
get_rdp_process_group = get_rdp_group
get_mp_process_group = get_mp_group
get_world_process_group = get_world_group


# -- partition / tp / checkpoint annotation APIs ------------------------
# Parity: reference smp.partition / smp.set_partition /
# smp.tensor_parallelism / smp.set_tensor_parallelism /
# smp.set_activation_checkpointing (torch/module_manager.py:969-1161).

def _module_manager():
    from smdistributed_modelparallel_tpu.module_manager import ModuleManager

    if state.module_manager is None:
        state.module_manager = ModuleManager(None)
    return state.module_manager


def partition(stage):
    """Context manager: flax modules constructed inside are assigned to
    pipeline stage `stage` (stamped at construction; harvested when
    DistributedModel walks the tree). Parity: reference ``smp.partition(i)``
    (``torch/module_manager.py:1161``)."""
    return _module_manager().partition(stage)


def set_partition(module_prefix, stage):
    _module_manager().set_partition(module_prefix, stage)


def get_partition(module_prefix):
    if not isinstance(module_prefix, str):
        raise SMPValidationError(
            "get_partition expects a '/'-joined module path string "
            f"(got {type(module_prefix).__name__})."
        )
    return _module_manager().stage_of(_module_manager_norm(module_prefix))


def _module_manager_norm(prefix):
    from smdistributed_modelparallel_tpu.module_manager import _normalize_prefix

    return _normalize_prefix(prefix)


def set_tensor_parallelism(module_prefix, enabled=True, **tp_config):
    _module_manager().set_tensor_parallelism(module_prefix, enabled, **tp_config)


from contextlib import contextmanager as _contextmanager


@_contextmanager
def tensor_parallelism(enabled=True, **tp_config):
    """Context manager: flax modules constructed inside are marked for TP
    distribution (stamped at construction; swapped for their registered
    smp.nn counterparts when DistributedModel walks the tree). Parity:
    reference ``smp.tensor_parallelism`` (``torch/module_manager.py:1095``).
    """
    mm = _module_manager()
    prev = getattr(mm, "_active_tp", None)
    mm._active_tp = {"enabled": enabled, **tp_config}
    try:
        yield
    finally:
        mm._active_tp = prev


@_contextmanager
def delay_param_initialization(enabled=True):
    """Parity: reference ``smp.delay_param_initialization``
    (``torch/parameter.py``). In this framework delayed initialization is
    STRUCTURAL, not opt-in: flax modules are declarative, and parameters
    materialize directly into their mesh shardings on the first step (or
    ``state_dict`` load) via ``eval_shape`` + ``jit(init, out_shardings)``
    — no full-size host tensor ever exists (``model.py``,
    ``tests/test_delayed_init.py``). The context is accepted for source
    compatibility; ``enabled=False`` cannot force eager host-side init
    and raises rather than silently diverging from the reference
    semantics.
    """
    if not enabled:
        from smdistributed_modelparallel_tpu.utils.exceptions import (
            SMPUnsupportedError,
        )

        raise SMPUnsupportedError(
            "delay_param_initialization(enabled=False) is not supported: "
            "parameters always initialize lazily and sharded under the "
            "JAX runtime (there is no eager host-side init to restore)."
        )
    yield


@_contextmanager
def model_creation(tensor_parallelism=False, dtype=None,
                   **tensor_parallel_config):
    """Parity: reference ``smp.model_creation`` (``torch/model.py:79``).

    Bundles the reference's model-construction concerns the way they map
    to this runtime: parameter initialization is always delayed (see
    ``delay_param_initialization``), and the training compute dtype is
    the ``bf16``/``fp16`` config (parameters stay fp32 master copies, as
    the reference's FP16_Module keeps). ``dtype`` must therefore agree
    with the configured half dtype — a mismatch raises instead of
    silently creating a model the step would cast differently. With
    ``tensor_parallelism=True``, modules constructed inside the context
    are marked for auto-distribution (``smp.tensor_parallelism``).
    """
    if dtype is not None:
        import jax.numpy as _jnp

        # state.cfg survives shutdown()/reset() (other surfaces read it
        # as a last-known config); the dtype check must only ever consult
        # the LIVE config, so an uninitialized session is an error rather
        # than a comparison against a dead or absent config.
        if not state.initialized:
            from smdistributed_modelparallel_tpu.utils.exceptions import (
                NotInitializedError,
            )

            raise NotInitializedError("smp.model_creation(dtype=...)")
        half = state.cfg.half_dtype
        want = _jnp.dtype(dtype)
        allowed = {_jnp.dtype(_jnp.float32)}
        if half is not None:
            allowed.add(_jnp.dtype(half))
        if want not in allowed:
            raise SMPValidationError(
                f"model_creation(dtype={want}) conflicts with the "
                f"configured compute dtype ({half or 'float32'}); set the "
                "bf16/fp16 config key instead of a per-model dtype."
            )
    # The parameter shadows the module-level context manager of the same
    # name (the reference's signature dictates both names).
    tp_ctx = globals()["tensor_parallelism"]
    with tp_ctx(enabled=tensor_parallelism, **tensor_parallel_config):
        with delay_param_initialization():
            yield


def set_activation_checkpointing(module_prefix, **config):
    _module_manager().set_activation_checkpointing(module_prefix, **config)


def checkpoint(fn, *args, **kwargs):
    """Rematerialize `fn` (parity: reference ``smp.checkpoint``)."""
    from smdistributed_modelparallel_tpu.parallel.memory import checkpoint as _ckpt

    return _ckpt(fn, *args, **kwargs)


def checkpoint_sequential(fns, input, strategy="each"):
    """Remat a chain (parity: reference ``smp.checkpoint_sequential``)."""
    from smdistributed_modelparallel_tpu.parallel.memory import (
        checkpoint_sequential as _ckpt_seq,
    )

    return _ckpt_seq(fns, input, strategy)
