"""Global framework state singleton.

Parity target: reference ``backend/state_mod.py:14-93`` (``ModelParallelState``)
and the PyTorch-side ``torch/state_mod.py:31-418`` (``PTModelParallelState``).
Under the SPMD design most of the reference's state (link-id maps, worker
bookkeeping, serialization managers) disappears; what remains is the config,
the core/topology, the current model/optimizer registrations, the module
manager, the tp registry, and RNG management.
"""

from smdistributed_modelparallel_tpu.backend.core import ModelParallelCore
from smdistributed_modelparallel_tpu.utils.exceptions import NotInitializedError


class ModelParallelState:
    def __init__(self):
        self.cfg = None
        self.core = ModelParallelCore()
        self.model = None           # current smp.DistributedModel
        self.optimizer = None       # current smp.DistributedOptimizer
        self.module_manager = None  # set by model.py on DistributedModel creation
        self.tp_registry = None     # lazily created TensorParallelismRegistry
        self.rng_manager = None
        self.loss_scaler = None     # DynamicLossScaler when cfg.fp16
        self.quant_state = None     # quant.QuantState when matmul_precision fp8
        self.timeline = None        # Timeline (SMP_TIMELINE_PATH)
        self.memory_metrics = None  # StepMemoryMetricsCollector
        self.step_count = 0
        self.step_rng = None        # device-carried RNG key advanced by the step program
        self.loaded_model_state = None      # deferred checkpoint payloads
        self.loaded_optimizer_state = None
        self.last_compile_report = None     # one_time_compile_report output
        self._comm = None                   # lazy CollectiveCommunicator
        # Bumped on every (re-)initialize: compiled-step cache keys include
        # it, so a program compiled under an old cfg/mesh can never serve a
        # re-initialized topology (the key's shapes/flags may collide).
        self.generation = 0

    @property
    def comm(self):
        """Host control-plane communicator (parity: reference
        ``state.comm``, ``backend/state_mod.py:14-93``). Lazy: collectives
        imports this module, so construction defers to first use."""
        if self._comm is None:
            from smdistributed_modelparallel_tpu.backend.collectives import (
                CollectiveCommunicator,
            )

            self._comm = CollectiveCommunicator()
        return self._comm

    @property
    def initialized(self):
        return self.core.initialized

    def initialize(self, cfg, devices=None):
        self.cfg = cfg
        self.generation += 1
        self.core.initialize(cfg, devices=devices)
        from smdistributed_modelparallel_tpu.utils.random import RngManager

        self.rng_manager = RngManager(cfg.tensor_parallel_seed)
        from smdistributed_modelparallel_tpu.nn.tp_registry import TensorParallelismRegistry

        if self.tp_registry is None:
            self.tp_registry = TensorParallelismRegistry()
        from smdistributed_modelparallel_tpu.nn.auto_distribute import (
            install_construction_hooks,
            register_builtins,
        )

        register_builtins(self.tp_registry)
        install_construction_hooks()
        from smdistributed_modelparallel_tpu.nn.huggingface import (
            register_predefined_hooks,
        )
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            record_hf_hooks_resolved,
        )

        # Hugging Face classes are registered when first looked up, not
        # here: registering them all imports transformers, torch and
        # tensorflow, half a minute that no Flax or smp model needs.
        self.tp_registry.late_resolver = register_predefined_hooks
        record_hf_hooks_resolved(0)
        if cfg.fp16:
            from smdistributed_modelparallel_tpu.fp16.loss_scaler import (
                DynamicLossScaler,
            )

            self.loss_scaler = DynamicLossScaler()
        else:
            self.loss_scaler = None
        from smdistributed_modelparallel_tpu import quant

        if quant.matmul_precision_mode(cfg) == "fp8":
            # Delayed-scaling amax/scale state, threaded through the
            # step like the loss scaler and checkpointed beside it
            # (quant_states.pt).
            self.quant_state = quant.QuantState()
        else:
            self.quant_state = None
        from smdistributed_modelparallel_tpu.utils.metrics import (
            StepMemoryMetricsCollector,
        )
        from smdistributed_modelparallel_tpu.utils.timeline import Timeline

        self.timeline = Timeline()
        self.memory_metrics = StepMemoryMetricsCollector()
        import jax

        if jax.process_count() > 1:
            # Multi-process bus bring-up is a global collective (endpoint
            # allgather) and so must happen HERE, where every process is
            # known to participate — not lazily from a subgroup op.
            self.comm.initialize_bus()
        from smdistributed_modelparallel_tpu.resilience.preemption import (
            preemption,
        )

        preemption.install()
        from smdistributed_modelparallel_tpu.resilience.supervisor import (
            supervisor,
        )

        # Arm the heartbeat failure detector (SMP_SUPERVISOR=on, multi-
        # process, bus up); re-arms on a recovery's re-initialize. Off is
        # a hard no-op: no thread, no bus traffic, step path untouched.
        supervisor.start()
        from smdistributed_modelparallel_tpu.utils.fleet import fleet

        # Fleet metrics plane (SMP_FLEET_INTERVAL): needs the bus AND
        # the supervisor's liveness verdicts, so it arms after both.
        # Unset/0 constructs nothing — no thread, no traffic, no port.
        fleet.start()
        from smdistributed_modelparallel_tpu.utils.goodput import goodput

        # Wall-clock attribution ledger (SMP_GOODPUT and friends): chains
        # onto the set_phase listener, so it arms after telemetry exists.
        # Idempotent — a recovery's re-initialize keeps the same ledger.
        goodput.start()
        from smdistributed_modelparallel_tpu.utils import profiling

        # SIGUSR2 arms a one-step profiler capture on a live run
        # (utils/profiling.py); the SMP_PROFILE window is read lazily at
        # the first step edge.
        profiling.capture.install_signal()

    def _check(self):
        if not self.initialized:
            raise NotInitializedError()

    @property
    def mesh(self):
        self._check()
        return self.core.mesh

    @property
    def topology(self):
        self._check()
        return self.core.topology

    def reset(self):
        """Testing hook: drop model/optimizer registrations and counters."""
        from smdistributed_modelparallel_tpu.utils import health
        from smdistributed_modelparallel_tpu.utils.flight_recorder import (
            flight_recorder,
        )
        from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

        from smdistributed_modelparallel_tpu.utils.fleet import fleet
        from smdistributed_modelparallel_tpu.utils.goodput import goodput

        from smdistributed_modelparallel_tpu.serving import (
            controller as serving_controller,
        )

        serving_controller.reset_all()
        goodput.reset()
        fleet.reset()
        telemetry.reset()
        flight_recorder.clear()
        health.reset()
        from smdistributed_modelparallel_tpu.utils import profiling

        profiling.capture.reset()
        from smdistributed_modelparallel_tpu.resilience import (
            reset as resilience_reset,
        )

        resilience_reset()
        if self._comm is not None:
            # Barrier ordinals restart with the session, like the metric
            # counters (a re-init resets them on every rank uniformly).
            self._comm._barrier_seq.clear()
        self.model = None
        self.optimizer = None
        self.module_manager = None
        self.step_count = 0
        self.step_rng = None
        self.loaded_model_state = None
        self.loaded_optimizer_state = None
        self.last_compile_report = None


state = ModelParallelState()
