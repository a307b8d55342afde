"""Process/topology core.

Parity target: reference ``backend/core.py:191-562`` (``ModelParallelCore``).
The reference wraps a C++ MPI/NCCL backend (ctypes ``smp_init`` etc., SURVEY
§2.1 N1); on TPU the same responsibilities map to:

- bootstrap: ``jax.distributed.initialize`` (multi-host) — no MPI;
- rank/group queries: pure ``Ranker`` arithmetic over device indices
  (reference ranks are 1:1 with GPUs; here 1:1 with TPU chips);
- barrier: ``multihost_utils.sync_global_devices``;
- timeline: see ``utils/timeline.py`` (host-side Perfetto trace, replacing
  the C++ ``smp_create_timeline`` family, SURVEY §2.1 N5).

One deliberate semantic difference: the reference runs one process per GPU,
so ``rank()`` is both a process and a device id. A JAX process drives many
local TPU chips; device-level queries (pp_rank/tp_rank/...) answer for a
given device index (default: this process's first addressable device), while
``process_index()`` exposes the host-level id for checkpoint coordination.
"""

import atexit
import os

import jax

from smdistributed_modelparallel_tpu.backend.topology import DeviceTopology
from smdistributed_modelparallel_tpu.utils.exceptions import (
    NotInitializedError,
    SMPValidationError,
)
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry, watchdog

logger = get_logger()


class ModelParallelCore:
    def __init__(self):
        self.cfg = None
        self.topology = None
        self._initialized = False
        self.exit_hook = None

    # -- lifecycle ------------------------------------------------------

    def initialize(self, cfg, devices=None):
        if self._initialized:
            logger.warning("smp core already initialized; re-initializing topology.")
        self.cfg = cfg
        telemetry.set_phase("init/distributed")
        self._maybe_init_distributed()
        # The first device enumeration is where a backend that cannot come
        # up stalls: guard it so an armed watchdog dumps instead of hanging
        # smp.init silently.
        telemetry.set_phase("init/topology")
        with watchdog.guard("init/topology"):
            # Rank identity first (inside the guard: process_index() itself
            # touches the backend and can wedge), so a topology stall dumps
            # rank-suffixed files instead of N ranks clobbering one path.
            telemetry.process_index = jax.process_index()
            telemetry.process_count = jax.process_count()
            self.topology = DeviceTopology(cfg, devices=devices)
        telemetry.set_phase("initialized")
        self._initialized = True
        self.attach_exit_hook()
        atexit.register(self.shutdown)
        logger.info("Initialized %r over %d device(s), %d process(es).",
                    self.topology, self.topology.size, jax.process_count())

    def attach_exit_hook(self):
        """Parity: reference ``attach_exit_hook`` (``backend/core.py:204``)."""
        if self.exit_hook is None:
            from smdistributed_modelparallel_tpu.utils.exit_hook import ExitHook

            self.exit_hook = ExitHook()
        self.exit_hook.hook()

    def exit_status(self):
        """True when this process is shutting down cleanly."""
        return self.exit_hook.success if self.exit_hook is not None else True

    def _maybe_init_distributed(self):
        """Multi-host bootstrap. Under SageMaker/launcher envs with a
        coordinator address set, bring up the JAX distributed runtime."""
        coord = os.environ.get("SMP_COORDINATOR_ADDRESS") or os.environ.get(
            "JAX_COORDINATOR_ADDRESS"
        )
        if coord and jax.process_count() == 1 and not self._initialized:
            try:
                jax.distributed.initialize()
            except Exception as e:  # already initialized or single-host
                logger.debug("jax.distributed.initialize skipped: %s", e)

    def shutdown(self):
        """Parity: reference ``shutdown`` (``backend/core.py:226-231``) —
        derive the consistent exit status from the exit hook and relay it
        (reference: ``smp_shutdown(success)``; here: best-effort status
        report to process 0 over the bus, which logs failing peers)."""
        if not self._initialized:
            return
        self._initialized = False
        # The fleet metrics plane stops FIRST: its final snapshot/window
        # flush needs the bus, which the exit-status relay below closes,
        # and its scrape server must be gone before the telemetry dump
        # becomes this process's record.
        from smdistributed_modelparallel_tpu.utils.fleet import fleet
        from smdistributed_modelparallel_tpu.utils.goodput import goodput

        # The serving controller closes its open scale events before the
        # fleet plane (its window source) goes away.
        try:
            from smdistributed_modelparallel_tpu.serving import (
                controller as serving_controller,
            )

            serving_controller.shutdown_all()
        except Exception as e:
            logger.warning("serving controller stop failed: %s", e)
        # Goodput ledger flushes BEFORE the fleet plane stops so the final
        # second-counters make the fleet's last aggregated window.
        try:
            goodput.stop()
        except Exception as e:
            logger.warning("goodput ledger stop failed: %s", e)
        try:
            fleet.stop()
        except Exception as e:
            logger.warning("fleet metrics plane stop failed: %s", e)
        success = self.exit_status()
        if not success:
            logger.error(
                "process %d shutting down after failure (exit_code=%r, "
                "exception=%r)", jax.process_index(),
                self.exit_hook.exit_code, self.exit_hook.exception,
            )
        self._relay_exit_status(success)
        # Drain pending async checkpoint saves BEFORE the shutdown dumps:
        # the dumps below are the post-mortem record of this process, and
        # on a crash-exit they must not race (or misrepresent) a
        # half-written checkpoint — once they run, every submitted save has
        # either committed or surfaced its error here.
        from smdistributed_modelparallel_tpu.checkpoint import (
            wait_for_checkpoints,
        )

        try:
            wait_for_checkpoints()
        except Exception as e:
            logger.error(
                "pending async checkpoint save failed during shutdown: %s", e
            )
        # The session timeline (state.timeline, fed by the step engine and
        # the barrier sync marks) flushes here: events recorded after the
        # last step's flush — the final barrier's sync mark above all —
        # must reach the file or trace_fuse loses its alignment signal.
        from smdistributed_modelparallel_tpu.backend.state import state

        # A profiler capture still open at shutdown (run ended inside its
        # window) is closed here so the trace file is usable.
        from smdistributed_modelparallel_tpu.utils import profiling

        profiling.capture.stop_if_active()
        if state.timeline is not None:
            state.timeline.flush()
        telemetry.set_phase("shutdown")
        telemetry.dump()  # no-op unless SMP_TELEMETRY_PATH is set
        from smdistributed_modelparallel_tpu.utils.flight_recorder import (
            flight_recorder,
        )

        flight_recorder.dump()  # no-op unless SMP_FLIGHT_RECORDER_PATH is set

    def _relay_exit_status(self, success):
        """Tell process 0 how this process ended; process 0 polls for peer
        reports against ONE shared deadline and logs failures. Best-effort:
        peers may already be gone at exit, so never block shutdown on this.
        Runs before the bus closes (this method owns closing it — atexit
        LIFO would otherwise tear the bus down under the relay)."""
        if jax.process_count() <= 1:
            return
        from smdistributed_modelparallel_tpu.backend.state import state

        comm = state._comm
        bus = comm._bus if comm is not None else None
        if bus is None:
            return
        try:
            import time

            # Reserved status tx: negative namespace distinct from barriers
            # (barrier ids are even*; -1 is never produced there).
            me = jax.process_index()
            if me != 0:
                bus.send_bytes(0, b"\x01" if success else b"\x00", -1)
            else:
                failed = [] if success else [0]
                pending = set(range(1, jax.process_count()))
                deadline = time.monotonic() + 2.0
                while pending and time.monotonic() < deadline:
                    for peer in list(pending):
                        if bus.poll(peer, -1):
                            if bus.recv_bytes(peer, -1, timeout_ms=0) == b"\x00":
                                failed.append(peer)
                            pending.discard(peer)
                    if pending:
                        time.sleep(0.01)
                if failed:
                    logger.error(
                        "shutdown status: process(es) %s reported failure.",
                        sorted(failed),
                    )
        except Exception:  # pragma: no cover - never block exit
            pass
        finally:
            comm.shutdown()

    @property
    def initialized(self):
        return self._initialized

    def _check(self):
        if not self._initialized:
            raise NotInitializedError("smp core")

    # -- process-level --------------------------------------------------

    def process_index(self):
        return jax.process_index()

    def process_count(self):
        return jax.process_count()

    def barrier(self, name="smp_barrier"):
        self._check()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            # A global device sync is not interruptible from Python; the
            # guard's timer thread dumps diagnostics if it stalls, and the
            # sync itself keeps waiting (see utils/telemetry.py).
            telemetry.set_phase(f"barrier/{name}")
            with watchdog.guard(f"barrier/{name}"):
                multihost_utils.sync_global_devices(name)

    # -- device-level rank queries (reference API parity) ---------------

    def _default_rank(self):
        """Device index answering rank queries: first local addressable device."""
        self._check()
        local = self.topology.mesh.local_devices
        if local:
            flat = list(self.topology.mesh.devices.flat)
            return flat.index(local[0])
        return 0

    def rank(self, device_index=None):
        self._check()
        return self._default_rank() if device_index is None else device_index

    def size(self):
        self._check()
        return self.topology.size

    def local_rank(self):
        self._check()
        return 0

    def local_size(self):
        """This process's devices IN THE MESH — a mesh built from a subset
        of the host's devices (``smp.init(cfg, devices=...)``) does not
        count the rest, like ``_default_rank``."""
        self._check()
        return len(self.topology.mesh.local_devices)

    def _flat_devices(self):
        """Cached rank -> device list (per topology: large pods shouldn't
        rebuild an O(devices) list per instance query)."""
        cached = getattr(self, "_flat_devices_cache", None)
        if cached is None or cached[0] is not self.topology:
            cached = (self.topology, list(self.topology.mesh.devices.flat))
            self._flat_devices_cache = cached
        return cached[1]

    def instance_id(self, rank=None):
        """Host id of the given device rank (default: this process's
        rank). Ranks index ``mesh.devices.flat``; each device belongs to
        exactly one jax process, and a process is host-bound — so the
        reference's "instance" (machine) maps to ``device.process_index``
        on a TPU pod. Parity: reference ``backend/core.py:486-489``."""
        self._check()
        r = self._default_rank() if rank is None else rank
        flat = self._flat_devices()
        if not 0 <= r < len(flat):
            raise SMPValidationError(
                f"rank {r} out of range [0, {len(flat)})."
            )
        return flat[r].process_index

    def is_in_same_instance(self, rank):
        """Whether device ``rank`` lives on the same host as this
        process. Parity: reference ``backend/core.py:479-481``."""
        return self.instance_id(rank) == self.instance_id()

    def is_multi_node(self):
        """Parity: reference ``backend/core.py:483-485``."""
        self._check()
        return jax.process_count() > 1

    def pp_rank(self, device_index=None):
        return self.topology.ranker.get_pp_rank(self.rank(device_index))

    def tp_rank(self, device_index=None):
        return self.topology.ranker.get_tp_rank(self.rank(device_index))

    def rdp_rank(self, device_index=None):
        return self.topology.ranker.get_rdp_rank(self.rank(device_index))

    def dp_rank(self, device_index=None):
        return self.topology.ranker.get_dp_rank(self.rank(device_index))

    def mp_rank(self, device_index=None):
        return self.topology.ranker.get_mp_rank(self.rank(device_index))

    def cp_rank(self, device_index=None):
        return self.topology.cp_rank(self.rank(device_index))

    def pp_size(self):
        self._check()
        return self.topology.pp_size

    def tp_size(self):
        self._check()
        return self.topology.tp_size

    def rdp_size(self):
        self._check()
        return self.topology.d_size

    def dp_size(self):
        self._check()
        return self.topology.dp_size

    def mp_size(self):
        self._check()
        return self.topology.pp_size * self.topology.tp_size

    def cp_size(self):
        self._check()
        return self.topology.cp_size

    def ep_size(self):
        self._check()
        return self.topology.ep_size

    # -- rank conversions (parity: reference backend/core.py:439-477) ---
    # Each converts a per-axis rank into the WORLD rank of the peer
    # holding that coordinate within this process's other-axis groups.

    @staticmethod
    def _axis_rank_in_range(value, size, name):
        """Numpy indexing would silently wrap negatives (pp_rank_to_rank(-1)
        -> last stage) — an off-by-one would target the wrong peer in a
        collective, so validate like instance_id does."""
        if not 0 <= value < size:
            raise SMPValidationError(
                f"{name} {value} out of range [0, {size})."
            )

    def pp_rank_to_rank(self, pp_rank):
        """World rank of pipeline stage ``pp_rank`` within this rank's
        tp x rdp group."""
        self._axis_rank_in_range(pp_rank, self.pp_size(), "pp_rank")
        rk = self.topology.ranker
        me = self._default_rank()
        return rk.translate(pp_rank=pp_rank, tp_rank=rk.get_tp_rank(me),
                            rdp_rank=rk.get_rdp_rank(me))

    def tp_rank_to_rank(self, tp_rank):
        self._axis_rank_in_range(tp_rank, self.tp_size(), "tp_rank")
        rk = self.topology.ranker
        me = self._default_rank()
        return rk.translate(pp_rank=rk.get_pp_rank(me), tp_rank=tp_rank,
                            rdp_rank=rk.get_rdp_rank(me))

    def rdp_rank_to_rank(self, rdp_rank):
        self._axis_rank_in_range(rdp_rank, self.rdp_size(), "rdp_rank")
        rk = self.topology.ranker
        me = self._default_rank()
        return rk.translate(pp_rank=rk.get_pp_rank(me),
                            tp_rank=rk.get_tp_rank(me), rdp_rank=rdp_rank)

    def dp_rank_to_rank(self, dp_rank):
        """World rank of composite-dp rank ``dp_rank`` in this rank's
        pp group (dp folds tp x rdp, reference composite order)."""
        self._axis_rank_in_range(dp_rank, self.dp_size(), "dp_rank")
        rk = self.topology.ranker
        me = self._default_rank()
        return rk.translate(
            pp_rank=rk.get_pp_rank(me),
            tp_rank=rk.get_tp_rank_from_dp_rank(dp_rank),
            rdp_rank=rk.get_rdp_rank_from_dp_rank(dp_rank),
        )

    def mp_rank_to_rank(self, mp_rank):
        """World rank of composite-mp rank ``mp_rank`` in this rank's
        rdp group (mp folds pp x tp)."""
        self._axis_rank_in_range(mp_rank, self.mp_size(), "mp_rank")
        rk = self.topology.ranker
        me = self._default_rank()
        return rk.translate(
            pp_rank=rk.get_pp_rank_from_mp_rank(mp_rank),
            tp_rank=rk.get_tp_rank_from_mp_rank(mp_rank),
            rdp_rank=rk.get_rdp_rank(me),
        )

    def get_pp_group(self, device_index=None):
        return self.topology.ranker.get_pp_group(self.rank(device_index))

    def get_tp_group(self, device_index=None):
        return self.topology.ranker.get_tp_group(self.rank(device_index))

    def get_rdp_group(self, device_index=None):
        return self.topology.ranker.get_rdp_group(self.rank(device_index))

    def get_dp_group(self, device_index=None):
        return self.topology.ranker.get_dp_group(self.rank(device_index))

    def get_mp_group(self, device_index=None):
        return self.topology.ranker.get_mp_group(self.rank(device_index))

    def get_cp_group(self, device_index=None):
        from smdistributed_modelparallel_tpu.backend.topology import CP_AXIS

        return self.topology.axis_group(self.rank(device_index), CP_AXIS)

    def get_world_group(self):
        self._check()
        return self.topology.ranker.get_world_group()

    @property
    def mesh(self):
        self._check()
        return self.topology.mesh

