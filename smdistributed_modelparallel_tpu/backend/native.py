"""Loader + ctypes wrappers for the native host runtime (``libsmptpu.so``).

Parity target: the reference loads its C++ backend ``smplib`` via ctypes at
init (reference ``backend/core.py:234-290``, symbol list in SURVEY §5.8).
The TPU build's device data plane is compiled XLA — collectives ride ICI
inside the step program — so the native layer here is deliberately smaller:

- **message bus** (``smp_async_send`` / ``smp_wait_recv`` /
  ``smp_poll_recv`` / ``smp_retrieve_object`` / ``smp_clean_recv_resources``
  — N2 parity): TCP mesh between host processes for control-plane object
  P2P and real subgroup barriers;
- **timeline recorder** (``smp_create_timeline`` family — N5 parity).

The library is built on demand from ``native/`` with the in-image g++
toolchain; every caller must tolerate ``load() is None`` (no toolchain, or
``SMP_DISABLE_NATIVE=1``) and fall back to pure Python.
"""

import ctypes
import hashlib
import os
import subprocess
import threading
import time

from smdistributed_modelparallel_tpu.resilience.chaos import chaos
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPPeerLost,
    SMPWatchdogTimeout,
)
from smdistributed_modelparallel_tpu.utils.flight_recorder import flight_recorder
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import watchdog

logger = get_logger()

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libsmptpu.so")
_HASH_PATH = _LIB_PATH + ".srchash"

_lock = threading.Lock()
_lib = None
_load_attempted = False


def _source_hash():
    """sha256 over the Makefile and ``src/*.cc`` — the rebuild key. File
    times say nothing in a fresh copy of the tree, so the key is content."""
    h = hashlib.sha256()
    src_dir = os.path.join(_NATIVE_DIR, "src")
    paths = [os.path.join(_NATIVE_DIR, "Makefile")] + sorted(
        os.path.join(src_dir, f) for f in os.listdir(src_dir)
        if f.endswith(".cc")
    )
    for path in paths:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def _stale():
    """True when the library is missing or was built from other sources
    than the ones on disk (the hash of what it was built from sits beside
    it in ``libsmptpu.so.srchash``)."""
    try:
        with open(_HASH_PATH) as fh:
            built_from = fh.read().strip()
    except OSError:
        return True
    return not os.path.exists(_LIB_PATH) or built_from != _source_hash()


def _build():
    """Build libsmptpu.so under an inter-process file lock, into a temp
    name, installed by atomic rename — N processes hit smp.init (and so
    this builder) simultaneously on one host, and an unlocked in-place make
    can hand a half-written .so to a peer's dlopen."""
    import fcntl

    lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
    tmp_name = f"libsmptpu.build.{os.getpid()}.so"
    try:
        with open(lock_path, "w") as lock_fh:
            fcntl.flock(lock_fh, fcntl.LOCK_EX)
            if not _stale():  # a peer built it while we waited
                return True
            src_hash = _source_hash()
            subprocess.run(
                ["make", "-C", _NATIVE_DIR, f"LIB={tmp_name}"],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(os.path.join(_NATIVE_DIR, tmp_name), _LIB_PATH)
            with open(_HASH_PATH + ".tmp", "w") as fh:
                fh.write(src_hash + "\n")
            os.replace(_HASH_PATH + ".tmp", _HASH_PATH)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("native build failed (%s); using pure-Python fallbacks.", e)
        try:
            os.unlink(os.path.join(_NATIVE_DIR, tmp_name))
        except OSError:
            pass
        return False


def _declare(lib):
    c = ctypes
    lib.smp_bus_listen.argtypes = [c.c_int]
    lib.smp_bus_listen.restype = c.c_int
    lib.smp_bus_connect.argtypes = [c.c_int, c.c_int, c.c_char_p]
    lib.smp_bus_connect.restype = c.c_int
    lib.smp_async_send.argtypes = [c.c_int, c.c_char_p, c.c_int64, c.c_int64]
    lib.smp_async_send.restype = c.c_int
    lib.smp_poll_recv.argtypes = [c.c_int, c.c_int64]
    lib.smp_poll_recv.restype = c.c_int
    lib.smp_wait_recv.argtypes = [c.c_int, c.c_int64, c.c_int]
    lib.smp_wait_recv.restype = c.c_int64
    lib.smp_retrieve_object.argtypes = [
        c.c_int, c.c_int64, c.POINTER(c.c_uint8), c.c_int64,
    ]
    lib.smp_retrieve_object.restype = c.c_int64
    lib.smp_clean_recv_resources.argtypes = [c.c_int, c.c_int64]
    lib.smp_clean_recv_resources.restype = None
    lib.smp_bus_barrier.argtypes = [c.POINTER(c.c_int), c.c_int, c.c_int]
    lib.smp_bus_barrier.restype = c.c_int
    lib.smp_peer_down.argtypes = [c.c_int]
    lib.smp_peer_down.restype = c.c_int
    lib.smp_bus_shutdown.argtypes = []
    lib.smp_bus_shutdown.restype = None

    lib.smp_create_timeline.argtypes = [c.c_char_p]
    lib.smp_create_timeline.restype = c.c_void_p
    lib.smp_destroy_timeline.argtypes = [c.c_void_p]
    lib.smp_destroy_timeline.restype = None
    lib.smp_timeline_start_step.argtypes = [c.c_void_p, c.c_int64]
    lib.smp_timeline_start_step.restype = None
    lib.smp_timeline_end_step.argtypes = [c.c_void_p, c.c_int64]
    lib.smp_timeline_end_step.restype = c.c_int64
    lib.smp_timeline_record_pipeline_event.argtypes = [
        c.c_void_p, c.c_char_p, c.c_double, c.c_double, c.c_int, c.c_char_p,
    ]
    lib.smp_timeline_record_pipeline_event.restype = None
    lib.smp_timeline_record_instant.argtypes = [
        c.c_void_p, c.c_char_p, c.c_double, c.c_char_p,
    ]
    lib.smp_timeline_record_instant.restype = None
    lib.smp_timeline_flush.argtypes = [c.c_void_p, c.c_int]
    lib.smp_timeline_flush.restype = c.c_int
    lib.smp_timeline_event_count.argtypes = [c.c_void_p]
    lib.smp_timeline_event_count.restype = c.c_int64
    return lib


def load():
    """Return the loaded native library, building it if needed; None when
    unavailable (caller falls back to pure Python)."""
    global _lib, _load_attempted
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("SMP_DISABLE_NATIVE", "0") == "1":
            return None
        if _stale() and not _build():
            return None
        try:
            _lib = _declare(ctypes.CDLL(_LIB_PATH))
        except OSError as e:
            logger.warning("could not load %s: %s", _LIB_PATH, e)
            _lib = None
        return _lib


def available():
    return load() is not None


class MessageBus:
    """Python face of the native bus; one per process.

    Transaction ids follow the reference's ``TransactionIdentifier``
    convention (2*id + is_user_api, reference ``backend/collectives.py:61-66``)
    — the bus itself only sees opaque int64 keys.
    """

    def __init__(self, lib):
        self._lib = lib
        self.rank = 0
        self.world = 1
        self.port = None
        self._connected = False

    def listen(self, port=0):
        self.port = self._lib.smp_bus_listen(port)
        if self.port < 0:
            raise OSError("smp_bus_listen failed")
        return self.port

    def connect(self, rank, world, endpoints):
        """endpoints: list of "host:port" strings indexed by process."""
        joined = ",".join(endpoints).encode()
        if self._lib.smp_bus_connect(rank, world, joined) != 0:
            raise OSError("smp_bus_connect failed")
        self.rank, self.world = rank, world
        self._connected = True

    def send_bytes(self, dest, payload, tx):
        """Enqueue one message, with dead-link detection + bounded retry.

        The C side reports two failures: ``-1`` (bus not connected / bad
        destination — caller misuse, raised as OSError immediately, as
        before) and ``-2`` (the sender thread for this link gave up:
        connect budget exhausted or the peer died mid-stream —
        ``message_bus.cc`` ``SendQueue.dead``). A dead link retries
        ``SMP_BUS_SEND_RETRIES`` times (default 3) with exponential
        backoff, then raises a structured ``SMPPeerLost`` carrying the
        peer index: a typed, attributable failure instead of frames
        silently queueing forever while the receiver hangs until the
        watchdog fires. The C side keeps a dead link marked for a ~2s
        cool-down — longer than the default backoff burst, so one send's
        retries fail typed and fast — and then revives it (fresh sender
        thread, fresh connect budget) on the next attempt, which is what
        lets a send to a RESTARTED peer eventually go through.
        """
        injected = chaos.on_bus_send(dest)
        if injected == "drop":
            flight_recorder.record_wait("bus_send", dest, tx, "chaos_drop", 0.0)
            return
        try:
            retries = max(int(os.environ.get("SMP_BUS_SEND_RETRIES", "3")), 0)
        except ValueError:
            logger.warning(
                "ignoring non-integer SMP_BUS_SEND_RETRIES=%r; using 3.",
                os.environ.get("SMP_BUS_SEND_RETRIES"),
            )
            retries = 3
        delay = 0.05
        for attempt in range(retries + 1):
            rc = (
                -2 if injected == "error" and attempt == 0
                else self._lib.smp_async_send(dest, payload, len(payload), tx)
            )
            if rc == 0:
                if attempt:
                    logger.warning(
                        "bus send to process %d succeeded after %d retr%s.",
                        dest, attempt, "y" if attempt == 1 else "ies",
                    )
                return
            if rc == -1:
                raise OSError(f"smp_async_send to {dest} failed ({rc})")
            if attempt < retries:
                flight_recorder.record_wait(
                    "bus_send", dest, tx, "retry", delay
                )
                time.sleep(delay)
                delay *= 2
        flight_recorder.record_wait("bus_send", dest, tx, "peer_lost", 0.0)
        raise SMPPeerLost(
            dest,
            f"native-bus link to process {dest} is dead (sender gave up "
            f"delivering; rc={rc}) after {retries} "
            f"retr{'y' if retries == 1 else 'ies'}.",
        )

    def poll(self, src, tx):
        return bool(self._lib.smp_poll_recv(src, tx))

    def peer_down(self, peer):
        """True when the link to `peer` is marked dead in either direction
        (sender thread gave up, or the peer's incoming connection hit EOF
        while the bus was running — its process died)."""
        return bool(self._lib.smp_peer_down(peer))

    def _wait_recv(self, src, tx, timeout_ms):
        """Blocking C wait, sliced for two early exits: an armed watchdog
        turns an unbounded wait into a diagnostics dump + raise instead of
        a silent wedge, and a peer whose link the bus has marked DEAD (in
        either direction) raises ``SMPPeerLost`` immediately — a wait on a
        frame that can never arrive must not burn the full watchdog/caller
        timeout. Frames already delivered before the death still drain
        first (the probe only fires when nothing is pending)."""
        if timeout_ms == 0:
            return self._lib.smp_wait_recv(src, tx, 0)
        now = time.monotonic()
        deadline = None if timeout_ms < 0 else now + timeout_ms / 1000.0
        # The watchdog guards UNBOUNDED waits only — a caller that chose
        # an explicit timeout keeps it (and its TimeoutError), even when
        # the watchdog window is shorter.
        wd = watchdog.timeout() if timeout_ms < 0 else None
        wd_deadline = None if wd is None else now + wd
        while True:
            if (
                src != self.rank
                and not self._lib.smp_poll_recv(src, tx)
                and self.peer_down(src)
            ):
                raise SMPPeerLost(
                    src,
                    f"bus recv from process {src} (tx={tx}): the link is "
                    "marked dead (peer process unreachable or exited).",
                )
            now = time.monotonic()
            slice_ms = 1000  # peer-death probe cadence
            if deadline is not None:
                left_ms = int((deadline - now) * 1000)
                if left_ms <= 0:
                    return -1  # caller's timeout
                slice_ms = min(slice_ms, max(left_ms, 1))
            if wd_deadline is not None:
                wd_left = int((wd_deadline - now) * 1000)
                if wd_left <= 0:
                    watchdog.dump(
                        f"bus recv from process {src} (tx={tx}) stalled >{wd}s"
                    )
                    raise SMPWatchdogTimeout(
                        f"watchdog: bus recv from process {src} stalled for "
                        f"more than {wd}s (diagnostics dumped)."
                    )
                slice_ms = min(slice_ms, max(wd_left, 1))
            n = self._lib.smp_wait_recv(src, tx, slice_ms)
            if n != -1:  # -1 = slice timed out; keep waiting
                return n

    def recv_bytes(self, src, tx, timeout_ms=-1):
        # Flight-record both edges of the wait: the begin event is what a
        # post-mortem ring shows when this rank wedged INSIDE the wait
        # (the end event never arrives), the end event carries the
        # measured wait latency and outcome.
        flight_recorder.record_wait("bus_recv", src, tx, "begin", 0.0)
        t0 = time.monotonic()
        try:
            n = self._wait_recv(src, tx, timeout_ms)
        except SMPWatchdogTimeout:
            flight_recorder.record_wait(
                "bus_recv", src, tx, "watchdog", time.monotonic() - t0
            )
            raise
        except SMPPeerLost:
            flight_recorder.record_wait(
                "bus_recv", src, tx, "peer_lost", time.monotonic() - t0
            )
            raise
        elapsed = time.monotonic() - t0
        if n == -1:
            flight_recorder.record_wait("bus_recv", src, tx, "timeout", elapsed)
            raise TimeoutError(f"recv from {src} (tx={tx}) timed out")
        if n < 0:
            flight_recorder.record_wait("bus_recv", src, tx, "error", elapsed)
            raise OSError(f"smp_wait_recv failed ({n})")
        flight_recorder.record_wait("bus_recv", src, tx, "ok", elapsed)
        buf = (ctypes.c_uint8 * int(n))()
        got = self._lib.smp_retrieve_object(src, tx, buf, n)
        if got != n:
            raise OSError(f"smp_retrieve_object failed ({got})")
        return bytes(buf)

    def clean(self, src, tx):
        self._lib.smp_clean_recv_resources(src, tx)

    def send_raw(self, dest, payload, tx):
        """Single unadorned enqueue: no chaos seam, no retries, no flight
        recording. Returns the C return code (0 ok, -1 misuse, -2 link
        dead). The heartbeat (tx -4) and fleet metric snapshot (tx -7)
        paths use this — a periodic beat must not consume chaos bus-send
        ordinals or flood the flight ring, and a dead-link result is
        itself the detection signal, not an error."""
        return self._lib.smp_async_send(dest, payload, len(payload), tx)

    def drain_bytes(self, src, tx, limit=256):
        """Drain every already-delivered frame for (src, tx) without
        blocking or flight-recording. Heartbeat receive path: beats arrive
        faster than the detector scans, and each scan wants *all* pending
        beats (the freshest carries the peer's current step edge). The
        fleet aggregator (tx -7) drains the same way — the freshest
        snapshot per peer wins."""
        out = []
        while len(out) < limit and self._lib.smp_poll_recv(src, tx):
            n = self._lib.smp_wait_recv(src, tx, 0)
            if n < 0:
                break
            buf = (ctypes.c_uint8 * int(n))()
            got = self._lib.smp_retrieve_object(src, tx, buf, n)
            if got != n:
                break
            out.append(bytes(buf))
        return out

    def barrier(self, ranks, timeout_ms=600000):
        # An armed watchdog tightens the C-side timeout so a wedged peer
        # produces the dump within the configured window, not after 10 min.
        wd = watchdog.timeout()
        if wd is not None:
            timeout_ms = min(timeout_ms, max(int(wd * 1000), 1))
        arr = (ctypes.c_int * len(ranks))(*sorted(ranks))
        flight_recorder.record_wait("bus_barrier", -1, len(ranks), "begin", 0.0)
        t0 = time.monotonic()
        rc = self._lib.smp_bus_barrier(arr, len(ranks), timeout_ms)
        if rc <= -100:
            # The C side identified a member whose link is marked dead:
            # typed and immediate, not a full-timeout stall.
            peer = -(rc + 100)
            flight_recorder.record_wait(
                "bus_barrier", peer, len(ranks), "peer_lost",
                time.monotonic() - t0,
            )
            raise SMPPeerLost(
                peer,
                f"bus barrier over {sorted(ranks)}: the link to process "
                f"{peer} is marked dead (peer unreachable or exited).",
            )
        if rc != 0:
            # The C side returns -1 for timeouts AND for immediate failures
            # (bus already shut down, dead peer): only a wait that actually
            # consumed the window is a stall — instant failures keep the
            # plain OSError their callers handle.
            elapsed_ms = (time.monotonic() - t0) * 1000
            if wd is not None and elapsed_ms >= 0.9 * timeout_ms:
                flight_recorder.record_wait(
                    "bus_barrier", -1, len(ranks), "watchdog", elapsed_ms / 1e3
                )
                watchdog.dump(
                    f"bus barrier over {sorted(ranks)} stalled >{timeout_ms}ms"
                )
                raise SMPWatchdogTimeout(
                    f"watchdog: bus barrier over {sorted(ranks)} stalled "
                    f"(diagnostics dumped)."
                )
            flight_recorder.record_wait(
                "bus_barrier", -1, len(ranks), "error", elapsed_ms / 1e3
            )
            raise OSError(f"bus barrier over {sorted(ranks)} failed")
        flight_recorder.record_wait(
            "bus_barrier", -1, len(ranks), "ok", time.monotonic() - t0
        )

    def shutdown(self):
        self._lib.smp_bus_shutdown()
        self._connected = False


class NativeTimeline:
    """ctypes face of the native timeline recorder (N5)."""

    def __init__(self, lib, path):
        self._lib = lib
        self._handle = lib.smp_create_timeline(path.encode())

    def start_step(self, step):
        self._lib.smp_timeline_start_step(self._handle, step)

    def end_step(self, step):
        return self._lib.smp_timeline_end_step(self._handle, step)

    def record_event(self, name, begin_us, end_us, microbatch=None, track="pipeline"):
        self._lib.smp_timeline_record_pipeline_event(
            self._handle, name.encode(), begin_us, end_us,
            -1 if microbatch is None else microbatch, track.encode(),
        )

    def record_instant(self, name, ts_us, track="pipeline"):
        self._lib.smp_timeline_record_instant(
            self._handle, name.encode(), ts_us, track.encode()
        )

    def flush(self, pid=0):
        return self._lib.smp_timeline_flush(self._handle, pid)

    def event_count(self):
        return self._lib.smp_timeline_event_count(self._handle)

    def close(self):
        if self._handle:
            self._lib.smp_destroy_timeline(self._handle)
            self._handle = None
