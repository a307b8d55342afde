"""Native host-runtime tests: message bus (N2 parity) + timeline (N5).

The multi-process tests spawn raw OS processes that load libsmptpu.so via
ctypes and talk over real TCP on 127.0.0.1 — the same cluster-free strategy
the reference uses for its backend tests (single-node MPI with N processes,
SURVEY §4), with the bus's endpoint list standing in for MPI's rendezvous.
"""

import json
import multiprocessing as mp
import pickle

import pytest

from smdistributed_modelparallel_tpu.backend import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable"
)


def _make_bus():
    lib = native.load()
    bus = native.MessageBus(lib)
    return bus


# ---------------------------------------------------------------------------
# single-process (self-send) behavior


def test_self_send_roundtrip():
    bus = _make_bus()
    port = bus.listen(0)
    assert port > 0
    bus.connect(0, 1, [f"127.0.0.1:{port}"])
    payload = pickle.dumps({"hello": [1, 2, 3]})
    bus.send_bytes(0, payload, tx=7)
    assert bus.poll(0, 7)
    assert not bus.poll(0, 8)
    out = bus.recv_bytes(0, 7, timeout_ms=1000)
    assert pickle.loads(out) == {"hello": [1, 2, 3]}
    assert not bus.poll(0, 7)  # consumed
    bus.shutdown()


def test_recv_timeout_and_clean():
    bus = _make_bus()
    port = bus.listen(0)
    bus.connect(0, 1, [f"127.0.0.1:{port}"])
    with pytest.raises(TimeoutError):
        bus.recv_bytes(0, 99, timeout_ms=50)
    bus.send_bytes(0, b"x", tx=5)
    bus.clean(0, 5)
    assert not bus.poll(0, 5)
    bus.shutdown()


def test_out_of_order_transactions():
    bus = _make_bus()
    port = bus.listen(0)
    bus.connect(0, 1, [f"127.0.0.1:{port}"])
    for tx in (3, 1, 2):
        bus.send_bytes(0, str(tx).encode(), tx=tx)
    # Retrieval keyed by tx, independent of arrival order.
    assert bus.recv_bytes(0, 2, 1000) == b"2"
    assert bus.recv_bytes(0, 3, 1000) == b"3"
    assert bus.recv_bytes(0, 1, 1000) == b"1"
    bus.shutdown()


# ---------------------------------------------------------------------------
# true multi-process TCP mesh


def _worker(rank, world, ports, conn, payload_kb):
    from smdistributed_modelparallel_tpu.backend import native as nat

    lib = nat.load()
    bus = nat.MessageBus(lib)
    port = bus.listen(ports[rank])
    assert port == ports[rank]
    endpoints = [f"127.0.0.1:{p}" for p in ports]
    bus.connect(rank, world, endpoints)
    try:
        # Ring: send a tagged blob to (rank+1)%world, receive from left.
        blob = bytes([rank]) * (payload_kb * 1024)
        bus.send_bytes((rank + 1) % world, blob, tx=101)
        got = bus.recv_bytes((rank - 1) % world, 101, timeout_ms=30000)
        assert got == bytes([(rank - 1) % world]) * (payload_kb * 1024)

        # Many interleaved transactions to one peer (0 gathers).
        for tx in range(10):
            bus.send_bytes(0, f"{rank}:{tx}".encode(), tx=1000 + tx)
        if rank == 0:
            for src in range(world):
                for tx in range(10):
                    msg = bus.recv_bytes(src, 1000 + tx, timeout_ms=30000)
                    assert msg == f"{src}:{tx}".encode()

        # Subgroup barrier (even ranks), then full barrier, repeated.
        evens = [r for r in range(world) if r % 2 == 0]
        for _ in range(3):
            if rank in evens:
                bus.barrier(evens, timeout_ms=30000)
            bus.barrier(list(range(world)), timeout_ms=30000)
        conn.send(("ok", rank))
    except Exception as e:  # pragma: no cover - surfaced in parent assert
        conn.send(("err", f"rank {rank}: {type(e).__name__}: {e}"))
    finally:
        bus.shutdown()


def _free_ports(n):
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


@pytest.mark.parametrize("world", [2, 4])
def test_multiprocess_mesh(world):
    ctx = mp.get_context("spawn")
    ports = _free_ports(world)
    parents, procs = [], []
    for rank in range(world):
        parent, child = ctx.Pipe()
        p = ctx.Process(
            target=_worker, args=(rank, world, ports, child, 64), daemon=True
        )
        p.start()
        parents.append(parent)
        procs.append(p)
    results = []
    for parent, p in zip(parents, procs):
        assert parent.poll(120), "worker timed out"
        results.append(parent.recv())
        p.join(timeout=30)
    errs = [r for r in results if r[0] != "ok"]
    assert not errs, errs


# ---------------------------------------------------------------------------
# receive-side dead-peer detection (satellite of the recovery supervisor):
# a wait blocking on a peer whose link the bus has marked dead raises a
# typed SMPPeerLost immediately instead of burning the full timeout.


def test_send_raw_and_drain_bytes_self():
    bus = _make_bus()
    port = bus.listen(0)
    bus.connect(0, 1, [f"127.0.0.1:{port}"])
    assert bus.send_raw(0, b"1:7", -4) == 0
    assert bus.send_raw(0, b"2:8", -4) == 0
    assert bus.drain_bytes(0, -4) == [b"1:7", b"2:8"]
    assert bus.drain_bytes(0, -4) == []
    assert not bus.peer_down(0)
    bus.shutdown()


def _dead_peer_victim(rank, world, ports, conn):
    """Rank 0: receives one frame from rank 1 (establishing the inbound
    connection + its source identity), then expects rank 1's death to
    surface as SMPPeerLost on both a recv wait and a group barrier —
    quickly, not after the 30s timeouts."""
    import time as _time

    from smdistributed_modelparallel_tpu.backend import native as nat
    from smdistributed_modelparallel_tpu.utils.exceptions import SMPPeerLost

    lib = nat.load()
    bus = nat.MessageBus(lib)
    bus.listen(ports[rank])
    bus.connect(rank, world, [f"127.0.0.1:{p}" for p in ports])
    try:
        assert bus.recv_bytes(1, 500, timeout_ms=30000) == b"hello"
        # Peer dies now (no second message ever sent). The recv must fail
        # typed and fast once the EOF lands, and so must a barrier.
        t0 = _time.monotonic()
        try:
            bus.recv_bytes(1, 501, timeout_ms=30000)
            conn.send(("err", "recv returned instead of raising"))
            return
        except SMPPeerLost as e:
            assert e.peer == 1, e.peer
        recv_s = _time.monotonic() - t0
        t0 = _time.monotonic()
        try:
            bus.barrier([0, 1], timeout_ms=30000)
            conn.send(("err", "barrier returned instead of raising"))
            return
        except SMPPeerLost as e:
            assert e.peer == 1, e.peer
        barrier_s = _time.monotonic() - t0
        assert bus.peer_down(1)
        # "Immediately": well under the 30s waits (EOF + one probe slice).
        assert recv_s < 15 and barrier_s < 15, (recv_s, barrier_s)
        conn.send(("ok", rank))
    except Exception as e:  # pragma: no cover - surfaced in parent
        conn.send(("err", f"rank {rank}: {type(e).__name__}: {e}"))
    finally:
        bus.shutdown()


def _dead_peer_casualty(rank, world, ports, conn):
    """Rank 1: send one frame (so rank 0 learns this connection's source),
    then die hard — os._exit with no bus shutdown, like a SIGKILL."""
    import os as _os
    import time as _time

    from smdistributed_modelparallel_tpu.backend import native as nat

    lib = nat.load()
    bus = nat.MessageBus(lib)
    bus.listen(ports[rank])
    bus.connect(rank, world, [f"127.0.0.1:{p}" for p in ports])
    bus.send_bytes(0, b"hello", 500)
    _time.sleep(1.0)  # let the frame land before dying
    conn.send(("ok", rank))
    _os._exit(0)  # hard exit: kernel closes the sockets, no goodbye


def test_recv_and_barrier_raise_peer_lost_on_dead_peer():
    ctx = mp.get_context("spawn")
    ports = _free_ports(2)
    targets = [_dead_peer_victim, _dead_peer_casualty]
    parents, procs = [], []
    for rank in range(2):
        parent, child = ctx.Pipe()
        p = ctx.Process(
            target=targets[rank], args=(rank, 2, ports, child), daemon=True
        )
        p.start()
        parents.append(parent)
        procs.append(p)
    results = []
    for parent, p in zip(parents, procs):
        assert parent.poll(120), "worker timed out"
        results.append(parent.recv())
        p.join(timeout=30)
    errs = [r for r in results if r[0] != "ok"]
    assert not errs, errs


# ---------------------------------------------------------------------------
# communicator integration (single process)


def test_communicator_send_recv_single_process(tmp_path):
    import smdistributed_modelparallel_tpu as smp

    smp.reset()
    smp.init({"microbatches": 1})
    smp.send({"k": 1}, dest=0)
    assert smp.recv_from(0) == {"k": 1}
    # In-order per-pair sequencing.
    smp.send("a", dest=0)
    smp.send("b", dest=0)
    assert smp.recv_from(0) == "a"
    assert smp.recv_from(0) == "b"
    # Group barriers are no-ops single-process but must not raise.
    smp.barrier(smp.TP_GROUP)
    smp.pp_barrier()
    smp.dp_barrier()


# ---------------------------------------------------------------------------
# native timeline


def test_native_timeline_roundtrip(tmp_path):
    lib = native.load()
    path = str(tmp_path / "trace.json")
    tl = native.NativeTimeline(lib, path)
    tl.start_step(0)
    tl.record_event("fwd_mb0", 10.0, 25.5, microbatch=0)
    tl.record_event("bwd_mb0", 30.0, 55.0, microbatch=0, track="bwd")
    tl.record_instant("step_0_end", 60.0)
    tl.end_step(0)
    assert tl.event_count() == 3
    assert tl.flush(pid=42) == 3
    with open(path) as f:
        data = json.load(f)
    events = data["traceEvents"]
    assert len(events) == 3
    by_name = {e["name"]: e for e in events}
    assert by_name["fwd_mb0"]["dur"] == pytest.approx(15.5)
    assert by_name["fwd_mb0"]["args"]["microbatch"] == 0
    assert by_name["fwd_mb0"]["args"]["step"] == 0
    assert by_name["bwd_mb0"]["tid"] == "bwd"
    assert by_name["step_0_end"]["ph"] == "i"
    assert all(e["pid"] == 42 for e in events)
    tl.close()


def test_python_timeline_uses_native(tmp_path, monkeypatch):
    from smdistributed_modelparallel_tpu.utils.timeline import Timeline

    path = str(tmp_path / "t.json")
    monkeypatch.setenv("SMP_TIMELINE_PATH", path)
    tl = Timeline()
    assert tl.enabled
    assert tl._native is not None
    tl.start_step(3)
    with tl.span("phase", microbatch=1):
        pass
    tl.end_step(3)
    tl.flush()
    with open(path) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]]
    assert "phase" in names and "step_3_begin" in names


def test_rebuild_is_keyed_on_source_content_not_file_times(monkeypatch):
    """A fresh copy of the tree gives every file a new time; what says the
    library is stale is the hash of the sources it was built from."""
    import os

    assert native.available()      # built (or found current) on first load
    assert not native._stale()
    src = os.path.join(native._NATIVE_DIR, "src", "timeline.cc")
    past = os.path.getmtime(native._LIB_PATH) - 10_000
    os.utime(native._LIB_PATH, (past, past))   # library "older" than sources
    os.utime(src)                              # sources "newer"
    assert not native._stale()
    monkeypatch.setattr(native, "_source_hash", lambda: "0" * 64)
    assert native._stale()                     # other sources: rebuild
