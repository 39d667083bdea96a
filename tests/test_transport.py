"""Transport-layer fault injection: framing fuzz, RPC robustness, WAL
group commit, replica lag, and the multi-process deployment contract.

The fast half attacks the wire format and RPC loop in-process (mirrors
`test_wal_fuzz`: random truncation and bit-flips must surface as
`FrameError`, never as garbage data or a wedged server), and drives
the WAL's group-commit accounting plus the batcher's deferred-ticket
release at the engine level.

The slow half spawns REAL worker processes: a 2-shard socket engine
must answer `np.array_equal` to the in-process engine (exact and ivf),
a WAL-tail replica must converge and degrade cleanly when killed, and
a shard worker killed mid-workload must error loudly and recover on
reopen with the exact `(version, epoch, fingerprint)` triple."""
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

from repro.graph.edges import make_labels
from repro.graph.generators import erdos_renyi
from repro.serving import GraphStore, ServingEngine
from repro.serving.batcher import MicroBatcher
from repro.serving.wal import WriteAheadLog
from repro.transport import (CallTimeout, FrameError, RemoteCallError,
                             ReplicaLagError, RpcClient, RpcServer,
                             TransportError, pack_obj, recv_msg,
                             send_msg, unpack_obj)
from repro.transport.replica import ReplicaEngine

N, K = 60, 4


def _mkstore(seed=7, n=N):
    g = erdos_renyi(n, 500, seed=seed, weighted=True)
    Y = make_labels(n, K, 0.4, np.random.default_rng(seed))
    return GraphStore(g, Y, K)


# -- codec -------------------------------------------------------------------

def test_codec_roundtrip_preserves_structure_and_dtypes():
    msg = {"id": 3, "method": "class_stats", "none": None,
           "flags": [True, False], "pi": 3.5, "name": "shard-0",
           "raw": b"\x00\xff", "tup": (1, "two", None),
           "args": [np.arange(6, dtype=np.int32).reshape(2, 3),
                    np.linspace(0, 1, 5, dtype=np.float32),
                    np.array([], dtype=np.int64),
                    np.array(7.5, dtype=np.float64)]}
    out = unpack_obj(pack_obj(msg))
    assert out["id"] == 3 and out["none"] is None
    assert out["flags"] == [True, False] and out["tup"] == (1, "two", None)
    assert out["raw"] == b"\x00\xff"
    for a, b in zip(msg["args"], out["args"]):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)


def test_codec_rejects_unencodable_and_corrupt():
    with pytest.raises(TypeError):
        pack_obj({"fn": object()})
    with pytest.raises(TypeError):
        pack_obj({1: "non-str key"})
    good = pack_obj({"a": np.arange(4)})
    with pytest.raises(FrameError):
        unpack_obj(good + b"x")          # trailing bytes
    with pytest.raises(FrameError):
        unpack_obj(b"\x7f")              # unknown tag
    with pytest.raises(FrameError):
        unpack_obj(good[:-3])            # truncated payload


def test_codec_fuzz_never_returns_garbage(rng):
    """Random corruption of a valid payload either decodes to SOME
    value (harmless — the RPC layer still checks ids) or raises
    FrameError; it must never raise anything else or hang."""
    base = pack_obj({"id": 1, "method": "rows",
                     "args": [np.arange(32, dtype=np.int32)],
                     "kwargs": {}})
    for _ in range(200):
        blob = bytearray(base)
        if rng.random() < 0.5:
            blob = blob[:int(rng.integers(0, len(blob)))]
        else:
            off = int(rng.integers(0, len(blob)))
            blob[off] ^= 1 << int(rng.integers(0, 8))
        try:
            unpack_obj(bytes(blob))
        except FrameError:
            pass


# -- socket framing ----------------------------------------------------------

def test_frame_roundtrip_and_torn_stream(rng):
    a, b = socket.socketpair()
    try:
        payload = {"z": np.arange(100, dtype=np.float32)}
        send_msg(a, payload)
        assert np.array_equal(recv_msg(b)["z"], payload["z"])
        # torn mid-message: send a truncated frame then close
        frame_bytes = pack_obj(payload)
        cut = int(rng.integers(1, len(frame_bytes) + 8))
        header = struct.pack("<II", len(frame_bytes),
                             zlib.crc32(frame_bytes))
        a.sendall((header + frame_bytes)[:cut])
        a.close()
        with pytest.raises(FrameError):
            recv_msg(b)
    finally:
        b.close()


def test_frame_bitflip_detected(rng):
    payload = pack_obj([1, 2, 3, "four"])
    for _ in range(32):
        a, b = socket.socketpair()
        try:
            blob = bytearray(struct.pack(
                "<II", len(payload), zlib.crc32(payload)) + payload)
            off = int(rng.integers(8, len(blob)))   # corrupt payload
            blob[off] ^= 1 << int(rng.integers(0, 8))
            a.sendall(bytes(blob))
            a.close()
            with pytest.raises(FrameError):
                recv_msg(b)
        finally:
            b.close()


def test_oversized_frame_rejected_without_allocation():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("<II", (1 << 31), 0))   # 2 GiB claim
        with pytest.raises(FrameError, match="MAX_FRAME"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


# -- RPC ---------------------------------------------------------------------

class _Handler:
    """Tiny RPC target for protocol tests (no jax anywhere)."""

    def __init__(self):
        self.count = 0

    def add(self, a, b):
        return a + b

    def rows(self, x):
        return np.asarray(x) * 2

    def bump(self):
        self.count += 1
        return self.count

    def bad_index(self):
        raise IndexError("node ids outside [0, 60)")

    def lagging(self):
        raise ReplicaLagError("replica at 3, pinned 7", have=3, want=7)

    def weird(self):
        raise OSError("handler-side disk error")

    def nap(self, seconds):
        time.sleep(seconds)
        return "woke"


@pytest.fixture
def server():
    srv = RpcServer(_Handler()).start()
    yield srv
    srv.close()


def test_rpc_loopback_arrays_and_typed_errors(server):
    c = RpcClient(server.address, timeout_s=5)
    assert c.call("add", 2, 3) == 5
    out = c.call("rows", np.arange(5, dtype=np.int32), idempotent=True)
    assert np.array_equal(out, np.arange(5) * 2)
    with pytest.raises(IndexError, match="outside"):
        c.call("bad_index")
    with pytest.raises(ReplicaLagError):
        c.call("lagging")
    # unmapped remote exception comes back as RemoteCallError with the
    # original type name — deterministic, so never retried
    with pytest.raises(RemoteCallError, match="OSError"):
        c.call("weird", idempotent=True)
    c.close()


def test_rpc_blocks_private_and_unknown_methods(server):
    c = RpcClient(server.address, timeout_s=5)
    with pytest.raises(RemoteCallError, match="AttributeError"):
        c.call("_Handler__count")
    with pytest.raises(RemoteCallError, match="AttributeError"):
        c.call("no_such_method")
    c.close()


def test_rpc_call_timeout_is_transport_error(server):
    c = RpcClient(server.address, timeout_s=5)
    with pytest.raises(CallTimeout):
        c.call("nap", 3.0, timeout_s=0.2)
    c.close()


def test_rpc_torn_connection_isolated_from_other_clients(server):
    good = RpcClient(server.address, timeout_s=5)
    assert good.call("add", 1, 1) == 2
    # a rogue peer sends garbage then a half frame and vanishes — that
    # connection dies alone; the server keeps serving everyone else
    for junk in (b"not a frame at all", b"\xff" * 7):
        rogue = socket.create_connection(
            ("127.0.0.1", int(server.address.rsplit(":", 1)[1])))
        rogue.sendall(junk)
        rogue.close()
    assert good.call("add", 2, 2) == 4
    good.close()


def test_rpc_duplicate_and_interleaved_idempotent_reads(server):
    """Duplicated reads (the retry story) and two clients interleaving
    out of order must all see consistent answers — ids pair each
    response to its own request."""
    c1 = RpcClient(server.address, timeout_s=5)
    c2 = RpcClient(server.address, timeout_s=5)
    x = np.arange(16, dtype=np.int64)
    for i in range(8):
        a = c1.call("rows", x + i, idempotent=True)
        b = c2.call("rows", x + i, idempotent=True)
        again = c1.call("rows", x + i, idempotent=True)   # duplicate
        assert np.array_equal(a, b) and np.array_equal(a, again)
    c1.close()
    c2.close()


def test_rpc_retry_policy_idempotent_reads_only(server, monkeypatch):
    """One injected transport fault: an idempotent read survives via
    bounded retry on a fresh connection; a mutation surfaces the error
    immediately and is never re-sent."""
    c = RpcClient(server.address, timeout_s=5, retries=2,
                  backoff_s=0.01)
    real = c._call_once
    fails = {"left": 1}

    def flaky(method, args, kwargs, timeout):
        if fails["left"]:
            fails["left"] -= 1
            raise TransportError("injected torn stream")
        return real(method, args, kwargs, timeout)

    monkeypatch.setattr(c, "_call_once", flaky)
    out = c.call("rows", np.arange(3), idempotent=True)
    assert np.array_equal(out, [0, 2, 4])
    fails["left"] = 1
    with pytest.raises(TransportError, match="injected"):
        c.call("bump")
    # the failed mutation never reached the handler — no double-apply
    assert server.handler.count == 0
    c.close()


def test_rpc_dead_server_errors_loudly():
    srv = RpcServer(_Handler()).start()
    addr = srv.address
    srv.close()
    c = RpcClient(addr, timeout_s=2, retries=2, backoff_s=0.01)
    with pytest.raises(TransportError):
        c.call("bump")                   # write: one attempt, loud
    with pytest.raises(TransportError):  # read: bounded retries, then
        c.call("rows", np.arange(2), idempotent=True)   # still loud
    c.close()


def test_rpc_client_reconnects_after_server_restart():
    srv = RpcServer(_Handler()).start()
    host, port = srv.addr
    c = RpcClient(srv.address, timeout_s=5)
    assert c.call("add", 1, 2) == 3
    srv.close()
    c.close()                            # connection died with it
    srv2 = RpcServer(_Handler(), host=host, port=port).start()
    try:
        assert c.call("add", 2, 2) == 4  # same client, fresh socket
        assert c.reconnects == 2
    finally:
        c.close()
        srv2.close()


def test_rpc_server_close_wakes_blocked_accept():
    srv = RpcServer(_Handler())
    t = threading.Thread(target=srv.serve_forever)
    t.start()
    time.sleep(0.1)
    srv.close()                          # must wake accept(), not hang
    t.join(timeout=5)
    assert not t.is_alive()


def test_rpc_shutdown_request_stops_server():
    srv = RpcServer(_Handler())
    t = threading.Thread(target=srv.serve_forever)
    t.start()
    c = RpcClient(srv.address, timeout_s=5)
    assert c.call("add", 1, 1) == 2
    c.shutdown_server()
    c.close()
    t.join(timeout=5)
    assert not t.is_alive()


# -- WAL group commit --------------------------------------------------------

def test_wal_group_commit_batches_fsync_barriers(tmp_path, rng):
    wal = WriteAheadLog(str(tmp_path / "g.wal"), fsync=True,
                        group_commit_bytes=1 << 20)
    wal.open()
    u = rng.integers(0, N, 8).astype(np.int32)
    w = rng.random(8).astype(np.float32)
    for i in range(10):
        wal.append_edges(i + 1, u, u, w)
    assert wal.pending_appends == 10 and wal.fsyncs == 0
    assert wal.sync() == 10              # one barrier covers them all
    assert wal.pending_appends == 0 and wal.fsyncs == 1
    assert wal.appends_per_fsync == 10.0
    assert wal.sync() == 0               # nothing pending: no-op
    wal.close()


def test_wal_group_commit_bytes_threshold_auto_syncs(tmp_path, rng):
    wal = WriteAheadLog(str(tmp_path / "g.wal"), fsync=True,
                        group_commit_bytes=64)
    wal.open()
    u = rng.integers(0, N, 16).astype(np.int32)
    wal.append_edges(1, u, u, np.ones(16, np.float32))   # > 64 bytes
    assert wal.fsyncs == 1 and wal.pending_appends == 0
    wal.close()


def test_wal_group_commit_age_threshold(tmp_path, rng):
    wal = WriteAheadLog(str(tmp_path / "g.wal"), fsync=True,
                        group_commit_ms=20.0,
                        group_commit_bytes=1 << 30)
    wal.open()
    u = rng.integers(0, N, 4).astype(np.int32)
    wal.append_edges(1, u, u, np.ones(4, np.float32))
    assert wal.sync_if_due() == 0        # too young
    time.sleep(0.03)
    assert wal.sync_if_due() == 1        # aged past the knob
    wal.close()


def test_wal_close_never_orphans_an_open_group(tmp_path, rng):
    from repro.serving.wal import scan_wal
    path = str(tmp_path / "g.wal")
    wal = WriteAheadLog(path, fsync=True, group_commit_bytes=1 << 30)
    wal.open()
    u = rng.integers(0, N, 4).astype(np.int32)
    for i in range(3):
        wal.append_edges(i + 1, u, u, np.ones(4, np.float32))
    wal.close()                          # implicit final barrier
    assert wal.fsyncs == 1 and wal.appends_covered == 3
    records, _ = scan_wal(path)
    assert len(records) == 3


def test_engine_group_commit_defers_tickets_until_barrier(tmp_path):
    eng = ServingEngine(_mkstore(), data_dir=str(tmp_path / "d"),
                        fsync=True, group_commit_bytes=1 << 20,
                        plan_cache=None)
    assert eng.wal.group_commit
    bat = MicroBatcher(eng, topk=5)
    t1 = bat.submit("insert", (np.array([1], np.int32),
                               np.array([2], np.int32),
                               np.ones(1, np.float32)))
    t2 = bat.submit("insert", (np.array([3], np.int32),
                               np.array([4], np.int32),
                               np.ones(1, np.float32)))
    tr = bat.submit("embed", np.array([0, 1]))
    bat.flush()
    # both writes acknowledged with their APPLY-time versions, covered
    # by ONE fsync barrier (plus the boot snapshot's none)
    assert t1.result() == 1 and t2.result() == 2
    assert (t1.version, t2.version) == (1, 2)
    assert tr.result().shape == (2, K)
    assert eng.wal.pending_appends == 0
    assert eng.wal.fsyncs == 1 and eng.wal.appends_per_fsync == 2.0
    dur = eng.stats()["durability"]
    assert dur["group_commit"] and dur["fsync"]
    assert dur["appends_per_fsync"] == 2.0
    assert dur["pending_appends"] == 0
    assert dur["fsync_seconds"] >= 0.0
    eng.close()


# -- replica engine (in-process: bootstrap, tail, version pinning) -----------

def test_replica_bootstraps_bit_equal_and_tails_the_wal(tmp_path, rng):
    d = str(tmp_path / "dep")
    eng = ServingEngine(_mkstore(), num_shards=2, data_dir=d,
                        plan_cache=None)
    rep = ReplicaEngine(d, start_tail=False, plan_cache=None)
    try:
        assert rep.status()["fingerprint"] == eng.fingerprint()
        nodes = rng.integers(0, N, 16).astype(np.int32)
        assert np.array_equal(rep.embed(nodes),
                              np.asarray(eng.query_embed(nodes)))
        # owner advances: the pinned read must refuse, not lie
        eng.apply_edge_delta(np.array([0], np.int32),
                             np.array([1], np.int32),
                             np.ones(1, np.float32))
        with pytest.raises(ReplicaLagError):
            rep.embed(nodes, min_version=eng.version)
        rep.poll()                       # tail the fresh WAL records
        assert rep.engine.version == eng.version
        assert np.array_equal(rep.embed(nodes, min_version=eng.version),
                              np.asarray(eng.query_embed(nodes)))
        ei, ev = eng.query_topk(nodes, k=5)
        ri, rv = rep.topk(nodes, k=5, min_version=eng.version)
        assert np.array_equal(ei, ri) and np.array_equal(ev, rv)
    finally:
        rep.close()
        eng.close()


def test_replica_ivf_read_before_index_record_is_lag(tmp_path, rng):
    d = str(tmp_path / "dep")
    eng = ServingEngine(_mkstore(), data_dir=d, plan_cache=None)
    rep = ReplicaEngine(d, start_tail=False, plan_cache=None)
    try:
        nodes = rng.integers(0, N, 8).astype(np.int32)
        with pytest.raises(ReplicaLagError):   # no quantizer yet
            rep.topk(nodes, k=5, mode="ivf")
    finally:
        rep.close()
        eng.close()


def test_replica_reloads_on_checkpoint_generation_flip(tmp_path, rng):
    d = str(tmp_path / "dep")
    eng = ServingEngine(_mkstore(), data_dir=d, plan_cache=None)
    rep = ReplicaEngine(d, start_tail=False, plan_cache=None)
    try:
        reloads0 = rep.status()["reloads"]   # the bootstrap load
        eng.apply_edge_delta(np.array([2], np.int32),
                             np.array([3], np.int32),
                             np.ones(1, np.float32))
        eng.checkpoint()                 # new generation, rotated WAL
        rep.poll()
        st = rep.status()
        assert st["generation"] == eng.generation
        assert st["fingerprint"] == eng.fingerprint()
        assert st["reloads"] == reloads0 + 1
    finally:
        rep.close()
        eng.close()


# -- multi-process deployments (spawn real workers) --------------------------

@pytest.mark.parametrize("role", ["shard", "replica"])
def test_socket_spawn_refuses_on_tpu_host(monkeypatch, tmp_path, role):
    """The router holds the chip: spawning workers on a TPU host fails
    loudly before any process starts (no hang, no silent CPU)."""
    import jax
    from repro.transport import procs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def no_spawn(*a, **k):
        raise AssertionError("a worker process was started")

    monkeypatch.setattr(procs.subprocess, "Popen", no_spawn)
    with pytest.raises(TransportError, match="TPU host"):
        if role == "shard":
            ServingEngine(_mkstore(), num_shards=2, transport="socket",
                          plan_cache=None)
        else:
            procs.spawn_replica_worker(str(tmp_path))


@pytest.mark.slow
def test_socket_engine_answers_equal_inprocess(tmp_path, rng):
    store_a, store_b = _mkstore(seed=11), _mkstore(seed=11)
    local = ServingEngine(store_a, num_shards=2, index="ivf",
                          plan_cache=None)
    sock = ServingEngine(store_b, num_shards=2, index="ivf",
                         transport="socket", plan_cache=None)
    try:
        assert all(s.proc is not None and s.proc.alive()
                   for s in sock.shards)
        nodes = rng.integers(0, N, 32).astype(np.int32)
        assert np.array_equal(np.asarray(local.query_embed(nodes)),
                              np.asarray(sock.query_embed(nodes)))
        for mode, nprobe in (("exact", None), ("ivf", 2)):
            li, lv = local.query_topk(nodes, k=5, mode=mode,
                                      nprobe=nprobe)
            si, sv = sock.query_topk(nodes, k=5, mode=mode,
                                     nprobe=nprobe)
            assert np.array_equal(li, si) and np.array_equal(lv, sv)
        # writes fan out over RPC and stay bit-equal
        b = 16
        du = rng.integers(0, N, b).astype(np.int32)
        dv = rng.integers(0, N, b).astype(np.int32)
        dw = rng.random(b).astype(np.float32) + 0.5
        local.apply_edge_delta(du, dv, dw)
        sock.apply_edge_delta(du, dv, dw)
        local.apply_label_delta(np.array([5, 6]), np.array([1, 2]))
        sock.apply_label_delta(np.array([5, 6]), np.array([1, 2]))
        assert sock.fingerprint() == local.fingerprint()
        assert (sock.version, sock.epoch) == (local.version, local.epoch)
        assert np.array_equal(np.asarray(local.Z), np.asarray(sock.Z))
    finally:
        procs = [s.proc for s in sock.shards]
        sock.close()
        local.close()
        assert all(p is None or not p.alive() for p in procs
                   if p is not None)


@pytest.mark.slow
def test_replica_worker_fallback_and_dead_replica_degrades(tmp_path, rng):
    d = str(tmp_path / "dep")
    eng = ServingEngine(_mkstore(), data_dir=d, replicas=1,
                        plan_cache=None)
    try:
        nodes = rng.integers(0, N, 16).astype(np.int32)
        # served (by replica or owner fallback) and always correct
        assert np.array_equal(np.asarray(eng.query_embed(nodes)),
                              np.asarray(eng.Z)[nodes])
        eng.apply_edge_delta(np.array([0], np.int32),
                             np.array([1], np.int32),
                             np.ones(1, np.float32))
        # immediately after a write the replica may lag — the read must
        # still answer from the CURRENT version via owner fallback
        assert np.array_equal(np.asarray(eng.query_embed(nodes)),
                              np.asarray(eng.Z)[nodes])
        deadline = time.time() + 30
        while time.time() < deadline:
            rows = eng.health()["replicas"]
            if rows and rows[0].get("lag") == 0:
                break
            time.sleep(0.1)
        else:
            pytest.fail(f"replica never converged: {eng.health()}")
        # kill the replica worker: reads fall back, health degrades
        eng._replica_procs[0].kill()
        assert np.array_equal(np.asarray(eng.query_embed(nodes)),
                              np.asarray(eng.Z)[nodes])
        h = eng.health()
        assert h["state"] == "degraded"
        assert "unreachable" in h["reason"]
    finally:
        eng.close()


@pytest.mark.slow
def test_kill_shard_worker_mid_batch_then_reopen_exact(
        tmp_path, rng, assert_topk_equivalent):
    """Kill a shard worker mid-workload: the write in flight errors
    loudly, but append-before-apply means it was already WAL-durable —
    reopening with fresh workers recovers the ORACLE state (every
    batch, including the torn one) with an exact triple."""
    b = 12
    batches = [(rng.integers(0, N, b).astype(np.int32),
                rng.integers(0, N, b).astype(np.int32),
                rng.random(b).astype(np.float32) + 0.5)
               for _ in range(4)]
    # in-process oracle: the same store, every batch applied cleanly
    # (durable too — the gen-0 snapshot boot advances the fingerprint,
    # so only a durable twin chains identically)
    oracle = ServingEngine(_mkstore(seed=13), num_shards=2,
                           data_dir=str(tmp_path / "oracle"),
                           plan_cache=None)
    d = str(tmp_path / "dep")
    eng = ServingEngine(_mkstore(seed=13), num_shards=2, data_dir=d,
                        transport="socket", plan_cache=None)
    try:
        for batch in batches[:3]:
            eng.apply_edge_delta(*batch)
            oracle.apply_edge_delta(*batch)
        # murder shard worker 0: the next write must error loudly (a
        # dead owner never silently drops or re-applies a mutation)
        eng.shards[0].proc.kill()
        with pytest.raises(TransportError):
            eng.apply_edge_delta(*batches[3])
    finally:
        eng.close()                      # tolerates the dead worker
    oracle.apply_edge_delta(*batches[3])
    # reopen with FRESH workers: the torn batch was appended to the
    # WAL before the fan-out died, so it IS part of the durable state
    rec = ServingEngine.open(d, transport="socket", plan_cache=None)
    try:
        assert (rec.version, rec.epoch, rec.fingerprint()) == \
            (oracle.version, oracle.epoch, oracle.fingerprint())
        nodes = rng.integers(0, N, 16).astype(np.int32)
        oi, ov = oracle.query_topk(nodes, k=5)
        ri, rv = rec.query_topk(nodes, k=5)
        # scores to float tolerance only: the oracle's Z is delta-
        # folded, the recovered one rebuilt from the replayed store
        assert_topk_equivalent(oi, ov, ri, rv, atol=1e-4)
    finally:
        rec.close()
        oracle.close()
