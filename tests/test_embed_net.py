"""Network parameter-server tests (native/embed/ps_net.cpp + embed/net.py).

Oracle style: a remote table with the same seed/config must behave
bit-identically to the in-process engine table (same C++ code path behind a
TCP hop) — the reference's PS tests run worker+server processes against
small YAML configs (tests/pstests/local_s2_w1.yml, test_apis.py).
"""

import os
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.embed.engine import HostEmbeddingTable
from hetu_tpu.embed.net import (EmbeddingServer, RemoteEmbeddingTable,
                                RemoteHostEmbedding)


@pytest.fixture
def server():
    with EmbeddingServer() as srv:
        yield srv


def test_remote_matches_local_oracle(server):
    addr = f"127.0.0.1:{server.port}"
    remote = RemoteEmbeddingTable(addr, 1, 64, 8, optimizer="adam",
                                  lr=0.01, seed=3)
    local = HostEmbeddingTable(64, 8, optimizer="adam", lr=0.01, seed=3)
    ids = np.array([1, 5, 7, 5])  # duplicate key exercises dedup-accumulate
    np.testing.assert_array_equal(remote.pull(ids), local.pull(ids))
    rng = np.random.default_rng(0)
    for _ in range(5):
        g = rng.normal(size=(4, 8)).astype(np.float32)
        remote.push(ids, g)
        local.push(ids, g)
    np.testing.assert_array_equal(remote.pull(np.arange(64)),
                                  local.pull(np.arange(64)))


def test_set_rows_save_load(server, tmp_path):
    addr = f"127.0.0.1:{server.port}"
    t = RemoteEmbeddingTable(addr, 2, 32, 4, optimizer="sgd", lr=0.1)
    t.set_rows([3], np.full((1, 4), 2.0, np.float32))
    np.testing.assert_array_equal(t.pull([3]), np.full((1, 4), 2.0))
    p = str(tmp_path / "tbl.bin")
    t.save(p)
    t.push([3], np.ones((1, 4), np.float32))
    assert t.pull([3]).sum() != 8.0
    t.load(p)
    np.testing.assert_array_equal(t.pull([3]), np.full((1, 4), 2.0))


def test_second_client_attaches_and_shape_mismatch(server):
    addr = f"127.0.0.1:{server.port}"
    a = RemoteEmbeddingTable(addr, 3, 16, 4)
    a.set_rows([0], np.ones((1, 4), np.float32))
    b = RemoteEmbeddingTable(addr, 3, 16, 4)  # attach, same shape
    np.testing.assert_array_equal(b.pull([0]), np.ones((1, 4)))
    with pytest.raises(RuntimeError):
        RemoteEmbeddingTable(addr, 3, 32, 4)  # wrong shape


def test_barrier(server):
    addr = f"127.0.0.1:{server.port}"
    a = RemoteEmbeddingTable(addr, 4, 8, 2)
    b = RemoteEmbeddingTable(addr, 4, 8, 2)
    done = []

    def waiter():
        b.barrier(11, 2)
        done.append(1)

    th = threading.Thread(target=waiter)
    th.start()
    time.sleep(0.2)
    assert not done  # blocked until the second arrival
    a.barrier(11, 2)
    th.join(5)
    assert done
    # reusable (next generation)
    th2 = threading.Thread(target=waiter)
    th2.start()
    a.barrier(11, 2)
    th2.join(5)
    assert len(done) == 2


def test_remote_host_embedding_trains(server):
    """CTR-style training with the table sharded over two server-backed
    stores; loss must drop (hybrid mode: dense on-device, sparse on PS)."""
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.exec import Trainer
    from hetu_tpu.layers import Linear
    from hetu_tpu.core.module import Module
    from hetu_tpu.ops import binary_cross_entropy_with_logits
    from hetu_tpu.optim import AdamOptimizer

    with EmbeddingServer() as srv2:
        addrs = [f"127.0.0.1:{server.port}", f"127.0.0.1:{srv2.port}"]
        set_random_seed(0)

        class Model(Module):
            def __init__(self):
                self.embed = RemoteHostEmbedding(200, 8, servers=addrs,
                                                 optimizer="sgd", lr=0.1)
                self.head = Linear(8 * 4, 1)

            def loss(self, sparse, label):
                e = self.embed(sparse).reshape(sparse.shape[0], -1)
                logits = self.head(e)[:, 0]
                return binary_cross_entropy_with_logits(logits, label).mean()

        m = Model()
        assert m.embed.n_shards == 2
        rng = np.random.default_rng(0)
        sp = rng.integers(0, 200, (32, 4))
        y = (sp.sum(1) % 2).astype(np.float32)
        tr = Trainer(m, AdamOptimizer(1e-2),
                     lambda mm, b, k: (mm.loss(b["sp"], b["y"]), {}))
        b = {"sp": jnp.asarray(sp), "y": jnp.asarray(y)}
        losses = []
        for _ in range(30):
            for mod in tr.staged_modules():
                mod.stage(sp)
            losses.append(float(tr.step(b)["loss"]))
        assert losses[-1] < losses[0]
        # traffic spread across both server shards
        loads = m.embed.loads()
        assert (loads["pull_rows"] > 0).all()


@pytest.mark.slow
def test_standalone_server_process(tmp_path):
    """The PS server as a separate OS process (reference server role)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "hetu_tpu.embed.net", "--port", "0"],
        stdout=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        line = proc.stdout.readline()
        port = int(line.rsplit(":", 1)[1])
        t = RemoteEmbeddingTable(f"127.0.0.1:{port}", 1, 16, 4, seed=1)
        local = HostEmbeddingTable(16, 4, seed=1)
        np.testing.assert_array_equal(t.pull(np.arange(16)),
                                      local.pull(np.arange(16)))
    finally:
        proc.terminate()
        proc.wait(10)


def test_two_layers_get_distinct_tables(server):
    """Auto table-id allocation: two same-shaped layers must not alias."""
    addrs = [f"127.0.0.1:{server.port}"]
    a = RemoteHostEmbedding(50, 4, servers=addrs, optimizer="sgd", lr=0.1)
    b = RemoteHostEmbedding(50, 4, servers=addrs, optimizer="sgd", lr=0.1)
    a.tables[0].set_rows([0], np.full((1, 4), 5.0, np.float32))
    assert b.tables[0].pull([0]).sum() != 20.0  # b untouched


def test_hostname_resolution(server):
    """DNS names (not just dotted quads) must connect — the launcher hands
    workers the yaml hostnames verbatim."""
    t = RemoteEmbeddingTable(f"localhost:{server.port}", 900, 8, 2)
    assert t.pull([0]).shape == (1, 2)


def test_garbage_connection_does_not_kill_server(server):
    """A stray client (port scan / HTTP probe) must not take the server
    down (the handler validates frames instead of crashing)."""
    import socket

    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n" * 4)
    s.close()
    time.sleep(0.2)
    t = RemoteEmbeddingTable(f"127.0.0.1:{server.port}", 901, 8, 2, seed=5)
    local = HostEmbeddingTable(8, 2, seed=5)
    np.testing.assert_array_equal(t.pull(np.arange(8)),
                                  local.pull(np.arange(8)))


def test_preduce_over_the_wire(server):
    """Partial-reduce partner matching via the network PS: fast workers
    group within the window; the straggler reduces with whoever remains
    (reference preduce.py get_partner semantics over kPReduceGetPartner)."""
    addr = f"127.0.0.1:{server.port}"
    clients = [RemoteEmbeddingTable(addr, 20 + i, 4, 2) for i in range(3)]
    rounds = {w: [] for w in range(3)}

    def fast(w):
        # two training iterations: round 1 groups the fast pair inside the
        # window; round 2 includes the straggler who arrived meanwhile
        rounds[w].append(clients[w].preduce_get_partner(
            33, w, 3, min_group=2, wait_ms=300.0))
        time.sleep(2.0)
        rounds[w].append(clients[w].preduce_get_partner(
            33, w, 3, min_group=2, wait_ms=300.0))

    def straggler(w):
        time.sleep(1.2)  # far past round 1's 300ms window
        rounds[w].append(clients[w].preduce_get_partner(
            33, w, 3, min_group=2, wait_ms=300.0))

    ts = [threading.Thread(target=fast, args=(0,)),
          threading.Thread(target=fast, args=(1,)),
          threading.Thread(target=straggler, args=(2,))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
    assert len(rounds[0]) == 2 and len(rounds[1]) == 2 and len(rounds[2]) == 1, \
        f"threads did not all complete: {rounds}"
    # round 1: the fast pair proceeds without the straggler
    assert sorted(rounds[0][0]) == [0, 1] and sorted(rounds[1][0]) == [0, 1]
    # round 2: everyone reduces together
    assert sorted(rounds[0][1]) == [0, 1, 2]
    assert sorted(rounds[2][0]) == [0, 1, 2]


class TestRemoteCache:
    """Client-side HET cache over the wire (RemoteCacheTable + delta sync)."""

    def test_write_through_matches_uncached_oracle(self, server):
        from hetu_tpu.embed.net import RemoteCacheTable

        addr = f"127.0.0.1:{server.port}"
        t = RemoteEmbeddingTable(addr, 50, 64, 8, optimizer="adam",
                                 lr=0.01, seed=7)
        cache = RemoteCacheTable(t, capacity=16, pull_bound=0, push_bound=0)
        local = HostEmbeddingTable(64, 8, optimizer="adam", lr=0.01, seed=7)
        rng = np.random.default_rng(0)
        for step in range(6):
            ids = rng.integers(0, 64, 12)  # working set > capacity: evicts
            np.testing.assert_array_equal(cache.sync(ids), local.pull(ids))
            g = rng.normal(size=(12, 8)).astype(np.float32)
            cache.push(ids, g)
            local.push(ids, g)
        cache.flush()
        np.testing.assert_array_equal(t.pull(np.arange(64)),
                                      local.pull(np.arange(64)))

    def test_bounded_staleness_and_hits(self, server):
        from hetu_tpu.embed.net import RemoteCacheTable

        addr = f"127.0.0.1:{server.port}"
        t = RemoteEmbeddingTable(addr, 51, 16, 4, optimizer="sgd", lr=1.0)
        cache = RemoteCacheTable(t, capacity=16, pull_bound=5, push_bound=100)
        before = cache.sync([3]).copy()
        # another client updates the row server-side (version +1 <= bound 5)
        other = RemoteEmbeddingTable(addr, 51, 16, 4)
        other.push([3], np.ones((1, 4), np.float32))
        served = cache.sync([3])
        np.testing.assert_array_equal(served, before)  # stale-but-in-bound
        st = cache.stats()
        assert st["hits"] >= 1
        # exceed the bound: six more server-side versions force a refresh
        for _ in range(6):
            other.push([3], np.ones((1, 4), np.float32))
        refreshed = cache.sync([3])
        assert not np.array_equal(refreshed, before)

    def test_cached_remote_host_embedding_trains(self, server):
        from hetu_tpu.core import set_random_seed

        set_random_seed(0)
        emb = RemoteHostEmbedding(
            100, 4, servers=[f"127.0.0.1:{server.port}"], optimizer="sgd",
            lr=0.5, cache_capacity=32, push_bound=2)
        ids = np.arange(8)
        emb.stage(ids)
        r0 = np.asarray(emb.rows).copy()
        emb.push_grads(np.ones((8, 4), np.float32))
        emb.flush()
        emb.stage(ids)
        np.testing.assert_allclose(np.asarray(emb.rows), r0 - 0.5, rtol=1e-5)
        assert emb.stats()["misses"] >= 8  # first stage cold

    def test_load_invalidates_cached_rows(self, server, tmp_path):
        """Checkpoint restore moves versions backward; cached copies must
        not survive it (regression: inherited load bypassed the cache)."""
        from hetu_tpu.core import set_random_seed

        set_random_seed(0)
        emb = RemoteHostEmbedding(
            20, 4, servers=[f"127.0.0.1:{server.port}"], optimizer="sgd",
            lr=1.0, cache_capacity=20, pull_bound=100)
        ids = np.arange(6)
        emb.stage(ids)
        ckpt = str(tmp_path / "emb")
        emb.save(ckpt)
        saved = np.asarray(emb.rows).copy()
        emb.push_grads(np.ones((6, 4), np.float32))
        emb.flush()
        emb.stage(ids)
        assert not np.allclose(np.asarray(emb.rows), saved)
        emb.load(ckpt)
        emb.stage(ids)
        np.testing.assert_allclose(np.asarray(emb.rows), saved, rtol=1e-6)

    def test_hot_key_batches_and_eviction_chunked(self, server):
        """Skewed batches (duplicated hot keys) with eviction churn stay
        numerically exact vs the local oracle."""
        from hetu_tpu.embed.net import RemoteCacheTable

        addr = f"127.0.0.1:{server.port}"
        t = RemoteEmbeddingTable(addr, 60, 32, 4, optimizer="sgd", lr=0.1,
                                 seed=2)
        cache = RemoteCacheTable(t, capacity=8, push_bound=3)
        local = HostEmbeddingTable(32, 4, optimizer="sgd", lr=0.1, seed=2)
        rng = np.random.default_rng(1)
        for _ in range(8):
            ids = np.concatenate([np.zeros(5, np.int64),  # hot key x5
                                  rng.integers(0, 32, 10)])
            cache.sync(ids)
            g = rng.normal(size=(15, 4)).astype(np.float32)
            cache.push(ids, g)
            # oracle: dedup-accumulate matching the cache's local accumulate
            acc = {}
            for k, gr in zip(ids, g):
                acc.setdefault(int(k), np.zeros(4, np.float32))
                acc[int(k)] += gr
            # local engine table applies per-push-batch dedup the same way
            lk = np.asarray(sorted(acc))
            local.push(lk, np.stack([acc[int(k)] for k in lk]))
        cache.flush()
        np.testing.assert_allclose(t.pull(np.arange(32)),
                                   local.pull(np.arange(32)), rtol=1e-5,
                                   atol=1e-6)

    def test_remote_prefetch_overlap(self, server):
        """Async prefetch warms the remote shard caches; a matching stage
        serves from the prefetch buffer (the reference SparsePull overlap)."""
        from hetu_tpu.core import set_random_seed

        set_random_seed(0)
        emb = RemoteHostEmbedding(
            40, 4, servers=[f"127.0.0.1:{server.port}"], optimizer="sgd",
            lr=0.5, cache_capacity=40)
        a, b = np.arange(8), np.arange(8, 16)
        emb.stage(a)
        emb.prefetch(b)
        emb.stage(b)  # served from prefetch buffer
        direct = emb.pull_rows(b).reshape(8, 4)
        np.testing.assert_allclose(np.asarray(emb.rows), direct, rtol=1e-6)
        assert emb._handle.prefetcher is not None  # overlap path engaged


def test_server_side_load_introspection(server):
    """startRecord/getLoads capability (reference executor.py:398-401,675):
    the server reports per-table traffic counters, and a skewed key
    distribution shows up as hot rows in the recorded touch histogram."""
    addr = f"127.0.0.1:{server.port}"
    t = RemoteEmbeddingTable(addr, 31, 100, 4, optimizer="sgd", lr=0.1)
    t.start_record(True)
    rng = np.random.default_rng(0)
    # zipf-ish skew: row 7 is hot, the rest cold
    for _ in range(20):
        ids = np.where(rng.random(16) < 0.75, 7,
                       rng.integers(0, 100, 16)).astype(np.int64)
        t.pull(ids)
        t.push(ids, np.ones((16, 4), np.float32))
    loads = t.get_loads(topk=3)
    assert loads["pull_reqs"] == 20 and loads["push_reqs"] == 20
    assert loads["pull_rows"] == loads["push_rows"] == 20 * 16
    hot = loads["hot_rows"]
    assert hot and hot[0][0] == 7  # the skewed key is the hottest
    # hot row dominates: ~75% of 2*320 touches
    assert hot[0][1] > 0.5 * (2 * 20 * 16)
    assert all(hot[i][1] >= hot[i + 1][1] for i in range(len(hot) - 1))
    # counters survive with recording off; histogram is freed
    t.start_record(False)
    loads2 = t.get_loads(topk=5)
    assert loads2["pull_reqs"] == 20
    assert loads2["hot_rows"] == []


def test_priority_channel_independent_of_bulk(server):
    """The P3-style two-channel client (ps-lite p3_van.h:12 capability): a
    blocking control op on the priority channel must not wedge bulk pulls on
    the same client.  With the old single shared connection this deadlocked:
    the pull waited on the connection mutex held by the in-flight barrier."""
    addr = f"127.0.0.1:{server.port}"
    a = RemoteEmbeddingTable(addr, 41, 32, 4, optimizer="sgd", lr=0.1)
    got = {}

    def blocked_barrier():
        a.barrier(900, 2)  # blocks until a second client arrives
        got["barrier"] = True

    th = threading.Thread(target=blocked_barrier)
    th.start()
    time.sleep(0.05)  # barrier is in flight on the priority channel
    got["pull"] = a.pull(np.arange(8))  # bulk channel: must not block
    assert got["pull"].shape == (8, 4)
    b = RemoteEmbeddingTable(addr, 41, 32, 4, optimizer="sgd", lr=0.1)
    b.barrier(900, 2)  # release
    th.join(timeout=10)
    assert got.get("barrier") and not th.is_alive()


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_server(port):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "hetu_tpu.embed.net", "--port", str(port)],
        stdout=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert "listening" in proc.stdout.readline()
    return proc


@pytest.mark.slow
def test_server_kill_restart_resume(tmp_path):
    """PS fault tolerance end to end: SIGKILL the server mid-training,
    restart it on the same port, and the client reconnects (bounded
    backoff), re-creates its table, reloads the server-side checkpoint
    (v2 format: weights + optimizer slots) and resumes — the final model
    matches an uninterrupted oracle run bit-for-bit-close.  The reference
    rides out drops via ps-lite's resender (ps-lite/src/resender.h); the
    equivalent contract here is checkpoint-based kill-restart-resume."""
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.core.module import Module
    from hetu_tpu.exec import Trainer
    from hetu_tpu.layers import Linear
    from hetu_tpu.ops import binary_cross_entropy_with_logits
    from hetu_tpu.optim import AdamOptimizer

    rng = np.random.default_rng(0)
    sp = rng.integers(0, 100, (32, 4))
    y = (sp.sum(1) % 2).astype(np.float32)
    b = {"sp": jnp.asarray(sp), "y": jnp.asarray(y)}
    ckpt = str(tmp_path / "table.ckpt")

    def build(addr, table_id, restore=None, attempts=0):
        set_random_seed(0)

        class Model(Module):
            def __init__(self):
                self.embed = RemoteHostEmbedding(
                    100, 8, servers=[addr], table_id=table_id,
                    optimizer="adagrad", lr=0.05, seed=11,
                    reconnect_attempts=attempts, reconnect_backoff=0.05,
                    restore_path=restore)
                self.head = Linear(8 * 4, 1)

            def loss(self, sparse, label):
                e = self.embed(sparse).reshape(sparse.shape[0], -1)
                return binary_cross_entropy_with_logits(
                    self.head(e)[:, 0], label).mean()

        m = Model()
        tr = Trainer(m, AdamOptimizer(1e-2),
                     lambda mm, bb, k: (mm.loss(bb["sp"], bb["y"]), {}))
        return m, tr

    def step(tr):
        for mod in tr.staged_modules():
            mod.stage(sp)
        return float(tr.step(b)["loss"])

    # --- oracle: 30 uninterrupted steps against an in-process server
    with EmbeddingServer() as srv:
        m, tr = build(f"127.0.0.1:{srv.port}", table_id=901)
        oracle_losses = [step(tr) for _ in range(30)]
        oracle_rows = m.embed.pull_rows(np.arange(100))

    # --- failure run: SIGKILL after a step-15 checkpoint, restart, resume
    port = _free_port()
    proc = _spawn_server(port)
    proc2 = None
    try:
        m, tr = build(f"127.0.0.1:{port}", table_id=902, restore=ckpt,
                      attempts=40)
        losses = [step(tr) for _ in range(15)]
        m.embed.save(ckpt)  # server-side save (absolute path)
        proc.kill()         # SIGKILL: no shutdown handler runs
        proc.wait(10)
        proc2 = _spawn_server(port)
        losses += [step(tr) for _ in range(15)]  # first stage() reconnects
        rows = m.embed.pull_rows(np.arange(100))
    finally:
        for p in (proc, proc2):
            if p is not None and p.poll() is None:
                p.terminate()
                p.wait(10)

    np.testing.assert_allclose(losses, oracle_losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(rows, oracle_rows, rtol=1e-5, atol=1e-6)


class _FlakyProxy:
    """Single-connection-at-a-time TCP forwarder whose link can be severed
    (and re-listened) while the REAL server stays up — simulates a
    transient network drop without a server restart."""

    def __init__(self, target_port):
        import socket
        self.target_port = target_port
        self.lsock = socket.socket()
        self.lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.lsock.bind(("127.0.0.1", 0))
        self.port = self.lsock.getsockname()[1]
        self.lsock.listen(8)
        self._stop = False
        self._conns = []
        self._thread = threading.Thread(target=self._accept, daemon=True)
        self._thread.start()

    def _accept(self):
        import socket
        while not self._stop:
            try:
                c, _ = self.lsock.accept()
            except OSError:
                return
            u = socket.create_connection(("127.0.0.1", self.target_port))
            self._conns += [c, u]
            for a, b in ((c, u), (u, c)):
                threading.Thread(target=self._pump, args=(a, b),
                                 daemon=True).start()

    def _pump(self, src, dst):
        try:
            while True:
                d = src.recv(65536)
                if not d:
                    break
                dst.sendall(d)
        except OSError:
            pass
        for s in (src, dst):
            try:
                s.close()
            except OSError:
                pass

    def sever(self):
        """Drop every in-flight connection (clients see a dead socket; the
        server sees normal disconnects) but keep listening for redials."""
        for s in self._conns:
            try:
                s.close()
            except OSError:
                pass
        self._conns = []

    def close(self):
        self._stop = True
        self.sever()
        self.lsock.close()


@pytest.mark.slow
def test_transient_drop_does_not_roll_back_live_server(server, tmp_path):
    """A socket drop on a server that did NOT die must reconnect WITHOUT
    reloading the checkpoint: the live table carries every push since the
    last save, and a reload would silently roll them back (review finding,
    round 4 — kCreate status 1 'already existed' gates the restore)."""
    proxy = _FlakyProxy(server.port)
    ckpt = str(tmp_path / "t.ckpt")
    try:
        t = RemoteEmbeddingTable(
            f"127.0.0.1:{proxy.port}", 950, 16, 4, optimizer="sgd", lr=1.0,
            reconnect_attempts=30, reconnect_backoff=0.05,
            restore_path=ckpt)
        t.set_rows(np.arange(16), np.zeros((16, 4), np.float32))
        t.save(ckpt)  # checkpoint with all-zero rows
        t.push([3], np.full((1, 4), -1.0, np.float32))  # row3 -> +1.0
        proxy.sever()  # transient drop; the SERVER keeps its state
        rows = t.pull(np.arange(16))  # reconnects through the proxy
        # the post-save push survived: a checkpoint reload would zero it
        np.testing.assert_array_equal(rows[3], np.full(4, 1.0))
        assert t._gen == 1  # exactly one reconnect happened
    finally:
        proxy.close()


def test_concurrent_dead_socket_exactly_one_redial(server):
    """The _reconnect generation protocol under actual concurrency: two
    threads whose RPCs hit a dead socket at the same time must produce
    exactly ONE redial — the first thread to take the lock reconnects and
    bumps the generation, the second sees the bump and just retries on the
    fresh connection (previously only the single-threaded path was
    tested)."""
    proxy = _FlakyProxy(server.port)
    try:
        t = RemoteEmbeddingTable(f"127.0.0.1:{proxy.port}", 980, 32, 4,
                                 optimizer="sgd", lr=1.0,
                                 reconnect_attempts=20,
                                 reconnect_backoff=0.01)
        t.pull(np.arange(4))  # warm the connection through the proxy
        proxy.sever()  # both threads' next RPC sees a dead socket
        start = threading.Barrier(2)
        results, errs = [], []

        def puller():
            try:
                start.wait(5)
                results.append(t.pull(np.arange(8)))
            except Exception as e:  # pragma: no cover - failure detail
                errs.append(e)

        ths = [threading.Thread(target=puller) for _ in range(2)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(15)
        assert not errs, errs
        assert len(results) == 2
        assert t._gen == 1, f"expected exactly one redial, got {t._gen}"
        np.testing.assert_array_equal(results[0], results[1])
    finally:
        proxy.close()


def test_push_replay_same_seq_applied_once(server):
    """Server-side push dedup (at-most-once across reconnects): replaying
    a (client_id, seq) the server has already applied is a no-op — the
    double-apply a naive retry would cause after a response-lost socket
    drop on a live server (review finding, round 4)."""
    t = RemoteEmbeddingTable(f"127.0.0.1:{server.port}", 960, 8, 2,
                             optimizer="sgd", lr=1.0)
    t.set_rows(np.arange(8), np.zeros((8, 2), np.float32))
    t.push([0], np.full((1, 2), -1.0, np.float32))  # row0 -> +1.0
    t._push_seq -= 1  # simulate a retry replaying the SAME seq
    t.push([0], np.full((1, 2), -1.0, np.float32))  # dup: must not apply
    np.testing.assert_array_equal(t.pull([0]), np.full((1, 2), 1.0))
    t.push([0], np.full((1, 2), -1.0, np.float32))  # fresh seq applies
    np.testing.assert_array_equal(t.pull([0]), np.full((1, 2), 2.0))


@pytest.mark.slow
def test_autosave_plus_restart_recovers_hands_off(tmp_path):
    """autosave(path, every) + restore_path on the same path = hands-off
    fault recovery: no manual save anywhere, SIGKILL the server, restart,
    training resumes from the last autosave (at most `every` steps of
    embedding updates lost) and keeps converging."""
    from hetu_tpu.core import set_random_seed
    from hetu_tpu.core.module import Module
    from hetu_tpu.exec import Trainer
    from hetu_tpu.layers import Linear
    from hetu_tpu.ops import binary_cross_entropy_with_logits
    from hetu_tpu.optim import AdamOptimizer

    rng = np.random.default_rng(1)
    sp = rng.integers(0, 80, (32, 4))
    y = (sp.sum(1) % 2).astype(np.float32)
    b = {"sp": jnp.asarray(sp), "y": jnp.asarray(y)}
    ckpt = str(tmp_path / "auto.ckpt")
    port = _free_port()
    proc = _spawn_server(port)
    proc2 = None
    try:
        set_random_seed(0)

        class Model(Module):
            def __init__(self):
                self.embed = RemoteHostEmbedding(
                    80, 8, servers=[f"127.0.0.1:{port}"], table_id=970,
                    optimizer="adagrad", lr=0.05, seed=3,
                    reconnect_attempts=40, reconnect_backoff=0.05,
                    restore_path=ckpt)
                self.head = Linear(8 * 4, 1)

            def loss(self, sparse, label):
                e = self.embed(sparse).reshape(sparse.shape[0], -1)
                return binary_cross_entropy_with_logits(
                    self.head(e)[:, 0], label).mean()

        m = Model()
        m.embed.autosave(ckpt, every=3)
        tr = Trainer(m, AdamOptimizer(1e-2),
                     lambda mm, bb, k: (mm.loss(bb["sp"], bb["y"]), {}))

        def step():
            for mod in tr.staged_modules():
                mod.stage(sp)
            return float(tr.step(b)["loss"])

        pre = [step() for _ in range(7)]  # autosaves after steps 3 and 6
        assert os.path.exists(ckpt + ".shard0")
        proc.kill()
        proc.wait(10)
        proc2 = _spawn_server(port)
        post = [step() for _ in range(13)]
        assert post[-1] < pre[0] * 0.7, (pre, post)
        assert post[-1] < post[0], (pre, post)
    finally:
        for p in (proc, proc2):
            if p is not None and p.poll() is None:
                p.terminate()
                p.wait(10)


def test_transport_scheme_selection(monkeypatch):
    """The client van's transport seam: tcp (default and explicit) connects;
    rdma — the documented drop-in slot with no verbs backend in this image
    — must fail LOUDLY at connect (null client), never silently fall back;
    unknown schemes likewise."""
    from hetu_tpu.embed.net import EmbeddingServer, _lib

    lib = _lib()
    with EmbeddingServer() as srv:
        def connect(scheme):
            if scheme is None:
                monkeypatch.delenv("HETU_PS_TRANSPORT", raising=False)
            else:
                monkeypatch.setenv("HETU_PS_TRANSPORT", scheme)
            c = lib.het_ps_connect(b"127.0.0.1", srv.port)
            if c:
                lib.het_ps_disconnect(c)
            return bool(c)

        assert connect(None)
        assert connect("tcp")
        assert not connect("rdma")
        assert not connect("quic")
