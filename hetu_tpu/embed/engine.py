"""ctypes binding to the host embedding engine (build/libhetu_embed.so).

Python facade over the native engine; mirrors the reference's worker-side
surface: ``parameterServerCommunicate``-style dense/sparse push-pull
(ps-lite/src/python_binding.cc:6-151), ``CacheSparseTable`` with async
waitable ops (python/hetu/cstable.py:19), SSP sync and partial-reduce
partner matching.
"""

from __future__ import annotations

import ctypes
import hashlib
import itertools
import os
import pathlib
import subprocess
import threading

import numpy as np

from hetu_tpu.obs import registry as _obs

__all__ = [
    "HostEmbeddingTable", "Int8HostEmbeddingTable", "CacheTable",
    "PythonCacheTable", "AsyncEngine", "SSPBarrier",
    "PartialReduceCoordinator", "PReduceGroup", "decode_preduce_mask",
    "PREDUCE_QUORUM_FAIL_BIT", "OPTIMIZERS", "POLICIES",
    "publish_cache_stats",
]

_REPO = pathlib.Path(__file__).resolve().parents[2]
_SO = _REPO / "build" / "libhetu_embed.so"
_SRC_DIR = _REPO / "native" / "embed"

OPTIMIZERS = {"sgd": 0, "momentum": 1, "adagrad": 2, "adam": 3, "adamw": 4}
POLICIES = {"lru": 0, "lfu": 1, "lfuopt": 2}

_lib = None


def _build_key() -> str:
    """Hash of everything the binary is a function of: the sources, the
    build script, and — because the script compiles ``-march=native`` —
    the CPU features of this machine.  A copy of the tree onto another
    machine (mtimes lost, other CPU) therefore rebuilds instead of
    loading a binary built for a CPU it is not running on."""
    h = hashlib.sha256()
    for f in sorted([*_SRC_DIR.glob("*.cpp"), _SRC_DIR / "build.sh"]):
        h.update(f.name.encode() + b"\0" + f.read_bytes())
    with open("/proc/cpuinfo") as f:
        h.update(next((ln for ln in f if ln.startswith("flags")),
                      "").encode())
    return h.hexdigest()


def _build_if_stale() -> None:
    """Build ``libhetu_embed.so`` from ``native/embed`` unless the key
    stored beside it matches :func:`_build_key`."""
    key = _build_key()
    stamp = _SO.with_name(_SO.name + ".key")
    if _SO.exists() and stamp.exists() and stamp.read_text() == key:
        return
    # build under a private name and rename: a concurrent process never
    # loads a half-written library
    tmp = _SO.with_name(f"{_SO.name}.tmp.{os.getpid()}")
    proc = subprocess.run(["sh", str(_SRC_DIR / "build.sh"), str(tmp)],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        tmp.unlink(missing_ok=True)
        raise RuntimeError(
            f"building {_SO.name} from {_SRC_DIR} failed (exit "
            f"{proc.returncode}):\n{proc.stderr}")
    os.replace(tmp, _SO)
    stamp.write_text(key)


def _load():
    global _lib
    if _lib is not None:
        return _lib
    _build_if_stale()
    lib = ctypes.CDLL(str(_SO))
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    sigs = {
        "het_table_create": ([ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
                              ctypes.c_float, ctypes.c_float, ctypes.c_float,
                              ctypes.c_float, ctypes.c_float, ctypes.c_float,
                              ctypes.c_uint64, ctypes.c_float],
                             ctypes.c_void_p),
        "het_table_destroy": ([ctypes.c_void_p], None),
        "het_table_set_lr": ([ctypes.c_void_p, ctypes.c_float], None),
        "het_table_pull": ([ctypes.c_void_p, i64p, ctypes.c_int64, f32p],
                           None),
        "het_table_push": ([ctypes.c_void_p, i64p, ctypes.c_int64, f32p],
                           None),
        "het_table_set_rows": ([ctypes.c_void_p, i64p, ctypes.c_int64, f32p],
                               None),
        "het_table_version": ([ctypes.c_void_p, ctypes.c_int64],
                              ctypes.c_uint64),
        "het_table_save": ([ctypes.c_void_p, ctypes.c_char_p], ctypes.c_int),
        "het_table_load": ([ctypes.c_void_p, ctypes.c_char_p], ctypes.c_int),
        "het_cache_create": ([ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                              ctypes.c_uint64, ctypes.c_int64],
                             ctypes.c_void_p),
        "het_cache_destroy": ([ctypes.c_void_p], None),
        "het_cache_sync": ([ctypes.c_void_p, i64p, ctypes.c_int64, f32p],
                           None),
        "het_cache_push": ([ctypes.c_void_p, i64p, ctypes.c_int64, f32p],
                           None),
        "het_cache_flush": ([ctypes.c_void_p], None),
        "het_cache_size": ([ctypes.c_void_p], ctypes.c_int64),
        "het_cache_stats": ([ctypes.c_void_p, u64p, u64p], None),
        "het_engine_create": ([ctypes.c_int], ctypes.c_void_p),
        "het_engine_destroy": ([ctypes.c_void_p], None),
        "het_cache_sync_async": ([ctypes.c_void_p, ctypes.c_void_p, i64p,
                                  ctypes.c_int64, f32p], ctypes.c_uint64),
        "het_cache_push_async": ([ctypes.c_void_p, ctypes.c_void_p, i64p,
                                  ctypes.c_int64, f32p], ctypes.c_uint64),
        "het_table_push_async": ([ctypes.c_void_p, ctypes.c_void_p, i64p,
                                  ctypes.c_int64, f32p], ctypes.c_uint64),
        "het_wait": ([ctypes.c_void_p, ctypes.c_uint64], None),
        "het_ssp_create": ([ctypes.c_int, ctypes.c_int], ctypes.c_void_p),
        "het_ssp_destroy": ([ctypes.c_void_p], None),
        "het_ssp_sync": ([ctypes.c_void_p, ctypes.c_int, ctypes.c_int], None),
        "het_preduce_create": ([ctypes.c_int, ctypes.c_double, ctypes.c_int],
                               ctypes.c_void_p),
        "het_preduce_create_g": ([ctypes.c_int, ctypes.c_double,
                                  ctypes.c_int, ctypes.c_double],
                                 ctypes.c_void_p),
        "het_preduce_destroy": ([ctypes.c_void_p], None),
        "het_preduce_get_partner": ([ctypes.c_void_p, ctypes.c_int],
                                    ctypes.c_uint64),
    }
    for name, (argtypes, restype) in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    _lib = lib
    return lib


_cache_metrics = None
# default telemetry names for caches constructed without one; the counter
# is process-local, so names are deterministic per construction order
_cache_names = itertools.count(0)


def publish_cache_stats(name: str, stats: dict) -> None:
    """Mirror one HET cache's cumulative hit/miss counters (and current
    size) into the process registry under the ``cache`` label.  Shared by
    the in-process ``CacheTable``, the network ``RemoteCacheTable``, and
    the HBM-tier layers so all expose one scrape surface.  An explicit
    ``evictions`` count in ``stats`` is used as-is (the HBM tier counts
    exactly — its misses include staleness refreshes that never insert);
    otherwise evictions are derived: every C-cache miss inserts, so
    ``misses - size`` rows have been evicted since the cache started
    empty."""
    global _cache_metrics
    if not _obs.enabled():
        return
    if _cache_metrics is None:
        reg = _obs.get_registry()
        _cache_metrics = {
            "hits": reg.counter("hetu_cache_hits_total",
                                "HET cache hits (mirrored from the C "
                                "engine's cumulative counters)", ("cache",)),
            "misses": reg.counter("hetu_cache_misses_total",
                                  "HET cache misses", ("cache",)),
            "evictions": reg.counter(
                "hetu_cache_evictions_total",
                "HET cache evictions (derived: misses - resident size)",
                ("cache",)),
            "size": reg.gauge("hetu_cache_size_rows",
                              "HET cache resident rows", ("cache",)),
            "hit_rate": reg.gauge("hetu_cache_hit_rate",
                                  "lifetime hit fraction", ("cache",)),
        }
    m = _cache_metrics
    m["hits"].labels(cache=name).set_total(stats["hits"])
    m["misses"].labels(cache=name).set_total(stats["misses"])
    m["evictions"].labels(cache=name).set_total(
        stats["evictions"] if "evictions" in stats
        else max(stats["misses"] - stats["size"], 0))
    m["size"].labels(cache=name).set(stats["size"])
    m["hit_rate"].labels(cache=name).set(stats["hit_rate"])


def _i64(a):
    a = np.ascontiguousarray(a, dtype=np.int64)
    return a, a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f32(a):
    a = np.ascontiguousarray(a, dtype=np.float32)
    return a, a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class HostEmbeddingTable:
    """Host-memory embedding table with a server-side optimizer.

    The "server" of the PS pair: rows live in host RAM, gradient pushes run
    the optimizer on the host (ps-lite optimizer.h:25 capability), versions
    track per-row update counts for cache staleness.

    ``storage`` selects the resident form: ``"f32"`` (default, the C
    engine's float rows) or ``"int8"`` — per-row-quantized codes with a
    float shadow of only the optimizer-touched rows (the VLDB'24
    compression suite's scale/middle/digit scheme applied to PS storage;
    see :class:`Int8HostEmbeddingTable`, which this constructor returns
    for ``storage="int8"``).
    """

    storage = "f32"

    def __new__(cls, rows=0, dim=0, **kw):
        if cls is HostEmbeddingTable and kw.get("storage", "f32") == "int8":
            return super().__new__(Int8HostEmbeddingTable)
        return super().__new__(cls)

    def __init__(self, rows: int, dim: int, *, optimizer: str = "sgd",
                 lr: float = 0.01, momentum: float = 0.9, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0, seed: int = 0,
                 init_scale: float = 0.01, storage: str = "f32"):
        if storage != "f32":
            raise ValueError(f"unknown storage {storage!r}: 'f32' or 'int8'")
        self._lib = _load()
        self.rows, self.dim = rows, dim
        self._h = self._lib.het_table_create(
            rows, dim, OPTIMIZERS[optimizer], lr, momentum, beta1, beta2,
            eps, weight_decay, seed, init_scale)

    def resident_bytes(self) -> int:
        """Host bytes resident for the ROW PAYLOAD (the quantity int8
        storage shrinks; per-row version counters and optimizer slots are
        excluded on both storage modes so the ratio compares payloads)."""
        return int(self.rows) * int(self.dim) * 4

    def pull_wire_bytes(self, n_rows: int) -> int:
        """Bytes a pull of ``n_rows`` moves across the PS boundary in this
        table's storage form (f32: full float rows)."""
        return int(n_rows) * int(self.dim) * 4

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.het_table_destroy(self._h)
            self._h = None

    def pull(self, keys) -> np.ndarray:
        keys, kp = _i64(keys)
        out = np.empty((len(keys), self.dim), np.float32)
        self._lib.het_table_pull(self._h, kp, len(keys),
                                 out.ctypes.data_as(
                                     ctypes.POINTER(ctypes.c_float)))
        return out

    def push(self, keys, grads):
        keys, kp = _i64(keys)
        grads, gp = _f32(grads)
        assert grads.shape == (len(keys), self.dim)
        self._lib.het_table_push(self._h, kp, len(keys), gp)

    def set_rows(self, keys, values):
        keys, kp = _i64(keys)
        values, vp = _f32(values)
        self._lib.het_table_set_rows(self._h, kp, len(keys), vp)

    def version(self, row: int) -> int:
        return int(self._lib.het_table_version(self._h, row))

    def set_lr(self, lr: float):
        self._lib.het_table_set_lr(self._h, lr)

    def save(self, path: str):
        rc = self._lib.het_table_save(self._h, str(path).encode())
        if rc != 0:
            raise IOError(f"save failed ({rc}): {path}")

    def load(self, path: str):
        rc = self._lib.het_table_load(self._h, str(path).encode())
        if rc != 0:
            raise IOError(f"load failed ({rc}): {path}")


class Int8HostEmbeddingTable(HostEmbeddingTable):
    """PS storage tier with per-row int8-quantized rows (VLDB'24 suite's
    scale/middle/digit scheme, ``compress.quant.quantize_rows``) — the
    ``storage="int8"`` form of :class:`HostEmbeddingTable`.

    Resident payload per row: ``dim`` int8 codes + one float16 scale + one
    float16 middle (vs ``4*dim`` f32 bytes), so a dim-32 table shrinks
    3.6x and dim-64 3.8x; ``pull`` dequantizes AT THE HOST BOUNDARY and
    returns ordinary float32 rows, so every consumer (caches, staged
    bridge, shard router, snapshot writer) is storage-oblivious.

    ``push`` applies gradients against a FLOAT SHADOW of only the
    optimizer-touched rows: the touched row's exact f32 value (and its
    momentum/adagrad/adam slots) lives beside the quantized store, so
    repeated updates never accumulate quantization error — cold rows pay
    1 byte/weight, hot rows pay float precision, which is the HET skew
    bet again at the storage layer.  Optimizer arithmetic mirrors the C
    engine exactly (dedup-accumulate per batch, one global step counter
    for adam bias correction), and the same ``seed`` produces the same
    initial rows as the f32 table (drawn through the C initializer, then
    quantized) so an int8-vs-f32 A/B starts from one init.
    """

    storage = "int8"

    def __init__(self, rows: int, dim: int, *, optimizer: str = "sgd",
                 lr: float = 0.01, momentum: float = 0.9, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0, seed: int = 0,
                 init_scale: float = 0.01, storage: str = "int8",
                 shadow_limit: int = 0):
        if storage != "int8":
            raise ValueError("Int8HostEmbeddingTable is storage='int8'")
        from collections import OrderedDict

        from hetu_tpu.embed.compress.quant import quantize_rows
        self.rows, self.dim = int(rows), int(dim)
        self._opt = OPTIMIZERS[optimizer]  # validated against the C enum
        self._lr = float(lr)
        self._momentum = float(momentum)
        self._beta1, self._beta2 = float(beta1), float(beta2)
        self._eps = float(eps)
        self._weight_decay = float(weight_decay)
        self._q = np.empty((self.rows, self.dim), np.int8)
        self._scale = np.empty((self.rows,), np.float16)
        self._middle = np.empty((self.rows,), np.float16)
        self._version = np.zeros((self.rows,), np.uint64)
        self._step = 0
        # float shadow: row id -> exact f32 row for optimizer-touched rows
        # (evictable beyond shadow_limit; 0 = unbounded); slot dicts are
        # NOT evictable — dropping an adagrad accumulator would change the
        # training trajectory, exactly like the C engine's persistent slots
        self._shadow = OrderedDict()
        self._m1 = {}
        self._m2 = {}
        self.shadow_limit = int(shadow_limit)
        self._lock = threading.Lock()
        # same-seed init parity with the f32 table: draw the rows through
        # the C initializer (mt19937_64 + normal), then quantize
        src = HostEmbeddingTable(self.rows, self.dim, seed=seed,
                                 init_scale=init_scale)
        chunk = 65536
        for lo in range(0, self.rows, chunk):
            ids = np.arange(lo, min(lo + chunk, self.rows), dtype=np.int64)
            q, s, m = quantize_rows(src.pull(ids))
            self._q[ids] = q
            self._scale[ids] = s.astype(np.float16)
            self._middle[ids] = m.astype(np.float16)
        del src

    def __del__(self):  # no C handle to release
        pass

    def resident_bytes(self) -> int:
        shadow = sum(v.nbytes for v in self._shadow.values())
        return (self._q.nbytes + self._scale.nbytes + self._middle.nbytes
                + shadow)

    def pull_wire_bytes(self, n_rows: int) -> int:
        return int(n_rows) * (int(self.dim) + 4)  # codes + f16 scale/middle

    def _dequant(self, keys: np.ndarray) -> np.ndarray:
        from hetu_tpu.embed.compress.quant import dequantize_rows
        rows = dequantize_rows(self._q[keys], self._scale[keys],
                               self._middle[keys])
        for i, k in enumerate(keys):
            w = self._shadow.get(int(k))
            if w is not None:
                rows[i] = w
        return rows

    def pull(self, keys) -> np.ndarray:
        keys = np.ascontiguousarray(np.asarray(keys).ravel(), np.int64)
        with self._lock:
            return self._dequant(keys)

    def push(self, keys, grads):
        keys = np.ascontiguousarray(np.asarray(keys).ravel(), np.int64)
        grads = np.ascontiguousarray(grads, np.float32).reshape(
            keys.size, self.dim)
        from hetu_tpu.embed.compress.quant import quantize_rows
        with self._lock:
            self._step += 1
            uniq, inv = np.unique(keys, return_inverse=True)
            g = np.zeros((uniq.size, self.dim), np.float32)
            np.add.at(g, inv, grads)  # dedup-accumulate (ApplySparse)
            w = self._dequant(uniq)
            kind, lr, wd = self._opt, self._lr, self._weight_decay
            if kind == OPTIMIZERS["sgd"]:
                w -= lr * (g + wd * w)
            elif kind == OPTIMIZERS["momentum"]:
                v = self._gather_slot(self._m1, uniq)
                gj = g + wd * w
                v = self._momentum * v + gj
                w -= lr * v
                self._scatter_slot(self._m1, uniq, v)
            elif kind == OPTIMIZERS["adagrad"]:
                a = self._gather_slot(self._m1, uniq)
                gj = g + wd * w
                a += gj * gj
                w -= lr * gj / (np.sqrt(a) + self._eps)
                self._scatter_slot(self._m1, uniq, a)
            else:  # adam / adamw
                m = self._gather_slot(self._m1, uniq)
                v = self._gather_slot(self._m2, uniq)
                t = np.float32(self._step)
                bc1 = 1.0 - np.float32(self._beta1) ** t
                bc2 = 1.0 - np.float32(self._beta2) ** t
                gj = g + wd * w if kind == OPTIMIZERS["adam"] else g
                m = self._beta1 * m + (1.0 - self._beta1) * gj
                v = self._beta2 * v + (1.0 - self._beta2) * gj * gj
                upd = (m / bc1) / (np.sqrt(v / bc2) + self._eps)
                if kind == OPTIMIZERS["adamw"]:
                    upd = upd + wd * w
                w -= lr * upd
                self._scatter_slot(self._m1, uniq, m)
                self._scatter_slot(self._m2, uniq, v)
            q, s, mid = quantize_rows(w)
            self._q[uniq] = q
            self._scale[uniq] = s.astype(np.float16)
            self._middle[uniq] = mid.astype(np.float16)
            self._version[uniq] += 1
            for i, k in enumerate(uniq):
                k = int(k)
                # copy, not a view: a view's base is the whole (uniq, dim)
                # work array, and one long-tail row would pin its entire
                # originating batch in memory
                self._shadow[k] = w[i].copy()
                self._shadow.move_to_end(k)
            if self.shadow_limit > 0:
                while len(self._shadow) > self.shadow_limit:
                    # the evicted row's quantized form is already current;
                    # only its float precision is given back
                    self._shadow.popitem(last=False)

    def _gather_slot(self, slot: dict, uniq: np.ndarray) -> np.ndarray:
        # slots default to zeros for never-touched rows (lazy, like the C
        # engine's ensure_slots)
        out = np.zeros((uniq.size, self.dim), np.float32)
        for i, k in enumerate(uniq):
            r = slot.get(int(k))
            if r is not None:
                out[i] = r
        return out

    def _scatter_slot(self, slot: dict, uniq: np.ndarray, vals: np.ndarray):
        for i, k in enumerate(uniq):
            slot[int(k)] = vals[i].copy()  # no views of the batch array

    def set_rows(self, keys, values):
        from hetu_tpu.embed.compress.quant import quantize_rows
        keys = np.ascontiguousarray(np.asarray(keys).ravel(), np.int64)
        values = np.ascontiguousarray(values, np.float32).reshape(
            keys.size, self.dim)
        with self._lock:
            q, s, m = quantize_rows(values)
            self._q[keys] = q
            self._scale[keys] = s.astype(np.float16)
            self._middle[keys] = m.astype(np.float16)
            self._version[keys] += 1
            # a direct write supersedes any float shadow: leaving one
            # would silently mask the install on the next pull
            for k in keys:
                self._shadow.pop(int(k), None)

    def version(self, row: int) -> int:
        return int(self._version[row])

    def versions(self, keys) -> np.ndarray:
        return self._version[np.asarray(keys, np.int64)]

    def set_lr(self, lr: float):
        self._lr = float(lr)

    def save(self, path: str):
        import io
        buf = io.BytesIO()
        sk = np.fromiter(self._shadow.keys(), np.int64,
                         count=len(self._shadow))
        sv = (np.stack(list(self._shadow.values()))
              if self._shadow else np.zeros((0, self.dim), np.float32))

        def pack(d):
            k = np.fromiter(d.keys(), np.int64, count=len(d))
            v = (np.stack(list(d.values())) if d
                 else np.zeros((0, self.dim), np.float32))
            return k, v

        m1k, m1v = pack(self._m1)
        m2k, m2v = pack(self._m2)
        np.savez(buf, q=self._q, scale=self._scale, middle=self._middle,
                 version=self._version, step=np.int64(self._step),
                 shadow_keys=sk, shadow_vals=sv, m1_keys=m1k, m1_vals=m1v,
                 m2_keys=m2k, m2_vals=m2v)
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            f.write(buf.getvalue())
        os.replace(tmp, path)

    def load(self, path: str):
        with np.load(path) as z:
            if z["q"].shape != (self.rows, self.dim):
                raise IOError(
                    f"load failed (-2): {path} holds shape {z['q'].shape}, "
                    f"table is {(self.rows, self.dim)}")
            self._q[:] = z["q"]
            self._scale[:] = z["scale"]
            self._middle[:] = z["middle"]
            self._version[:] = z["version"]
            self._step = int(z["step"])
            self._shadow.clear()
            for k, v in zip(z["shadow_keys"], z["shadow_vals"]):
                self._shadow[int(k)] = np.asarray(v, np.float32)
            self._m1 = {int(k): np.asarray(v, np.float32)
                        for k, v in zip(z["m1_keys"], z["m1_vals"])}
            self._m2 = {int(k): np.asarray(v, np.float32)
                        for k, v in zip(z["m2_keys"], z["m2_vals"])}


class CacheTable:
    """Worker-side cache over a HostEmbeddingTable (HET protocol).

    ``sync(keys)`` = syncEmbedding: serve rows, re-pulling those staler than
    ``pull_bound`` server updates. ``push(keys, grads)`` = pushEmbedding:
    accumulate locally, flushing rows after ``push_bound`` accumulations.
    (src/hetu_cache/include/hetu_client.h:19-30.)

    Over an ``storage="int8"`` table (a Python object with no C handle)
    the constructor returns a :class:`PythonCacheTable` with the same
    facade and semantics.
    """

    is_het_cache = True  # duck tag shared with PythonCacheTable

    def __new__(cls, table=None, capacity: int = 0, **kw):
        if cls is CacheTable and getattr(table, "storage", "f32") != "f32":
            return PythonCacheTable(table, capacity, **kw)
        return super().__new__(cls)

    def __init__(self, table: HostEmbeddingTable, capacity: int, *,
                 policy: str = "lru", pull_bound: int = 0,
                 push_bound: int = 0, name: str | None = None,
                 read_only: bool = False):
        self._lib = _load()
        self.table = table
        self.dim = table.dim
        # telemetry label (see publish_cache_stats); pass an explicit name
        # when you need run-to-run stable labels across rebuilds
        self.name = name if name is not None else f"cache{next(_cache_names)}"
        # Serving mode: pushes raise instead of training the table.  The C
        # engine sizes optimizer slots lazily on the first gradient apply
        # (embed_engine.cpp ensure_slots), so a read-only cache also never
        # allocates optimizer state — an inference worker pays for rows
        # only, not rows + momentum/adam moments.
        self.read_only = bool(read_only)
        self._h = self._lib.het_cache_create(
            table._h, capacity, POLICIES[policy], pull_bound, push_bound)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.het_cache_destroy(self._h)
            self._h = None

    def sync(self, keys) -> np.ndarray:
        keys, kp = _i64(keys)
        out = np.empty((len(keys), self.dim), np.float32)
        self._lib.het_cache_sync(self._h, kp, len(keys),
                                 out.ctypes.data_as(
                                     ctypes.POINTER(ctypes.c_float)))
        if _obs.enabled():
            self.stats()  # refresh the registry mirror for live scrapes
        return out

    def push(self, keys, grads):
        if self.read_only:
            raise RuntimeError(
                f"cache {self.name!r} is read-only (serving mode): "
                f"gradient pushes are disabled so inference cannot "
                f"silently train the table")
        keys, kp = _i64(keys)
        grads, gp = _f32(grads)
        self._lib.het_cache_push(self._h, kp, len(keys), gp)

    def flush(self):
        # deliberately NOT gated on read_only: pushes buffered BEFORE the
        # flag was flipped (push_bound accumulation during training) must
        # stay drainable, and flushing an empty buffer is a no-op
        self._lib.het_cache_flush(self._h)

    def stats(self) -> dict:
        h, m = ctypes.c_uint64(), ctypes.c_uint64()
        self._lib.het_cache_stats(self._h, ctypes.byref(h), ctypes.byref(m))
        total = h.value + m.value
        out = {"hits": h.value, "misses": m.value, "size":
               int(self._lib.het_cache_size(self._h)),
               "hit_rate": h.value / total if total else 0.0}
        publish_cache_stats(self.name, out)
        return out


class PythonCacheTable:
    """HET worker-side cache in Python — the :class:`CacheTable` facade
    (sync/push/flush/stats/read_only) over tables the C cache cannot wrap
    (the ``storage="int8"`` Python table has no C handle).

    Same protocol: ``sync`` serves cached rows, re-pulling those whose
    server version advanced more than ``pull_bound`` updates past the
    cached copy (one batched table pull per sync); ``push`` accumulates
    locally and flushes a row after ``push_bound`` accumulations; LRU
    eviction at capacity flushes the victim's pending grads first.  A
    lock serializes readers and writers, so the staged layer's
    ``async_push`` worker is safe against ``stage()`` pulls — the same
    guarantee the C engine cache provides.
    """

    is_het_cache = True

    def __init__(self, table, capacity: int, *, policy: str = "lru",
                 pull_bound: int = 0, push_bound: int = 0,
                 name: str | None = None, read_only: bool = False):
        from collections import OrderedDict
        if capacity <= 0:
            raise ValueError("cache capacity must be > 0")
        if policy not in POLICIES:
            raise ValueError(f"unknown policy {policy!r}")
        self.table = table
        self.dim = table.dim
        self.capacity = int(capacity)
        self.pull_bound = int(pull_bound)
        self.push_bound = int(push_bound)
        self.name = name if name is not None else f"cache{next(_cache_names)}"
        self.read_only = bool(read_only)
        # key -> [row f32, fetched_version, pending_grad|None, pending_n]
        self._entries = OrderedDict()  # order = LRU (lfu/lfuopt degrade to
        # LRU here; the C cache keeps the exact policies)
        self._hits = 0
        self._misses = 0
        self._lock = threading.Lock()

    def _server_versions(self, keys: np.ndarray) -> np.ndarray:
        vfn = getattr(self.table, "versions", None)
        if vfn is not None:
            return np.asarray(vfn(keys), np.uint64)
        return np.fromiter((self.table.version(int(k)) for k in keys),
                           np.uint64, count=keys.size)

    def _flush_entry(self, key: int, ent) -> None:
        if ent[2] is not None and ent[3] > 0:
            self.table.push(np.asarray([key], np.int64), ent[2][None, :])
            ent[2], ent[3] = None, 0

    def sync(self, keys) -> np.ndarray:
        keys = np.ascontiguousarray(np.asarray(keys).ravel(), np.int64)
        out = np.empty((keys.size, self.dim), np.float32)
        with self._lock:
            sv = self._server_versions(keys)
            need_idx = []
            for i, k in enumerate(keys):
                k = int(k)
                ent = self._entries.get(k)
                if ent is not None and int(sv[i]) - int(ent[1]) \
                        <= self.pull_bound:
                    out[i] = ent[0]
                    self._entries.move_to_end(k)
                    self._hits += 1
                else:
                    need_idx.append(i)
                    self._misses += 1
            if need_idx:
                need_idx = np.asarray(need_idx, np.int64)
                need = keys[need_idx]
                # a stale entry's pending grads flush BEFORE the re-pull so
                # the refreshed copy reflects them (C cache sync semantics)
                for k in need:
                    ent = self._entries.get(int(k))
                    if ent is not None:
                        self._flush_entry(int(k), ent)
                fresh = self.table.pull(need)
                sv_need = self._server_versions(need)
                for j, k in enumerate(need):
                    k = int(k)
                    out[need_idx[j]] = fresh[j]
                    ent = self._entries.get(k)
                    if ent is None:
                        self._entries[k] = [fresh[j].copy(),
                                            int(sv_need[j]), None, 0]
                    else:
                        ent[0] = fresh[j].copy()
                        ent[1] = int(sv_need[j])
                    self._entries.move_to_end(k)
                while len(self._entries) > self.capacity:
                    vk, vent = self._entries.popitem(last=False)
                    self._flush_entry(vk, vent)
        if _obs.enabled():
            self.stats()  # refresh the registry mirror for live scrapes
        return out

    # plain pull = cache-served read (same aliasing as RemoteCacheTable)
    pull = sync

    def push(self, keys, grads):
        if self.read_only:
            raise RuntimeError(
                f"cache {self.name!r} is read-only (serving mode): "
                f"gradient pushes are disabled so inference cannot "
                f"silently train the table")
        keys = np.ascontiguousarray(np.asarray(keys).ravel(), np.int64)
        grads = np.ascontiguousarray(grads, np.float32).reshape(
            keys.size, self.dim)
        with self._lock:
            flush_k, flush_g = [], []
            for i, k in enumerate(keys):
                k = int(k)
                ent = self._entries.get(k)
                if ent is None:
                    # evicted between fwd and bwd: apply directly (C path)
                    flush_k.append(k)
                    flush_g.append(grads[i])
                    continue
                if ent[2] is None:
                    ent[2] = grads[i].copy()
                else:
                    ent[2] += grads[i]
                ent[3] += 1
                if ent[3] > self.push_bound:
                    flush_k.append(k)
                    flush_g.append(ent[2])
                    ent[2], ent[3] = None, 0
            if flush_k:
                self.table.push(np.asarray(flush_k, np.int64),
                                np.stack(flush_g))

    def flush(self):
        with self._lock:
            for k, ent in self._entries.items():
                self._flush_entry(k, ent)

    def invalidate(self):
        """Flush pending grads and drop every cached copy."""
        self.flush()
        with self._lock:
            self._entries.clear()

    def size(self) -> int:
        return len(self._entries)

    def stats(self) -> dict:
        total = self._hits + self._misses
        out = {"hits": self._hits, "misses": self._misses,
               "size": len(self._entries),
               "hit_rate": self._hits / total if total else 0.0}
        publish_cache_stats(self.name, out)
        return out


class AsyncEngine:
    """Thread pool issuing cache/table ops off the training thread; returns
    waitable tickets (reference CSEvent/PSEvent, python/hetu/stream.py:73)."""

    def __init__(self, n_threads: int = 2):
        self._lib = _load()
        self._h = self._lib.het_engine_create(n_threads)
        self._live = {}  # ticket -> pinned buffers

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.het_engine_destroy(self._h)
            self._h = None

    def sync_async(self, cache: CacheTable, keys):
        keys, kp = _i64(keys)
        out = np.empty((len(keys), cache.dim), np.float32)
        t = self._lib.het_cache_sync_async(
            self._h, cache._h, kp, len(keys),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        self._live[t] = (keys, out)
        return t, out

    def push_async(self, cache: CacheTable, keys, grads):
        if cache.read_only:
            # same invariant as the synchronous push(): a frozen serving
            # cache must not be trainable through ANY entry point
            raise RuntimeError(
                f"cache {cache.name!r} is read-only (serving mode): "
                f"async gradient pushes are disabled")
        keys, kp = _i64(keys)
        grads, gp = _f32(grads)
        t = self._lib.het_cache_push_async(self._h, cache._h, kp, len(keys),
                                           gp)
        self._live[t] = (keys, grads)
        return t

    def table_push_async(self, table: HostEmbeddingTable, keys, grads):
        keys, kp = _i64(keys)
        grads, gp = _f32(grads)
        t = self._lib.het_table_push_async(self._h, table._h, kp, len(keys),
                                           gp)
        self._live[t] = (keys, grads)
        return t

    def wait(self, ticket):
        self._lib.het_wait(self._h, ticket)
        self._live.pop(ticket, None)


class SSPBarrier:
    """Bounded-staleness barrier (ssp_handler.h:12): ``sync(worker, clock)``
    blocks until the slowest worker is within ``staleness`` clocks."""

    def __init__(self, n_workers: int, staleness: int):
        self._lib = _load()
        self._h = self._lib.het_ssp_create(n_workers, staleness)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.het_ssp_destroy(self._h)
            self._h = None

    def sync(self, worker: int, clock: int):
        self._lib.het_ssp_sync(self._h, worker, clock)


# bit 62 of the partner mask flags a round that was force-closed below
# min_group after the grace period (bit 63 is kept clear so the mask can
# ride the network transport's signed status channel)
PREDUCE_QUORUM_FAIL_BIT = 1 << 62


class PReduceGroup(list):
    """Worker ids matched into one partial-reduce round.  ``quorum_met`` is
    False when the group was force-closed after the grace period with fewer
    than ``min_group`` members (e.g. a dead peer): the caller still makes
    progress — the straggler tolerance the scheme exists for — but can tell
    degraded progress apart from a healthy round."""

    def __init__(self, members, quorum_met: bool = True):
        super().__init__(members)
        self.quorum_met = quorum_met


def decode_preduce_mask(mask: int, n_workers: int) -> PReduceGroup:
    return PReduceGroup(
        [w for w in range(n_workers) if mask & (1 << w)],
        quorum_met=not (mask & PREDUCE_QUORUM_FAIL_BIT))


class PartialReduceCoordinator:
    """Dynamic reduce-group matching (preduce_handler.cc; SIGMOD'21):
    ``get_partner(worker)`` returns the workers grouped with the caller —
    whoever arrived within the wait window.  A round can close below
    ``min_group`` only after a bounded grace period (dead-peer tolerance);
    such rounds are flagged via ``PReduceGroup.quorum_met``."""

    def __init__(self, n_workers: int, wait_ms: float = 10.0,
                 min_group: int = 2, grace_ms: float = -1.0):
        if not 0 < n_workers <= 62:
            raise ValueError("n_workers must be in [1, 62] (mask bits 62/63 "
                             "are reserved)")
        self._lib = _load()
        self.n_workers = n_workers
        self._h = self._lib.het_preduce_create_g(n_workers, wait_ms,
                                                 min_group, grace_ms)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.het_preduce_destroy(self._h)
            self._h = None

    def get_partner(self, worker: int) -> PReduceGroup:
        mask = self._lib.het_preduce_get_partner(self._h, worker)
        return decode_preduce_mask(mask, self.n_workers)
