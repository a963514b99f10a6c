"""Native (C++) runtime components + ctypes bindings.

Sources live in ``native/`` at the repo root; this package builds them on
demand with ``make`` (g++, no external deps) and exposes:

- :func:`tail_binary_path` — the ``apm_tail`` per-file tailer binary
  (perl_tail.pl role), consumed by ingest.tailer.NativeTailer/TailManager.
- :class:`LineRing` — lock-free SPSC byte ring (native/ring.cpp): the
  bounded host buffer between producers and the device step loop, with
  full-ring push failure as the backpressure signal (queue.js:250-256 role).
- :class:`TxDecoder` — batch tx pipe-CSV decoder (native/decoder.cpp): one
  C++ pass over a newline-joined blob -> dense (end_ts, elapsed, key id,
  line span) arrays with first-appearance key interning; the host intake
  hot path behind pipeline.feed_csv_batch.
- :class:`ParserEngineNative` — the log-correlation parser's ingest fast
  path (native/parser.cpp): chunked marker pre-filter + field extraction +
  the (logId, service) TTL correlation join, plus the per-file SOAP/audit
  state machines; consumed by ingest.parser.TransactionParser.read_lines
  (APM_PARSE_NO_NATIVE=1 kills it).
- :func:`frames_pack_native` — the APF1 frame-batch packer (apmfrm_pack in
  native/parser.cpp): newline-joined tx lines -> one packed frame batch
  for transport/frames.py (APM_FRAMES_NO_NATIVE=1 kills it).

Everything degrades gracefully: with no compiler available the build
functions return None and callers fall back to the pure-Python paths.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import threading
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "native")
_BUILD_LOCK = threading.Lock()
_BUILD_RESULT: dict = {}


def native_source_dir() -> str:
    return os.path.abspath(_NATIVE_DIR)


def ensure_built(*, quiet: bool = True, timeout_s: float = 45.0) -> Optional[str]:
    """Run ``make`` in native/ once per process; returns the build dir or
    None when the toolchain/sources are unavailable.

    ``APM_NATIVE_SANITIZE=1`` switches to the ASan+UBSan instrumented
    artifacts (``make sanitize`` -> build-sanitize/) — the hardened mode
    ``run_tests.sh --sanitize`` drives the differential fuzz suite under,
    with libasan LD_PRELOADed so the instrumented .so files resolve their
    runtime inside the stock Python process."""
    sanitize = os.environ.get("APM_NATIVE_SANITIZE", "") not in ("", "0")
    key = "sanitize-dir" if sanitize else "dir"
    with _BUILD_LOCK:
        if key in _BUILD_RESULT:
            return _BUILD_RESULT[key]
        src = native_source_dir()
        result: Optional[str] = None
        if os.path.isfile(os.path.join(src, "Makefile")):
            cmd = ["make", "-C", src] + (["sanitize"] if sanitize else [])
            try:
                proc = subprocess.run(
                    cmd,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    timeout=timeout_s,
                )
                if proc.returncode == 0:
                    result = os.path.join(
                        src, "build-sanitize" if sanitize else "build")
                elif not quiet:
                    raise RuntimeError(f"native build failed:\n{proc.stdout.decode()}")
            except (OSError, subprocess.TimeoutExpired):
                if not quiet:
                    raise
        _BUILD_RESULT[key] = result
        return result


def use_build(build_dir: str) -> None:
    """Load the native pieces from ``build_dir``, the output of ``make -C
    native BUILD=<build_dir>``, instead of running ``make`` into native/build.
    Must come before anything loads a native piece in this process."""
    build_dir = os.path.abspath(build_dir)
    with _BUILD_LOCK:
        loaded = _BUILD_RESULT.get("dir", build_dir)
        if loaded != build_dir:
            raise RuntimeError(f"native pieces already loaded from {loaded}")
        _BUILD_RESULT["dir"] = build_dir


def tail_binary_path() -> Optional[str]:
    """Path to the apm_tail binary, building if needed; None if unavailable."""
    build = ensure_built()
    if build is None:
        return None
    path = os.path.join(build, "apm_tail")
    return path if os.access(path, os.X_OK) else None


_ring_lib = None


def _load_ring_lib():
    global _ring_lib
    if _ring_lib is not None:
        return _ring_lib
    build = ensure_built()
    if build is None:
        return None
    so = os.path.join(build, "libapmring.so")
    if not os.path.isfile(so):
        return None
    lib = ctypes.CDLL(so)
    lib.apmring_create.restype = ctypes.c_void_p
    lib.apmring_create.argtypes = [ctypes.c_uint64]
    lib.apmring_destroy.argtypes = [ctypes.c_void_p]
    lib.apmring_capacity.restype = ctypes.c_uint64
    lib.apmring_capacity.argtypes = [ctypes.c_void_p]
    lib.apmring_used.restype = ctypes.c_uint64
    lib.apmring_used.argtypes = [ctypes.c_void_p]
    lib.apmring_dropped.restype = ctypes.c_uint64
    lib.apmring_dropped.argtypes = [ctypes.c_void_p]
    lib.apmring_push.restype = ctypes.c_int
    lib.apmring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    lib.apmring_pop.restype = ctypes.c_int64
    lib.apmring_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
    _ring_lib = lib
    return lib


_decode_lib = None


def _load_decode_lib():
    global _decode_lib
    if _decode_lib is not None:
        return _decode_lib
    build = ensure_built()
    if build is None:
        return None
    so = os.path.join(build, "libapmdecode.so")
    if not os.path.isfile(so):
        return None
    lib = ctypes.CDLL(so)
    lib.apmdec_create.restype = ctypes.c_void_p
    lib.apmdec_create.argtypes = []
    lib.apmdec_destroy.argtypes = [ctypes.c_void_p]
    lib.apmdec_key_count.restype = ctypes.c_int32
    lib.apmdec_key_count.argtypes = [ctypes.c_void_p]
    lib.apmdec_batch.restype = ctypes.c_int64
    lib.apmdec_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.apmdec_keys.restype = ctypes.c_int64
    lib.apmdec_keys.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p, ctypes.c_uint64,
    ]
    _decode_lib = lib
    return lib


class TxDecoder:
    """Batch decoder for ``tx|...`` wire lines over libapmdecode.

    ``decode(blob)`` parses a newline-separated byte blob in one native pass
    and returns numpy arrays; (server, service) keys are interned to dense
    int32 ids in first-appearance order, monotonic for the decoder's
    lifetime (``key_count``/``keys_from`` expose the id -> key mapping).
    Numeric fields follow entries.js_parse_int semantics; records whose
    numeric fields contain non-ASCII bytes come back flagged so the caller
    re-parses them with the Python reference implementation.
    """

    def __init__(self):
        lib = _load_decode_lib()
        if lib is None:
            raise RuntimeError("native decoder unavailable (no toolchain?)")
        self._lib = lib
        self._h = lib.apmdec_create()
        if not self._h:
            raise MemoryError("apmdec_create failed")

    def decode(self, blob: bytes):
        """-> (end_ts[f8], elapsed[f8], keyid[i4], line_off[i8], line_len[i4],
        flags[u1], n_bad). Arrays are trimmed to the parsed record count."""
        import numpy as np

        if not self._h:
            raise RuntimeError("decoder closed")
        # upper bound on records: one per newline + the unterminated tail
        cap = blob.count(b"\n") + 1
        end_ts = np.empty(cap, np.float64)
        elapsed = np.empty(cap, np.float64)
        keyid = np.empty(cap, np.int32)
        line_off = np.empty(cap, np.int64)
        line_len = np.empty(cap, np.int32)
        flags = np.empty(cap, np.uint8)
        n_bad = ctypes.c_uint64(0)
        n = self._lib.apmdec_batch(
            self._h, blob, len(blob),
            end_ts.ctypes.data_as(ctypes.c_void_p),
            elapsed.ctypes.data_as(ctypes.c_void_p),
            keyid.ctypes.data_as(ctypes.c_void_p),
            line_off.ctypes.data_as(ctypes.c_void_p),
            line_len.ctypes.data_as(ctypes.c_void_p),
            flags.ctypes.data_as(ctypes.c_void_p),
            cap, ctypes.byref(n_bad),
        )
        n = int(n)
        return (end_ts[:n], elapsed[:n], keyid[:n], line_off[:n], line_len[:n],
                flags[:n], int(n_bad.value))

    @property
    def key_count(self) -> int:
        return int(self._lib.apmdec_key_count(self._h)) if self._h else 0

    def keys_from(self, start: int):
        """[(server, service), ...] for interned ids >= start, in id order."""
        if not self._h:
            return []
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = int(self._lib.apmdec_keys(self._h, start, buf, cap))
            if n >= 0:
                raw = buf.raw[:n]
                break
            cap = -n
        out = []
        for rec in raw.split(b"\n"):
            if rec:
                srv, _, svc = rec.partition(b"\x00")
                out.append((srv.decode("utf-8", "replace"), svc.decode("utf-8", "replace")))
        return out

    def close(self) -> None:
        if self._h:
            self._lib.apmdec_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass


class LineRing:
    """SPSC byte-record ring over libapmring. One pushing thread, one popping
    thread; ``push`` returning False = ring full = raise backpressure."""

    def __init__(self, capacity_bytes: int = 1 << 22, *, max_record: int = 1 << 16):
        lib = _load_ring_lib()
        if lib is None:
            raise RuntimeError("native ring unavailable (no toolchain?)")
        self._lib = lib
        self._ring = lib.apmring_create(ctypes.c_uint64(capacity_bytes))
        if not self._ring:
            raise MemoryError("apmring_create failed")
        self._buf = ctypes.create_string_buffer(max_record)
        # guards every native call against close(): an interval-stats timer
        # or an in-flight broker delivery can overlap shutdown, and apmring_*
        # dereference the handle blindly. The producer and consumer do
        # contend on this mutex per record, but an uncontended/lightly
        # contended futex (~tens of ns) is noise next to the ctypes call
        # itself (~1 us) — measured intake with the locked hot path is
        # ~236k lines/s, unchanged from the lock-free version.
        self._close_lock = threading.Lock()

    def push(self, data: bytes) -> bool:
        with self._close_lock:
            if not self._ring:
                return False
            return bool(self._lib.apmring_push(self._ring, data, len(data)))

    def pop(self) -> Optional[bytes]:
        """One record, or None when empty. The pop-side buffer grows to fit
        oversized records (SPSC: only the popping thread touches it)."""
        with self._close_lock:
            if not self._ring:
                return None
            n = self._lib.apmring_pop(self._ring, self._buf, len(self._buf))
            if n == 0:
                return None
            if n < 0:  # record larger than our buffer: grow and retry
                self._buf = ctypes.create_string_buffer(int(-n))
                n = self._lib.apmring_pop(self._ring, self._buf, len(self._buf))
                if n <= 0:
                    return None
            return self._buf.raw[:n]

    def _stat(self, fn) -> int:
        with self._close_lock:
            if not self._ring:
                return 0
            return int(fn(self._ring))

    @property
    def used_bytes(self) -> int:
        return self._stat(self._lib.apmring_used)

    @property
    def dropped(self) -> int:
        return self._stat(self._lib.apmring_dropped)

    @property
    def capacity(self) -> int:
        return self._stat(self._lib.apmring_capacity)

    def close(self) -> None:
        with self._close_lock:
            if self._ring:
                self._lib.apmring_destroy(self._ring)
                self._ring = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------------------- parser

_parser_lib = None


def _load_parser_lib():
    global _parser_lib
    if _parser_lib is not None:
        return _parser_lib
    build = ensure_built()
    if build is None:
        return None
    so = os.path.join(build, "libapmparser.so")
    if not os.path.isfile(so):
        return None
    lib = ctypes.CDLL(so)
    lib.apmpar_create.restype = ctypes.c_void_p
    lib.apmpar_create.argtypes = [ctypes.c_double, ctypes.c_double, ctypes.c_double]
    lib.apmpar_destroy.argtypes = [ctypes.c_void_p]
    lib.apmpar_stats.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
    lib.apmpar_sweep.argtypes = [ctypes.c_void_p, ctypes.c_double]
    lib.apmpar_clear.argtypes = [ctypes.c_void_p]
    lib.apmpar_park.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_char_p,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_char_p, ctypes.c_int32,
        ctypes.c_double,
    ]
    lib.apmpar_take.restype = ctypes.c_int32
    lib.apmpar_take.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_char_p,
        ctypes.c_int32, ctypes.c_double, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.apmpar_pool.restype = ctypes.c_void_p
    lib.apmpar_pool.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
    lib.apmpar_peek.restype = ctypes.c_int64
    lib.apmpar_peek.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_double,
    ]
    lib.apmpar_drain_expired.restype = ctypes.c_int64
    lib.apmpar_drain_expired.argtypes = [ctypes.c_void_p]
    lib.apmpar_expired_pending.restype = ctypes.c_uint64
    lib.apmpar_expired_pending.argtypes = [ctypes.c_void_p]
    lib.apmpar_chunk.restype = ctypes.c_int64
    lib.apmpar_chunk.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_double, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.apmpar_soap_get.restype = ctypes.c_int32
    lib.apmpar_soap_get.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.apmpar_soap_set.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p, ctypes.c_int32,
    ]
    lib.apmpar_soap_arm.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.apmpar_soap_close.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    try:
        lib.apmfrm_pack.restype = ctypes.c_int64
        lib.apmfrm_pack.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
        ]
    except AttributeError:
        # stale pre-frame .so: the codec's Python encoder takes over
        pass
    _parser_lib = lib
    return lib


def have_native_parser() -> bool:
    """True when libapmparser built/loaded (toolchain present)."""
    return _load_parser_lib() is not None


def frames_pack_native(lines_b):
    """Pack line bytes into one APF1 frame batch via the native scanner
    (apmfrm_pack). Returns a bytearray whose exotic records still carry
    NaN numerics — transport/frames.py patches those with the full
    js_parse_int semantics — or None when the library is unavailable, the
    symbol is stale, or the native record count disagrees with the input
    (embedded newline) and the Python encoder must take over."""
    lib = _load_parser_lib()
    if lib is None or not hasattr(lib, "apmfrm_pack"):
        return None
    blob = b"\n".join(lines_b)
    cap = 16 + 32 * len(lines_b) + len(blob) + 1
    out = ctypes.create_string_buffer(cap)
    ret = lib.apmfrm_pack(blob, len(blob), out, cap)
    if ret <= 0 or ret > cap:
        return None
    raw = bytearray(out.raw[:ret])
    (nrec,) = struct.unpack_from("<I", raw, 4)
    if nrec != len(lines_b):
        return None
    return raw


def _parser_event_dtype():
    """numpy mirror of ApmEvent (native/parser.cpp). Spans with off >= 0
    index the chunk buffer; off < 0 index the returned pool at (-off - 1);
    len < 0 means the field is absent."""
    import numpy as np

    return np.dtype([
        ("line_off", np.int64), ("line_len", np.int32),
        ("cls", np.int32), ("flags", np.int32),
        ("logid_off", np.int32), ("logid_len", np.int32),
        ("ts_off", np.int32), ("ts_len", np.int32),
        ("svc_off", np.int32), ("svc_len", np.int32),
        ("ela_off", np.int32), ("ela_len", np.int32),
        ("jts_off", np.int32), ("jts_len", np.int32),
        ("jserver", np.int32),
        ("baf_off", np.int32), ("baf_len", np.int32),
        ("bits", np.int32),
        ("_pad", np.int32),  # C tail padding made explicit (sizeof == 80)
    ], align=False)


class ParserEngineNative:
    """Ingest fast path over libapmparser: batched marker pre-filter +
    field extraction + the (logId, service) TTL correlation map.

    One instance backs one TransactionParser. ``chunk()`` processes a
    newline-separated byte blob for one file and returns the event array;
    ``park``/``take``/``peek`` are the per-line shims that let the Python
    reference handler (RAW-line fallback, read_line API, tests) operate on
    the SAME correlation map. All entry points take ``now`` from the
    parser's injectable clock — TTL semantics replicate ingest/ttlcache.py.
    """

    # class constants mirrored from parser.cpp
    CLS_RAW = 0
    CLS_EJB_ENTRY = 1
    CLS_EJB_EXIT = 2
    CLS_CT_ENTRY = 3
    CLS_CT_EXIT = 4
    CLS_SOAP_ACCT = 12
    CLS_SOAP_ALT_VALUE = 14
    CLS_ACCT_SAVE_BAF = 21
    CLS_AUDIT_STOP = 22
    CLS_AUDIT_LOG = 23
    FL_JOIN_FOUND = 1
    FL_BAF = 2
    FL_LOGID_EMPTY = 4
    FL_JOIN_NOKEY = 8
    FL_INSERT_DB = 16
    LOG_MISSING_CTX = 1
    LOG_UNRESOLVED = 2
    LOG_NO_START = 3
    LOG_NO_STOP = 4
    LOG_DATA_INDEX = 5

    def __init__(self, ttl_s: float, sweep_interval_s: float, now: float):
        lib = _load_parser_lib()
        if lib is None:
            raise RuntimeError("native parser unavailable (no toolchain?)")
        self._lib = lib
        self._h = lib.apmpar_create(
            ctypes.c_double(ttl_s), ctypes.c_double(sweep_interval_s),
            ctypes.c_double(now),
        )
        if not self._h:
            raise MemoryError("apmpar_create failed")
        self.dtype = _parser_event_dtype()

    def _pool_bytes(self) -> bytes:
        n = ctypes.c_uint64(0)
        ptr = self._lib.apmpar_pool(self._h, ctypes.byref(n))
        if not n.value:
            return b""
        return ctypes.string_at(ptr, n.value)

    def chunk(self, data: bytes, kind: int, server_id: int, file_id: int,
              now: float):
        """-> (events structured array, pool bytes, counts tuple). counts =
        (lines, prefilter_rejected, parked, events, pool_bytes, consumed).
        ``consumed < len(data)`` means the scan stopped at a RAW barrier:
        process the events, then call again on ``data[consumed:]``."""
        import numpy as np

        cap = data.count(b"\n") + 1
        ev = np.zeros(cap, self.dtype)
        counts = (ctypes.c_uint64 * 6)()
        n = self._lib.apmpar_chunk(
            self._h, data, len(data), kind, server_id, file_id,
            ctypes.c_double(now),
            ev.ctypes.data_as(ctypes.c_void_p), cap, counts,
        )
        if n < 0:  # structurally impossible (cap >= line count); never retry
            raise RuntimeError("apmpar_chunk event overflow")
        # snapshot the pool NOW: the next native call on this handle
        # invalidates it
        return ev[: int(n)], self._pool_bytes(), tuple(int(c) for c in counts)

    # -- soap context shims (shared state for the per-line reference path) --
    def soap_get(self, file_id: int):
        """(log_id bytes, pull flag) of the open context, or None."""
        rc = self._lib.apmpar_soap_get(self._h, file_id)
        if rc < 0:
            return None
        return self._pool_bytes(), rc == 1

    def soap_set(self, file_id: int, log_id: bytes) -> None:
        self._lib.apmpar_soap_set(self._h, file_id, log_id, len(log_id))

    def soap_arm(self, file_id: int) -> None:
        self._lib.apmpar_soap_arm(self._h, file_id)

    def soap_close(self, file_id: int) -> None:
        self._lib.apmpar_soap_close(self._h, file_id)

    def park(self, log_id: bytes, service: bytes, server_id: int,
             start_ts: bytes, now: float) -> None:
        self._lib.apmpar_park(
            self._h, log_id, len(log_id), service, len(service), server_id,
            start_ts, len(start_ts), ctypes.c_double(now),
        )

    def take(self, log_id: bytes, service: bytes, now: float):
        """-> None (no key), () (key but no service), or (server_id,
        start_ts bytes) when found+popped — mirroring _join_exit's three
        cases."""
        srv = ctypes.c_int32(-1)
        ts_off = ctypes.c_int32(0)
        ts_len = ctypes.c_int32(0)
        rc = self._lib.apmpar_take(
            self._h, log_id, len(log_id), service, len(service),
            ctypes.c_double(now), ctypes.byref(srv), ctypes.byref(ts_off),
            ctypes.byref(ts_len),
        )
        if rc == 0:
            return None
        if rc == 1:
            return ()
        pool = self._pool_bytes()
        off = -int(ts_off.value) - 1
        return int(srv.value), pool[off: off + int(ts_len.value)]

    def peek(self, log_id: bytes, now: float):
        """TTLCache.get parity view: None on miss (counted), else the
        live {service: (server_id, start_ts)} map (hit counted)."""
        n = self._lib.apmpar_peek(self._h, log_id, len(log_id),
                                  ctypes.c_double(now))
        if n < 0:
            return None
        out = {}
        for rec in self._pool_bytes().split(b"\x1e"):
            if rec:
                svc, srv, ts = rec.split(b"\x1f")
                out[svc] = (int(srv), ts)
        return out

    def sweep(self, now: float) -> None:
        self._lib.apmpar_sweep(self._h, ctypes.c_double(now))

    def clear(self) -> None:
        self._lib.apmpar_clear(self._h)

    def stats(self):
        out = (ctypes.c_uint64 * 3)()
        self._lib.apmpar_stats(self._h, out)
        return int(out[0]), int(out[1]), int(out[2])  # keys, hits, misses

    def expired_pending(self) -> int:
        return int(self._lib.apmpar_expired_pending(self._h))

    def drain_expired(self):
        """[(log_id bytes, service bytes), ...] expired since last drain."""
        self._lib.apmpar_drain_expired(self._h)
        out = []
        for rec in self._pool_bytes().split(b"\x1e"):
            if rec:
                log_id, _, svc = rec.partition(b"\x1f")
                out.append((log_id, svc))
        return out

    def close(self) -> None:
        if self._h:
            self._lib.apmpar_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------- percentile

_pct_lib = None


def _load_percentile_lib():
    global _pct_lib
    if _pct_lib is not None:
        return _pct_lib
    build = ensure_built()
    if build is None:
        return None
    so = os.path.join(build, "libapmpercentile.so")
    if not os.path.isfile(so):
        return None
    lib = ctypes.CDLL(so)
    lib.apm_window_percentiles.restype = ctypes.c_int
    lib.apm_window_percentiles.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
    ]
    lib.apm_window_percentiles_counts.restype = ctypes.c_int
    lib.apm_window_percentiles_counts.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.c_void_p,
    ]
    _pct_lib = lib
    return lib


def have_native_percentiles() -> bool:
    """True when libapmpercentile built/loaded (toolchain present)."""
    return _load_percentile_lib() is not None


def window_percentiles_native(samples, mask, ps, counts=None):
    """Exact reference percentiles over the window reservoir, selected with
    std::nth_element per row — the CPU-fallback fast path for the staged
    executor's percentile stage (native/percentile.cpp; exact-parity with
    ops/stats.py topk/sort in the no-overflow regime, fuzz-tested).

    samples: [S, NB, CAP] float32 C-contiguous numpy (NaN = empty slot);
    mask: [NB] bool window-slot selector; ps: iterable of int percentiles;
    counts (optional): [S, NB] int32 filled-prefix lengths (the engine's
    nsamples panel) — lets the kernel gather only each bucket's live
    prefix instead of NaN-scanning all CAP slots (the dominant tick cost
    at sparse occupancy; results identical, fuzz-tested).
    Returns [S, len(ps)] float32 (NaN where a row's window is empty).
    Raises RuntimeError when the library is unavailable or rejects the call.
    """
    import numpy as np

    lib = _load_percentile_lib()
    if lib is None:
        raise RuntimeError("libapmpercentile unavailable (no native toolchain?)")
    samples = np.ascontiguousarray(samples, dtype=np.float32)
    S, NB, CAP = samples.shape
    mask_u8 = np.ascontiguousarray(np.asarray(mask, bool), dtype=np.uint8)
    if mask_u8.shape != (NB,):
        raise ValueError(f"mask shape {mask_u8.shape} != ({NB},)")
    ps_arr = np.ascontiguousarray(list(ps), dtype=np.int32)
    out = np.empty((S, len(ps_arr)), np.float32)
    if counts is None:
        rc = lib.apm_window_percentiles(
            samples.ctypes.data, S, NB, CAP,
            mask_u8.ctypes.data, ps_arr.ctypes.data, len(ps_arr), out.ctypes.data,
        )
    else:
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        if counts.shape != (S, NB):
            raise ValueError(f"counts shape {counts.shape} != ({S}, {NB})")
        rc = lib.apm_window_percentiles_counts(
            samples.ctypes.data, S, NB, CAP,
            mask_u8.ctypes.data, counts.ctypes.data,
            ps_arr.ctypes.data, len(ps_arr), out.ctypes.data,
        )
    if rc != 0:
        raise RuntimeError(f"apm_window_percentiles rc={rc}")
    return out


# ------------------------------------------------------------------ rebuild

_rebuild_lib = None


def _load_rebuild_lib():
    global _rebuild_lib
    if _rebuild_lib is not None:
        return _rebuild_lib
    build = ensure_built()
    if build is None:
        return None
    so = os.path.join(build, "libapmrebuild.so")
    if not os.path.isfile(so):
        return None
    lib = ctypes.CDLL(so)
    lib.apm_rebuild_window_aggs.restype = ctypes.c_int
    lib.apm_rebuild_window_aggs.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    _rebuild_lib = lib
    return lib


def have_native_rebuild() -> bool:
    """True when libapmrebuild built/loaded (toolchain present)."""
    return _load_rebuild_lib() is not None


def window_aggs_native(ring_chunk, anchor, last_slot: int):
    """Streaming anchored window moments over a [R, 3, L] ring chunk — the
    native partial producer of the staggered sliding-aggregate rebuild
    (native/rebuild.cpp; double accumulators, so strictly tighter than the
    f32 XLA reduce it substitutes on the CPU path). Merge-back happens in
    ops/zscore.py merge_agg_slice, shared with the XLA producer.

    ring_chunk: [R, 3, L] C-contiguous numpy, float32 or bfloat16 exposed as
    uint16 (ml_dtypes bfloat16 views also accepted); anchor: [R, 3] float32;
    last_slot: the (pos - 1) mod L ring slot of the most recent push.
    Returns (cnt i32, vsum f32, vsumsq f32, vmin f32, vmax f32, last_push
    f32), each [R, 3]. Raises RuntimeError when the library is unavailable.
    """
    import numpy as np

    lib = _load_rebuild_lib()
    if lib is None:
        raise RuntimeError("libapmrebuild unavailable (no native toolchain?)")
    ring_chunk = np.ascontiguousarray(ring_chunk)
    if ring_chunk.dtype == np.float32:
        is_bf16 = 0
    elif ring_chunk.dtype.itemsize == 2:  # bfloat16 (ml_dtypes) or uint16 bits
        is_bf16 = 1
    else:
        raise ValueError(f"unsupported ring dtype {ring_chunk.dtype}")
    R, M, L = ring_chunk.shape
    if M != 3:
        raise ValueError(f"expected metric axis 3, got {M}")
    anchor = np.ascontiguousarray(anchor, np.float32)
    if anchor.shape != (R, 3):
        raise ValueError(f"anchor shape {anchor.shape} != ({R}, 3)")
    cnt = np.empty((R, 3), np.int32)
    vsum = np.empty((R, 3), np.float32)
    vsumsq = np.empty((R, 3), np.float32)
    vmin = np.empty((R, 3), np.float32)
    vmax = np.empty((R, 3), np.float32)
    last_push = np.empty((R, 3), np.float32)
    rc = lib.apm_rebuild_window_aggs(
        ring_chunk.ctypes.data, is_bf16, R, L, int(last_slot),
        anchor.ctypes.data, cnt.ctypes.data, vsum.ctypes.data,
        vsumsq.ctypes.data, vmin.ctypes.data, vmax.ctypes.data,
        last_push.ctypes.data,
    )
    if rc != 0:
        raise RuntimeError(f"apm_rebuild_window_aggs rc={rc}")
    return cnt, vsum, vsumsq, vmin, vmax, last_push
