"""Incremental delta checkpoints: the crash-consistent chain store.

The at-least-once epoch cycle (runtime/worker.py) used to pay a full-state
npz snapshot per commit — state-size-proportional, which is why the
`bench_rolling details.delivery` gap sat at −35% and why sub-second epochs
at 8192-row shapes were impossible. This module makes the epoch commit
*ingest-rate*-proportional: a chain is

    base snapshot  +  ordered delta segments  (+ MANIFEST pointing at the base)

where each delta carries only what changed since the previous commit (the
driver's dirty-cell / tick capture, pipeline.py `save_resume_delta`), and a
periodic compaction rewrites the base off the hot path. Recovery replays
``base + deltas`` back into the exact full-snapshot ``data`` dict the npz
loader installs, so a chain restore is bit-identical to a full-snapshot
restore of the same state (asserted by tests/test_delta_chain.py and the
kill−9 chaos harness).

Durability model (the journal + alive-sentinel idiom of obs/flight.py,
promoted to state checkpoints):

- a delta segment is written to a ``.tmp`` name, optionally fsynced, then
  ``os.replace``d into ``delta-<epoch>.seg`` — the RENAME is the commit.
  A crash at any byte before the rename leaves only an ignorable tmp file;
  a crash after it leaves a committed epoch (whose messages, not yet acked,
  are redelivered and absorbed by the dedup window inside that segment).
- every segment carries CRC32s over header and payload plus a random
  ``uid`` and its predecessor's ``prev_uid``. Recovery walks the chain from
  the base and stops at the first missing/invalid/foreign segment — a torn
  tail, a bad length, or a *stale duplicate tail* (a leftover same-epoch
  segment from a pre-crash incarnation whose predecessor was itself
  replaced) can never be replayed past a committed boundary.
- compaction writes ``base-<epoch>.npz`` (tmp + fsync + rename), then swaps
  MANIFEST (tmp + rename), then GCs. The PREVIOUS base and its deltas are
  kept until the *next* compaction, so a base write torn by a crash — or a
  base that later turns out unreadable — falls back one compaction
  generation, exactly like the orbax keep=2 retention in
  parallel/checkpoint.py. Appends continue concurrently during compaction:
  segments are standalone files valid under either base.

Hostile-storage fault injection (the chaos tier): ``APM_CHAOS_FS`` installs
a deterministic fault plan into the write path — ENOSPC/EIO after N
segment writes (leaving a torn tmp, like a real full disk), or SIGKILL of
the process at a named compaction point. Production runs never read the
env var beyond one cached check. See :class:`StorageFaultPlan`.
"""

from __future__ import annotations

import io
import json
import os
import re
import struct
import threading
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

_MAGIC = b"APMDCSG1"
_FOOTER = b"APMDCEND"
_MANIFEST = "MANIFEST.json"
_SEG_RE = re.compile(r"^delta-(\d{12})\.seg$")
_BASE_RE = re.compile(r"^base-(\d{12})\.npz$")


class CheckpointWriteError(Exception):
    """A checkpoint write failed (ENOSPC/EIO/permission/...). The caller owns
    the retry/backoff/degradation policy (runtime/worker.py); the chain's
    on-disk state is still a committed epoch boundary."""


class InvalidSegment(Exception):
    """Segment failed validation (torn, truncated, CRC, foreign chain)."""


# ---------------------------------------------------------------------------
# Hostile-storage fault injection (testing seam, APM_CHAOS_FS)
# ---------------------------------------------------------------------------


class StorageFaultPlan:
    """Deterministic storage-fault plan parsed from ``APM_CHAOS_FS``.

    Grammar (';'-separated clauses):

    - ``enospc:after=N[,count=M]`` — segment writes N+1..N+M fail with
      ENOSPC *after* writing partial bytes (a torn tmp file, like a real
      full disk). ``eio:`` is the same with EIO.
    - ``kill:compact=pre_base|pre_manifest`` — SIGKILL this process at the
      named compaction point (before the new base is published / base
      published but MANIFEST not yet swapped) — the two nastiest
      crash-during-compaction windows, made deterministic.

    The plan is process-local state seeded once from the env; the chaos
    harness passes the env var to its worker subprocess.
    """

    def __init__(self, spec: str):
        self.seg_writes = 0  # guarded-by: _lock
        self.fail_after: Optional[int] = None
        self.fail_count = 0
        self.fail_errno = 28  # ENOSPC
        self.kill_at: Optional[str] = None
        self._lock = threading.Lock()
        for clause in spec.split(";"):
            clause = clause.strip()
            if not clause:
                continue
            kind, _, rest = clause.partition(":")
            if kind in ("enospc", "eio"):
                opts = dict(p.split("=", 1) for p in rest.split(",") if "=" in p)
                self.fail_after = int(opts.get("after", 0))
                self.fail_count = int(opts.get("count", 1))
                self.fail_errno = 28 if kind == "enospc" else 5
            elif kind == "kill":
                opts = dict(p.split("=", 1) for p in rest.split(",") if "=" in p)
                self.kill_at = opts.get("compact", "pre_manifest")
            else:
                raise ValueError(f"unknown APM_CHAOS_FS clause: {clause!r}")

    def on_segment_write(self, fh, blob: bytes) -> None:
        """Called with the open tmp file BEFORE the real write; may write a
        torn prefix and raise OSError to simulate a full/broken disk."""
        if self.fail_after is None:
            return
        with self._lock:
            self.seg_writes += 1
            n = self.seg_writes
        if self.fail_after < n <= self.fail_after + self.fail_count:
            fh.write(blob[: max(1, len(blob) // 2)])  # torn partial write
            fh.flush()
            raise OSError(self.fail_errno, "injected storage fault (APM_CHAOS_FS)")

    def on_compact_point(self, point: str) -> None:
        if self.kill_at == point:
            import signal

            os.kill(os.getpid(), signal.SIGKILL)


_fault_plan: Optional[StorageFaultPlan] = None
_fault_checked = False


def _faults() -> Optional[StorageFaultPlan]:
    global _fault_plan, _fault_checked
    if not _fault_checked:
        _fault_checked = True
        spec = os.environ.get("APM_CHAOS_FS")
        if spec:
            _fault_plan = StorageFaultPlan(spec)
    return _fault_plan


def install_fault_plan(plan: Optional[StorageFaultPlan]) -> None:
    """Test hook: install (or clear) a fault plan without the env var."""
    global _fault_plan, _fault_checked
    _fault_plan = plan
    _fault_checked = True


# ---------------------------------------------------------------------------
# Segment encode / decode
# ---------------------------------------------------------------------------


def _encode_segment(
    epoch: int, chain_id: str, uid: str, prev_uid: str,
    arrays: Dict[str, np.ndarray], meta: dict,
) -> bytes:
    """One delta segment as bytes: magic | header_len | header_crc | header
    JSON | raw array payload | payload_crc | footer magic. Every array is
    C-contiguous raw bytes located by (offset, nbytes) in the header — no
    pickling, no zip structure whose truncation behavior is zlib's to
    define; torn-read detection is OURS (CRC + bounds + footer)."""
    entries = []
    payload = io.BytesIO()
    for name in sorted(arrays):
        # np.asarray, NOT ascontiguousarray: the latter promotes 0-d scalars
        # (latest_bucket, ring cursors) to shape (1,); tobytes() below copies
        # in C order regardless of contiguity
        arr = np.asarray(arrays[name])
        if arr.dtype == object:
            raise TypeError(f"object arrays not allowed in delta segments: {name}")
        off = payload.tell()
        blob = arr.tobytes()
        payload.write(blob)
        entries.append(
            {"k": name, "dt": arr.dtype.str, "sh": list(arr.shape),
             "off": off, "n": len(blob)}
        )
    payload_b = payload.getvalue()
    header = {
        "epoch": int(epoch),
        "chain": chain_id,
        "uid": uid,
        "prev_uid": prev_uid,
        "arrays": entries,
        "meta": meta,
    }
    header_b = json.dumps(header, separators=(",", ":")).encode("utf-8")
    out = io.BytesIO()
    out.write(_MAGIC)
    out.write(struct.pack("<II", len(header_b), zlib.crc32(header_b) & 0xFFFFFFFF))
    out.write(header_b)
    out.write(payload_b)
    out.write(struct.pack("<I", zlib.crc32(payload_b) & 0xFFFFFFFF))
    out.write(_FOOTER)
    return out.getvalue()


def _decode_segment(blob: bytes) -> Tuple[dict, Dict[str, np.ndarray]]:
    """Parse + validate one segment; raises :class:`InvalidSegment` on any
    torn/truncated/corrupt/foreign shape (the fixture matrix in
    tests/test_delta_chain.py drives each branch)."""
    fixed = len(_MAGIC) + 8
    if len(blob) < fixed + len(_FOOTER) + 4:
        raise InvalidSegment("truncated: shorter than fixed framing")
    if blob[: len(_MAGIC)] != _MAGIC:
        raise InvalidSegment("bad magic")
    header_len, header_crc = struct.unpack_from("<II", blob, len(_MAGIC))
    if header_len <= 0 or fixed + header_len + 4 + len(_FOOTER) > len(blob):
        raise InvalidSegment("bad header length")
    header_b = blob[fixed : fixed + header_len]
    if zlib.crc32(header_b) & 0xFFFFFFFF != header_crc:
        raise InvalidSegment("header CRC mismatch")
    try:
        header = json.loads(header_b.decode("utf-8"))
    except Exception as e:
        raise InvalidSegment(f"header JSON: {e!r}")
    if blob[-len(_FOOTER):] != _FOOTER:
        raise InvalidSegment("missing footer (torn tail)")
    payload = blob[fixed + header_len : -(len(_FOOTER) + 4)]
    (payload_crc,) = struct.unpack_from("<I", blob, len(blob) - len(_FOOTER) - 4)
    if zlib.crc32(payload) & 0xFFFFFFFF != payload_crc:
        raise InvalidSegment("payload CRC mismatch")
    arrays: Dict[str, np.ndarray] = {}
    for ent in header.get("arrays", ()):
        off, n = int(ent["off"]), int(ent["n"])
        if off < 0 or off + n > len(payload):
            raise InvalidSegment(f"array {ent.get('k')!r} out of payload bounds")
        arr = np.frombuffer(payload[off : off + n], dtype=np.dtype(ent["dt"]))
        shape = tuple(int(s) for s in ent["sh"])
        if int(np.prod(shape, dtype=np.int64)) != arr.size:
            raise InvalidSegment(f"array {ent.get('k')!r} shape/size mismatch")
        arrays[ent["k"]] = arr.reshape(shape).copy()  # own the memory
    return header, arrays


# ---------------------------------------------------------------------------
# Replay: apply one delta onto the full-snapshot `data` dict
# ---------------------------------------------------------------------------


def _grow_data(data: dict, new_capacity: int) -> None:
    """Grow every per-row array to ``new_capacity`` rows with the EXACT pad
    semantics of the live engine's growth-by-recompile (dstats/dzscore/dewma
    grow_state): counts/sums/nsamples/fill/counters/var/count/trend pad 0,
    samples/z values/ewma mean pad NaN. Bit-identical to a run that grew."""
    for key, arr in list(data.items()):
        if key in ("latest_bucket", "registry", "pending_tx", "delivery_state"):
            continue
        if arr.ndim == 0 or arr.shape[0] >= new_capacity:
            continue
        pad = new_capacity - arr.shape[0]
        widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        if key.endswith("_samples") or key == "samples" or key.endswith("_values") or key.endswith("_mean"):
            data[key] = np.pad(arr, widths, constant_values=np.nan)
        else:
            data[key] = np.pad(arr, widths)


def _advance_stats(data: dict, nb: int, tick_labels: List[int]) -> None:
    """Replay the stats-ring advance for each tick label: clear the (at most
    NB) slots the labels (latest, new] claim — the numpy mirror of
    dstats.advance_span/advance_one, including the stale-label clamp."""
    latest = int(np.asarray(data["latest_bucket"]))
    counts, sums = data["counts"], data["sums"]
    samples, nsamples = data["samples"], data["nsamples"]
    for nl in tick_labels:
        nl = max(int(nl), latest)
        k = min(nl - latest, nb)
        for j in range(k):
            slot = (nl - j) % nb
            counts[:, slot] = 0
            sums[:, slot] = 0
            nsamples[:, slot] = 0
            samples[:, slot, :] = np.nan
        latest = nl
    data["latest_bucket"] = np.asarray(np.int32(latest))


def apply_delta(data: dict, header: dict, arrays: Dict[str, np.ndarray]) -> None:
    """Mutate the full-snapshot ``data`` dict (save_resume key schema) to
    the state this delta's commit captured. Replay order matters only up to
    the clear-then-write rule: every stored value is the POST-epoch content
    of its cell/column, so tick clears replay first and captured writes land
    on top — any feed/clear interleave inside the epoch collapses to the
    same final bits (tests/test_delta_chain.py equivalence suite)."""
    meta = header["meta"]
    cap = int(meta["capacity"])
    _grow_data(data, cap)

    ticks = [int(t) for t in meta.get("ticks", ())]
    if ticks:
        _advance_stats(data, int(meta["nb"]), ticks)

    if "cell_rows" in arrays:
        rows = arrays["cell_rows"].astype(np.int64)
        slots = arrays["cell_slots"].astype(np.int64)
        data["counts"][rows, slots] = arrays["cell_counts"]
        data["sums"][rows, slots] = arrays["cell_sums"]
        data["nsamples"][rows, slots] = arrays["cell_nsamples"]
        data["samples"][rows, slots, :] = arrays["cell_samples"]

    for zk in meta.get("zchannels", ()):
        # zk: {"key": "z360", "lag": L, "pos0": p} — arrays hold either the
        # gathered pushed columns (T < L) or the full ring (T >= L rewrote
        # it). The push array may be tier-padded wider than the tick count
        # (bounded-compile capture shapes); only the first len(ticks)
        # columns are real.
        key, L = zk["key"], int(zk["lag"])
        if f"{key}_push" in arrays:
            T = min(len(ticks), arrays[f"{key}_push"].shape[-1])
            positions = [(int(zk["pos0"]) + t) % L for t in range(T)]
            data[f"{key}_values"][:, :, positions] = arrays[f"{key}_push"][:, :, :T]
        elif f"{key}_values" in arrays:
            data[f"{key}_values"] = arrays[f"{key}_values"]
        data[f"{key}_fill"] = arrays[f"{key}_fill"]
        data[f"{key}_pos"] = arrays[f"{key}_pos"]
        data[f"{key}_counters"] = arrays[f"{key}_counters"]

    for ck in meta.get("echannels", ()):
        # ck: {"key": "e-1x24x360", "slots": [...]} — slot columns touched
        # by this epoch's ticks (or full arrays when every slot was)
        key = ck["key"]
        slots = [int(s) for s in ck.get("slots", ())]
        if f"{key}_mean_cols" in arrays:
            m = len(slots)  # column arrays may be tier-padded wider
            data[f"{key}_mean"][:, :, slots] = arrays[f"{key}_mean_cols"][:, :, :m]
            data[f"{key}_var"][:, :, slots] = arrays[f"{key}_var_cols"][:, :, :m]
            data[f"{key}_trend"][:, :, slots] = arrays[f"{key}_trend_cols"][:, :, :m]
            data[f"{key}_count"][:, slots] = arrays[f"{key}_count_cols"][:, :m]
        else:
            for f in ("mean", "var", "trend", "count"):
                if f"{key}_{f}" in arrays:
                    data[f"{key}_{f}"] = arrays[f"{key}_{f}"]
        data[f"{key}_counters"] = arrays[f"{key}_counters"]

    new_keys = meta.get("registry_new", ())
    if new_keys:
        reg = data["registry"].tolist() if "registry" in data else []
        reg.extend(new_keys)
        data["registry"] = np.array(reg, dtype=object)

    if meta.get("pending") is not None:
        data["pending_tx"] = np.array(meta["pending"], dtype=object)

    dd = meta.get("delivery_delta")
    if dd is not None:
        # incremental dedup-window replay: the window is an append-right /
        # evict-left FIFO, so final = (old + added)[evicted:], and epoch /
        # deduped_total replace wholesale — rate-proportional persistence of
        # the same commit unit the full snapshot carries in delivery_state
        try:
            old = (
                json.loads(data["delivery_state"].item())
                if "delivery_state" in data else {}
            )
        except Exception:
            old = {}
        for qname, rec in dd.items():
            prev = old.get(qname, {})
            window = list(prev.get("dedup", []))
            window.extend(rec.get("added", []))
            evicted = int(rec.get("evicted", 0))
            if evicted:
                window = window[evicted:]
            old[qname] = {
                "epoch": rec.get("epoch", prev.get("epoch", 0)),
                "dedup": window,
                "deduped_total": rec.get(
                    "deduped_total", prev.get("deduped_total", 0)
                ),
            }
        data["delivery_state"] = np.array(json.dumps(old), dtype=object)


# ---------------------------------------------------------------------------
# The chain store
# ---------------------------------------------------------------------------


class RecoveredChain:
    """Result of :func:`DeltaChain.load`: the replayed full-snapshot data
    dict plus the chain position the writer continues from."""

    def __init__(self, data: Optional[dict], epoch: int, chain_id: str,
                 tail_uid: str, base_epoch: int, dropped: List[str], bytes_read: int = 0):
        self.data = data
        self.epoch = epoch  # last committed epoch the chain recovers to
        self.chain_id = chain_id
        self.tail_uid = tail_uid
        self.base_epoch = base_epoch
        self.dropped = dropped  # invalid/foreign tail files (diagnostics)
        self.bytes_read = bytes_read  # the base's and the replayed segments' bytes


class DeltaChain:
    """Writer + reader for one checkpoint chain directory.

    Thread model: ``append``/``compact``/``gc`` share ``_lock``; compaction
    usually runs on the caller's background thread (``compact_async``) while
    the epoch timer keeps appending — the on-disk protocol is safe for that
    (segments are standalone; MANIFEST swap is atomic), the lock only
    serializes the in-process bookkeeping.
    """

    def __init__(self, directory: str, *, fsync: bool = True, logger=None):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.fsync = bool(fsync)
        self.logger = logger
        self._lock = threading.Lock()
        self._chain_id: Optional[str] = None  # guarded-by: _lock
        self._tail_epoch = 0  # guarded-by: _lock
        self._tail_uid = ""  # guarded-by: _lock
        self._base_epoch = 0  # guarded-by: _lock
        self._compact_thread: Optional[threading.Thread] = None  # guarded-by: _lock
        self.last_delta_bytes = 0  # guarded-by: _lock (telemetry)
        self.compactions = 0  # guarded-by: _lock (telemetry)

    # -- paths ---------------------------------------------------------------
    def _seg_path(self, epoch: int) -> str:
        return os.path.join(self.directory, f"delta-{epoch:012d}.seg")

    def _base_path(self, epoch: int) -> str:
        return os.path.join(self.directory, f"base-{epoch:012d}.npz")

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.directory, _MANIFEST)

    @property
    def tail_epoch(self) -> int:
        with self._lock:
            return self._tail_epoch

    @property
    def initialized(self) -> bool:
        with self._lock:
            return self._chain_id is not None

    def manifest_record(self) -> dict:
        """The chain-position facts a foreign checkpoint (e.g. the sharded
        orbax meta, parallel/checkpoint.py) records so a restore can
        continue THIS chain: id, base, tail epoch and the tail uid the next
        delta must link from."""
        with self._lock:
            return {
                "chain": self._chain_id,
                "dir": self.directory,
                "base_epoch": self._base_epoch,
                "tail_epoch": self._tail_epoch,
                "tail_uid": self._tail_uid,
            }

    # -- io helpers ----------------------------------------------------------
    def _fsync_dir(self) -> None:
        if not self.fsync:
            return
        try:
            fd = os.open(self.directory, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        except OSError:
            pass  # platform without dir fsync: rename atomicity still holds

    def _write_atomic(self, path: str, blob: bytes, *, seg_faults: bool = False) -> None:
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "wb") as fh:
                plan = _faults()
                if plan is not None and seg_faults:
                    plan.on_segment_write(fh, blob)
                fh.write(blob)
                fh.flush()
                if self.fsync:
                    os.fsync(fh.fileno())
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self._fsync_dir()

    # -- chain lifecycle -----------------------------------------------------
    def initialize(self, base_arrays: Dict[str, np.ndarray], epoch: int = 0) -> None:
        """Create a brand-new chain: base at ``epoch``, fresh chain id, swap
        MANIFEST. Raises CheckpointWriteError on storage failure."""
        chain_id = os.urandom(8).hex()
        uid = os.urandom(8).hex()
        try:
            self._write_base(epoch, chain_id, uid, base_arrays)
            self._write_manifest(chain_id, epoch, uid)
        except OSError as e:
            raise CheckpointWriteError(f"chain initialize failed: {e}") from e
        with self._lock:
            self._chain_id = chain_id
            self._base_epoch = epoch
            self._tail_epoch = epoch
            self._tail_uid = uid

    def _write_base(self, epoch: int, chain_id: str, uid: str,
                    arrays: Dict[str, np.ndarray]) -> None:
        buf = io.BytesIO()
        chain_meta = np.array(
            json.dumps({"chain": chain_id, "epoch": epoch, "uid": uid}),
            dtype=object,
        )
        np.savez_compressed(buf, chain_meta=chain_meta, **arrays)
        self._write_atomic(self._base_path(epoch), buf.getvalue())

    def _write_manifest(self, chain_id: str, base_epoch: int, base_uid: str) -> None:
        blob = json.dumps(
            {"format": 1, "chain": chain_id, "base_epoch": base_epoch,
             "base_uid": base_uid}
        ).encode("utf-8")
        self._write_atomic(self.manifest_path, blob)

    def load(self) -> Optional[RecoveredChain]:
        """Recover the newest committed epoch boundary: MANIFEST's base (or,
        when that base is unreadable/absent, the newest older base on disk —
        the keep-one-generation fallback), then replay the contiguous valid
        delta chain from it. Returns None when no readable chain exists —
        the caller starts fresh, never crashes (load_resume contract). The
        writer continues from the recovered tail.

        Candidate selection (hardened after protocol model checking,
        DESIGN.md §9.4): every readable base is evaluated and the chain
        recovering the HIGHEST epoch wins (ties go to the manifest base).
        A non-manifest fallback base is additionally cross-checked against
        the delta segment at its own epoch — a compaction that crashed
        before the manifest swap leaves an orphan base, and if the chain
        was later rewritten below it the orphan's content matches no
        committed state; a valid same-epoch delta with a different uid
        (or an unreadable one) exposes it. Within the single-fault storage
        contract the model proves all candidates converge, so this changes
        nothing there — it is defense in depth for multi-fault excursions,
        which degrade to the best surviving boundary instead of the first
        readable one."""
        bases = self._scan_bases()
        manifest = self._read_manifest()
        order: List[int] = []
        if manifest is not None and manifest["base_epoch"] in bases:
            order.append(manifest["base_epoch"])
        order.extend(e for e in sorted(bases, reverse=True) if e not in order)
        best: Optional[RecoveredChain] = None
        for base_epoch in order:
            authoritative = manifest is not None and base_epoch == manifest["base_epoch"]
            rec = self._try_chain(base_epoch, authoritative=authoritative)
            if rec is None:
                continue
            if best is None or rec.epoch > best.epoch:
                best = rec
        if best is None:
            return None
        with self._lock:
            self._chain_id = best.chain_id
            self._base_epoch = best.base_epoch
            self._tail_epoch = best.epoch
            self._tail_uid = best.tail_uid
        if best.dropped and self.logger:
            self.logger.warning(
                f"Checkpoint chain recovered to epoch {best.epoch}; dropped "
                f"uncommitted/invalid tail: {', '.join(best.dropped)}"
            )
        return best

    def _scan_bases(self) -> Dict[int, str]:
        out: Dict[int, str] = {}
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for n in names:
            m = _BASE_RE.match(n)
            if m:
                out[int(m.group(1))] = os.path.join(self.directory, n)
        return out

    def _read_manifest(self) -> Optional[dict]:
        try:
            with open(self.manifest_path, "r", encoding="utf-8") as fh:
                m = json.load(fh)
            return {"chain": m["chain"], "base_epoch": int(m["base_epoch"]),
                    "base_uid": m.get("base_uid", "")}
        except Exception:
            return None

    def _try_chain(self, base_epoch: int,
                   authoritative: bool = True) -> Optional[RecoveredChain]:
        path = self._base_path(base_epoch)
        try:
            with np.load(path, allow_pickle=True) as npz:
                data = {name: npz[name] for name in npz.files}
            cm = json.loads(data.pop("chain_meta").item())
            chain_id, uid = cm["chain"], cm.get("uid", "")
            nbytes = os.path.getsize(path)
        except Exception as e:
            if self.logger:
                self.logger.error(f"Checkpoint base unreadable (falling back): {path}: {e}")
            return None
        if not authoritative and base_epoch > 0:
            # stale-orphan cross-check: a fallback base must agree with the
            # delta segment that committed its epoch. An orphan base from a
            # dead compaction, stranded above a rewritten chain, carries a
            # uid no longer on the chain — reject it rather than recover a
            # state no commit ever produced. (Absent segment = the epoch's
            # delta was GC'd below a completed compaction: a legitimate
            # previous-generation base.)
            own = self._seg_path(base_epoch)
            if os.path.exists(own):
                try:
                    with open(own, "rb") as fh:
                        own_header, _ = _decode_segment(fh.read())
                    own_uid = own_header.get("uid", "")
                except (InvalidSegment, OSError):
                    own_uid = None  # unreadable delta: ambiguous, reject
                if own_uid != uid:
                    if self.logger:
                        self.logger.warning(
                            f"Checkpoint base {os.path.basename(path)} is a "
                            f"stale orphan (delta-{base_epoch:012d}.seg "
                            f"contradicts its uid); skipping"
                        )
                    return None
        epoch = base_epoch
        dropped: List[str] = []
        while True:
            seg = self._seg_path(epoch + 1)
            if not os.path.exists(seg):
                break
            try:
                with open(seg, "rb") as fh:
                    blob = fh.read()
                header, arrays = _decode_segment(blob)
                if header.get("chain") != chain_id:
                    raise InvalidSegment("foreign chain id (stale tail)")
                if header.get("prev_uid") != uid:
                    raise InvalidSegment("broken predecessor linkage (duplicate tail)")
                if int(header.get("epoch", -1)) != epoch + 1:
                    raise InvalidSegment("epoch mismatch")
                apply_delta(data, header, arrays)
            except (InvalidSegment, OSError) as e:
                dropped.append(f"{os.path.basename(seg)} ({e})")
                break
            epoch += 1
            uid = header["uid"]
            nbytes += len(blob)
        return RecoveredChain(data, epoch, chain_id, uid, base_epoch, dropped, nbytes)

    # -- the per-epoch hot path ----------------------------------------------
    def append(self, arrays: Dict[str, np.ndarray], meta: dict) -> int:
        """Commit one epoch: encode + atomically publish the next delta
        segment. Returns the committed epoch. Raises CheckpointWriteError on
        any storage failure — the tail is unchanged and the same (or a
        larger) delta can be retried."""
        with self._lock:
            if self._chain_id is None:
                raise CheckpointWriteError("chain not initialized (call initialize/load)")
            epoch = self._tail_epoch + 1
            chain_id, prev_uid = self._chain_id, self._tail_uid
        uid = os.urandom(8).hex()
        blob = _encode_segment(epoch, chain_id, uid, prev_uid, arrays, meta)
        try:
            self._write_atomic(self._seg_path(epoch), blob, seg_faults=True)
        except OSError as e:
            raise CheckpointWriteError(f"delta append failed at epoch {epoch}: {e}") from e
        with self._lock:
            self._tail_epoch = epoch
            self._tail_uid = uid
            self.last_delta_bytes = len(blob)
        return epoch

    # -- compaction (off the hot path) ----------------------------------------
    def compact(self, epoch: int, arrays: Dict[str, np.ndarray]) -> None:
        """Write a new base at ``epoch`` (a full capture of the state the
        epoch-``epoch`` commit described), swap MANIFEST, GC one generation
        back. Appends may run concurrently — segments > ``epoch`` stay valid
        under both bases. Storage failures raise CheckpointWriteError; the
        old chain remains fully intact."""
        with self._lock:
            chain_id = self._chain_id
            old_base = self._base_epoch
        if chain_id is None:
            raise CheckpointWriteError("chain not initialized")
        # the new base's uid is the uid of the delta segment that committed
        # this epoch (or the current base's for epoch == base): linkage from
        # the base to its successor segment must keep matching
        uid = self._uid_of(epoch)
        if uid is None:
            raise CheckpointWriteError(f"compaction epoch {epoch} not on the chain")
        plan = _faults()
        try:
            if plan is not None:
                plan.on_compact_point("pre_base")
            self._write_base(epoch, chain_id, uid, arrays)
            if plan is not None:
                plan.on_compact_point("pre_manifest")
            self._write_manifest(chain_id, epoch, uid)
        except OSError as e:
            raise CheckpointWriteError(f"compaction at epoch {epoch} failed: {e}") from e
        with self._lock:
            self._base_epoch = epoch
            self.compactions += 1
        # retention after the swap: the NEW base, the OLD base (one
        # generation of fallback against a new base that later proves
        # unreadable) and every delta above the old base. Deltas at/below
        # the old base are covered by it; bases older than it are not on
        # any fallback path anymore.
        self._gc(prev_base=old_base)

    def compact_async(self, epoch: int, arrays: Dict[str, np.ndarray],
                      on_error=None) -> bool:
        """Run :meth:`compact` on a background thread (the hot path only
        pays the state capture). At most one compaction in flight — returns
        False when one is already running (the cadence retries next time)."""
        with self._lock:
            if self._compact_thread is not None and self._compact_thread.is_alive():
                return False

            def _run():
                try:
                    self.compact(epoch, arrays)
                except Exception as e:
                    if self.logger:
                        self.logger.error(f"Background compaction failed: {e}")
                    if on_error is not None:
                        on_error(e)

            t = threading.Thread(target=_run, name="ckpt-compact", daemon=True)
            self._compact_thread = t
        t.start()
        return True

    def wait_compaction(self, timeout_s: float = 60.0) -> None:
        with self._lock:
            t = self._compact_thread
        if t is not None:
            t.join(timeout=timeout_s)

    def _uid_of(self, epoch: int) -> Optional[str]:
        with self._lock:
            base_epoch = self._base_epoch
        if epoch == base_epoch:
            m = self._read_manifest()
            return m.get("base_uid", "") if m else None
        seg = self._seg_path(epoch)
        try:
            with open(seg, "rb") as fh:
                header, _ = _decode_segment(fh.read())
            return header["uid"]
        except Exception:
            return None

    def _gc(self, prev_base: int) -> None:
        """Delete deltas at/below the previous base and bases older than it,
        plus orphaned tmp files. Best-effort: GC failures never fail a
        commit (worst case the directory carries extra history)."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        for n in names:
            path = os.path.join(self.directory, n)
            try:
                if n.endswith(".tmp"):
                    os.unlink(path)
                    continue
                m = _SEG_RE.match(n)
                if m and int(m.group(1)) <= prev_base:
                    os.unlink(path)
                    continue
                b = _BASE_RE.match(n)
                if b and int(b.group(1)) < prev_base:
                    os.unlink(path)
            except OSError:
                pass
