"""Communication layer for the forest algorithms: one `Comm` surface,
three bindings.

The forest code (`repro.core.forest`) is written SPMD style: every rank
computes its own view, and all cross-rank data moves through the two
collectives below.  A process may host one rank (production) or all P ranks
(the in-process simulator used by tests and benchmarks); the `Comm` object
says which global ranks are resident via `local_ranks`, and every collective
takes/returns *per-local-rank* payload lists so the same forest code runs
unchanged under either hosting:

  SimComm(P)   all P ranks in this process; collectives are list shuffles.
               This is the seed's simulator, conformed to the shared surface.
  LocalComm()  the degenerate single-rank world (P = 1, no wire anywhere).
  DistComm()   one rank per process, bound to mpi4py when available and
               initialized, otherwise to the jax.distributed coordination
               service (each payload travels through the key-value store of
               the coordinator that `jax.distributed.initialize` brings up).

Every collective also exists in a *nonblocking* form — `iallgather` /
`ialltoallv` return a `CommHandle` whose `wait()` delivers the same result
the blocking call would (the blocking calls are literally post + wait).
`SimComm` handles complete immediately, `DistComm` posts mpi4py nonblocking
point-to-point exchanges or KV-store writes and only blocks in `wait()`,
and `LatencyComm` simulates round-trip time so overlap can be measured
in-process.  Handles of one communicator must be waited in the order they
were posted, the same on every rank (the SPMD forest code does this; MPI
tag/collective matching relies on it).  A handle that polls `done() ==
True` has a free `wait()`: the data is cached and no transport round-trips
remain.

Payloads are nested tuples/lists/dicts of numpy arrays and scalars.  The
base class meters every collective *at post time*: bytes that would cross a
rank boundary are accumulated into per-phase counters
(`comm.phase("balance")`), which is how the benchmarks attribute wire
volume to Balance / Ghost / Partition and how the boundary-layer exchange
is shown to beat the allgathered-leaf-table baseline.
"""

from __future__ import annotations

import contextlib
import hashlib
import random
import struct
import time
import zlib
from typing import Callable, Sequence

import numpy as np

from . import telemetry
from .errors import CommTimeoutError, WireFormatError, WireIntegrityError

__all__ = [
    "Comm",
    "CommHandle",
    "SimComm",
    "LocalComm",
    "LatencyComm",
    "DistComm",
    "payload_nbytes",
    "encode_payload",
    "decode_payload",
    "frame_blob",
    "unframe_blob",
    "CommTimeoutError",
    "WireFormatError",
    "WireIntegrityError",
]


# ------------------------------------------------------------- byte metering
def payload_nbytes(obj) -> int:
    """Wire size of a nested payload (arrays dominate; scalars count 8)."""
    if obj is None:
        return 1
    if isinstance(obj, np.ndarray):
        return obj.nbytes
    if isinstance(obj, (bytes, bytearray)):
        return len(obj)
    if isinstance(obj, str):
        return len(obj.encode())
    if isinstance(obj, (bool, int, float, np.integer, np.floating, np.bool_)):
        return 8
    if isinstance(obj, dict):
        return sum(payload_nbytes(k) + payload_nbytes(v) for k, v in obj.items())
    if isinstance(obj, (list, tuple)):
        return sum(payload_nbytes(v) for v in obj)
    raise TypeError(f"unsupported payload type {type(obj)!r}")


# ------------------------------------------------------- wire serialization
# Self-describing tagged format for the payload types above — the ONE wire
# codec of BOTH DistComm transports.  No pickle: only data, no code.  (The
# mpi4py binding used mpi4py's pickling object collectives while the KV
# path used this codec, so the two bindings moved different bytes; the
# mpi4py path now ships exactly these buffers over MPI.BYTE point-to-point
# exchanges, and `DistComm.wire_digest()` lets tests pin the parity.)
def _enc(obj, out: list) -> None:
    if obj is None:
        out.append(b"N")
    elif isinstance(obj, (bool, np.bool_)):
        out.append(b"T" if obj else b"F")
    elif isinstance(obj, (int, np.integer)):
        v = int(obj)
        if 0 <= v < 1 << 64:
            out.append(b"u" + struct.pack("<Q", v))
        elif -(1 << 63) <= v < 1 << 63:
            out.append(b"i" + struct.pack("<q", v))
        else:  # arbitrary precision fallback
            s = str(v).encode()
            out.append(b"I" + struct.pack("<I", len(s)) + s)
    elif isinstance(obj, (float, np.floating)):
        out.append(b"f" + struct.pack("<d", float(obj)))
    elif isinstance(obj, str):
        s = obj.encode()
        out.append(b"s" + struct.pack("<I", len(s)) + s)
    elif isinstance(obj, (bytes, bytearray)):
        out.append(b"y" + struct.pack("<I", len(obj)) + bytes(obj))
    elif isinstance(obj, np.ndarray):
        assert obj.dtype.names is None, "structured dtypes are not wire types"
        dt = obj.dtype.str.encode()
        a = np.ascontiguousarray(obj)
        out.append(b"a" + struct.pack("<B", len(dt)) + dt
                   + struct.pack("<B", a.ndim)
                   + struct.pack(f"<{a.ndim}I", *a.shape)
                   + a.tobytes())
    elif isinstance(obj, (list, tuple)):
        out.append((b"l" if isinstance(obj, list) else b"t")
                   + struct.pack("<I", len(obj)))
        for v in obj:
            _enc(v, out)
    elif isinstance(obj, dict):
        out.append(b"d" + struct.pack("<I", len(obj)))
        for k, v in obj.items():
            _enc(k, out)
            _enc(v, out)
    else:
        raise TypeError(f"unsupported payload type {type(obj)!r}")


def encode_payload(obj) -> bytes:
    out: list = []
    _enc(obj, out)
    return b"".join(out)


def _need(buf: bytes, off: int, n: int, what: str) -> None:
    """Bounds check: the next `n` bytes must exist, else the buffer is
    truncated — a structured `WireFormatError`, never an IndexError or a
    short `struct.error` read."""
    if n < 0 or off + n > len(buf):
        raise WireFormatError(
            f"truncated wire payload: need {n} byte(s) for {what} at "
            f"offset {off}, have {len(buf) - off}")


def _dec(buf: bytes, off: int):
    _need(buf, off, 1, "tag")
    tag = buf[off:off + 1]
    off += 1
    if tag == b"N":
        return None, off
    if tag == b"T":
        return True, off
    if tag == b"F":
        return False, off
    if tag == b"u":
        _need(buf, off, 8, "u64")
        return struct.unpack_from("<Q", buf, off)[0], off + 8
    if tag == b"i":
        _need(buf, off, 8, "i64")
        return struct.unpack_from("<q", buf, off)[0], off + 8
    if tag == b"I":
        _need(buf, off, 4, "bigint length")
        n = struct.unpack_from("<I", buf, off)[0]
        _need(buf, off + 4, n, "bigint digits")
        try:
            v = int(buf[off + 4:off + 4 + n].decode())
        except (UnicodeDecodeError, ValueError) as e:
            raise WireFormatError(
                f"malformed bigint in wire payload at offset {off}: {e}"
            ) from e
        return v, off + 4 + n
    if tag == b"f":
        _need(buf, off, 8, "f64")
        return struct.unpack_from("<d", buf, off)[0], off + 8
    if tag == b"s":
        _need(buf, off, 4, "string length")
        n = struct.unpack_from("<I", buf, off)[0]
        _need(buf, off + 4, n, "string bytes")
        try:
            s = buf[off + 4:off + 4 + n].decode()
        except UnicodeDecodeError as e:
            raise WireFormatError(
                f"malformed utf-8 string in wire payload at offset {off}: {e}"
            ) from e
        return s, off + 4 + n
    if tag == b"y":
        _need(buf, off, 4, "bytes length")
        n = struct.unpack_from("<I", buf, off)[0]
        _need(buf, off + 4, n, "bytes body")
        return buf[off + 4:off + 4 + n], off + 4 + n
    if tag == b"a":
        _need(buf, off, 1, "dtype length")
        dl = struct.unpack_from("<B", buf, off)[0]
        off += 1
        _need(buf, off, dl, "dtype string")
        try:
            dt = np.dtype(buf[off:off + dl].decode())
        except (UnicodeDecodeError, TypeError, ValueError) as e:
            raise WireFormatError(
                f"bad array dtype in wire payload at offset {off}: {e}"
            ) from e
        if dt.hasobject:
            raise WireFormatError(
                f"object dtype {dt!r} is not a wire type (offset {off})")
        off += dl
        _need(buf, off, 1, "ndim")
        ndim = struct.unpack_from("<B", buf, off)[0]
        off += 1
        _need(buf, off, 4 * ndim, "shape")
        shape = struct.unpack_from(f"<{ndim}I", buf, off)
        off += 4 * ndim
        n = 1
        for s in shape:
            n *= int(s)
        if not ndim:
            n = 1
        nb = n * dt.itemsize
        _need(buf, off, nb, f"array body {dt.str}{tuple(shape)}")
        try:
            arr = np.frombuffer(buf[off:off + nb], dt).reshape(shape).copy()
        except (ValueError, TypeError) as e:
            raise WireFormatError(
                f"malformed array in wire payload at offset {off}: {e}"
            ) from e
        return arr, off + nb
    if tag in (b"l", b"t"):
        _need(buf, off, 4, "sequence count")
        n = struct.unpack_from("<I", buf, off)[0]
        off += 4
        # every element takes >= 1 byte, so a count beyond the remaining
        # bytes is garbage — reject before allocating or looping on it
        _need(buf, off, n, f"{n} sequence element(s)")
        items = []
        for _ in range(n):
            v, off = _dec(buf, off)
            items.append(v)
        return (items if tag == b"l" else tuple(items)), off
    if tag == b"d":
        _need(buf, off, 4, "dict count")
        n = struct.unpack_from("<I", buf, off)[0]
        off += 4
        _need(buf, off, 2 * n, f"{n} dict item(s)")
        d = {}
        for _ in range(n):
            k, off = _dec(buf, off)
            v, off = _dec(buf, off)
            try:
                d[k] = v
            except TypeError as e:  # unhashable decoded key
                raise WireFormatError(
                    f"unhashable dict key in wire payload at offset {off}: {e}"
                ) from e
        return d, off
    raise WireFormatError(f"bad wire tag {tag!r} at offset {off - 1}")


def decode_payload(buf: bytes):
    """Decode one `encode_payload` buffer.  Malformed input of ANY shape —
    truncation, trailing garbage, bad tags, bogus counts/dtypes — raises a
    structured `WireFormatError` (a ValueError subclass); it never leaks a
    bare `struct.error`, never returns silently wrong columns."""
    buf = bytes(buf)
    try:
        obj, off = _dec(buf, 0)
    except WireFormatError:
        raise
    except (struct.error, ValueError, TypeError, OverflowError,
            MemoryError, RecursionError) as e:
        raise WireFormatError(f"malformed wire payload: {e}") from e
    if off != len(buf):
        raise WireFormatError(
            f"trailing bytes in wire payload: decoded {off} of {len(buf)}")
    return obj


# ------------------------------------------------------- integrity framing
# Every blob a DistComm transport moves travels inside a 16-byte integrity
# frame: magic, u64 body length, CRC32 of the body.  `unframe_blob` turns
# corruption, truncation, and duplication into a typed `WireIntegrityError`
# instead of a downstream mis-decode — and because the smallest frame is 16
# bytes, no transport value can ever be the 1-byte blob that segfaults
# jaxlib's `blocking_key_value_get_bytes` (`encode_payload(None)` is b"N").
# Framing lives strictly BETWEEN the codec and the transport: byte meters
# and `wire_digest()` both see the unframed payload blobs, so digests stay
# comparable across bindings and with the in-process simulators.
_FRAME = struct.Struct("<4sQI")
_FRAME_MAGIC = b"RW01"


def frame_blob(blob: bytes) -> bytes:
    """Wrap a payload blob for the wire: magic + length + CRC32 header."""
    blob = bytes(blob)
    return _FRAME.pack(_FRAME_MAGIC, len(blob), zlib.crc32(blob)) + blob


def unframe_blob(buf: bytes, *, where: str = "") -> bytes:
    """Verify and strip a `frame_blob` header; raises `WireIntegrityError`
    (tagged with `where`: phase/generation/peer) on any mismatch."""
    buf = bytes(buf)
    if len(buf) < _FRAME.size:
        raise WireIntegrityError("frame shorter than header", where=where,
                                 expected=_FRAME.size, actual=len(buf))
    magic, length, crc = _FRAME.unpack_from(buf, 0)
    if magic != _FRAME_MAGIC:
        raise WireIntegrityError("bad frame magic", where=where,
                                 expected=_FRAME_MAGIC, actual=magic)
    body = buf[_FRAME.size:]
    if len(body) != length:
        raise WireIntegrityError("frame length mismatch", where=where,
                                 expected=int(length), actual=len(body))
    got = zlib.crc32(body)
    if got != crc:
        raise WireIntegrityError("frame checksum mismatch", where=where,
                                 expected=int(crc), actual=int(got))
    return body


# ------------------------------------------------------------------ handles
class CommHandle:
    """Waitable result of a nonblocking collective (`iallgather` /
    `ialltoallv`).

    `wait()` blocks until delivery and returns the collective's result —
    idempotent, later calls return the same object.  `done()` polls for
    completion without blocking and doubles as the transport's progress
    driver; once it returns True, `wait()` performs no further transport
    round-trips.  Handles must be waited in posting order, identically on
    every rank (MPI tag and collective matching rely on it); the SPMD
    forest code always does.

    Every handle is stamped by the posting `Comm` with the `phase` active
    at post time and a monotonically increasing `seq`, and — when the comm
    has a deadline (`comm.set_deadline(s)`, off by default) — a wall-clock
    deadline.  A deadlined `wait()` drives the transport's poll in an
    exponential-backoff + jitter loop and raises a structured
    `CommTimeoutError` (phase, seq, elapsed, retries, pending peers,
    liveness detail) instead of hanging; without a deadline, `wait()` is
    the exact single blocking transport call it always was.
    """

    __slots__ = ("_complete", "_poll", "_result", "_done",
                 "phase", "seq", "_deadline", "_pending", "_diagnose")

    def __init__(self, complete: Callable | None = None,
                 poll: Callable[[], bool] | None = None,
                 result=None, done: bool = False):
        self._complete = complete
        self._poll = poll
        self._result = result
        self._done = done
        self.phase = "default"
        self.seq = -1
        self._deadline = None    # absolute time.monotonic() bound, or None
        self._pending = None     # () -> [peer ranks not yet delivered]
        self._diagnose = None    # () -> detail dict for CommTimeoutError

    @classmethod
    def ready(cls, result) -> "CommHandle":
        """An already-completed handle (immediate transports, e.g. SimComm)."""
        return cls(result=result, done=True)

    def done(self) -> bool:
        """True once the collective's data is available — `wait()` will not
        block on peers' payloads and performs no transport round-trips.  A
        deferred handle whose binding supplied no poll conservatively
        reports False."""
        if self._done:
            return True
        if self._poll is not None:
            return self._poll()
        return False

    def wait(self, timeout: float | None = None):
        """Deliver the result, blocking if the exchange is still in flight.

        With a deadline (stamped at post time, or the tighter of that and
        an explicit `timeout`), completion is driven through the poll with
        exponential backoff + jitter and expiry raises `CommTimeoutError`;
        with none (the default), this is one blocking transport call."""
        if self._done:
            return self._result
        # the exposed wait on a collective in flight (immediate transports
        # never get here)
        with telemetry.span("comm.wait", phase=self.phase):
            deadline = self._deadline
            if timeout is not None:
                t = time.monotonic() + timeout
                deadline = t if deadline is None else min(deadline, t)
            if deadline is not None and self._poll is not None:
                start = time.monotonic()
                retries = 0
                delay = 0.0005
                while not self._poll():
                    now = time.monotonic()
                    if now >= deadline:
                        raise CommTimeoutError(
                            phase=self.phase, seq=self.seq,
                            elapsed_s=now - start, retries=retries,
                            pending=self._pending() if self._pending else None,
                            detail=self._diagnose() if self._diagnose else None)
                    retries += 1
                    time.sleep(min(delay, deadline - now) * (0.5 + random.random()))
                    delay = min(delay * 2.0, 0.05)
            self._result = self._complete()
            self._complete = self._poll = None
            self._done = True
            return self._result


# ----------------------------------------------------------------- the seam
class Comm:
    """Abstract communicator: rank/size plus the two forest collectives.

    `local_ranks` lists the global ranks resident in this process; every
    collective consumes a list with one payload per local rank and returns,
    per local rank, the global view (`allgather`: length-P list; `alltoallv`:
    length-P list of what each global rank sent here).  Both collectives
    exist blocking (`allgather`/`alltoallv`) and nonblocking
    (`iallgather`/`ialltoallv` -> `CommHandle`); the blocking forms are
    post + `wait()`.  Subclasses implement `_allgather` / `_alltoallv` (and
    optionally the nonblocking `_iallgather` / `_ialltoallv`, which default
    to immediate completion); the base class meters byte volume into
    per-phase counters at post time.
    """

    size: int
    rank: int            # first (usually only) local rank
    local_ranks: range
    deadline_s: float | None = None   # per-collective wait budget (opt-in)

    def __init__(self):
        self.counters: dict = {}
        self._phases: list[str] = []
        self._hseq = 0

    def set_deadline(self, seconds: float | None) -> None:
        """Give every subsequently posted collective a wall-clock wait
        budget: `wait()` past it raises `CommTimeoutError` naming the
        phase, seq, and (where the transport knows) the pending peers.
        `None` (the default) restores plain blocking waits."""
        self.deadline_s = seconds

    def _stamp(self, h: CommHandle) -> CommHandle:
        """Tag a freshly posted handle with phase/seq/deadline context."""
        self._hseq += 1
        h.seq = self._hseq
        h.phase = self._phases[-1] if self._phases else "default"
        if self.deadline_s is not None and not h._done:
            h._deadline = time.monotonic() + self.deadline_s
        return h

    # -- metering ----------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name: str):
        """Attribute subsequent traffic to `name` (nested phases stack; the
        innermost label wins — forest algorithms label their own traffic)."""
        self._phases.append(name)
        try:
            yield self
        finally:
            self._phases.pop()

    def _bucket(self) -> dict:
        name = self._phases[-1] if self._phases else "default"
        return self.counters.setdefault(
            name, {"allgather_bytes": 0, "alltoallv_bytes": 0,
                   "allgather_calls": 0, "alltoallv_calls": 0})

    def bytes_for(self, phase: str | None = None) -> int:
        """Total bytes crossing rank boundaries (one phase, or all)."""
        buckets = ([self.counters.get(phase, {})] if phase is not None
                   else list(self.counters.values()))
        return sum(b.get("allgather_bytes", 0) + b.get("alltoallv_bytes", 0)
                   for b in buckets)

    def stats(self) -> dict:
        out = {k: dict(v) for k, v in self.counters.items()}
        out["total_bytes"] = self.bytes_for()
        return out

    def reset_counters(self) -> None:
        self.counters.clear()

    # -- collectives -------------------------------------------------------
    def allgather(self, per_local: Sequence) -> list:
        """per_local[i] from local rank i -> full per-global-rank list."""
        return self.iallgather(per_local).wait()

    def alltoallv(self, send: Sequence[Sequence]) -> list:
        """send[i][q]: payload from local rank i to global rank q.
        Returns recv[i][p]: what global rank p sent to local rank i."""
        return self.ialltoallv(send).wait()

    def iallgather(self, per_local: Sequence) -> CommHandle:
        """Nonblocking `allgather`: posts the exchange, meters its bytes to
        the phase active NOW, and returns a waitable `CommHandle`."""
        assert len(per_local) == len(self.local_ranks)
        b = self._bucket()
        b["allgather_calls"] += 1
        b["allgather_bytes"] += sum(
            payload_nbytes(x) * (self.size - 1) for x in per_local)
        return self._stamp(self._iallgather(list(per_local)))

    def ialltoallv(self, send: Sequence[Sequence]) -> CommHandle:
        """Nonblocking `alltoallv`: posts, meters at post time, returns a
        `CommHandle` delivering recv[i][p] on `wait()`."""
        assert len(send) == len(self.local_ranks)
        b = self._bucket()
        b["alltoallv_calls"] += 1
        for i, g in enumerate(self.local_ranks):
            assert len(send[i]) == self.size
            b["alltoallv_bytes"] += sum(
                payload_nbytes(x) for q, x in enumerate(send[i]) if q != g)
        return self._stamp(self._ialltoallv([list(row) for row in send]))

    def barrier(self) -> None:  # pragma: no cover - trivial default
        pass

    def _allgather(self, per_local: list) -> list:
        raise NotImplementedError

    def _alltoallv(self, send: list) -> list:
        raise NotImplementedError

    # Default nonblocking forms: complete-at-post via the blocking transport
    # (correct for any binding; real transports override to defer the wait).
    def _iallgather(self, per_local: list) -> CommHandle:
        return CommHandle.ready(self._allgather(per_local))

    def _ialltoallv(self, send: list) -> CommHandle:
        return CommHandle.ready(self._alltoallv(send))


class SimComm(Comm):
    """All P ranks in this process — the tests/benchmarks simulator.

    Collectives are pure list shuffles; the byte counters still meter what
    WOULD cross rank boundaries, which is what the benchmarks record.
    """

    def __init__(self, num_ranks: int):
        super().__init__()
        self.size = num_ranks
        self.rank = 0
        self.local_ranks = range(num_ranks)

    # legacy alias (the seed called it .P everywhere)
    @property
    def P(self) -> int:
        return self.size

    def _allgather(self, per_local: list) -> list:
        return list(per_local)

    def _alltoallv(self, send: list) -> list:
        P = self.size
        return [[send[p][q] for p in range(P)] for q in range(P)]


class LocalComm(SimComm):
    """Degenerate single-rank world: every collective is the identity."""

    def __init__(self):
        super().__init__(1)


class LatencyComm(SimComm):
    """SimComm plus a simulated per-collective round-trip time.

    A collective's result is not *deliverable* until `latency_s` after it
    was posted: blocking calls (post + wait) therefore pay the full latency,
    while a nonblocking handle matures in the background and `wait()` only
    sleeps whatever the caller's compute did not already cover.  This is the
    in-process stand-in for transports dominated by round-trip time (the
    DistComm KV store's per-exchange RPCs); the overlap benchmark uses it to
    measure how much of a Balance round's communication the double-buffered
    loop actually hides.  Results are bit-identical to `SimComm` — only
    timing changes.
    """

    def __init__(self, num_ranks: int, latency_s: float = 0.0):
        super().__init__(num_ranks)
        self.latency_s = latency_s

    def _delayed(self, result) -> CommHandle:
        ready_at = time.monotonic() + self.latency_s

        def complete():
            rem = ready_at - time.monotonic()
            if rem > 0:
                time.sleep(rem)
            return result

        return CommHandle(complete, poll=lambda: time.monotonic() >= ready_at)

    def _iallgather(self, per_local: list) -> CommHandle:
        return self._delayed(self._allgather(per_local))

    def _ialltoallv(self, send: list) -> CommHandle:
        return self._delayed(self._alltoallv(send))


class DistComm(Comm):
    """One rank per process, over mpi4py or the jax.distributed coordinator.

    Binding order: an initialized mpi4py world with more than one process
    wins; otherwise `jax.distributed.initialize()` must have been called and
    payloads travel through the coordination service's key-value store (one
    key per peer per generation, deleted by its single reader right after
    the fetch — no cleanup barrier anywhere).  Either way the surface is
    identical to `SimComm` with `local_ranks == [rank]`, so the forest
    algorithms run unmodified.

    BOTH transports move exactly the `encode_payload` buffers — the mpi4py
    binding ships alltoallv rows as MPI.BYTE point-to-point pairs (length
    header, then payload) and allgathers as native Iallgather+Iallgatherv
    over the same packed bytes, never mpi4py's pickling object collectives —
    so the bindings are byte-for-byte interchangeable; `wire_digest()`
    exposes a running sha256 over every posted payload blob for tests to
    pin that.

    Nonblocking semantics: `iallgather`/`ialltoallv` *post* (KV writes are
    issued, MPI sends/collectives and header receives are in flight) and
    return a `CommHandle`; the blocking receive side runs in `wait()`, and
    `done()` polls (an MPI progress driver that posts the payload
    receives/collectives once the size headers land, or a short-timeout KV
    probe that caches what it fetches).  Once `done()` is True, `wait()` is
    free: no KV round-trips, no blocking MPI calls.  Handles must be waited
    in posting order, identically on every rank.  `namespace` isolates
    several DistComm instances sharing one runtime (e.g. an overlapped and
    a serialized benchmark run): it prefixes the KV keys, and gives the
    mpi4py binding its own duplicated communicator so interleaved exchanges
    cannot cross-match by tag or collective order.
    """

    def __init__(self, timeout_s: float = 120.0, namespace: str = "",
                 beacon: bool = False):
        super().__init__()
        self._timeout_ms = int(timeout_s * 1000)
        self._ns = namespace
        self._gen = 0
        self._mpi = None
        self._MPI = None
        self._client = None
        self._wire = hashlib.sha256()
        self.retry_counts: dict[str, int] = {}
        mpi = self._try_mpi()
        if mpi is not None:
            from mpi4py import MPI  # noqa: PLC0415

            # a namespaced instance needs its own tag-matching space: MPI
            # matches by (source, tag, communicator), and two instances
            # with independent generation counters would cross-match on a
            # shared communicator (Dup is collective — every rank builds
            # its DistComm instances in the same order).  The dup is owned
            # by this instance: `close()` frees it (context ids are a
            # finite MPI resource).
            self._owns_mpi = bool(namespace)
            self._mpi = mpi.Dup() if namespace else mpi
            self._MPI = MPI
            self.rank = mpi.Get_rank()
            self.size = mpi.Get_size()
        else:
            import jax
            from jax._src import distributed

            client = getattr(distributed.global_state, "client", None)
            if client is None:
                raise RuntimeError(
                    "DistComm needs an initialized jax.distributed runtime "
                    "(call jax.distributed.initialize) or an mpi4py world")
            self._client = client
            self.rank = jax.process_index()
            self.size = jax.process_count()
        # the liveness beacon is KV-only and OPT-IN: each posted generation
        # leaves a breadcrumb key so survivors can report a dead peer's
        # last-alive generation in CommTimeoutError diagnostics
        self._beacon = bool(beacon) and self._client is not None
        self.local_ranks = range(self.rank, self.rank + 1)

    @classmethod
    def _testing_instance(cls, rank: int, size: int, *, mpi=None, MPI=None,
                          client=None, timeout_s: float = 5.0,
                          namespace: str = "",
                          beacon: bool = False) -> "DistComm":
        """Build a DistComm over injected transports (fake MPI module / fake
        KV client) without a real runtime — the offline transport tests."""
        self = cls.__new__(cls)
        Comm.__init__(self)
        self._timeout_ms = int(timeout_s * 1000)
        self._ns = namespace
        self._gen = 0
        self._mpi = mpi
        self._MPI = MPI
        self._client = client
        self._wire = hashlib.sha256()
        self.retry_counts = {}
        self._beacon = bool(beacon) and client is not None
        self.rank = rank
        self.size = size
        self.local_ranks = range(rank, rank + 1)
        return self

    @staticmethod
    def _try_mpi():
        try:
            from mpi4py import MPI  # noqa: PLC0415
        except ImportError:
            return None
        if not MPI.Is_initialized() or MPI.COMM_WORLD.Get_size() < 2:
            return None
        return MPI.COMM_WORLD

    # legacy alias
    @property
    def P(self) -> int:
        return self.size

    def close(self) -> None:
        """Release owned transport resources: frees the communicator a
        namespaced mpi4py binding Dup()ed (collective — close on every
        rank, after all handles are waited).  The KV binding holds nothing
        beyond per-generation keys, which each exchange already cleans."""
        if getattr(self, "_owns_mpi", False) and self._mpi is not None:
            self._mpi.Free()
            self._mpi = None
            self._owns_mpi = False

    # -- wire accounting ---------------------------------------------------
    def _wire_update(self, outbox: dict[int, bytes]) -> None:
        """Fold every posted payload blob into the running wire digest, in
        deterministic (peer, length, bytes) order — transport independent."""
        for q in sorted(outbox):
            self._wire.update(struct.pack("<II", q, len(outbox[q])))
            self._wire.update(outbox[q])

    def wire_digest(self) -> str:
        """sha256 over every payload blob this rank has posted so far; equal
        runs over either transport yield equal digests (the packed-codec
        parity the tests assert)."""
        return self._wire.hexdigest()

    # -- KV-store transport ------------------------------------------------
    # Every exchange posts one payload key per peer (both collectives build
    # a full outbox — an empty alltoallv row still encodes as b"N"), so the
    # key's presence IS the posted signal: no targets index, and cleanup is
    # reader-side (rank q deletes `p>q` right after fetching it — exactly
    # one reader per key, so no barrier is needed anywhere).  Fetched blobs
    # are cached in the exchange state, which is what keeps cleanup off the
    # `wait()` critical path: once the poll has seen every peer
    # (`done() == True`), `wait()` touches the KV store zero times.
    def _key(self, gen: int, tag: str, rest: str) -> str:
        return f"repro_comm/{self._ns}{gen}/{tag}/{rest}"

    def _bkey(self, rank: int, gen: int) -> str:
        return f"repro_beacon/{self._ns}/{rank}/{gen}"

    def _kv_post(self, outbox: dict[int, bytes], tag: str):
        """Publish outbox[q] for each rank q; the exchange state carries the
        inbox cache that the poll and the wait fill cooperatively."""
        c = self._client
        gen = self._gen
        self._gen += 1
        me = self.rank
        for q, blob in outbox.items():
            c.key_value_set_bytes(self._key(gen, tag, f"{me}>{q}"), blob)
        if self._beacon:
            c.key_value_set_bytes(self._bkey(me, gen),
                                  frame_blob(struct.pack("<Q", gen)))
        return {"gen": gen, "tag": tag, "inbox": {},
                "phase": self._phases[-1] if self._phases else "default"}

    def _kv_fetch(self, st, p: int, timeout_ms: int) -> None:
        """Fetch-and-delete peer p's payload into the inbox cache (raises on
        timeout; the single-reader delete is this exchange's only cleanup)."""
        c = self._client
        key = self._key(st["gen"], st["tag"], f"{p}>{self.rank}")
        st["inbox"][p] = c.blocking_key_value_get_bytes(key, timeout_ms)
        c.key_value_delete(key)

    def _kv_complete(self, st) -> dict[int, bytes]:
        """Blocking receive side: fetch whatever the poll has not already
        cached — short probes in a bounded exponential-backoff + jitter
        loop instead of one flat transport-timeout RPC per peer, so a dead
        peer surfaces as a `CommTimeoutError` carrying the phase, the
        generation, the pending peers, and (with the beacon on) each one's
        last-alive generation.  Returns {p: payload_from_p}; no barrier,
        and no KV traffic at all when the handle already polled done."""
        missing = [p for p in range(self.size)
                   if p != self.rank and p not in st["inbox"]]
        if not missing:
            return st["inbox"]
        start = time.monotonic()
        deadline = start + self._timeout_ms / 1000.0
        probe_ms = max(1, min(50, self._timeout_ms))
        retries = 0
        delay = 0.0005
        while True:
            for p in list(missing):
                try:
                    self._kv_fetch(st, p, probe_ms)
                    missing.remove(p)
                except Exception:  # noqa: BLE001 - not posted yet
                    pass
            if not missing:
                if retries:
                    ph = st["phase"]
                    self.retry_counts[ph] = self.retry_counts.get(ph, 0) + retries
                return st["inbox"]
            now = time.monotonic()
            if now >= deadline:
                ph = st["phase"]
                self.retry_counts[ph] = self.retry_counts.get(ph, 0) + retries
                raise CommTimeoutError(
                    phase=ph, seq=st["gen"], elapsed_s=now - start,
                    retries=retries, rank=self.rank, size=self.size,
                    pending=missing, detail=self._beacon_probe(missing))
            retries += 1
            time.sleep(min(delay, deadline - now) * (0.5 + random.random()))
            delay = min(delay * 2.0, 0.05)

    def _beacon_probe(self, peers) -> dict:
        """Last-alive generation per stalled peer (or -1 if none seen in
        the probe window).  Beacon keys are write-only breadcrumbs, never
        deleted while the run lives, so this is a read-only diagnosis."""
        if not self._beacon:
            return {}
        out = {}
        lo = max(0, self._gen - 16)
        for p in peers:
            last = -1
            for g in range(self._gen, lo - 1, -1):
                try:
                    self._client.blocking_key_value_get_bytes(
                        self._bkey(p, g), 1)
                    last = g
                    break
                except Exception:  # noqa: BLE001 - no beacon at this gen
                    continue
            out[int(p)] = last
        return {"last_alive_gen": out}

    def _kv_ready(self, st) -> bool:
        """Poll-as-progress-driver: probe missing peers with a zero-ish
        timeout and cache (and clean up) whatever has landed, so a True
        return means `wait()` is KV-free."""
        for p in range(self.size):
            if p == self.rank or p in st["inbox"]:
                continue
            try:
                self._kv_fetch(st, p, 1)
            except Exception:  # noqa: BLE001 - miss/timeout: not posted yet
                return False
        return True

    # -- mpi4py transport --------------------------------------------------
    # Point-to-point packed exchange (alltoallv): each peer gets an 8-byte
    # length header then the integrity-framed `encode_payload` blob, both
    # as MPI.BYTE-class buffers (no pickle anywhere).  Sends and header receives post
    # immediately; payload receives post once the headers have sized their
    # buffers (in wait() or the poll).  Allgather does NOT use this path:
    # replicating one blob to P-1 peers as point-to-point pairs is O(P^2)
    # messages across the world, so it rides the native nonblocking
    # collectives below instead — same `encode_payload` buffers, same wire
    # digest.
    def _mpi_post(self, outbox: dict[int, bytes]):
        MPI, w = self._MPI, self._mpi
        gen = self._gen
        self._gen += 1
        t_hdr = (2 * gen) % 32000
        t_pay = t_hdr + 1
        keep, sreqs = [], []
        for q, blob in outbox.items():
            hdr = np.array([len(blob)], np.int64)
            buf = np.frombuffer(blob, np.uint8) if blob else np.zeros(0, np.uint8)
            keep.append((hdr, buf))
            sreqs.append(w.Isend([hdr, MPI.INT64_T], dest=q, tag=t_hdr))
            sreqs.append(w.Isend([buf, MPI.BYTE], dest=q, tag=t_pay))
        peers = [p for p in range(self.size) if p != self.rank]
        rhdr = {p: np.empty(1, np.int64) for p in peers}
        rreq = [w.Irecv([rhdr[p], MPI.INT64_T], source=p, tag=t_hdr)
                for p in peers]
        return {"keep": keep, "sreqs": sreqs, "peers": peers,
                "rhdr": rhdr, "rreq": rreq, "t_pay": t_pay}

    def _mpi_payload_recvs(self, st) -> None:
        """Once the headers are in, size the buffers and post the payload
        receives (idempotent; shared by the poll and the blocking wait)."""
        if "bufs" in st:
            return
        MPI, w = self._MPI, self._mpi
        st["bufs"] = {p: np.empty(int(st["rhdr"][p][0]), np.uint8)
                      for p in st["peers"]}
        st["preq"] = [w.Irecv([st["bufs"][p], MPI.BYTE], source=p,
                              tag=st["t_pay"])
                      for p in st["peers"]]

    def _mpi_complete(self, st) -> dict[int, bytes]:
        MPI = self._MPI
        if "bufs" not in st:
            MPI.Request.Waitall(st["rreq"])
            self._mpi_payload_recvs(st)
        MPI.Request.Waitall(st["preq"])
        MPI.Request.Waitall(st["sreqs"])
        return {p: st["bufs"][p].tobytes() for p in st["peers"]}

    def _mpi_test(self, st) -> bool:
        """Nonblocking progress driver: posts the payload receives as soon
        as the headers have completed, and reports True only when payloads
        AND sends are done — i.e. `wait()` will not block."""
        MPI = self._MPI
        if "bufs" not in st:
            if not MPI.Request.Testall(st["rreq"]):
                return False
            self._mpi_payload_recvs(st)
        return (bool(MPI.Request.Testall(st["preq"]))
                and bool(MPI.Request.Testall(st["sreqs"])))

    # Native-collective allgather: one Iallgather of the int64 blob sizes,
    # then one Iallgatherv of the payload bytes sized by it.  The payload
    # collective can only post once the sizes are in, and MPI matches
    # nonblocking collectives by POSTING ORDER on the communicator, so
    # pending payload posts drain through a FIFO — every rank posts them in
    # the same order no matter which handle's poll or wait drives progress.
    def _mpi_iag_post(self, blob: bytes):
        MPI, w = self._MPI, self._mpi
        hdr = np.array([len(blob)], np.int64)
        counts = np.zeros(self.size, np.int64)
        sbuf = np.frombuffer(blob, np.uint8) if blob else np.zeros(0, np.uint8)
        st = {"hdr": hdr, "counts": counts, "sbuf": sbuf,
              "hreq": w.Iallgather([hdr, MPI.INT64_T], [counts, MPI.INT64_T])}
        if not hasattr(self, "_iag_fifo"):
            self._iag_fifo = []
        self._iag_fifo.append(st)
        return st

    def _mpi_iag_drain(self) -> None:
        """Post payload Iallgatherv's for every pending exchange whose size
        collective has completed, in FIFO order; stop at the first that has
        not (posting a later one first would mismatch across ranks)."""
        MPI, w = self._MPI, self._mpi
        while self._iag_fifo:
            st = self._iag_fifo[0]
            if not MPI.Request.Testall([st["hreq"]]):
                return
            counts = st["counts"]
            displs = np.zeros(self.size, np.int64)
            np.cumsum(counts[:-1], out=displs[1:])
            st["rbuf"] = np.empty(int(counts.sum()), np.uint8)
            st["displs"] = displs
            st["preq"] = w.Iallgatherv(
                [st["sbuf"], MPI.BYTE],
                [st["rbuf"], counts.tolist(), displs.tolist(), MPI.BYTE])
            self._iag_fifo.pop(0)

    def _mpi_iag_complete(self, st) -> dict[int, bytes]:
        MPI = self._MPI
        if "preq" not in st:
            MPI.Request.Waitall([st["hreq"]])
            self._mpi_iag_drain()
            assert "preq" in st, "iallgather waited out of posting order"
        MPI.Request.Waitall([st["preq"]])
        d, c, buf = st["displs"], st["counts"], st["rbuf"]
        return {p: buf[int(d[p]):int(d[p]) + int(c[p])].tobytes()
                for p in range(self.size)}

    def _mpi_iag_test(self, st) -> bool:
        self._mpi_iag_drain()
        return ("preq" in st
                and bool(self._MPI.Request.Testall([st["preq"]])))

    # -- collectives -------------------------------------------------------
    def barrier(self) -> None:
        if self._mpi is not None:
            self._mpi.Barrier()
        else:
            gen = self._gen
            self._gen += 1
            self._client.wait_at_barrier(
                f"repro_comm_{self._ns}{gen}_b", self._timeout_ms)

    def _post(self, outbox: dict[int, bytes], tag: str):
        """Post one packed exchange on whichever transport is bound; returns
        (complete, poll, pending, diagnose) — closures delivering/probing
        {p: blob_from_p}, naming the undelivered peers, and (beacon on)
        reporting their last-alive generations.  The digest and the byte
        meters see the raw codec blobs; each transport value travels inside
        an integrity frame that `complete` verifies and strips, so a
        corrupted/truncated/duplicated wire byte surfaces as a
        `WireIntegrityError` naming the phase, generation, and peer."""
        self._wire_update(outbox)
        phase = self._phases[-1] if self._phases else "default"
        gen = self._gen
        framed = {q: frame_blob(b) for q, b in outbox.items()}
        if self._mpi is not None:
            st = self._mpi_post(framed)
            raw, poll = (lambda: self._mpi_complete(st)), \
                        (lambda: self._mpi_test(st))
            pending = None
        else:
            st = self._kv_post(framed, tag)
            raw, poll = (lambda: self._kv_complete(st)), \
                        (lambda: self._kv_ready(st))
            pending = lambda: [p for p in range(self.size)
                               if p != self.rank and p not in st["inbox"]]

        def complete():
            return {p: unframe_blob(
                        b, where=f"{phase}:{tag}:gen{gen}:{p}->{self.rank}")
                    for p, b in raw().items()}

        diagnose = ((lambda: self._beacon_probe(pending()))
                    if (pending is not None and self._beacon) else None)
        return complete, poll, pending, diagnose

    def _iallgather(self, per_local: list) -> CommHandle:
        x = per_local[0]
        blob = encode_payload(x)
        outbox = {q: blob for q in range(self.size) if q != self.rank}
        if self._mpi is not None:
            # native collective path: O(log P) fan-out instead of P-1 p2p
            # pairs per rank, over the SAME per-peer logical blobs — the
            # digest folds them exactly as the KV binding does, so
            # `wire_digest()` parity across bindings is preserved.  The
            # collective moves the framed blob; every rank's slice is
            # integrity-checked on delivery.
            self._wire_update(outbox)
            phase = self._phases[-1] if self._phases else "default"
            gen = self._gen
            st = self._mpi_iag_post(frame_blob(blob))

            def deliver():
                parts = self._mpi_iag_complete(st)
                out = [decode_payload(unframe_blob(
                           parts[p],
                           where=f"{phase}:iag:gen{gen}:{p}->{self.rank}"))
                       for p in range(self.size)]
                out[self.rank] = x
                return out

            return CommHandle(deliver, poll=lambda: self._mpi_iag_test(st))
        complete, poll, pending, diagnose = self._post(outbox, "ag")

        def deliver():
            out = [None] * self.size
            out[self.rank] = x
            for p, b in complete().items():
                out[p] = decode_payload(b)
            return out

        h = CommHandle(deliver, poll=poll)
        h._pending = pending
        h._diagnose = diagnose
        return h

    def _ialltoallv(self, send: list) -> CommHandle:
        row = send[0]
        outbox = {q: encode_payload(row[q])
                  for q in range(self.size) if q != self.rank}
        complete, poll, pending, diagnose = self._post(outbox, "a2a")

        def deliver():
            recv = [None] * self.size
            recv[self.rank] = row[self.rank]
            for p, b in complete().items():
                recv[p] = decode_payload(b)
            return [recv]

        h = CommHandle(deliver, poll=poll)
        h._pending = pending
        h._diagnose = diagnose
        return h
