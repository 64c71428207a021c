"""Python bindings for the native transport + array codec + TCP (DCN) path.

Three layers:
  * build/bind the C++ shared-memory primitives (ShmRing, ShmMailbox) —
    the intra-host hot path between actor processes and the learner service
    (actors/_native/transport.cc; built on demand with g++, keyed on the
    source's content);
  * a zero-copy-ish numpy array codec (tiny JSON header + raw buffers) so
    trajectory batches cross process boundaries without pickle overhead;
  * TcpRecordTransport — the same length-prefixed record stream over a
    socket for actors on *other* hosts (the true-DCN path). One consumer
    thread drains TCP records into the same queue interface as the ring.
"""
from __future__ import annotations

import ctypes
import hashlib
import json
import os
import socket
import struct
import subprocess
import threading
import time
import zlib
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from dist_dqn_tpu import chaos
from dist_dqn_tpu.telemetry import get_registry
from dist_dqn_tpu.telemetry.collectors import (TRANSPORT_CORRUPT,
                                               TRANSPORT_SHED)

_NATIVE_DIR = Path(__file__).parent / "_native"
_lib = None
_lib_lock = threading.Lock()


def build_native_lib(src_name: str, lib_name: str,
                     directory: Optional[Path] = None) -> Path:
    """Compile one _native/*.cc into a shared lib on demand.

    The library's file name carries a digest of the source and the
    compile flags, so a library found on disk was built from exactly
    this source: a stale ``.so`` (the build products are ignored by git
    and may be copied around with arbitrary mtimes) has another name and
    can never shadow an edited ``.cc``. The compiler writes to a
    per-process name that is renamed into place, so concurrent builders
    (spawned actors) never load a half-written file.
    """
    native_dir = directory or _NATIVE_DIR
    src = native_dir / src_name
    flags = ["-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]
    digest = hashlib.sha256(
        src.read_bytes() + " ".join(flags).encode()).hexdigest()[:16]
    stem, suffix = os.path.splitext(lib_name)
    out = native_dir / f"{stem}.{digest}{suffix}"
    if out.exists():
        return out
    tmp = native_dir / f"{stem}.{digest}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *flags, str(src), "-o", str(tmp)],
                       check=True, capture_output=True)
        os.replace(tmp, out)
    finally:
        tmp.unlink(missing_ok=True)
    return out


def _build_native() -> Path:
    return build_native_lib("transport.cc", "libdqntransport.so")


def native_lib() -> ctypes.CDLL:
    """Build (if needed) and load the C++ transport library."""
    global _lib
    with _lib_lock:
        if _lib is None:
            lib = ctypes.CDLL(str(_build_native()))
            lib.dqn_ring_create.restype = ctypes.c_void_p
            lib.dqn_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
            lib.dqn_ring_attach.restype = ctypes.c_void_p
            lib.dqn_ring_attach.argtypes = [ctypes.c_char_p]
            lib.dqn_ring_push.restype = ctypes.c_int
            lib.dqn_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_uint32]
            lib.dqn_ring_pop.restype = ctypes.c_long
            lib.dqn_ring_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_uint64]
            lib.dqn_ring_peek_len.restype = ctypes.c_long
            lib.dqn_ring_peek_len.argtypes = [ctypes.c_void_p]
            lib.dqn_ring_dropped.restype = ctypes.c_uint64
            lib.dqn_ring_dropped.argtypes = [ctypes.c_void_p]
            lib.dqn_ring_pending.restype = ctypes.c_uint64
            lib.dqn_ring_pending.argtypes = [ctypes.c_void_p]
            lib.dqn_box_create.restype = ctypes.c_void_p
            lib.dqn_box_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
            lib.dqn_box_attach.restype = ctypes.c_void_p
            lib.dqn_box_attach.argtypes = [ctypes.c_char_p]
            lib.dqn_box_write.restype = ctypes.c_int
            lib.dqn_box_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                          ctypes.c_uint64, ctypes.c_uint64]
            lib.dqn_box_read.restype = ctypes.c_long
            lib.dqn_box_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_uint64,
                                         ctypes.POINTER(ctypes.c_uint64)]
            _lib = lib
    return _lib


def shm_dir() -> Path:
    d = Path("/dev/shm") if Path("/dev/shm").is_dir() else Path("/tmp")
    p = d / "dqn_tpu"
    p.mkdir(exist_ok=True)
    return p


class ShmRing:
    """MPSC byte-record ring over shared memory (see transport.cc)."""

    def __init__(self, name: str, capacity: int = 0, create: bool = False):
        self.path = str(shm_dir() / name).encode()
        lib = native_lib()
        if create:
            self._h = lib.dqn_ring_create(self.path, capacity)
        else:
            self._h = lib.dqn_ring_attach(self.path)
        if not self._h:
            raise OSError(f"ring {'create' if create else 'attach'} failed: "
                          f"{self.path.decode()}")
        self._lib = lib

    def push(self, payload: bytes) -> bool:
        rc = self._lib.dqn_ring_push(self._h, payload, len(payload))
        return rc == 0

    def pop(self) -> Optional[bytes]:
        n = self._lib.dqn_ring_peek_len(self._h)
        if n < 0:
            return None
        buf = ctypes.create_string_buffer(int(n))
        got = self._lib.dqn_ring_pop(self._h, buf, int(n))
        if got < 0:
            return None
        return buf.raw[:got]

    @property
    def dropped(self) -> int:
        return int(self._lib.dqn_ring_dropped(self._h))

    @property
    def pending_bytes(self) -> int:
        return int(self._lib.dqn_ring_pending(self._h))

    def unlink(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass


class ShmMailbox:
    """Single-writer / many-reader versioned broadcast slot."""

    def __init__(self, name: str, max_size: int = 0, create: bool = False):
        self.path = str(shm_dir() / name).encode()
        lib = native_lib()
        self._h = (lib.dqn_box_create(self.path, max_size) if create
                   else lib.dqn_box_attach(self.path))
        if not self._h:
            raise OSError(f"mailbox {'create' if create else 'attach'} "
                          f"failed: {self.path.decode()}")
        self._lib = lib
        self._cap = max_size
        self._read_buf = None   # lazily sized, reused across read() calls

    def write(self, payload: bytes, version: int) -> None:
        rc = self._lib.dqn_box_write(self._h, payload, len(payload), version)
        if rc != 0:
            raise ValueError("payload exceeds mailbox size")

    def read(self, max_size: int = 1 << 20) -> Tuple[Optional[bytes], int]:
        # The scratch buffer is reused: actors poll their mailbox every
        # few hundred microseconds, and a fresh 1 MB allocation per poll
        # was a measurable share of the steady-state ingest profile. One
        # reader per mailbox by protocol, so reuse is race-free. The
        # scratch is clamped to the creation-time capacity when known
        # (a 1 KB mailbox must not pin a 1 MB scratch for its lifetime);
        # attach-side readers (capacity unknown) size to the request.
        if self._cap:
            max_size = min(max_size, self._cap)
        buf = self._read_buf
        if buf is None or ctypes.sizeof(buf) < max_size:
            self._read_buf = buf = ctypes.create_string_buffer(max_size)
        ver = ctypes.c_uint64(0)
        n = self._lib.dqn_box_read(self._h, buf, max_size,
                                   ctypes.byref(ver))
        if n < 0:
            raise ValueError("mailbox read buffer too small")
        if n == 0:
            return None, 0
        return buf.raw[:n], int(ver.value)

    def unlink(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# Array codec: dict[str, np.ndarray] <-> bytes
# ---------------------------------------------------------------------------

# Payload integrity checking (race/corruption detection, SURVEY.md §5):
# with DQN_TRANSPORT_CRC=1 every encoded record carries a crc32 of its
# array bytes and decode verifies it — a torn shm read (ring-discipline
# bug) or a TCP framing slip surfaces as a CRC mismatch at the record
# boundary instead of silent garbage training data. Off by default: the
# checksum costs ~1 GB/s/core on pixel payloads. Tests run with it on.
_CRC_ENABLED = os.environ.get("DQN_TRANSPORT_CRC") == "1"


# Compress records above this body size when compress="auto" — pixel
# observation stacks (84x84x4 uint8, mostly background) shrink severalfold
# under zlib-1, a big win on DCN links; small vector records are not worth
# the CPU. Intra-host shm callers keep compress=False (memcpy beats zlib).
_COMPRESS_AUTO_MIN = 16 * 1024


def encode_arrays(arrays: Dict[str, np.ndarray],
                  meta: Optional[Dict] = None,
                  compress: "bool | str" = False) -> bytes:
    body_parts = [np.ascontiguousarray(v).tobytes()
                  for v in arrays.values()]
    header = {
        "meta": meta or {},
        "arrays": [[k, v.dtype.str, list(v.shape)]
                   for k, v in arrays.items()],
    }
    body_len = sum(len(p) for p in body_parts)
    if compress == "auto":
        compress = body_len >= _COMPRESS_AUTO_MIN
    if compress:
        import zlib
        blob = zlib.compress(b"".join(body_parts), 1)
        header["z"] = body_len  # uncompressed body length (decode check)
        body_parts = [blob]
    if _CRC_ENABLED:
        # Frame: len(hb) | hb | crc32(hb + body) | body. The checksum
        # covers the HEADER bytes too — a flipped actor id or shape digit
        # misroutes training data just as badly as a flipped pixel.
        header["crc"] = True
        hb = json.dumps(header).encode()
        import zlib
        crc = zlib.crc32(hb)
        for part in body_parts:
            crc = zlib.crc32(part, crc)
        return b"".join([struct.pack("<I", len(hb)), hb,
                         struct.pack("<I", crc)] + body_parts)
    hb = json.dumps(header).encode()
    return b"".join([struct.pack("<I", len(hb)), hb] + body_parts)


def decode_arrays(buf: bytes) -> Tuple[Dict[str, np.ndarray], Dict]:
    (hlen,) = struct.unpack_from("<I", buf, 0)
    header = json.loads(buf[4:4 + hlen].decode())
    off = 4 + hlen
    if header.get("crc"):
        # Verify BEFORE decompressing/materializing: the checksum covers
        # the WIRE form (header + compressed blob when compressed).
        import zlib
        (want,) = struct.unpack_from("<I", buf, off)
        off += 4
        view = memoryview(buf)
        got = zlib.crc32(view[off:], zlib.crc32(view[4:4 + hlen]))
        if got != want:
            raise ValueError(
                f"transport record CRC mismatch (got {got:#010x}, frame "
                f"says {want:#010x}): torn or corrupted record")
    if "z" in header:
        import zlib
        # Untrusted input (the TCP listener may face other hosts): bound
        # the inflate by the declared size so a deflate bomb fails cheaply
        # as one bad record instead of exhausting learner memory; zero-copy
        # view into the wire buffer.
        want_len = int(header["z"])
        d = zlib.decompressobj()
        body = d.decompress(memoryview(buf)[off:], want_len + 1)
        if len(body) != want_len or d.unconsumed_tail:
            raise ValueError(
                f"transport record decompressed to {len(body)}(+) bytes, "
                f"header says {want_len}")
        buf, off = body, 0
    out: Dict[str, np.ndarray] = {}
    for name, dtype, shape in header["arrays"]:
        dt = np.dtype(dtype)
        count = int(np.prod(shape, dtype=np.int64))
        arr = np.frombuffer(buf, dtype=dt, count=count, offset=off)
        out[name] = arr.reshape(shape).copy()
        off += count * dt.itemsize
    return out, header["meta"]


# ---------------------------------------------------------------------------
# TCP record transport (cross-host DCN path)
# ---------------------------------------------------------------------------

# Wire frame integrity (ISSUE 8 tentpole hardening): every TCP frame is
#
#     magic(4) | length(4, LE) | crc32(4, LE, over payload) | payload
#
# Before this header existed a single flipped bit on the wire (or a
# framing slip after a partial write) flowed straight into the array
# codec as training data — json.loads of a corrupt header at best,
# silently garbage pixels at worst. Now:
#   * bad magic / out-of-bound length  -> the stream is desynced; the
#     connection is dropped and the peer reconnects (counted under
#     {reason="bad_magic"|"length"});
#   * CRC mismatch -> the frame BOUNDARY is still trustworthy (length
#     was verified), so only the frame is dropped ({reason="crc"}) and
#     the server NACKs down the reply channel so the lock-step actor
#     reconnects immediately instead of waiting out its stall bound.
# CRC32 runs ~1-3 GB/s/core — noise next to any DCN link this path can
# see — so frame integrity is ALWAYS on (unlike the optional payload
# CRC above, which guards intra-host shm reads under tests only).
FRAME_MAGIC = b"DQF1"
_FRAME_HDR = struct.Struct("<4sII")
#: Far above any sane record (a 256-lane pixel step is ~15 MB), far
#: below a memory-exhaustion length from a corrupt/hostile header.
MAX_FRAME_BYTES = 256 << 20

#: Reply-channel control record: the server could not use the actor's
#: last frame (CRC drop) — reconnect and re-hello rather than waiting
#: out the stall bound for an action that will never come.
CORRUPT_FRAME_NACK_KIND = "corrupt_frame"

#: Reply-channel control record (ISSUE 9 satellite): the hello declared
#: a wire protocol version / transport mode this service does not
#: speak. Unlike corrupt_frame this is NOT churn — the actor must fail
#: loudly (build drift), not reconnect-retry. ``meta["detail"]``
#: carries the human-readable reason.
PROTO_MISMATCH_NACK_KIND = "proto_mismatch"


def frame_encode(payload) -> bytes:
    """One integrity-framed wire record (accepts any bytes-like payload,
    e.g. the zero-copy encoder's memoryview — one join, no extra
    copies)."""
    return b"".join((_FRAME_HDR.pack(FRAME_MAGIC, len(payload),
                                     zlib.crc32(payload)), payload))


def _frame_check(payload: bytes, want_crc: int) -> bool:
    return zlib.crc32(payload) == want_crc


def _corrupt_frame_counter(reason: str, side: str):
    return get_registry().counter(
        TRANSPORT_CORRUPT,
        "TCP frames failing the magic/length/CRC32 integrity check",
        labels={"reason": reason, "side": side})


class TcpRecordServer:
    """Full-duplex record endpoint for actors on OTHER hosts (the DCN path).

    Accepts length-prefixed records from remote actors and can send reply
    records (actions) back down the same connection: ``pop()`` returns
    ``(conn_id, payload)`` and ``send(conn_id, payload)`` routes a reply —
    the learner service maps actor ids to the connection their last record
    arrived on, so routing survives actor restarts/reconnects.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 max_backlog: int = 4096,
                 max_backpressure_wait_s: float = 30.0):
        # socket: accept loop below sets a 0.2s timeout before use.
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.address = self._sock.getsockname()
        self._records: List[Tuple[int, bytes]] = []
        self._conns: Dict[int, socket.socket] = {}
        # Per-connection WRITE locks: replies come from the service
        # thread while corrupt-frame NACKs (ISSUE 8) come from that
        # connection's serve thread — two concurrent sendall()s on one
        # socket could interleave mid-frame and desync the reply
        # stream the integrity header would then reject.
        self._send_locks: Dict[int, threading.Lock] = {}
        self._next_conn = 0
        self._lock = threading.Lock()
        self._max_backlog = max_backlog
        # Degrade-don't-wedge bound (ISSUE 8): backpressure is still the
        # first response to a full backlog (TCP flow control throttles
        # the sender), but a drain that has stopped ENTIRELY — learner
        # wedged, loop dead — must not pin every serve thread in the
        # wait loop forever. Past this wait the record is shed, counted
        # (dqn_transport_tcp_shed_total) and alarmed once per episode.
        self._max_backpressure_wait_s = float(max_backpressure_wait_s)
        self.dropped = 0              # shm-ring-style producer overruns: n/a
        self.backpressure_events = 0  # records that had to wait for space
        self.shed_records = 0         # records dropped after the wait bound
        self.corrupt_frames = 0       # frames failing the integrity check
        self._shed_alarmed = False
        # Telemetry (ISSUE 1): the DCN ingress queue. Backlog depth is
        # THE learner-behind signal on this path (full backlog = TCP
        # flow control throttling every remote actor).
        reg = get_registry()
        self._c_records = reg.counter("dqn_transport_tcp_records_total",
                                      "records accepted from remote actors")
        self._g_backlog = reg.gauge("dqn_transport_tcp_backlog",
                                    "records queued awaiting service drain")
        self._c_backpressure = reg.counter(
            "dqn_transport_tcp_backpressure_total",
            "records that had to wait for backlog space")
        self._c_shed = reg.counter(
            TRANSPORT_SHED,
            "records shed after the bounded backpressure wait (drain "
            "stopped entirely — degrade instead of wedging)")
        self._g_conns = reg.gauge("dqn_transport_tcp_connections",
                                  "live remote-actor connections")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._accept_loop,
                                        name="tcp-accept", daemon=True)
        self._thread.start()

    def _accept_loop(self):
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            with self._lock:
                conn_id = self._next_conn
                self._next_conn += 1
                self._conns[conn_id] = conn
                self._send_locks[conn_id] = threading.Lock()
                self._g_conns.set(len(self._conns))
            threading.Thread(target=self._serve, args=(conn_id, conn),
                             name=f"tcp-serve-{conn_id}",
                             daemon=True).start()

    def _serve(self, conn_id: int, conn: socket.socket):
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        try:
            while not self._stop.is_set():
                hdr = self._recv_exact(conn, _FRAME_HDR.size)
                if hdr is None:
                    return
                magic, n, crc = _FRAME_HDR.unpack(hdr)
                if magic != FRAME_MAGIC:
                    # The byte stream is desynced (corrupt length on an
                    # earlier frame, a peer speaking the old unframed
                    # protocol, or garbage): there is no trustworthy
                    # boundary to resume at — drop the connection; the
                    # actor's reconnect + re-hello path recovers it.
                    self._count_corrupt("bad_magic")
                    return
                if n > MAX_FRAME_BYTES:
                    self._count_corrupt("length")
                    return
                payload = self._recv_exact(conn, n)
                if payload is None:
                    self._count_corrupt("truncated")
                    return
                ev = chaos.fire("transport.recv")
                if ev is not None:
                    if ev.fault == "bit_flip":
                        # Corrupt BEFORE verification: the CRC gate
                        # below must catch it — the e2e corrupt-frame
                        # invariant (a flipped bit never reaches the
                        # array codec).
                        payload = chaos.corrupt_bytes(payload, ev)
                    elif ev.fault == "drop":
                        continue
                    elif ev.fault == "delay":
                        chaos.sleep_for(ev)
                    elif ev.fault == "disconnect":
                        return
                if not _frame_check(payload, crc):
                    # Frame boundary verified (length matched), payload
                    # did not: drop JUST this frame, keep the stream,
                    # and NACK so the lock-step sender re-hellos now
                    # instead of waiting out its stall bound for an
                    # action that will never come.
                    self._count_corrupt("crc")
                    self.send(conn_id, encode_arrays(
                        {}, {"kind": CORRUPT_FRAME_NACK_KIND}))
                    continue
                chaos.mark_recovered("transport.recv")
                # Backpressure, not drops: pausing this connection's reads
                # fills the kernel socket buffers and TCP flow control
                # throttles the sender — a dropped record would stall its
                # lock-step actor for a full reply timeout instead. Only
                # once the wait bound says the drain is DEAD (not slow)
                # does the record shed.
                waited = False
                wait_start = None
                while not self._stop.is_set():
                    with self._lock:
                        if len(self._records) < self._max_backlog:
                            self._records.append((conn_id, payload))
                            self._g_backlog.set(len(self._records))
                            self._c_records.inc()
                            # The drain is alive again: close the shed
                            # episode so the NEXT one alarms too.
                            self._shed_alarmed = False
                            break
                        if not waited:
                            waited = True
                            wait_start = time.monotonic()
                            self.backpressure_events += 1
                            self._c_backpressure.inc()
                    if (wait_start is not None and time.monotonic()
                            - wait_start > self._max_backpressure_wait_s):
                        self._shed(conn_id)
                        break
                    time.sleep(0.001)
        finally:
            with self._lock:
                self._conns.pop(conn_id, None)
                self._send_locks.pop(conn_id, None)
                self._g_conns.set(len(self._conns))
            conn.close()

    def _count_corrupt(self, reason: str) -> None:
        # Under the lock: serve threads count corrupt frames
        # concurrently; an unlocked += across threads loses updates.
        with self._lock:
            self.corrupt_frames += 1
        _corrupt_frame_counter(reason, side="server").inc()

    def _shed(self, conn_id: int) -> None:
        # Under the lock (lock-discipline fix, ISSUE 13): _shed runs on
        # every serve thread whose wait bound expired at once, and
        # _shed_alarmed is reset under the lock by the push loop — the
        # unlocked read-then-set here let concurrent shedders each see
        # False and emit duplicate "one per episode" alarms, and the
        # unlocked += lost shed_records increments across threads.
        with self._lock:
            self.shed_records += 1
            alarm = not self._shed_alarmed
            self._shed_alarmed = True
        self._c_shed.inc()
        if alarm:
            # One alarm per shed episode, not one per record: the
            # signal is "the drain is dead", already screamed by the
            # backlog gauge; per-record lines would swamp the log.
            print(json.dumps({
                "transport_shedding": True, "conn_id": conn_id,
                "backlog": self._max_backlog,
                "waited_s": self._max_backpressure_wait_s}), flush=True)

    @staticmethod
    def _recv_exact(conn, n) -> Optional[bytes]:
        chunks = []
        while n:
            try:
                b = conn.recv(n)
            except OSError:
                return None
            if not b:
                return None
            chunks.append(b)
            n -= len(b)
        return b"".join(chunks)

    def pop(self) -> Optional[Tuple[int, bytes]]:
        with self._lock:
            if not self._records:
                return None
            rec = self._records.pop(0)
            self._g_backlog.set(len(self._records))
            return rec

    def send(self, conn_id: int, payload: bytes) -> bool:
        """Reply down a connection (False if it is gone — actor churn).
        Thread-safe per connection: the write lock serializes service
        replies against serve-thread NACKs so frames never interleave."""
        with self._lock:
            conn = self._conns.get(conn_id)
            send_lock = self._send_locks.get(conn_id)
        if conn is None or send_lock is None:
            return False
        try:
            with send_lock:
                conn.sendall(frame_encode(payload))
            return True
        except OSError:
            return False

    def close(self):
        self._stop.set()
        with self._lock:
            conns = list(self._conns.values())
            self._conns.clear()
        for c in conns:
            try:
                # shutdown() sends FIN immediately even while a serve
                # thread blocks in recv on the same socket; bare close()
                # would leave remote peers hanging until their timeout.
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        try:
            self._sock.close()
        except OSError:
            pass


class TcpRecordClient:
    """Actor-side endpoint: push records, block on the action reply.

    The remote-actor protocol is lock-step per actor (send observations,
    wait for actions), so replies are read synchronously off the same
    socket — no background thread, no reordering to handle.

    A recv timeout is NOT a dead connection: the service legitimately
    stalls for long stretches (first jit compile, checkpoint writes,
    evaluation), so ``read_reply`` keeps waiting through timeouts while
    ``keep_waiting()`` approves, and returns None only on EOF/error — a
    learner stall must not make the whole fleet tear down healthy
    connections and drop assembly windows.
    """

    def __init__(self, address: Tuple[str, int], timeout_s: float = 5.0,
                 max_stall_s: float = 300.0):
        # socket: create_connection sets the connect+recv timeout.
        self._sock = socket.create_connection(address, timeout=timeout_s)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        # Dead-peer floor below the app-level stall bound: a silent
        # partition (no FIN/RST) still gets torn down by the kernel.
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)
        self._timeout_s = timeout_s
        self._max_stall_s = max_stall_s

    def push(self, payload: bytes) -> bool:
        frame = frame_encode(payload)
        ev = chaos.fire("transport.send")
        if ev is not None:
            if ev.fault == "drop":
                # Simulated wire loss: report success, send nothing —
                # the reply never comes and the stall/reconnect path
                # must recover the lane.
                return True
            if ev.fault == "delay":
                chaos.sleep_for(ev)
            elif ev.fault == "bit_flip":
                # Corrupt AFTER the CRC was computed: genuine wire
                # corruption — the server's integrity gate must drop
                # and NACK it.
                frame = chaos.corrupt_bytes(frame, ev)
            elif ev.fault == "truncate":
                frame = chaos.truncate_bytes(frame, ev)
                try:
                    self._sock.sendall(frame)
                finally:
                    self.close()   # a half frame can never resync
                return False
            elif ev.fault == "disconnect":
                self.close()
                return False
        # sendall's partial progress cannot be resumed after a timeout, so
        # sends get the full stall bound: server-side backpressure pauses
        # reads during learner stalls, and a large (pixel) record can
        # legitimately sit mid-send well past the short recv timeout.
        try:
            self._sock.settimeout(self._max_stall_s)
            self._sock.sendall(frame)
            return True
        except OSError:
            return False
        finally:
            try:
                self._sock.settimeout(self._timeout_s)
            except OSError:
                pass

    def _recv_exact(self, n: int, keep_waiting) -> Optional[bytes]:
        deadline = time.monotonic() + self._max_stall_s
        chunks = []
        while n:
            try:
                b = self._sock.recv(n)
            except socket.timeout:
                # Keep waiting through service stalls (compile/checkpoint/
                # eval), but not forever: past max_stall_s the peer is
                # treated as dead even without a FIN (silent partition).
                if keep_waiting() and time.monotonic() < deadline:
                    continue
                return None
            except OSError:
                return None
            if not b:
                return None
            chunks.append(b)
            n -= len(b)
            deadline = time.monotonic() + self._max_stall_s
        return b"".join(chunks)

    def read_reply(self, keep_waiting=lambda: True) -> Optional[bytes]:
        """Block for the next reply record; None = connection dead,
        stalled past ``max_stall_s``, ``keep_waiting`` said stop, or
        the reply failed the frame integrity check (a corrupt reply is
        indistinguishable from a desynced stream — reconnect)."""
        hdr = self._recv_exact(_FRAME_HDR.size, keep_waiting)
        if hdr is None:
            return None
        magic, n, crc = _FRAME_HDR.unpack(hdr)
        if magic != FRAME_MAGIC or n > MAX_FRAME_BYTES:
            _corrupt_frame_counter(
                "bad_magic" if magic != FRAME_MAGIC else "length",
                side="client").inc()
            return None
        payload = self._recv_exact(n, keep_waiting)
        if payload is None:
            return None
        if not _frame_check(payload, crc):
            _corrupt_frame_counter("crc", side="client").inc()
            return None
        return payload

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass
