"""Native transport tests: shm ring (incl. multi-process producers),
mailbox seqlock, array codec, TCP record path."""
import multiprocessing as mp
import os
import uuid

import numpy as np
import pytest

from dist_dqn_tpu.actors.transport import (ShmMailbox, ShmRing,
                                           TcpRecordClient, TcpRecordServer,
                                           decode_arrays, encode_arrays)


def _name():
    return f"test_{uuid.uuid4().hex[:8]}"


def test_codec_roundtrip_dtypes():
    arrays = {
        "u8": np.random.default_rng(0).integers(0, 255, (3, 4, 4),
                                                dtype=np.uint8),
        "f32": np.random.default_rng(1).normal(size=(5,)).astype(np.float32),
        "i32": np.array([[1, -2], [3, 4]], np.int32),
        "empty": np.zeros((0, 7), np.float32),
    }
    buf = encode_arrays(arrays, {"actor": 3, "kind": "step"})
    out, meta = decode_arrays(buf)
    assert meta == {"actor": 3, "kind": "step"}
    for k, v in arrays.items():
        np.testing.assert_array_equal(out[k], v)
        assert out[k].dtype == v.dtype


def test_codec_crc_detects_corruption():
    """With checksums on (conftest sets DQN_TRANSPORT_CRC=1), a flipped
    payload byte surfaces as a ValueError at the record boundary — the
    torn-read/corruption detector for the shm and TCP paths."""
    import pytest

    from dist_dqn_tpu.actors import transport as tr

    assert tr._CRC_ENABLED, "conftest should enable transport CRC in tests"
    payload = encode_arrays({"x": np.arange(64, dtype=np.float32)},
                            {"kind": "step", "actor": 3, "t": 9})
    arrays, meta = decode_arrays(payload)   # clean record passes
    np.testing.assert_allclose(arrays["x"], np.arange(64))
    assert meta["actor"] == 3
    corrupted = bytearray(payload)
    corrupted[-5] ^= 0xFF                   # flip one array byte
    with pytest.raises(ValueError, match="CRC mismatch"):
        decode_arrays(bytes(corrupted))
    # Header corruption is covered too: rewrite the actor id digit inside
    # the JSON header (still valid JSON — would silently misroute lanes).
    hdr = bytearray(payload)
    i = payload.index(b'"actor": 3')
    hdr[i + len(b'"actor": ')] = ord("9")
    with pytest.raises(ValueError, match="CRC mismatch"):
        decode_arrays(bytes(hdr))


def test_codec_compression_roundtrip_and_auto_threshold():
    """compress=True shrinks pixel-like records severalfold; "auto"
    compresses big bodies and skips small ones; decode is transparent and
    the CRC covers the wire (compressed) form."""
    import pytest

    big = {"obs": np.zeros((8, 84, 84, 4), np.uint8),
           "reward": np.arange(8, dtype=np.float32)}
    big["obs"][:, 10:20, 10:20, :] = 255
    plain = encode_arrays(big, {"kind": "step", "actor": 1, "t": 2})
    packed = encode_arrays(big, {"kind": "step", "actor": 1, "t": 2},
                           compress=True)
    assert len(packed) < len(plain) // 4
    arrays, meta = decode_arrays(packed)
    np.testing.assert_array_equal(arrays["obs"], big["obs"])
    np.testing.assert_allclose(arrays["reward"], big["reward"])
    assert meta == {"kind": "step", "actor": 1, "t": 2}

    auto_big = encode_arrays(big, {"kind": "step", "actor": 1, "t": 2},
                             compress="auto")
    assert len(auto_big) == len(packed)            # over threshold
    small = {"x": np.arange(16, dtype=np.float32)}
    assert len(encode_arrays(small, compress="auto")) \
        == len(encode_arrays(small))               # under: untouched

    # Corruption inside the compressed blob still dies at the CRC gate.
    bad = bytearray(packed)
    bad[-3] ^= 0x55
    with pytest.raises(ValueError, match="CRC mismatch"):
        decode_arrays(bytes(bad))

    # Decompression-bomb guard: a record whose blob inflates past the
    # declared size fails at the bound, not after inflating gigabytes.
    import json as _json
    import struct as _struct
    import zlib as _zlib
    bomb_body = _zlib.compress(b"\x00" * (1 << 20), 1)
    hdr = {"meta": {}, "arrays": [["x", "|u1", [64]]], "z": 64}
    hb = _json.dumps(hdr).encode()
    bomb = _struct.pack("<I", len(hb)) + hb + bomb_body
    with pytest.raises(ValueError, match="decompressed"):
        decode_arrays(bomb)


def test_ring_fifo_and_overflow():
    name = _name()
    ring = ShmRing(name, capacity=1 << 12, create=True)
    try:
        msgs = [os.urandom(100) for _ in range(10)]
        for m in msgs:
            assert ring.push(m)
        for m in msgs:
            assert ring.pop() == m
        assert ring.pop() is None
        # Overflow: pushes beyond capacity are rejected and counted.
        big = os.urandom(1000)
        pushed = 0
        while ring.push(big):
            pushed += 1
        assert 0 < pushed <= 4
        assert ring.dropped >= 1
        # Draining frees space again.
        for _ in range(pushed):
            assert ring.pop() == big
        assert ring.push(big)
    finally:
        ring.unlink()


def _producer(name: str, pid: int, count: int):
    from dist_dqn_tpu.actors.transport import ShmRing, encode_arrays
    ring = ShmRing(name)
    for i in range(count):
        payload = encode_arrays(
            {"v": np.full((8,), pid * 10_000 + i, np.int64)})
        while not ring.push(payload):
            pass


@pytest.mark.slow
def test_ring_multiprocess_producers():
    name = _name()
    ring = ShmRing(name, capacity=1 << 16, create=True)
    try:
        ctx = mp.get_context("spawn")
        count = 200
        procs = [ctx.Process(target=_producer, args=(name, pid, count))
                 for pid in range(2)]
        for p in procs:
            p.start()
        seen = []
        while len(seen) < 2 * count:
            rec = ring.pop()
            if rec is None:
                continue
            arrays, _ = decode_arrays(rec)
            seen.append(int(arrays["v"][0]))
        for p in procs:
            p.join(timeout=30)
            assert p.exitcode == 0
        # Every record from both producers arrived exactly once, and each
        # producer's records arrived in order.
        assert sorted(seen) == sorted(
            pid * 10_000 + i for pid in range(2) for i in range(count))
        for pid in range(2):
            mine = [v - pid * 10_000 for v in seen
                    if v // 10_000 == pid]
            assert mine == sorted(mine)
    finally:
        ring.unlink()


def test_mailbox_versioned_broadcast():
    name = _name()
    box = ShmMailbox(name, max_size=1 << 10, create=True)
    try:
        assert box.read() == (None, 0)
        box.write(b"v1", 1)
        box.write(b"v2-longer", 2)
        data, ver = box.read()
        assert data == b"v2-longer" and ver == 2
        # Reads are non-destructive.
        assert box.read()[1] == 2
    finally:
        box.unlink()


def test_tcp_record_transport():
    server = TcpRecordServer()
    try:
        client = TcpRecordClient(server.address)
        payloads = [encode_arrays({"x": np.arange(i + 1)}) for i in range(5)]
        for p in payloads:
            assert client.push(p)
        got = []
        import time
        deadline = time.time() + 10
        while len(got) < 5 and time.time() < deadline:
            rec = server.pop()
            if rec is not None:
                got.append(rec)
        # pop() yields (conn_id, payload); one client => one conn id,
        # payloads in send order.
        assert [p for _, p in got] == payloads
        assert len({c for c, _ in got}) == 1
        client.close()
    finally:
        server.close()


def test_shed_bookkeeping_is_threadsafe(capsys):
    """Regression for the ISSUE 13 lock-discipline race fix: _shed runs
    on every serve thread whose backpressure wait expired at once, and
    the unlocked read-then-set of _shed_alarmed let concurrent shedders
    each see False and emit duplicate "once per episode" alarms (while
    the unlocked += lost shed_records increments). Under the lock the
    invariants are exact: N concurrent sheds -> N counted records, ONE
    alarm line per episode."""
    import json as _json
    import sys as _sys
    import threading

    server = TcpRecordServer()
    n_threads = 16
    old_interval = _sys.getswitchinterval()
    _sys.setswitchinterval(1e-6)  # make the lost-update window huge
    try:
        start = threading.Barrier(n_threads)

        def shed():
            start.wait()
            for _ in range(50):
                server._shed(0)

        workers = [threading.Thread(target=shed, name=f"shed-{i}",
                                    daemon=True)
                   for i in range(n_threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
    finally:
        _sys.setswitchinterval(old_interval)
        server.close()
    assert server.shed_records == n_threads * 50
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "transport_shedding" in ln]
    assert len(lines) == 1, lines
    assert _json.loads(lines[0])["transport_shedding"] is True
    # A successful append resets the episode under the lock; the NEXT
    # shed alarms again (one alarm PER EPISODE, not one per process).
    with server._lock:
        server._shed_alarmed = False
    server._shed(0)
    assert "transport_shedding" in capsys.readouterr().out


def test_stale_native_lib_never_shadows_edited_source(tmp_path):
    """The build is keyed on the source's CONTENT: a library left on
    disk by an older source (the .so files are ignored by git and may be
    copied around with any mtime) has another name, so an edited .cc is
    always rebuilt and an unchanged one is reused."""
    import ctypes
    import time

    from dist_dqn_tpu.actors.transport import build_native_lib

    src = tmp_path / "answer.cc"
    src.write_text('extern "C" int answer() { return 1; }\n')
    first = build_native_lib("answer.cc", "libanswer.so", directory=tmp_path)
    assert ctypes.CDLL(str(first)).answer() == 1
    assert build_native_lib("answer.cc", "libanswer.so",
                            directory=tmp_path) == first

    src.write_text('extern "C" int answer() { return 2; }\n')
    # Make the stale library look NEWER than the edited source — the
    # case an mtime comparison gets wrong.
    future = time.time() + 3600
    os.utime(first, (future, future))
    second = build_native_lib("answer.cc", "libanswer.so",
                              directory=tmp_path)
    assert second != first
    assert ctypes.CDLL(str(second)).answer() == 2
    assert not list(tmp_path.glob("*.tmp"))
