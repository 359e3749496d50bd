"""Native data-plane fast path (ISSUE 19): byte-identity with the Python
path, by golden corpus.

Every fast-path arm — AEAD seal/open, GenerateResponse/GenerateRequest
envelope encode, strict envelope decode, frame batching — must produce
bytes (or decoded values) IDENTICAL to the pure-Python path it replaces,
including nonce sequencing, the 10 MB frame cap, and corrupt/truncated
frame rejection.  The swarm must also serve correctly with the native
plane disabled outright (CROWDLLAMA_NO_NATIVE=1), and the first compile
must never stall a live event loop.
"""

import asyncio
import ctypes
import time

import pytest

from crowdllama_tpu import native
from crowdllama_tpu.core import llama_v1_pb2 as pb
from crowdllama_tpu.core import wire
from crowdllama_tpu.core.messages import genresp_frame_bytes, resp_msg
from crowdllama_tpu.net import secure
from crowdllama_tpu.utils.crypto_compat import ChaCha20Poly1305, InvalidTag

lib = native.ensure_built() and native.load()
needs_native = pytest.mark.skipif(not lib, reason="no native toolchain")

KEY = bytes(range(32))


# ------------------------------------------------------------- AEAD seal


def _py_seal_frames(aead, ctr: int, data: bytes, chunk: int,
                    with_eof: bool = False) -> tuple[bytes, int]:
    """The SecureWriter Python path, verbatim: chunk, seal, frame."""
    out = bytearray()
    chunks = [data[i:i + chunk] for i in range(0, len(data), chunk)]
    if with_eof or not data:
        chunks.append(b"")
    for c in chunks:
        nonce = ctr.to_bytes(12, "big")
        ctr += 1
        ct = aead.encrypt(nonce, c, None)
        out += len(ct).to_bytes(4, "big") + ct
    return bytes(out), ctr


AEAD_CORPUS = [
    (b"", 256, True),                     # pure authenticated EOF
    (b"x", 256, False),                   # single tiny frame
    (b"hello \xf0\x9f\xa6\x99 world", 256, False),
    (bytes(range(256)) * 4, 256, False),  # 4 exact chunk boundaries
    (b"q" * 1000, 256, True),             # partial tail + EOF marker
    (b"z" * (256 * 1024 + 17), 256 * 1024, False),  # real CHUNK size
]


@needs_native
def test_aead_seal_golden_corpus_byte_identity():
    """Native seal output == Python seal output, frame for frame, across
    chunk boundaries, EOF markers and an advancing nonce counter — on ONE
    session so the sequence numbers themselves are exercised."""
    nat = native.AeadSession(lib, KEY, secure._NATIVE_FLAVOR)
    aead = ChaCha20Poly1305(KEY)
    ctr = 0
    for data, chunk, with_eof in AEAD_CORPUS:
        want, ctr = _py_seal_frames(aead, ctr, data, chunk, with_eof)
        got = nat.seal_frames(data, chunk, with_eof=with_eof) if data \
            else nat.seal_frames(b"", chunk, with_eof=True)
        assert got == want, f"case {data[:16]!r} len={len(data)}"
        assert nat.counter == ctr


@needs_native
def test_aead_open_parity_and_counter_on_tamper():
    """Python-sealed frames open natively; a corrupted frame is rejected
    by BOTH paths and both counters still advance (replay alignment)."""
    aead = ChaCha20Poly1305(KEY)
    nat = native.AeadSession(lib, KEY, secure._NATIVE_FLAVOR)
    pt0, pt1, pt2 = b"alpha", b"bravo" * 100, b"charlie"
    cts = []
    for i, p in enumerate((pt0, pt1, pt2)):
        cts.append(aead.encrypt(i.to_bytes(12, "big"), p, None))

    assert nat.open(cts[0]) == pt0
    # Frame 1 corrupted: native returns None, Python raises InvalidTag —
    # and both advance their counter past the bad frame.
    bad = bytearray(cts[1])
    bad[7] ^= 0x40
    assert nat.open(bytes(bad)) is None
    assert nat.counter == 2
    with pytest.raises(InvalidTag):
        aead.decrypt((1).to_bytes(12, "big"), bytes(bad), None)
    # Frame 2 still opens: the counters stayed in lockstep.
    assert nat.open(cts[2]) == pt2

    # Truncated ciphertext (shorter than the tag) is rejected too.
    assert nat.open(cts[0][:10]) is None
    with pytest.raises(InvalidTag):
        aead.decrypt((3).to_bytes(12, "big"), cts[0][:10], None)


@needs_native
def test_rfc8439_chacha20poly1305_vector():
    """The ChaCha20-Poly1305 arm is pinned to RFC 8439 §2.8.2 — not just
    self-consistent, actually the cipher."""
    key = bytes.fromhex(
        "808182838485868788898a8b8c8d8e8f"
        "909192939495969798999a9b9c9d9e9f")
    nonce = bytes.fromhex("070000004041424344454647")
    aad = bytes.fromhex("50515253c0c1c2c3c4c5c6c7")
    plaintext = (b"Ladies and Gentlemen of the class of '99: If I could "
                 b"offer you only one tip for the future, sunscreen would "
                 b"be it.")
    want_ct = bytes.fromhex(
        "d31a8d34648e60db7b86afbc53ef7ec2a4aded51296e08fea9e2b5a736ee62d6"
        "3dbea45e8ca9671282fafb69da92728b1a71de0a9e060b2905d6a5b67ecd3b36"
        "92ddbd7f2d778b8c9803aee328091b58fab324e4fad675945585808b4831d7bc"
        "3ff4def08e4b7a9de576d26586cec64b6116")
    want_tag = bytes.fromhex("1ae10b594f09e26a7e902ecbd0600691")
    out = ctypes.create_string_buffer(len(plaintext) + 16)
    n = lib.cl_aead_seal_raw(key, native.FLAVOR_CHACHA, nonce, aad,
                             len(aad), plaintext, len(plaintext), out,
                             len(out))
    assert n == len(plaintext) + 16
    assert out.raw[:len(plaintext)] == want_ct
    assert out.raw[len(plaintext):n] == want_tag


@needs_native
async def test_secure_stream_cross_mode_interop(monkeypatch):
    """A native SecureWriter's bytes decrypt on a pure-Python
    SecureReader and vice versa: the wire format is one format."""

    class _Sink:
        def __init__(self):
            self.buf = bytearray()

        def write(self, b):
            self.buf += b

        def write_eof(self):
            pass

    payload = b"interop " * 5000  # > one CHUNK

    def _writer_bytes(no_native: bool) -> bytes:
        if no_native:
            monkeypatch.setenv("CROWDLLAMA_NO_NATIVE", "1")
        else:
            monkeypatch.delenv("CROWDLLAMA_NO_NATIVE", raising=False)
        sink = _Sink()
        w = secure.SecureWriter(sink, KEY)
        assert (w._native is None) == no_native
        w.write(payload)
        w.write_eof()
        return bytes(sink.buf)

    native_bytes = _writer_bytes(no_native=False)
    python_bytes = _writer_bytes(no_native=True)
    assert native_bytes == python_bytes  # golden: full wire identity

    for reader_native, data in ((True, python_bytes),
                                (False, native_bytes)):
        if reader_native:
            monkeypatch.delenv("CROWDLLAMA_NO_NATIVE", raising=False)
        else:
            monkeypatch.setenv("CROWDLLAMA_NO_NATIVE", "1")
        r = asyncio.StreamReader()
        r.feed_data(data)
        r.feed_eof()
        sr = secure.SecureReader(r, KEY)
        assert (sr._native is not None) == reader_native
        assert await sr.read(-1) == payload


@needs_native
async def test_tampered_stream_rejected_identically(monkeypatch):
    """Flipping one ciphertext byte raises TamperError on both reader
    paths — same error class, same surviving-frame prefix."""

    class _Sink:
        def __init__(self):
            self.buf = bytearray()

        def write(self, b):
            self.buf += b

    sink = _Sink()
    w = secure.SecureWriter(sink, KEY)
    w.write(b"frame-one")
    w.write(b"frame-two")
    data = bytearray(sink.buf)
    data[-3] ^= 0x01  # corrupt the second frame's ciphertext

    for no_native in (False, True):
        if no_native:
            monkeypatch.setenv("CROWDLLAMA_NO_NATIVE", "1")
        else:
            monkeypatch.delenv("CROWDLLAMA_NO_NATIVE", raising=False)
        r = asyncio.StreamReader()
        r.feed_data(bytes(data))
        r.feed_eof()
        sr = secure.SecureReader(r, KEY)
        assert await sr.readexactly(len(b"frame-one")) == b"frame-one"
        with pytest.raises(secure.TamperError):
            await sr.readexactly(len(b"frame-two"))


# -------------------------------------------------------- envelope encode


def _pb_genresp_frame(model, response, worker_id="", done=True,
                      done_reason="stop", total_duration_ns=0,
                      prompt_tokens=0, completion_tokens=0, created_ns=0,
                      trace_id="", parent_span="") -> bytes:
    """The pb reference path, mirroring messages.genresp_frame_bytes."""
    resp = pb.GenerateResponse(
        model=model, response=response, done=done,
        done_reason=done_reason if done else "", worker_id=worker_id,
        total_duration=total_duration_ns, prompt_tokens=prompt_tokens,
        completion_tokens=completion_tokens)
    resp.created_at.FromNanoseconds(created_ns)
    msg = resp_msg(resp)
    if trace_id:
        msg.trace_id = trace_id
    if parent_span:
        msg.parent_span = parent_span
    return wire.encode_frame(msg)


GENRESP_CORPUS = [
    dict(model="m", response="tok"),
    dict(model="", response="", done=False, done_reason="ignored",
         created_ns=0),
    dict(model="llama-70b", response="héllo 🦙", worker_id="w-1234",
         done=True, done_reason="stop", total_duration_ns=2**62,
         prompt_tokens=2**31 - 1, completion_tokens=12345,
         created_ns=1_712_345_678_901_234_567, trace_id="t" * 32,
         parent_span="gateway"),
    dict(model="m", response="x" * 300_000, created_ns=999_999_999),
    dict(model="m", response="mid", done=False,
         created_ns=1_000_000_000),  # zero-nanos timestamp edge
]


@needs_native
def test_genresp_encode_golden_corpus_byte_identity():
    for kw in GENRESP_CORPUS:
        got = wire.encode_genresp_frame(**kw)
        assert got is not None
        assert got == _pb_genresp_frame(**kw), kw.get("response", "")[:20]


@needs_native
def test_genresp_frame_bytes_uses_one_timestamp():
    created = time.time_ns()
    frame = genresp_frame_bytes("m", "r", created_ns=created)
    assert frame == _pb_genresp_frame("m", "r", created_ns=created)


def _pb_genreq_frame(trace_id="", parent_span="", kv_donor="",
                     migrate=False, **kw) -> bytes:
    from crowdllama_tpu.core.messages import create_generate_request

    msg = create_generate_request(**kw)
    if kv_donor:
        msg.generate_request.kv_donor = kv_donor
    if migrate:
        msg.generate_request.migrate = True
    if trace_id:
        msg.trace_id = trace_id
    if parent_span:
        msg.parent_span = parent_span
    return wire.encode_frame(msg)


GENREQ_CORPUS = [
    dict(model="m", prompt="hi"),
    dict(model="llama", stream=True,
         messages=({"role": "user", "content": "q?"},
                   {"role": "assistant", "content": "a 🦙"},
                   {"role": "user", "content": ""}),
         max_tokens=512, temperature=0.75, top_p=0.9,
         seed=2**63 + 12345, stop=("\n\n", "###"), top_k=40,
         repeat_penalty=1.1, trace_id="trace-abc", parent_span="gw"),
    dict(model="m", prompt="p" * 200_000, seed=2**64 - 1),
    dict(model="m", prompt="cont", kv_donor="donor-peer", migrate=True,
         trace_id="t1"),
    dict(model="m", messages=({"content": "defaults-to-user-role"},)),
]


@needs_native
def test_genreq_encode_golden_corpus_byte_identity():
    for kw in GENREQ_CORPUS:
        got = wire.encode_genreq_frame(**kw)
        assert got is not None
        assert got == _pb_genreq_frame(**kw), kw["model"]


@needs_native
def test_genreq_ambiguous_values_fall_back():
    """Shapes whose proto3 serialization is ambiguous (or that the pb
    builder rejects) return None — the caller's pb path is authoritative."""
    assert wire.encode_genreq_frame(model="m", seed=-1) is None
    assert wire.encode_genreq_frame(model="m", seed=2**64) is None
    assert wire.encode_genreq_frame(model="m", max_tokens=2**31) is None
    assert wire.encode_genreq_frame(model="m", temperature=-0.0) is None
    assert wire.encode_genreq_frame(
        model="m", messages=({"role": "user", "content": 7},)) is None


@needs_native
def test_encode_respects_10mb_cap_identically():
    """The 10 MB frame cap (pbwire.go:53) raises the SAME WireError on
    both paths — the native path must not smuggle oversized frames."""
    big = "x" * (wire.MAX_MESSAGE_SIZE + 10)
    with pytest.raises(wire.WireError, match="exceeds maximum"):
        wire.encode_genresp_frame(model="m", response=big)
    with pytest.raises(wire.WireError, match="exceeds maximum"):
        _pb_genresp_frame(model="m", response=big)


# -------------------------------------------------------- envelope decode


@needs_native
def test_decode_fast_golden_corpus_value_identity(monkeypatch):
    # Pin the size-aware dispatch open so every corpus entry (including
    # the tiny ones upb would normally take) drives the native decoder.
    monkeypatch.setattr(wire, "NATIVE_ENVELOPE_MIN_BYTES", 0)
    for kw in GENRESP_CORPUS:
        payload = _pb_genresp_frame(**kw)[4:]
        fast = wire.decode_payload_fast(payload)
        ref = wire.decode_payload(payload)
        assert isinstance(fast, wire.FastBaseMessage)
        assert fast.WhichOneof("message") == ref.WhichOneof("message")
        assert fast.trace_id == ref.trace_id
        assert fast.parent_span == ref.parent_span
        f, r = fast.generate_response, ref.generate_response
        for field in ("model", "response", "done", "done_reason",
                      "worker_id", "total_duration", "prompt_tokens",
                      "completion_tokens"):
            assert getattr(f, field) == getattr(r, field), field
        assert f.created_at.ToNanoseconds() == r.created_at.ToNanoseconds()


@needs_native
def test_decode_fast_refuses_unusual_shapes(monkeypatch):
    """Anything that is not a canonical GenerateResponse envelope comes
    back as a REAL pb message: other arms, unknown fields, trailing
    garbage — parity by refusal."""
    monkeypatch.setattr(wire, "NATIVE_ENVELOPE_MIN_BYTES", 0)
    req_payload = _pb_genreq_frame(model="m", prompt="p")[4:]
    assert isinstance(wire.decode_payload_fast(req_payload), pb.BaseMessage)

    resp_payload = _pb_genresp_frame(model="m", response="r")[4:]
    # Unknown field appended (field 15, varint 1): pb keeps it, the strict
    # decoder refuses.
    unknown = resp_payload + bytes([15 << 3, 1])
    out = wire.decode_payload_fast(unknown)
    assert isinstance(out, pb.BaseMessage)
    assert out.generate_response.model == "m"

    # Truncated payload: both paths reject (pb raises; fast must not
    # fabricate a message from a prefix).
    with pytest.raises(Exception):
        wire.decode_payload(resp_payload[:-3])
    with pytest.raises(Exception):
        fast = wire.decode_payload_fast(resp_payload[:-3])
        assert isinstance(fast, pb.BaseMessage)  # pragma: no cover


# ------------------------------------------------------------- batching


class _RecordingWriter:
    def __init__(self, fail_after: int | None = None):
        self.writes: list[bytes] = []
        self.fail_after = fail_after

    def write(self, data: bytes) -> None:
        if self.fail_after is not None and len(self.writes) >= self.fail_after:
            raise ConnectionResetError("boom")
        self.writes.append(bytes(data))

    async def drain(self) -> None:
        pass


async def test_frame_batcher_coalesces_one_tick():
    w = _RecordingWriter()
    b = wire.FrameBatcher(w)
    frames = [f"frame-{i}".encode() for i in range(10)]
    for f in frames:
        b.write(f)
    # First frame goes out inline (TTFT bound); the rest wait for the tick.
    assert w.writes == [frames[0]]
    await asyncio.sleep(0)         # let the call_soon tick run
    assert w.writes == [frames[0], b"".join(frames[1:])]
    assert b.batched_writes == 10 and b.flushes == 2


async def test_frame_batcher_first_frame_lands_without_suspending():
    """A producer that never yields to the loop must still get its first
    frame on the wire BEFORE the stream can end or die: a chaos
    kill_stream at chunk 4 has to be observable by the gateway as
    MID-stream progress (→ the counted token-replay failover path, the
    soak's stall_watchdog_counters invariant), and TTFT must not degrade
    to whole-stream latency when the engine bursts."""
    w = _RecordingWriter()
    b = wire.FrameBatcher(w)
    for i in range(5):
        b.write(f"chunk-{i}".encode())
    # No suspension has happened; if the transport is severed here the
    # peer's first chunk is already out.
    assert w.writes == [b"chunk-0"]
    await asyncio.sleep(0)
    assert w.writes == [b"chunk-0", b"chunk-1chunk-2chunk-3chunk-4"]


async def test_frame_batcher_bounds_pending_bytes():
    w = _RecordingWriter()
    b = wire.FrameBatcher(w, max_pending=100)
    b.write(b"first")              # first frame: inline (TTFT)
    b.write(b"a" * 60)
    assert w.writes == [b"first"]
    b.write(b"b" * 60)             # crosses the cap: inline flush
    assert len(w.writes) == 2 and len(w.writes[1]) == 120


async def test_frame_batcher_surfaces_write_error_on_drain():
    w = _RecordingWriter(fail_after=0)
    b = wire.FrameBatcher(w)
    b.write(b"doomed")
    await asyncio.sleep(0)
    with pytest.raises(ConnectionResetError):
        await b.drain()


async def test_frame_batcher_flush_forces_pending():
    w = _RecordingWriter()
    b = wire.FrameBatcher(w)
    b.write(b"tail")
    await b.flush()
    assert w.writes == [b"tail"]


# ------------------------------------------------- fallback + observability


def test_no_native_env_disables_and_counts_fallbacks(monkeypatch):
    monkeypatch.setenv("CROWDLLAMA_NO_NATIVE", "1")
    assert native.load() is None
    assert not native.native_enabled()
    before = native.stats()["fallbacks"].get("envelope", 0)
    assert wire.encode_genresp_frame(model="m", response="r") is None
    assert native.stats()["fallbacks"]["envelope"] == before + 1
    from crowdllama_tpu.obs.http import native_metric_lines

    lines = native_metric_lines()
    assert "crowdllama_native_enabled 0" in lines
    assert any(l.startswith(
        'crowdllama_native_fallbacks_total{component="envelope"}')
        for l in lines)


@needs_native
def test_native_metric_lines_when_enabled():
    from crowdllama_tpu.obs.http import native_metric_lines

    lines = native_metric_lines()
    assert "crowdllama_native_enabled 1" in lines
    # Declared components always present (rate() without sparse gaps).
    for comp in ("aead", "envelope", "frame_scan"):
        assert any(f'component="{comp}"' in l for l in lines), comp


# ---------------------------------------------------- async-build bugfix


@needs_native
async def test_first_build_never_blocks_event_loop(monkeypatch, tmp_path):
    """Regression (ISSUE 19 satellite bugfix): the first native compile
    used to run subprocess.run synchronously under the event loop,
    freezing every connection for the length of a g++ run.  load() must
    return None immediately and hand the build to a daemon thread; the
    loop's worst tick gap while the (slow) build runs must stay tiny."""
    import shutil
    import threading

    native._reset_for_tests()
    build_started = threading.Event()

    def _slow_compile(src, out):
        build_started.set()
        time.sleep(0.5)            # a synchronous stall the loop must dodge
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(so_real, out)

    so_real = native._so_path()
    monkeypatch.setattr(native, "_so_path",
                        lambda: tmp_path / "fresh" / "native.so")
    monkeypatch.setattr(native, "_compile", _slow_compile)
    try:
        t0 = time.perf_counter()
        assert native.load() is None          # immediate Python fallback
        assert time.perf_counter() - t0 < 0.1
        # Heartbeat across the build: if the compile ran on-loop, one gap
        # would be ~0.5s.
        max_gap, last = 0.0, time.perf_counter()
        deadline = last + 5.0
        while native.load() is None and time.perf_counter() < deadline:
            await asyncio.sleep(0.01)
            now = time.perf_counter()
            max_gap = max(max_gap, now - last)
            last = now
        assert build_started.is_set()
        assert native.load() is not None, "background build never finished"
        assert max_gap < 0.25, (
            f"event loop stalled {max_gap:.2f}s during the native build — "
            "the compile ran on the loop thread")
    finally:
        native._reset_for_tests()
        monkeypatch.undo()
        native.load()              # restore the real library for later tests


@needs_native
def test_ensure_built_is_synchronous_outside_loop():
    assert native.ensure_built() is True
    assert native.load() is not None


# ------------------------------------------------------ no-native swarm e2e


async def test_swarm_serves_with_native_disabled(monkeypatch):
    """CROWDLLAMA_NO_NATIVE=1 end-to-end: a worker + gateway swarm boots,
    streams a chat response, and closes cleanly on the pure-Python data
    plane — the fallback is a first-class mode, not a degraded one."""
    import aiohttp

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import FakeEngine
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    monkeypatch.setenv("CROWDLLAMA_NO_NATIVE", "1")
    assert not native.native_enabled()

    model = "tiny-test"
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    def _cfg():
        return Configuration(listen_host="127.0.0.1", model=model,
                             bootstrap_peers=[bootstrap],
                             intervals=Intervals.default())

    worker = Peer(Ed25519PrivateKey.generate(), _cfg(),
                  engine=FakeEngine(models=[model]), worker_mode=True)
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(),
                    engine=FakeEngine(models=[]), worker_mode=False)
    gateway = Gateway(consumer, port=0, host="127.0.0.1")
    started = False
    try:
        await worker.start()
        await consumer.start()
        await gateway.start()
        started = True
        gw_port = gateway._runner.addresses[0][1]

        deadline = asyncio.get_running_loop().time() + 30
        while asyncio.get_running_loop().time() < deadline:
            healthy = [p for p in consumer.peer_manager.get_healthy_peers()
                       if p.is_worker]
            if healthy:
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError("discovery stalled")

        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = {"model": model,
                "messages": [{"role": "user", "content": "no-native probe"}],
                "stream": True}
        async with aiohttp.ClientSession() as s:
            async with s.post(url, json=body) as resp:
                assert resp.status == 200, await resp.text()
                chunks = (await resp.text()).strip().splitlines()
        assert len(chunks) >= 1
        import json as _json

        last = _json.loads(chunks[-1])
        assert last.get("done") is True
        # The whole request ran on the Python path and counted at least
        # one AEAD fallback (every secure stream records one).
        assert native.stats()["fallbacks"].get("aead", 0) >= 1
    finally:
        if started:
            await gateway.stop()
        await consumer.stop()
        await worker.stop()
        await boot_host.close()
