"""Authenticated encryption for host streams (X25519 + ChaCha20-Poly1305).

The reference gets transport security for free from libp2p's noise/TLS
defaults (/root/reference/pkg/dht/dht.go:91-98,
internal/discovery/discovery.go:48-84); this module is the counterpart for
the asyncio host.  The existing signed-nonce handshake (net/host.py) gains
an ephemeral X25519 key in each signed hello — the Ed25519 signature binds
the ephemeral key to the peer identity, so a middleman cannot substitute its
own — and both sides HKDF the ECDH secret into two directional
ChaCha20-Poly1305 keys.  Every byte after the handshake crosses the wire as
AEAD frames: ``4-byte BE ciphertext length || ciphertext``, nonce = 96-bit
big-endian frame counter per direction.  Tampering, truncation mid-frame,
and replay (counter reuse) all fail the AEAD tag and surface as
``TamperError`` — a ``ConnectionResetError`` subclass so every existing
wire-error handler treats it as a dead stream.

The adapters expose the asyncio Stream{Reader,Writer} surface the protocol
code actually uses (readexactly / read / write / drain / write_eof / close /
wait_closed / get_extra_info), so json frames, length-prefixed protobuf and
tensor frames work unchanged on top.
"""

from __future__ import annotations

import asyncio
import time

from crowdllama_tpu import native
from crowdllama_tpu.utils.crypto_compat import (
    HAVE_CRYPTOGRAPHY,
    HKDF,
    SHA256,
    ChaCha20Poly1305,
    InvalidTag,
    X25519PrivateKey,
    X25519PublicKey,
)

MAX_FRAME = 1 * 1024 * 1024  # ciphertext cap per frame (plaintext chunks 256K)
CHUNK = 256 * 1024

# Process-wide AEAD CPU attribution (seal + open), fed by every
# SecureWriter/SecureReader in the process.  Per-request CPU breakdowns
# (gateway.hotpath_snapshot) read deltas of these to report aead_us.
# Process-wide is deliberate: splitting the counter per stream would put
# a dict lookup on every frame for no analytical gain.
_aead_ns = 0
_aead_ops = 0


def aead_stats() -> tuple[int, int]:
    """(total nanoseconds spent in AEAD seal/open, operation count)."""
    return _aead_ns, _aead_ops


class TamperError(ConnectionResetError):
    """AEAD verification failed: modified, truncated or replayed traffic.

    Subclasses ConnectionResetError so every existing wire-error handler
    (stream services, discovery, health probes) already treats it as a dead
    stream — which is the only safe response."""


def derive_keys(
    shared: bytes, proto: str, client_id: str, server_id: str,
    client_nonce: str, server_nonce: str,
) -> tuple[bytes, bytes]:
    """(client→server key, server→client key) from the ECDH secret, bound to
    the protocol, both identities and both handshake nonces."""
    # v2: authenticated close frames (empty-plaintext EOF marker).  The
    # version lives in the KDF info so a mixed-version pair fails at the
    # first frame (garbage keys) instead of mid-stream with a confusing
    # TamperError on every legitimate EOF.
    info = "|".join(["crowdllama-tpu-secure-v2", proto, client_id, server_id,
                     client_nonce, server_nonce]).encode()
    okm = HKDF(algorithm=SHA256(), length=64,
               salt=b"crowdllama-tpu-hkdf-salt", info=info).derive(shared)
    return okm[:32], okm[32:]


def ecdh(private: X25519PrivateKey, peer_public_raw: bytes) -> bytes:
    return private.exchange(X25519PublicKey.from_public_bytes(peer_public_raw))


# The native AEAD context must match the cipher the Python path would use:
# real ChaCha20-Poly1305 when the ``cryptography`` package is installed,
# otherwise the compat encrypt-then-MAC scheme.  Wire bytes are identical
# either way — asserted by tests/test_native_dataplane.py's golden corpus.
_NATIVE_FLAVOR = native.FLAVOR_CHACHA if HAVE_CRYPTOGRAPHY else native.FLAVOR_COMPAT


def _native_session(key: bytes) -> "native.AeadSession | None":
    lib = native.load()
    if lib is None:
        native.record_fallback("aead")
        return None
    try:
        return native.AeadSession(lib, key, _NATIVE_FLAVOR)
    except Exception:
        native.record_fallback("aead")
        return None


class SecureWriter:
    """Encrypting adapter over an asyncio StreamWriter."""

    def __init__(self, writer: asyncio.StreamWriter, key: bytes):
        self._w = writer
        self._native = _native_session(key)
        self._aead = None if self._native is not None else ChaCha20Poly1305(key)
        self._ctr = 0

    @property
    def counter(self) -> int:
        """Frames sealed so far (native or Python path)."""
        return self._native.counter if self._native is not None else self._ctr

    def _frame(self, chunk: bytes) -> None:
        """Seal exactly one frame (empty chunk = authenticated close)."""
        global _aead_ns, _aead_ops
        if self._native is not None:
            t0 = time.perf_counter_ns()
            if chunk:
                frame = self._native.seal_frames(bytes(chunk), len(chunk))
            else:
                frame = self._native.seal_frames(b"", CHUNK, with_eof=True)
            _aead_ns += time.perf_counter_ns() - t0
            _aead_ops += 1
            self._w.write(frame)
            return
        nonce = self._ctr.to_bytes(12, "big")
        self._ctr += 1
        t0 = time.perf_counter_ns()
        ct = self._aead.encrypt(nonce, chunk, None)
        _aead_ns += time.perf_counter_ns() - t0
        _aead_ops += 1
        self._w.write(len(ct).to_bytes(4, "big") + ct)

    def write(self, data: bytes) -> None:
        global _aead_ns, _aead_ops
        if self._native is not None:
            if not data:
                return
            t0 = time.perf_counter_ns()
            before = self._native.counter
            frames = self._native.seal_frames(bytes(data), CHUNK)
            _aead_ns += time.perf_counter_ns() - t0
            _aead_ops += self._native.counter - before
            self._w.write(frames)
            return
        data = bytes(data)
        for off in range(0, len(data), CHUNK):
            self._frame(data[off:off + CHUNK])

    async def drain(self) -> None:
        await self._w.drain()

    def write_eof(self) -> None:
        # Authenticated close: an empty-plaintext frame marks intentional
        # end-of-stream.  A bare TCP FIN (which an on-path attacker can
        # inject at a frame boundary) is then distinguishable from a
        # legitimate end by read-to-EOF consumers.
        self._frame(b"")
        self._w.write_eof()

    def can_write_eof(self) -> bool:
        return self._w.can_write_eof()

    def close(self) -> None:
        self._w.close()

    def is_closing(self) -> bool:
        return self._w.is_closing()

    async def wait_closed(self) -> None:
        await self._w.wait_closed()

    def get_extra_info(self, name, default=None):
        return self._w.get_extra_info(name, default)


class SecureReader:
    """Decrypting adapter over an asyncio StreamReader."""

    def __init__(self, reader: asyncio.StreamReader, key: bytes):
        self._r = reader
        self._native = _native_session(key)
        self._aead = None if self._native is not None else ChaCha20Poly1305(key)
        self._ctr = 0
        self._buf = bytearray()
        self._eof = False
        self._authenticated_eof = False  # saw the empty close frame

    @property
    def counter(self) -> int:
        """Frames consumed so far (native or Python path)."""
        return self._native.counter if self._native is not None else self._ctr

    async def _fill(self) -> None:
        """Read and decrypt one frame into the plaintext buffer."""
        try:
            header = await self._r.readexactly(4)
        except asyncio.IncompleteReadError as e:
            if e.partial:
                raise TamperError("stream cut mid-frame header") from e
            self._eof = True  # bare FIN at a frame boundary (unauthenticated)
            return
        length = int.from_bytes(header, "big")
        if not 16 <= length <= MAX_FRAME:
            raise TamperError(f"bad frame length {length}")
        try:
            ct = await self._r.readexactly(length)
        except asyncio.IncompleteReadError as e:
            raise TamperError("stream cut mid-frame") from e
        global _aead_ns, _aead_ops
        if self._native is not None:
            # The native context advances its counter on success AND on tag
            # failure, matching the ``finally`` of the Python path below.
            t0 = time.perf_counter_ns()
            pt = self._native.open(ct)
            _aead_ns += time.perf_counter_ns() - t0
            _aead_ops += 1
            if pt is None:
                raise TamperError("frame failed authentication")
        else:
            nonce = self._ctr.to_bytes(12, "big")
            self._ctr += 1
            t0 = time.perf_counter_ns()
            try:
                pt = self._aead.decrypt(nonce, ct, None)
            except InvalidTag as e:
                raise TamperError("frame failed authentication") from e
            finally:
                _aead_ns += time.perf_counter_ns() - t0
                _aead_ops += 1
        if not pt:  # authenticated close marker (SecureWriter.write_eof)
            self._eof = True
            self._authenticated_eof = True
            return
        self._buf += pt

    async def readexactly(self, n: int) -> bytes:
        while len(self._buf) < n:
            if self._eof:
                raise asyncio.IncompleteReadError(bytes(self._buf), n)
            await self._fill()
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out

    async def read(self, n: int = -1) -> bytes:
        if n < 0:
            while not self._eof:
                await self._fill()
            if not self._authenticated_eof:
                # An attacker can inject a FIN at a frame boundary; a
                # read-to-EOF consumer must not accept the prefix as the
                # complete message unless the peer sent the signed close.
                raise TamperError("stream ended without authenticated close")
            out = bytes(self._buf)
            self._buf.clear()
            return out
        while not self._buf and not self._eof:
            await self._fill()
        if not self._buf and self._eof and not self._authenticated_eof:
            # Bounded-read loops (read(n) until b"") are also read-to-EOF
            # consumers — same truncation rule as read(-1).
            raise TamperError("stream ended without authenticated close")
        out = bytes(self._buf[:n])
        del self._buf[:n]
        return out

    def at_eof(self) -> bool:
        # Consult the UNDERLYING reader too: asyncio marks it at_eof as
        # soon as the transport feeds a FIN, without any read having run —
        # so a pooled idle stream whose remote died is detectable here
        # before a borrower burns a roundtrip on it (StreamPool.get).
        # _buf must be empty either way: buffered plaintext is still
        # readable data, EOF or not.
        return not self._buf and (self._eof or self._r.at_eof())
