"""Native (C++) runtime components, loaded via ctypes.

The reference's runtime is compiled Go; these are the framework's C++
equivalents for the data-plane hot paths (wire frame scanning, Kademlia
routing table, per-session AEAD seal/open, llama.v1 envelope fast paths —
see _src/crowdllama_native.cpp and docs/NATIVE.md).  The library is
compiled on demand with g++ into ``_build/`` keyed by a source hash; every
consumer falls back to pure Python when the toolchain or a prior build is
unavailable, so the package works without a compiler.

The first build can take tens of seconds.  ``load()`` therefore refuses to
compile synchronously while an asyncio event loop is running on the
calling thread — it kicks the build to a daemon thread and returns None
(Python fallback) until the artifact is ready.  Call ``ensure_built()``
from synchronous startup code (or ``make test``) to
front-load the compile.

Set CROWDLLAMA_NO_NATIVE=1 to force the Python fallbacks.
"""

from __future__ import annotations

import asyncio
import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from pathlib import Path

from crowdllama_tpu.utils.env import env_flag

log = logging.getLogger("crowdllama.native")

_SRC = Path(__file__).parent / "_src" / "crowdllama_native.cpp"
_BUILD_DIR = Path(__file__).parent / "_build"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_attempted = False
_bg_build: threading.Thread | None = None

ID_BYTES = 32
TAG_LEN = 16

# AEAD flavors (must match AeadCtx.flavor in the C++ source).
FLAVOR_COMPAT = 0  # SHAKE-256 stream + HMAC-SHA256/128 (crypto_compat)
FLAVOR_CHACHA = 1  # ChaCha20-Poly1305 (RFC 8439)

# ---------------------------------------------------------------------------
# Fallback accounting (exported on /metrics by gateway + obs.http).

_fallback_lock = threading.Lock()
_fallbacks: dict[str, int] = {}


def record_fallback(component: str) -> None:
    """Count one Python-fallback dispatch for a native-capable component."""
    with _fallback_lock:
        _fallbacks[component] = _fallbacks.get(component, 0) + 1


def native_enabled() -> bool:
    """True when the native library is loaded and dispatching."""
    return _lib is not None and not env_flag("CROWDLLAMA_NO_NATIVE")


def stats() -> dict:
    """Snapshot for /metrics: enabled flag + per-component fallback counts."""
    with _fallback_lock:
        return {"enabled": native_enabled(), "fallbacks": dict(_fallbacks)}


# ---------------------------------------------------------------------------
# ctypes mirrors of the C structs (see _src/crowdllama_native.cpp).


class ClGenRespFields(ctypes.Structure):
    _fields_ = [
        ("model", ctypes.c_char_p), ("model_len", ctypes.c_size_t),
        ("response", ctypes.c_char_p), ("response_len", ctypes.c_size_t),
        ("done_reason", ctypes.c_char_p), ("done_reason_len", ctypes.c_size_t),
        ("worker_id", ctypes.c_char_p), ("worker_id_len", ctypes.c_size_t),
        ("trace_id", ctypes.c_char_p), ("trace_id_len", ctypes.c_size_t),
        ("parent_span", ctypes.c_char_p), ("parent_span_len", ctypes.c_size_t),
        ("created_seconds", ctypes.c_int64),
        ("total_duration", ctypes.c_int64),
        ("created_nanos", ctypes.c_int32),
        ("has_created", ctypes.c_int32),
        ("done", ctypes.c_int32),
        ("prompt_tokens", ctypes.c_int32),
        ("completion_tokens", ctypes.c_int32),
        ("_pad", ctypes.c_int32),
    ]


class ClGenReqFields(ctypes.Structure):
    _fields_ = [
        ("model", ctypes.c_char_p), ("model_len", ctypes.c_size_t),
        ("prompt", ctypes.c_char_p), ("prompt_len", ctypes.c_size_t),
        ("kv_donor", ctypes.c_char_p), ("kv_donor_len", ctypes.c_size_t),
        ("trace_id", ctypes.c_char_p), ("trace_id_len", ctypes.c_size_t),
        ("parent_span", ctypes.c_char_p), ("parent_span_len", ctypes.c_size_t),
        ("msg_roles", ctypes.POINTER(ctypes.c_char_p)),
        ("msg_role_lens", ctypes.POINTER(ctypes.c_size_t)),
        ("msg_contents", ctypes.POINTER(ctypes.c_char_p)),
        ("msg_content_lens", ctypes.POINTER(ctypes.c_size_t)),
        ("stops", ctypes.POINTER(ctypes.c_char_p)),
        ("stop_lens", ctypes.POINTER(ctypes.c_size_t)),
        ("n_msgs", ctypes.c_int32),
        ("n_stop", ctypes.c_int32),
        ("stream", ctypes.c_int32),
        ("max_tokens", ctypes.c_int32),
        ("temperature", ctypes.c_float),
        ("top_p", ctypes.c_float),
        ("repeat_penalty", ctypes.c_float),
        ("top_k", ctypes.c_int32),
        ("seed", ctypes.c_uint64),
        ("migrate", ctypes.c_int32),
        ("_pad", ctypes.c_int32),
    ]


class ClGenRespView(ctypes.Structure):
    _fields_ = [
        ("model_off", ctypes.c_uint32), ("model_len", ctypes.c_uint32),
        ("response_off", ctypes.c_uint32), ("response_len", ctypes.c_uint32),
        ("done_reason_off", ctypes.c_uint32), ("done_reason_len", ctypes.c_uint32),
        ("worker_id_off", ctypes.c_uint32), ("worker_id_len", ctypes.c_uint32),
        ("trace_id_off", ctypes.c_uint32), ("trace_id_len", ctypes.c_uint32),
        ("parent_span_off", ctypes.c_uint32), ("parent_span_len", ctypes.c_uint32),
        ("created_seconds", ctypes.c_int64),
        ("total_duration", ctypes.c_int64),
        ("created_nanos", ctypes.c_int32),
        ("has_created", ctypes.c_int32),
        ("done", ctypes.c_int32),
        ("prompt_tokens", ctypes.c_int32),
        ("completion_tokens", ctypes.c_int32),
        ("_pad", ctypes.c_int32),
    ]


# -O3/-march=native matter here: the AEAD keystream and tag loops run
# ~2x faster than at -O2 on the bench host (the library is built on the
# machine that runs it, so tuning for the local CPU is safe).  The flag
# set AND the host CPU's identity participate in the .so cache key
# (_so_path): changing the flags invalidates stale artifacts, and a build
# directory that travels to another machine (a copied checkout) is rebuilt
# there instead of loading code tuned for the CPU it came from.
_CXX_FLAGS = ["-O3", "-march=native", "-funroll-loops", "-std=c++17",
              "-shared", "-fPIC"]


def _compile(src: Path, out: Path) -> None:
    out.parent.mkdir(parents=True, exist_ok=True)
    # Unique tmp per process: concurrent first-run compiles must not clobber
    # each other's output mid-write (the final replace is atomic).
    tmp = out.with_suffix(f".so.tmp.{os.getpid()}")
    try:
        try:
            subprocess.run(
                ["g++", *_CXX_FLAGS, "-o", str(tmp), str(src)],
                check=True, capture_output=True, timeout=120,
            )
        except subprocess.CalledProcessError:
            # Some toolchains reject -march=native (cross compilers,
            # exotic arches); the portable flag set is still correct.
            subprocess.run(
                ["g++", *[f for f in _CXX_FLAGS if f != "-march=native"],
                 "-o", str(tmp), str(src)],
                check=True, capture_output=True, timeout=120,
            )
        tmp.replace(out)
    finally:
        tmp.unlink(missing_ok=True)


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.cl_frame_scan.restype = ctypes.c_long
    lib.cl_frame_scan.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.cl_rt_new.restype = ctypes.c_void_p
    lib.cl_rt_new.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.cl_rt_free.restype = None
    lib.cl_rt_free.argtypes = [ctypes.c_void_p]
    lib.cl_rt_upsert.restype = ctypes.c_int
    lib.cl_rt_upsert.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 u8p, ctypes.POINTER(ctypes.c_int)]
    lib.cl_rt_remove.restype = ctypes.c_int
    lib.cl_rt_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.cl_rt_size.restype = ctypes.c_long
    lib.cl_rt_size.argtypes = [ctypes.c_void_p]
    lib.cl_rt_closest.restype = ctypes.c_long
    lib.cl_rt_closest.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_int, u8p]
    lib.cl_rt_dump.restype = ctypes.c_long
    lib.cl_rt_dump.argtypes = [ctypes.c_void_p, u8p, ctypes.c_long]
    lib.cl_aead_new.restype = ctypes.c_void_p
    lib.cl_aead_new.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.cl_aead_free.restype = None
    lib.cl_aead_free.argtypes = [ctypes.c_void_p]
    lib.cl_aead_ctr.restype = ctypes.c_uint64
    lib.cl_aead_ctr.argtypes = [ctypes.c_void_p]
    lib.cl_aead_set_ctr.restype = None
    lib.cl_aead_set_ctr.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.cl_aead_seal_frames.restype = ctypes.c_long
    lib.cl_aead_seal_frames.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
        ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.cl_aead_open.restype = ctypes.c_long
    lib.cl_aead_open.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.cl_aead_seal_raw.restype = ctypes.c_long
    lib.cl_aead_seal_raw.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.cl_env_encode_genresp.restype = ctypes.c_long
    lib.cl_env_encode_genresp.argtypes = [
        ctypes.POINTER(ClGenRespFields), ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.cl_env_encode_genreq.restype = ctypes.c_long
    lib.cl_env_encode_genreq.argtypes = [
        ctypes.POINTER(ClGenReqFields), ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.cl_env_decode_genresp.restype = ctypes.c_long
    lib.cl_env_decode_genresp.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ClGenRespView),
    ]
    lib.cl_env_seal_genresp.restype = ctypes.c_long
    lib.cl_env_seal_genresp.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ClGenRespFields), ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    return lib


def _cpu_identity() -> bytes:
    """What -march=native keyed the build on: the CPU model and its
    feature flags (first processor of /proc/cpuinfo), or the bare machine
    architecture where that file does not exist."""
    try:
        lines = Path("/proc/cpuinfo").read_text().splitlines()
    except OSError:
        return platform.machine().encode()
    picked = {}
    for line in lines:
        name, _, value = line.partition(":")
        name = name.strip()
        if name in ("model name", "flags", "Features") and name not in picked:
            picked[name] = value.strip()
    return (platform.machine() + repr(sorted(picked.items()))).encode()


def _so_path() -> Path:
    src_hash = hashlib.sha256(
        _SRC.read_bytes() + " ".join(_CXX_FLAGS).encode()
        + _cpu_identity()).hexdigest()[:16]
    return _BUILD_DIR / f"crowdllama_native-{src_hash}.so"


def _build_and_load() -> None:
    """Compile (if needed) + dlopen + declare; sets _lib. Caller holds _lock
    or runs on the dedicated background build thread."""
    global _lib
    so = _so_path()
    if not so.exists():
        _compile(_SRC, so)
    try:
        lib = _declare(ctypes.CDLL(str(so)))
    except OSError:
        # A corrupt cached artifact must not poison the cache forever:
        # drop it and rebuild once.
        so.unlink(missing_ok=True)
        _compile(_SRC, so)
        lib = _declare(ctypes.CDLL(str(so)))
    _lib = lib
    log.debug("native library loaded: %s", so.name)


def _in_running_loop() -> bool:
    try:
        asyncio.get_running_loop()
        return True
    except RuntimeError:
        return False


def load() -> ctypes.CDLL | None:
    """Build (if needed) and load the native library; None on any failure.

    Never compiles synchronously on a thread that is running an asyncio
    event loop: a cold g++ build takes seconds and would stall every
    connection on the loop.  In that case the build is started on a daemon
    thread and this call returns None (Python fallback); once the thread
    finishes, subsequent calls return the library.
    """
    global _lib, _load_attempted, _bg_build
    if env_flag("CROWDLLAMA_NO_NATIVE"):
        return None
    if _lib is not None:
        return _lib
    # A background build holds _lock for the whole compile; hot-path
    # callers must not queue on that mutex (it would stall the loop just
    # as badly as compiling inline would).
    bg = _bg_build
    if bg is not None and bg.is_alive():
        return None
    with _lock:
        if _lib is not None or _load_attempted:
            return _lib
        so_ready = False
        try:
            so_ready = _so_path().exists()
        except OSError:
            pass
        if not so_ready and _in_running_loop():
            # First build under a live event loop: compile off-loop.
            if _bg_build is None or not _bg_build.is_alive():
                def _bg() -> None:
                    global _load_attempted
                    with _lock:
                        if _lib is not None or _load_attempted:
                            return
                        try:
                            _build_and_load()
                        except Exception as e:
                            _load_attempted = True
                            log.info(
                                "native background build failed (%s); "
                                "using Python fallbacks",
                                e.__class__.__name__)
                _bg_build = threading.Thread(
                    target=_bg, name="crowdllama-native-build", daemon=True)
                _bg_build.start()
            return None
        try:
            _build_and_load()
        except Exception as e:  # no g++, compile error, load error → fallback
            _load_attempted = True
            log.info("native library unavailable (%s); using Python fallbacks",
                     e.__class__.__name__)
            _lib = None
        else:
            _load_attempted = True
        return _lib


def ensure_built() -> bool:
    """Blocking build+load for synchronous startup paths (make test,
    process main before the loop starts).  Returns True when native is
    ready."""
    if env_flag("CROWDLLAMA_NO_NATIVE"):
        return False
    if _lib is not None:
        return True
    global _load_attempted
    with _lock:
        if _lib is None and not _load_attempted:
            try:
                _build_and_load()
            except Exception as e:
                log.info("native build failed (%s); using Python fallbacks",
                         e.__class__.__name__)
            _load_attempted = True
    return _lib is not None


def _reset_for_tests() -> None:
    """Drop cached load state so tests can exercise load() transitions."""
    global _lib, _load_attempted, _bg_build
    with _lock:
        _lib = None
        _load_attempted = False
        _bg_build = None
    with _fallback_lock:
        _fallbacks.clear()


# ---------------------------------------------------------------------------
# AEAD session wrapper.


class AeadSession:
    """One direction of a secure stream: pooled native cipher context with
    an internal 96-bit big-endian nonce counter and reusable scratch
    buffers.  Construct only when ``load()`` returned a library."""

    __slots__ = ("_lib", "_h", "_out", "_pt")

    def __init__(self, lib: ctypes.CDLL, key: bytes, flavor: int) -> None:
        if len(key) != 32:
            raise ValueError("AEAD key must be 32 bytes")
        h = lib.cl_aead_new(key, flavor)
        if not h:
            raise ValueError(f"unsupported AEAD flavor {flavor}")
        self._lib = lib
        self._h = h
        self._out = ctypes.create_string_buffer(64 * 1024)
        self._pt = ctypes.create_string_buffer(64 * 1024)

    def __del__(self) -> None:  # pragma: no cover - interpreter teardown
        h = getattr(self, "_h", None)
        if h:
            try:
                self._lib.cl_aead_free(h)
            except Exception:
                pass
            self._h = None

    @property
    def counter(self) -> int:
        return int(self._lib.cl_aead_ctr(self._h))

    def seal_frames(self, data: bytes, chunk: int, with_eof: bool = False) -> bytes:
        """Chunk + seal ``data`` into concatenated wire frames
        ([4B BE len][ct||tag]...), advancing the nonce counter once per
        frame — byte-identical to SecureWriter's Python path."""
        n = len(data)
        nframes = (n + chunk - 1) // chunk + (1 if with_eof else 0)
        need = n + nframes * (4 + TAG_LEN)
        if need > len(self._out):
            self._out = ctypes.create_string_buffer(max(need, 2 * len(self._out)))
        w = self._lib.cl_aead_seal_frames(
            self._h, data, n, chunk, 1 if with_eof else 0, self._out, len(self._out))
        if w < 0:
            raise RuntimeError("native seal capacity error")
        # string_at copies exactly w bytes; .raw[:w] would memcpy the whole
        # scratch buffer (64KB+) first — dominant cost on small frames.
        return ctypes.string_at(self._out, w)

    def open(self, ct: bytes) -> bytes | None:
        """Open one ciphertext frame body (no length prefix).  Returns the
        plaintext, or None on authentication failure.  The counter advances
        in both cases, matching SecureReader's finally block."""
        n = len(ct)
        if n - TAG_LEN > len(self._pt):
            self._pt = ctypes.create_string_buffer(max(n, 2 * len(self._pt)))
        r = self._lib.cl_aead_open(self._h, ct, n, self._pt, len(self._pt))
        if r == -1:
            return None
        if r < 0:
            raise RuntimeError("native open capacity error")
        return ctypes.string_at(self._pt, r)
