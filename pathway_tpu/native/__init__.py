"""Native runtime core loader.

Compiles ``src/_native.cpp`` with g++ on first use (cached as a .so keyed by
the source hash), registers the engine's value classes and slow-path codec
helpers, and exposes the module.  Pure-Python fallbacks stay in place when
compilation is unavailable (``PATHWAY_NATIVE=0`` forces them).

Parity role: the reference's value/key/snapshot hot paths are Rust
(src/engine/value.rs, src/persistence/input_snapshot.rs); here they are C++
behind the same Python interfaces.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "_native.cpp")
_BUILD_DIR = os.path.join(_HERE, "build")
# compile flags participate in the build-cache key (a flag change must
# rebuild even with identical source)
_FLAGS_DIGEST = b"O3-march-native-v1"

_lock = threading.Lock()
_loaded = False
_module = None


def _cpu_tag() -> bytes:
    """Host-CPU identity for the build-cache key: -march=native binaries
    must not be dlopened on a CPU without the ISA extensions they were
    compiled for (SIGILL via a shared/rsync'd build dir)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return hashlib.blake2b(
                        line.encode(), digest_size=4
                    ).hexdigest().encode()
    except OSError:
        pass
    import platform

    return platform.machine().encode()


def _compile() -> str | None:
    with open(_SRC, "rb") as f:
        src_hash = hashlib.blake2b(
            f.read() + _FLAGS_DIGEST + _cpu_tag(), digest_size=8
        ).hexdigest()
    # key the cache by interpreter ABI too: a .so built for another CPython
    # version/ABI (including free-threaded or debug builds, which share a
    # hexversion) must not be dlopened into this one
    abi = sysconfig.get_config_var("SOABI") or f"{sys.hexversion:08x}"
    so_path = os.path.join(_BUILD_DIR, f"_native_{src_hash}_{abi}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # processes that start together in a fresh checkout (a test run's
    # workers) build one .so: the first to take the lock builds it, the
    # others wait and find it built
    import fcntl

    with open(so_path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.exists(so_path):
            return so_path
        return _build(so_path)


def _build(so_path: str) -> str | None:
    """Run g++ into a temp file of this process's own, then move it into
    place (a half-written .so is never at ``so_path``)."""
    tmp = f"{so_path}.{os.getpid()}.tmp"
    include = sysconfig.get_paths()["include"]
    cmd = [
        "g++",
        "-O3",
        # the .so is built on (and cached per) the machine that runs it,
        # so native tuning is safe — it vectorizes the HNSW distance loops
        "-march=native",
        "-std=c++17",
        "-shared",
        "-fPIC",
        f"-I{include}",
        _SRC,
        "-o",
        tmp,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
    except (subprocess.SubprocessError, OSError) as exc:
        import logging

        detail = getattr(exc, "stderr", "") or str(exc)
        logging.getLogger("pathway_tpu.native").warning(
            "native core build failed, using Python fallbacks: %s", detail[-2000:]
        )
        return None
    os.replace(tmp, so_path)
    return so_path


def _load():
    so_path = _compile()
    if so_path is None:
        return None
    # module name must match PyInit__native
    spec = importlib.util.spec_from_file_location("_native", so_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # register classes + slow-path helpers
    import numpy as np

    from pathway_tpu.engine import codec
    from pathway_tpu.engine import types as tz

    def encode_slow(v):
        import io as _io

        out = _io.BytesIO()
        codec.encode_value(v, out)
        return out.getvalue()

    def decode_slow(tag, view, pos):
        # pos points just past the tag byte; codec.decode_value re-reads it.
        # Same corrupt-buffer contract as decode_row_py: everything decode
        # raises surfaces as the one documented, catchable ValueError.
        try:
            return codec.decode_value(view, pos - 1)
        except ValueError:
            raise
        except MemoryError:
            raise
        except Exception as exc:
            raise ValueError(f"codec: corrupt buffer ({exc})") from exc

    def ser_slow(v):
        out: list[bytes] = []
        tz._ser_value(v, out)
        return b"".join(out)

    mod.setup(
        tz.Pointer,
        tz.Json,
        tz.PyObjectWrapper,
        np.ndarray,
        tz.ERROR,
        encode_slow,
        decode_slow,
        ser_slow,
    )
    return mod


def get():
    """The native module, or None when disabled/unavailable."""
    global _loaded, _module
    if _loaded:
        return _module
    with _lock:
        if _loaded:
            return _module
        from pathway_tpu.internals.config import env_bool

        if not env_bool("PATHWAY_NATIVE"):
            _module = None
        else:
            try:
                _module = _load()
            except Exception:
                import logging

                logging.getLogger("pathway_tpu.native").warning(
                    "native core unavailable, using Python fallbacks",
                    exc_info=True,
                )
                _module = None
        _loaded = True
    return _module
