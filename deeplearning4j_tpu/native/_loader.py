"""Shared native-library loader: locate the .so under native/build/,
rebuild via make when it is missing or the source is newer, fall back to
None (callers use numpy fallbacks, and a WARNING says so) when the
toolchain is unavailable."""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Callable, Dict, Optional

log = logging.getLogger(__name__)

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_BUILD_DIR = os.path.join(NATIVE_DIR, "build")

_build_lock = threading.Lock()
_build_attempted = False
_built_here: set = set()     # .so names this process's make (re)wrote


def _so_mtimes() -> Dict[str, float]:
    if not os.path.isdir(_BUILD_DIR):
        return {}
    return {n: os.path.getmtime(os.path.join(_BUILD_DIR, n))
            for n in os.listdir(_BUILD_DIR)}


class NativeLib:
    """Lazily-loaded native library handle."""

    def __init__(self, so_name: str, src_name: str,
                 configure: Callable[[ctypes.CDLL], None]):
        self.so_name = so_name
        self.so_path = os.path.join(_BUILD_DIR, so_name)
        self.src_path = os.path.join(NATIVE_DIR, "src", src_name)
        self._configure = configure
        self._lib: Optional[ctypes.CDLL] = None
        self._lock = threading.Lock()
        #: how load() obtained the library — "built" (make compiled it
        #: from native/src in this process), "prebuilt" (the .so was
        #: already on disk) or "unavailable" (numpy fallbacks); None
        #: until load() has run
        self.origin: Optional[str] = None

    def load(self) -> Optional[ctypes.CDLL]:
        global _build_attempted
        if self._lib is not None:
            return self._lib
        with self._lock:
            if self._lib is not None:
                return self._lib
            stale = (os.path.exists(self.so_path) and
                     os.path.exists(self.src_path) and
                     os.path.getmtime(self.src_path) >
                     os.path.getmtime(self.so_path))
            if not os.path.exists(self.so_path) or stale:
                with _build_lock:
                    if not _build_attempted:
                        _build_attempted = True
                        before = _so_mtimes()
                        try:
                            subprocess.run(["make", "-C", NATIVE_DIR],
                                           check=True, capture_output=True,
                                           timeout=120)
                        except (OSError, subprocess.SubprocessError) as e:
                            log.warning("native build unavailable (%s); "
                                        "using numpy fallbacks", e)
                        _built_here.update(
                            n for n, m in _so_mtimes().items()
                            if before.get(n) != m)
            self.origin = "unavailable"
            if not os.path.exists(self.so_path):
                return None
            try:
                lib = ctypes.CDLL(self.so_path)
            except OSError as e:
                log.warning("native lib %s load failed (%s); numpy "
                            "fallbacks", self.so_path, e)
                return None
            self._configure(lib)
            self._lib = lib
            self.origin = ("built" if self.so_name in _built_here
                           else "prebuilt")
            return self._lib

    def available(self) -> bool:
        return self.load() is not None
