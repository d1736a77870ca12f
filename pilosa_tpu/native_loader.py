"""Shared lazy build-and-load machinery for the C++ components.

One implementation of the g++-compile / ctypes-load / once-per-process
dance used by every native module (roaring codec, libpql), including
stale-binary recovery: if the on-disk .so fails to dlopen (foreign ABI,
torn write), it is rebuilt once from source and retried.  Build failures
latch — callers fall back to their Python implementations for the rest
of the process — but never silently: the failure is printed once on
stderr and kept for ``status()`` (the ``native`` section of
``/debug/devices``)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading


class NativeLib:
    """Lazily-built shared library.  `setup(lib)` declares the ctypes
    signatures after a successful load."""

    def __init__(self, src: str, so: str, setup,
                 extra_flags: tuple[str, ...] = ()):
        self.src = src
        self.so = so
        self.setup = setup
        self.extra_flags = tuple(extra_flags)
        self.name = os.path.splitext(os.path.basename(src))[0]
        #: why the library is not native (None while it is, or untried)
        self.error: str | None = None
        self._lib = None
        self._failed = False
        self._lock = threading.Lock()

    def _build(self, force: bool = False) -> None:
        if (not force and os.path.exists(self.so)
                and os.path.getmtime(self.so) >= os.path.getmtime(self.src)):
            return
        os.makedirs(os.path.dirname(self.so), exist_ok=True)
        # per-process tmp name: concurrent cold builds must not publish
        # a torn .so
        tmp = f"{self.so}.tmp.{os.getpid()}"
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 *self.extra_flags, "-o", tmp, self.src],
                check=True, capture_output=True)
            os.replace(tmp, self.so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    def load(self):
        # double-checked: no lock on the hot path once loaded
        if self._lib is not None or self._failed:
            return self._lib
        with self._lock:
            if self._lib is not None or self._failed:
                return self._lib
            try:
                self._build()
                try:
                    lib = ctypes.CDLL(self.so)
                except OSError:
                    # stale or foreign-ABI binary: rebuild, retry once
                    self._build(force=True)
                    lib = ctypes.CDLL(self.so)
                self.setup(lib)
                self._lib = lib
            except Exception as e:  # noqa: BLE001 — any build/load/ABI
                # failure means the Python implementation serves
                self._failed = True
                self._lib = None
                detail = getattr(e, "stderr", None)
                if isinstance(detail, bytes):
                    detail = detail.decode(errors="replace")
                self.error = (f"{type(e).__name__}: {e}"
                              + (f": {detail.strip()[-400:]}"
                                 if detail else ""))
                print(f"native: {self.name} did not build/load "
                      f"({self.error}); the Python implementation "
                      "serves for the life of this process",
                      file=sys.stderr)
            return self._lib

    def available(self) -> bool:
        return self.load() is not None


def status() -> dict[str, dict]:
    """Load the four native libraries and say which are native:
    ``{name: {"loaded": bool, "error": str | None}}``."""
    from pilosa_tpu import csvload
    from pilosa_tpu.ops import hostkernels
    from pilosa_tpu.pql import native as pqlnative
    from pilosa_tpu.storage import roaring

    return {lib.name: {"loaded": lib.available(), "error": lib.error}
            for lib in (hostkernels._NATIVE, roaring._NATIVE,
                        pqlnative._NATIVE, csvload._NATIVE)}
