"""Process start-up: where compiled programs are cached, and which
backend this process ended up on.

``configure_compile_cache`` is called by ``python -m pilosa_tpu`` before
anything touches a JAX backend, and is the one place in the tree that
sets JAX's persistent compilation cache directory.  ``backend_info`` is
what a server says about itself in its log, on ``/status`` and on
``/debug/devices``: a process that silently runs the numpy host engine
must not look like one serving from an accelerator.
"""

from __future__ import annotations

import os

#: The cache directory when the operator placed none: one fixed path
#: inside the checkout.  The path is part of what JAX keys a cached
#: executable on, so it must never move (no tempfile, pid or timestamp).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads
    it itself and this code sets no directory; otherwise the cache goes
    to ``DEFAULT_COMPILE_CACHE_DIR``.

    The persistence floor is 0 s (JAX's default skips executables that
    compiled in under 1 s): this program's executables are mostly small
    — one per fused tree shape, per tape bucket, per pow2 gather width —
    and a restarted server re-lowers dozens of them, so the sub-second
    ones together ARE the warm start's compile wall.  An operator who
    exports ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` keeps their
    value."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def backend_info() -> dict:
    """Platform, device kind and device count as JAX reports them, and
    whether this process computes on the host instead (one CPU device:
    numpy + native C++, nothing touches XLA — ``ops/bitmap.host_mode``).
    Initialises the backend; an accelerator that fails to come up
    raises here rather than turning into a CPU server."""
    import jax

    from pilosa_tpu.ops import bitmap as bm

    devs = jax.devices()
    host = bm.host_mode()
    return {
        "platform": devs[0].platform,
        "deviceKind": devs[0].device_kind,
        "deviceCount": len(devs),
        "hostMode": host,
        "engine": ("host (numpy + native C++; no XLA)" if host
                   else "device (XLA + Pallas)"),
        "compileCacheDir": jax.config.jax_compilation_cache_dir,
    }
