"""Hand the compilers' freed heap back to the system.

An XLA or Mosaic backend compile allocates on a pool of threads, and
glibc keeps what a thread frees in that thread's arena: the pages stay
resident until something asks for them back. A job's set-up runs 70-85
backend compiles, so the resident set grew with every one that missed
the persistent cache and never came down: on one v5e host, where
13.6 GB are the TPU runtime's mappings from the third second on, a
process ended its first job at 16.7 GB with every program cached and at
19.8 GB where the consensus programs compiled (PERF.md §6, PR 34).
Which programs miss follows the input (the aligner's chunk geometries),
so the peak did too.

:func:`arm` registers one ``jax.monitoring`` listener that calls
``malloc_trim(0)`` when a backend compile ends: after every one that
took ``REAL_COMPILE_S`` or longer (a program that missed the cache),
and after the short ones (cache loads, trivial programs) at most once in
``MIN_INTERVAL_S``. A job that compiles nothing (every job after a
resident process's first) never calls it. Off glibc it does nothing.
"""

from __future__ import annotations

import ctypes
import threading
import time

# between two trims: a burst of cache loads (tens of events a second at
# start-up) costs one walk over the arenas, not one per event
MIN_INTERVAL_S = 2.0
# a backend compile this long built a program; shorter ones loaded one
REAL_COMPILE_S = 1.0

_lock = threading.Lock()
_armed = False
_last = 0.0
_trim = None


def _load():
    """glibc's ``malloc_trim``, or None where the C library has none."""
    try:
        fn = ctypes.CDLL(None).malloc_trim
    except (OSError, AttributeError):
        return None
    fn.argtypes = [ctypes.c_size_t]
    fn.restype = ctypes.c_int
    return fn


def release() -> bool:
    """Return the free pages of every malloc arena to the system; True
    if glibc gave any back."""
    return _trim is not None and bool(_trim(0))


def _on_duration(event, duration, **kwargs) -> None:
    global _last
    if "backend_compile" not in str(event):
        return
    now = time.monotonic()
    with _lock:
        if duration < REAL_COMPILE_S and now - _last < MIN_INTERVAL_S:
            return
        _last = now
    release()


def arm() -> bool:
    """Register the listener (idempotent); False off glibc or without
    jax."""
    global _armed, _trim
    with _lock:
        if _armed:
            return True
        _trim = _load()
        if _trim is None:
            return False
        try:
            import jax.monitoring as jmon
        except ImportError:
            return False
        jmon.register_event_duration_secs_listener(_on_duration)
        _armed = True
    return True
