"""Batched device kernels — the TPU compute path.

- ``racon_tpu.ops.pallas_nw`` — Pallas (Mosaic) kernels: banded wavefront
  NW forward with VMEM-resident wavefronts + DMA-streamed direction rows,
  the wavefront-synchronized walk, and the fused walk+vote emitter.
- ``racon_tpu.ops.nw``  — batched banded NW + on-device traceback with
  bucketing/escalation and the XLA twin kernels (role of the
  reference's cudaaligner batches, ``src/cuda/cudaaligner.cpp``).
- ``racon_tpu.ops.poa`` — device-resident batched POA consensus refinement
  (role of cudapoa, ``src/cuda/cudabatch.cpp``).
- ``racon_tpu.ops.swar`` — SWAR packed-lane primitives (int16x2 score
  lanes, 2-bit bases), the bit-exact availability probe and the int16
  overflow guard shared by both DP kernel families.
- ``racon_tpu.ops.overlap_seed`` — strand-canonical minimizer seeding
  for the first-party overlapper (``--overlaps auto``): batched
  windowed-minimum kernel over 2-bit codes with a device compaction
  path (role of minimap2's sketch pass).
- ``racon_tpu.ops.chain`` — seed matching + banded integer chain DP
  emitting ``Overlap`` rows (role of minimap2's chaining), the fourth
  kernel family next to NW and POA.
"""

import os as _os
from typing import Optional as _Optional

from .. import flags as _flags
from ..utils.logger import log_swallowed as _log_swallowed


# the fixed default cache path: <checkout>/.xla_cache, derived from the
# package's own location (the path is part of the cache key, so it must
# not move between runs — never $HOME, a temporary name, a pid or a time)
DEFAULT_COMPILE_CACHE = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))), ".xla_cache")


def configure_compile_cache(cache_dir: _Optional[str] = None,
                            min_compile_time_s: float = 0.5
                            ) -> _Optional[str]:
    """Place XLA's persistent compilation cache; returns the directory
    in effect.

    The kernels are recompiled per (bucket shape x batch size) and a
    cold CLI/test run pays tens of seconds of compile time otherwise.
    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set JAX reads it itself, this function sets no directory in code
    and an explicit ``cache_dir`` (the CLI ``--compile-cache``) yields
    to it with a stderr note. Unset: ``cache_dir``, else
    :data:`DEFAULT_COMPILE_CACHE`. Called once at import with the
    default; calling again (any time before the compiles it should
    capture) re-points the cache. An uncreatable directory is logged
    and leaves the cache off (None) — the cache is an optimization."""
    import jax as _jax

    _jax.config.update("jax_persistent_cache_min_compile_time_secs",
                       min_compile_time_s)
    env_dir = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        if cache_dir and _os.path.abspath(cache_dir) != \
                _os.path.abspath(env_dir):
            import sys as _sys
            print(f"[racon_tpu] note: JAX_COMPILATION_CACHE_DIR="
                  f"{env_dir} places the compile cache; ignoring "
                  f"--compile-cache {cache_dir}", file=_sys.stderr)
        return env_dir
    cache_dir = cache_dir or DEFAULT_COMPILE_CACHE
    try:
        _os.makedirs(cache_dir, exist_ok=True)
    except OSError as _e:  # cache is an optimization, never fatal
        _log_swallowed("ops: persistent XLA compile cache setup", _e)
        return None
    _jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


# Persist XLA compilations across processes by default. Opt out with
# RACON_TPU_NO_COMPILE_CACHE=1.
if not _flags.get_bool("RACON_TPU_NO_COMPILE_CACHE"):
    configure_compile_cache()

# Process-wide compile attribution (round 18): every XLA compile lands
# in the obs registry (the scoped ``compile.jax_s`` timer) and as one row
# per program in the compile watch's ring, under JAX's name for it and
# attributed to (function, shape signature, phase, scope).  Armed here
# because importing ops precedes every kernel compile; idempotent, and
# a no-op without jax.
from ..obs import compilewatch as _compilewatch  # noqa: E402

_compilewatch.arm()

# The compilers free what they allocated into glibc's per-thread arenas,
# which keep it resident: give it back as each backend compile ends
# (utils/heap.py), or the process's peak follows how many programs
# missed the cache.
from ..utils import heap as _heap  # noqa: E402

_heap.arm()
