"""utils/heap.py: the compilers' freed heap goes back to the system as
each backend compile ends."""

import types

import pytest

from racon_tpu.utils import heap

BACKEND = "/jax/core/compile/backend_compile_duration"


def _rss_kb() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1])
    raise AssertionError("no VmRSS")


@pytest.fixture
def releases(monkeypatch):
    """heap with a counted ``release`` and a clock the test sets."""
    calls = []
    clock = [1000.0]
    monkeypatch.setattr(heap, "release", lambda: calls.append(clock[0]))
    monkeypatch.setattr(heap, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0]))
    monkeypatch.setattr(heap, "_last", 0.0)
    return calls, clock


def test_arm_is_idempotent_and_importing_ops_arms_it():
    import racon_tpu.ops  # noqa: F401
    assert heap._armed
    assert heap.arm() is True
    assert heap.arm() is True


@pytest.mark.parametrize("event", [
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
])
def test_only_backend_compiles_release(releases, event):
    calls, _ = releases
    heap._on_duration(event, 30.0)
    assert calls == []


def test_every_real_compile_releases_whatever_came_just_before(releases):
    calls, clock = releases
    heap._on_duration(BACKEND, 0.01)
    clock[0] += 0.1
    heap._on_duration(BACKEND, heap.REAL_COMPILE_S)
    clock[0] += 0.1
    heap._on_duration(BACKEND, 40.0)
    assert len(calls) == 3


def test_a_burst_of_cache_loads_releases_once_an_interval(releases):
    calls, clock = releases
    for _ in range(50):
        heap._on_duration(BACKEND, 0.02)
        clock[0] += heap.MIN_INTERVAL_S / 25
    assert len(calls) == 2
    clock[0] += heap.MIN_INTERVAL_S
    heap._on_duration(BACKEND, 0.02)
    assert len(calls) == 3


def test_a_jit_compile_reaches_the_listener(monkeypatch):
    import jax
    import jax.numpy as jnp
    import racon_tpu.ops  # noqa: F401
    calls = []
    monkeypatch.setattr(heap, "release", lambda: calls.append(1))
    monkeypatch.setattr(heap, "_last", 0.0)
    jax.jit(lambda x: x * 3 + 7)(jnp.arange(11)).block_until_ready()
    assert calls == [1]


def test_release_gives_freed_blocks_back_to_the_system():
    """Blocks under glibc's mmap threshold, freed with a live one left
    between every 16: the holes stay resident until ``release``."""
    assert heap.arm()
    heap.release()
    blocks = [bytearray(64 * 1024) for _ in range(4096)]    # 256 MiB
    for b in blocks:
        b[::4096] = b"\x01" * 16
    kept = blocks[::16]
    del blocks, b
    held = _rss_kb()
    heap.release()
    assert held - _rss_kb() > 100 * 1024, (held, _rss_kb())
    assert len(kept) == 256
