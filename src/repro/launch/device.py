"""What the launchers read from the device they run on, and how many
programs they compile.

Entry points (``chip_smoke.py``, the ``main()`` of ``launch/train.py`` and
``launch/serve.py``) call :func:`enable_compile_cache` before their first
compile; library modules never do, so importing them (tests included)
leaves JAX's persistent compilation cache as the environment set it.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir, os.pardir, os.pardir))
# nominal capacity of one TPU v5e chip (Google Cloud, "TPU v5e"): the
# target a CPU-only run estimates for, since host memory is no HBM
V5E_HBM_BYTES = 16 * 2**30


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself), else at
    the fixed ``<repo root>/.jax_cache`` — the directory is part of the
    cache key, so it must not move between runs. Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts the executables JAX builds for this process, and their
    seconds: a ``jax.monitoring`` duration listener on the backend-compile
    event, which JAX records around every compile, including one answered
    from the persistent compilation cache. Entry points that time steps
    (``chip_smoke.py``) install one to show that nothing compiles inside
    a timed loop; library code never does."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def install(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def remove(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def hbm_bytes(device=None) -> int:
    """Capacity the admission gate holds a job to: the device's own
    ``memory_stats()["bytes_limit"]``. A CPU device stands in for a v5e
    (:data:`V5E_HBM_BYTES`); an accelerator that reports no limit is an
    error, never a default."""
    device = device or jax.devices()[0]
    stats = device.memory_stats() or {}
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if device.platform == "cpu":
        return V5E_HBM_BYTES
    raise RuntimeError(f"{device.device_kind} reports no memory limit "
                       f"(memory_stats: {stats or None})")


def peak_bytes_in_use(device=None) -> int:
    """The device's peak allocated bytes since the process started; an
    error where the backend does not report it."""
    device = device or jax.devices()[0]
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        raise RuntimeError(f"{device.device_kind} reports no peak memory")
    return int(stats["peak_bytes_in_use"])


def bytes_in_use(device=None) -> int | None:
    """Device bytes currently allocated (None where the backend does not
    report memory, as on the CPU)."""
    device = device or jax.devices()[0]
    stats = device.memory_stats()
    return None if stats is None else int(stats["bytes_in_use"])
