"""Per-process JAX setup: which devices a process sees, and where its
compiled programs are cached.

The device helpers must run before the process's first JAX backend starts
(they only edit ``os.environ``); none of them starts one itself.

* ``force_host_devices(n)`` splits the host CPU into ``n`` XLA devices —
  how the sharded fleet path runs without an accelerator.
* ``tpu_chips_on_host()`` counts the TPU chips on the PCI bus, or 0 where
  the process is held to other platforms (``JAX_PLATFORMS``).
* ``tpu_chip_env(i)`` is the environment that makes chip ``i`` the only
  device of a process.  A chip belongs to one process at a time, so a
  pool of one-chip worker processes starts each worker with its own chip.
* ``enable_compile_cache()`` turns JAX's persistent compilation cache on;
  the scripts (``chip_smoke.py``, benchmarks, examples) call it, the
  library and the tests do not.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Dict

# the checkout root: src/repro/launch/process.py → three levels up
REPO_ROOT = Path(__file__).resolve().parents[3]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"

# libtpu's per-process port: each one-chip process of a pool gets its own,
# and names it as the only address of its (one-process) slice — a port
# missing from the addresses is refused when libtpu starts its metric server
_TPU_PORT_BASE = 8476


def force_host_devices(n: int) -> None:
    """Split the host CPU into ``n`` XLA devices (no-op for n <= 1).
    Appends to inherited ``XLA_FLAGS`` and never overrides a split that is
    already there."""
    if n <= 1:
        return
    prev = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev:
        os.environ["XLA_FLAGS"] = (
            prev + f" --xla_force_host_platform_device_count={n}").strip()


def tpu_chips_on_host() -> int:
    """TPU chips attached to this host, counted without starting JAX.
    Returns 0 when ``JAX_PLATFORMS`` holds the process to platforms that
    exclude the TPU (the CPU test runs)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id
    return num_available_tpu_chips_and_device_id()[0]


def tpu_chip_env(chip: int) -> Dict[str, str]:
    """Environment that makes TPU chip ``chip`` a process's only device and
    the TPU its only platform: a process that cannot get its chip fails at
    JAX start-up instead of running on the CPU.  Several such processes
    share one host, each on its own chip, so the host-wide libtpu load
    lock is lifted for them; the visible-chip sets keep them apart.  It
    must be in place when the process starts (JAX reads ``JAX_PLATFORMS``
    on import), so a parent passes it to the child it spawns."""
    return {
        "JAX_PLATFORMS": "tpu",
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(_TPU_PORT_BASE + chip),
        "TPU_PROCESS_ADDRESSES": f"localhost:{_TPU_PORT_BASE + chip}",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


@contextlib.contextmanager
def environ(updates: Dict[str, str]):
    """``os.environ`` with ``updates`` applied, restored on exit — for
    starting a child process with its own environment."""
    prev = {k: os.environ.get(k) for k in updates}
    os.environ.update(updates)
    try:
        yield
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def backend_started() -> bool:
    """Has this process started a JAX backend (and so taken its devices)?"""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    — a fixed path, because the directory is part of what a later process
    must find again — and the variable is exported with it, so processes
    this one spawns (the ingest workers) share the cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
