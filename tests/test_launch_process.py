"""Per-process JAX setup (``repro.launch.process``) and the worker pool's
one-chip-per-process rules — everything here runs without a TPU."""
import os
import subprocess
import sys

import pytest

from repro.launch import process

CACHE_PROBE = (
    "import jax; from repro.launch.process import enable_compile_cache; "
    "d = enable_compile_cache(); "
    "print(d, jax.config.jax_compilation_cache_dir, "
    "__import__('os').environ.get('JAX_COMPILATION_CACHE_DIR'))")


def _probe(env_cache):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_cache is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_cache
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_compile_cache_defaults_to_the_checkout():
    want = str(process.REPO_ROOT / ".jax_cache")
    # the helper's dir, JAX's setting, and the variable spawned workers
    # inherit all name the fixed in-checkout path
    assert _probe(None) == [want, want, want]


def test_compile_cache_honours_the_environment(tmp_path):
    d = str(tmp_path / "cache")
    assert _probe(d) == [d, d, d]


def test_force_host_devices_appends_and_never_overrides(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/dev/null")
    process.force_host_devices(1)
    assert os.environ["XLA_FLAGS"] == "--xla_dump_to=/dev/null"
    process.force_host_devices(4)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_dump_to=/dev/null --xla_force_host_platform_device_count=4")
    process.force_host_devices(8)
    assert os.environ["XLA_FLAGS"].count("device_count") == 1


def test_tpu_chip_env_and_environ_restore(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    env = process.tpu_chip_env(2)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_PROCESS_BOUNDS"] == env["TPU_CHIPS_PER_PROCESS_BOUNDS"]
    assert process.tpu_chip_env(3)["TPU_PROCESS_PORT"] != \
        env["TPU_PROCESS_PORT"]
    assert env["TPU_PROCESS_ADDRESSES"] == \
        f"localhost:{env['TPU_PROCESS_PORT']}"
    with process.environ(env):
        assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
        assert os.environ["JAX_PLATFORMS"] == "tpu"
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_no_tpu_chips_when_held_to_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert process.tpu_chips_on_host() == 0


@pytest.mark.parametrize("chips,workers,devices,started,err", [
    (1, 2, 0, False, "2 workers for 1 TPU chips"),
    (4, 4, 2, False, "splits the host CPU"),
    (4, 4, 0, True, "holds the TPU chips"),
])
def test_pool_refuses_what_one_chip_per_worker_forbids(
        monkeypatch, chips, workers, devices, started, err):
    """On a TPU host the pool checks before it spawns anything."""
    from repro.ingest import FleetSimulator, workers as W

    monkeypatch.setattr(W, "tpu_chips_on_host", lambda: chips)
    monkeypatch.setattr(W, "backend_started", lambda: started)
    sim = FleetSimulator(n_patients=2, windows=1, n_cough=0)
    with pytest.raises((ValueError, RuntimeError), match=err):
        W.run_worker_fleet(sim, workers, devices=devices)
