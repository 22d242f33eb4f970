"""Bring-up guards for running on the chip, exercised on the CPU.

``chip_smoke.py`` refuses a host without a TPU; its phases run here at a
tiny width (the one-chip phase in process, the four-chip phase on four
forced host devices in a subprocess, since the device count must be set
before JAX starts); a pool that several devices cannot split evenly is
refused; and the compile-cache helper honours ``JAX_COMPILATION_CACHE_DIR``.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.core.runtime import enable_compile_cache

REPO = pathlib.Path(__file__).parent.parent


def _forced_devices_env(n):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _run(code, env, timeout=600):
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "chip_smoke.py"], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "platform 'cpu'" in res.stderr


def test_smoke_one_chip_phase_at_tiny_width():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    chip_smoke.run_one_chip(chip_smoke.CompileClock(), pool=16)


def test_smoke_four_chip_phase_on_forced_host_devices():
    code = ("import chip_smoke as c; "
            "c.run_four_chips(c.CompileClock(), pool=8, requests=40)")
    res = _run(code, _forced_devices_env(4))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "equal per rid between the sharded and the unsharded" in res.stdout
    assert "0:(8, 32768), 1:(8, 32768), 2:(8, 32768), 3:(8, 32768)" \
        in res.stdout


def test_non_dividing_pool_is_refused_over_several_devices():
    code = """
import json
import jax.numpy as jnp
from repro.core import fleet as F
from repro.parallel.sharding import fleet_divisor, shard_fleet
from repro.serve.fleet_server import FleetServer
out = {"divisor": fleet_divisor(8)}
for name, call in (
        ("shard_fleet", lambda: shard_fleet(
            None, jnp.zeros(6, jnp.int32), F.make_halted_states(6))),
        ("fleet_divisor", lambda: fleet_divisor(6)),
        ("server", lambda: FleetServer(pool=6, shard=True))):
    try:
        call()
        out[name] = "accepted"
    except ValueError as e:
        out[name] = str(e)
print(json.dumps(out))
"""
    res = _run(code, _forced_devices_env(4))
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["divisor"] == 4
    for name in ("shard_fleet", "fleet_divisor", "server"):
        assert "6-lane fleet cannot be split evenly over 4" in out[name], out


def test_one_device_sharding_stays_a_noop():
    from repro.parallel.sharding import fleet_divisor, fleet_mesh
    assert fleet_divisor(7, fleet_mesh(jax.devices()[:1])) == 1


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own read


def test_compile_cache_defaults_to_one_fixed_repo_path(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert enable_compile_cache() == first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
