"""Contracts of the chip bring-up path that hold without a chip.

``chip_smoke.py`` itself only passes on a TPU; what tier-1 can pin is
that it REFUSES anything else, that the compile-cache helper can be
placed from outside, and that ``bench.py`` has no default peak.
"""

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_cpu_pin_before_any_leg():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode not in (0, None)
    assert "cpu" in proc.stderr and "JAX_PLATFORMS" in proc.stderr
    assert proc.stdout == ""           # no leg ran, no result printed


@pytest.mark.slow
def test_chip_smoke_dry_cpu_runs_every_leg():
    """The whole script at 'tiny' on the CPU backend (~1 min): loud about
    being a dry run, and still one JSON object on the last line."""
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py"),
                           "--dry-cpu"], capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "DRY RUN ON THE CPU BACKEND" in proc.stdout
    for leg in "KTS":
        assert f"leg {leg} PASSED" in proc.stdout
    last = proc.stdout.strip().splitlines()[-1]
    assert '"dry_cpu": true' in last and '"platform": "cpu"' in last


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    from hadoop_tpu.util import jaxcache
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.setenv(jaxcache.ENV_VAR, "/some/dir")
    assert jaxcache.configure_compile_cache() == "/some/dir"
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from hadoop_tpu.util import jaxcache
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.delenv(jaxcache.ENV_VAR, raising=False)
    try:
        got = jaxcache.configure_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])


def test_bench_has_no_default_peak():
    bench = _load("bench")
    assert bench.peak_flops("TPU v5 lite") == 197e12
    for kind in ("cpu", "TPU v9 imaginary"):
        with pytest.raises(ValueError, match=kind):
            bench.peak_flops(kind)
