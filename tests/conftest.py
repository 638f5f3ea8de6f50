"""Test harness configuration.

Multi-chip code paths are tested on a virtual 8-device CPU mesh (the
minicluster philosophy of the reference — real protocols, simulated fleet;
ref: MiniDFSCluster.java:157): JAX must see these flags before first import.
"""

import atexit
import os
import shutil
import tempfile

# Tests always run on the virtual 8-device CPU mesh for determinism and
# multi-chip coverage — pinned in the environment (subprocess children
# inherit it) and in jax.config (wins over whatever the shell exported).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# One persistent compile cache per test session, in a temp dir removed at
# exit. The suite builds the same tiny programs over and over (every
# DecodeEngine, every make_train_step, every replica child process), and
# this makes each distinct program compile once: the whole run measured
# 793 s with it against 965 s without, on a suite with an 870 s budget.
# It stays hermetic — placed from outside, so util/jaxcache.py touches
# nothing and no run reads or leaves <checkout>/.jax_cache. Set before
# jax is imported (jax reads these at import) so children inherit them.
_jax_cache = tempfile.mkdtemp(prefix="htpu-test-jaxcache-")


def _drop_jax_cache(owner=os.getpid()):
    if os.getpid() == owner:        # not from a forked child that exits
        shutil.rmtree(_jax_cache, ignore_errors=True)


atexit.register(_drop_jax_cache)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _jax_cache
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.3"

import jax

jax.config.update("jax_platforms", "cpu")

import logging

import pytest

logging.basicConfig(level=logging.INFO)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")


@pytest.fixture(scope="session")
def tap_logits():
    """``tap_logits(engine, taps)``: every row's float32 logits, appended
    to ``taps`` once a step — the rows the family's layers hand back,
    through the engine's own final norm and head. (The step itself makes
    logits only for the rows it reads: the lanes' and the one at a
    chunk's tip.) Call it before the engine's first step."""
    import jax.numpy as jnp
    import numpy as np

    from hadoop_tpu.serving import engine as engine_mod

    def tap(eng, taps):
        run = eng._family.run_layers

        def tapped(params, h, pools, lane, rows):
            out = run(params, h, pools, lane, rows)
            x = engine_mod._norm(out[0], params["final_norm_w"],
                                 params.get("final_norm_b"), eng.cfg)
            logits = x @ engine_mod.head_matrix(params, eng.cfg, x.dtype)
            jax.debug.callback(lambda v: taps.append(np.asarray(v)),
                               logits.astype(jnp.float32), ordered=True)
            return out

        eng._family.run_layers = tapped

    return tap


@pytest.fixture(scope="session")
def jaxpr_eqns():
    """``jaxpr_eqns(jaxpr)``: (primitive name, first output's shape) of
    every equation, those of its sub-jaxprs (branches, loop bodies,
    jitted calls) too."""
    def walk(jaxpr, found=None):
        found = [] if found is None else found
        for eqn in jaxpr.eqns:
            found.append((eqn.primitive.name, eqn.outvars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, found)
        return found

    return walk


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test gets a clean config registry and metrics system."""
    from hadoop_tpu.conf import ConfigRegistry
    from hadoop_tpu.dfs.protocol import datatransfer
    from hadoop_tpu.metrics import metrics_system
    yield
    ConfigRegistry.reset_for_tests()
    metrics_system().reset_for_tests()
    datatransfer.set_default_security(None)
    from hadoop_tpu.security.ugi import UserGroupInformation
    UserGroupInformation._login_user = None
    from hadoop_tpu.tracing.collector import span_collector
    span_collector().reset_for_tests()
    from hadoop_tpu.tracing.tracer import global_tracer
    global_tracer().set_sample_rate(1.0)
    from hadoop_tpu.obs.comm import comm_runtime
    comm_runtime().reset_for_tests()
    from hadoop_tpu.obs.hbm import hbm_ledger
    hbm_ledger().reset_for_tests()
