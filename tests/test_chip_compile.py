"""Compile the served kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached.  These tests compile the Pallas kernels of
the served path at ``chip_smoke.py``'s widths with ``interpret=False``
and assert the kernels survive into the compiled program
(``tpu_custom_call``) under their stable names (``pallas_call(name=)``,
the op name a profiler capture shows): interpret mode on the CPU cannot
show a block shape or an in-kernel op the chip's compiler refuses.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and under several test workers
every worker imports this file.
"""
import importlib.util
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import index as pfo
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels as the chip runs them: never interpret mode."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


@pytest.fixture(scope="module")
def smoke():
    return _smoke()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(kernel, fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call", text), kernel


def test_lsh_hash_compiles_for_v5e(one_chip, compiled_kernels, smoke):
    cfg = smoke.smoke_config()
    _assert_kernel("lsh_hash", lambda x, a: ops.lsh_hash(x, a),
                   _spec(one_chip, (smoke.BUCKET, cfg.dim), jnp.float32),
                   _spec(one_chip, (cfg.dim, cfg.L * cfg.M), jnp.float32))


@pytest.mark.parametrize("staged", [False, True])
def test_gather_rank_compiles_for_v5e(one_chip, compiled_kernels, smoke,
                                      staged):
    cfg = smoke.smoke_config()
    q, c = 64, cfg.max_candidates_total
    args = [_spec(one_chip, (q, cfg.dim), jnp.float32),
            _spec(one_chip, (cfg.store_capacity, cfg.dim), jnp.float32),
            _spec(one_chip, (q, c), jnp.int32),
            _spec(one_chip, (q, c), jnp.bool_)]
    if staged:
        args.append(_spec(one_chip, (8192, cfg.dim), jnp.float32))

        def fn(qv, store, slots, valid, staging):
            return ops.gather_rank(qv, store, slots, valid, cfg.metric,
                                   staging=staging)
    else:
        def fn(qv, store, slots, valid):
            return ops.gather_rank(qv, store, slots, valid, cfg.metric)
    _assert_kernel("gather_rank_staged" if staged else "gather_rank", fn,
                   *args)


def test_insert_step_compiles_for_v5e(one_chip, compiled_kernels, smoke):
    """The whole served insert round at the smoke config and bucket."""
    cfg = smoke.smoke_config()
    state = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda k: pfo.init_state(cfg, k),
                       jax.random.PRNGKey(0)))
    b = smoke.BUCKET
    host = types.SimpleNamespace(cfg=cfg)     # capacity heuristics only
    mcap = pfo.PFOIndex._main_capacity(host, b)
    lcap = pfo.PFOIndex._lsh_capacity(host, b)
    lowered = pfo.insert_step.lower(
        state, _spec(one_chip, (b,), jnp.int32),
        _spec(one_chip, (b, cfg.dim), jnp.float32),
        _spec(one_chip, (b,), jnp.int32), _spec(one_chip, (b,), jnp.bool_),
        _spec(one_chip, (b * cfg.L,), jnp.bool_), cfg, mcap, lcap)
    assert "tpu_custom_call" in lowered.compile().as_text()
