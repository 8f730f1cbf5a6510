"""Ahead-of-time compiles for a TPU v5e, with no chip attached.

The TPU compiler is installed on every host, so the kernels and one
whole ``select()`` program are compiled here for a described ``v5e:2x2``
topology at deployment widths.  This catches what interpret-mode tests
cannot see: block shapes the Mosaic lowering refuses, loops it cannot
lower, VMEM oversubscription, and datasets baked into executables.

The topology is described inside a fixture (never at import), and every
test of this kind lives in this one file: only one process at a time may
load the TPU library, so a worker that is not given this file must not
touch it.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aopt_gains import ops as aopt_ops
from repro.kernels.common import VMEM_BUDGET
from repro.kernels.filter_gains import ops as filter_ops
from repro.kernels.logistic_gains import ops as logistic_ops
from repro.kernels.marginal_gains import ops as marginal_ops

D, K, M, B = 1024, 200, 8, 10     # features, k, samples, block ⌈k/r⌉
N_KERNEL = 16384                  # candidates per kernel compile
N_SELECT = 1 << 20                # candidates in the whole-program compile
HBM_BYTES = 16 * 1024 ** 3        # one v5e chip
_OPS = (aopt_ops, filter_ops, logistic_ops, marginal_ops)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def tpu_path(monkeypatch, topo, no_persistent_cache):
    """Steer the wrappers as they run on a TPU host (this host's backend
    is the CPU), and record each launch's VMEM working set at the block
    size the wrapper chose."""
    vmem = []
    for mod in _OPS:
        monkeypatch.setattr(mod, "resolve_path", lambda interpret: (False, False))
        pick = mod.tuned_block_n

        def record(kernel, prec, dims, vmem_bytes, _pick=pick, **kw):
            bn = _pick(kernel, prec, dims, vmem_bytes, **kw)
            vmem.append((kernel, bn, vmem_bytes(bn)))
            return bn

        monkeypatch.setattr(mod, "tuned_block_n", record)
    return vmem


def _kernel_case(name, sds):
    n = N_KERNEL
    if name == "regression_gains":
        return (lambda X, Q, r, c, p: marginal_ops.regression_gains(
            X, Q, r, c, precision=p),
            (sds(D, n), sds(D, K), sds(D), sds(n)))
    if name == "aopt_gains":
        return (lambda X, W, p: aopt_ops.aopt_gains(X, W, 1.0, precision=p),
                (sds(D, n), sds(D, n)))
    if name == "logistic_gains":
        return (lambda X, y, e, p: logistic_ops.logistic_gains(
            X, y, e, precision=p), (sds(D, n), sds(D), sds(D)))
    if name == "filter_gains":
        return (lambda X, Q, Dd, R, c, p: filter_ops.filter_gains(
            X, Q, Dd, R, c, precision=p),
            (sds(D, n), sds(D, K), sds(M, D, B), sds(M, D), sds(n)))
    if name == "aopt_filter_gains":
        return (lambda X, W, E, F, p: filter_ops.aopt_filter_gains(
            X, W, E, F, 1.0, precision=p),
            (sds(D, n), sds(D, n), sds(M, D, B), sds(M, B, B)))
    assert name == "logistic_filter_gains"
    return (lambda X, y, e, p: filter_ops.logistic_filter_gains(
        X, y, e, precision=p), (sds(D, n), sds(D), sds(M, D)))


def _wide_sorts(hlo_text, width):
    """Sort instructions of a compiled module with a ``width``-wide axis."""
    dim = re.compile(rf"[\[,]{width}[\],]")
    return [line for line in hlo_text.splitlines()
            if " sort(" in line and dim.search(line.split(" sort(")[0])]


KERNELS = ("regression_gains", "aopt_gains", "logistic_gains",
           "filter_gains", "aopt_filter_gains", "logistic_filter_gains")


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, precision, one_chip, tpu_path):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fn, args = _kernel_case(name, sds)
    lowered = jax.jit(lambda *a: fn(*a, precision)).lower(*args)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert tpu_path, "the wrapper chose no block size"
    for kernel, bn, nbytes in tpu_path:
        assert nbytes <= VMEM_BUDGET, (kernel, bn, nbytes)


def test_nested_vmap_sampler_lowers_to_topk(one_chip, no_persistent_cache):
    """The lattice's sampler, vmapped over 8 guesses and 8 samples at
    n = 2^17, m = 5: XLA's ``TopK``, not a full sort of n."""
    from repro.core.estimators import sample_set_from_mask

    G, S, n, m = 8, 8, 1 << 17, 5
    keys = jax.ShapeDtypeStruct((G, S, 2), jnp.uint32, sharding=one_chip)
    masks = jax.ShapeDtypeStruct((G, n), jnp.bool_, sharding=one_chip)
    draw = jax.vmap(jax.vmap(lambda k, mk: sample_set_from_mask(k, mk, m),
                             in_axes=(0, None)))
    text = jax.jit(draw).lower(keys, masks).compile().as_text()
    assert 'custom_call_target="TopK"' in text
    assert " sort(" not in text, _wide_sorts(text, n)


def test_select_dash_program_compiles_with_data_as_arguments(one_chip,
                                                             tpu_path):
    """The whole default ``select("dash")`` program at deployment size:
    the Pallas kernels are in it, the dataset is a parameter (the lowered
    text stays a few MiB against a 4 GiB X), and it fits one chip."""
    from repro.core import RegressionObjective, select

    k = K
    X = jax.ShapeDtypeStruct((D, N_SELECT), jnp.float32)
    y = jax.ShapeDtypeStruct((D,), jnp.float32)
    obj = jax.eval_shape(
        lambda X, y: RegressionObjective(X, y, kmax=k, use_kernel=True), X, y)
    obj = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        obj)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    lowered = jax.jit(
        lambda o, kk: select("dash", o, k, kk).sel_mask).lower(obj, key)
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    assert len(text) < 16 * 1024 ** 2, len(text)
    compiled = lowered.compile()
    assert not _wide_sorts(compiled.as_text(), N_SELECT)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * D * N_SELECT
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


def test_sharded_select_dash_compiles_on_v5e_2x2(topo, tpu_path):
    """``select("dash", mesh=...)`` with X's columns sharded four ways on
    the model axis of a (pod, data, model) = (1, 1, 4) mesh: a 16 GiB X
    that no single chip holds.  Each chip gets a quarter of X, the
    kernels run shard-locally (no gather of X), and the shards meet in
    collectives."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import RegressionObjective, select

    k, n = K, 4 * N_SELECT
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 1, 4),
                ("pod", "data", "model"))

    def placed(s):
        spec = {(D, n): P(None, "model"), (n,): P("model")}.get(s.shape, P())
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, spec))

    obj = jax.eval_shape(
        lambda X, y: RegressionObjective(X, y, kmax=k, use_kernel=True),
        jax.ShapeDtypeStruct((D, n), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32))
    obj = jax.tree_util.tree_map(placed, obj)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))

    compiled = jax.jit(
        lambda o, kk: select("dash", o, k, kk, mesh=mesh).sel_mask
    ).lower(obj, key).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text
    assert not _wide_sorts(text, n // 4)
    mem = compiled.memory_analysis()          # per device
    assert 4 * D * n // 4 <= mem.argument_size_in_bytes < 2 * D * n
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
