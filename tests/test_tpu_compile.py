"""Compile the main-path Pallas kernels for a described TPU v5e at SIFT1M
shapes (N=1M, D=128, k=10, nlist=1024, bucket cap 2048).

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, and asserts the Mosaic kernel is in the compiled program
(`tpu_custom_call`), so a kernel the TPU compiler refuses (VMEM, tiling)
fails here at no chip time. The topology is described inside a fixture,
never at import: only one process may load the TPU library at a time.
Kernels are compiled with `interpret=False` explicitly, since the
backend resolver sees the CPU in this process.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.gbdt.model import GBDTParams
from repro.index import ivf
from repro.kernels import ops

B, N, D, K = 256, 1_000_000, 128, 10
NLIST, CAP = 1024, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_l2_topk_compiles_sift1m(one_chip):
    fn = jax.jit(lambda q, x: ops.l2_topk(q, x, k=K, interpret=False))
    _assert_kernel(fn.lower(_sds((B, D), jnp.float32, one_chip),
                            _sds((N, D), jnp.float32, one_chip)).compile())


def _bucket_probe_compiled(sharding, cap, dtype):
    fn = jax.jit(lambda q, v, s, i, bias, kth, rd, ri: ops.bucket_probe(
        q, v, s, i, bias, kth, rd, ri, interpret=False))
    args = [_sds((B, D), jnp.float32, sharding),
            _sds((B, cap, D), dtype, sharding),
            _sds((B, cap), jnp.float32, sharding),
            _sds((B, cap), jnp.int32, sharding),
            _sds((B, 1), jnp.float32, sharding),
            _sds((B, 1), jnp.float32, sharding),
            _sds((B, K), jnp.float32, sharding),
            _sds((B, K), jnp.int32, sharding)]
    return fn.lower(*args).compile()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_bucket_topk_tiled_compiles_cap2048(one_chip, dtype):
    _assert_kernel(_bucket_probe_compiled(one_chip, CAP, dtype))


def test_bucket_topk_compiles_sift1m_one_chip_cap(one_chip):
    # the largest bucket of the SIFT1M-shape index on one chip, a cap
    # that no tile width divides
    _assert_kernel(_bucket_probe_compiled(one_chip, 1560, jnp.float32))


def test_gbdt_predict_compiles(one_chip):
    trees, depth, feats = 100, 6, 11
    params = GBDTParams(
        feat=_sds((trees, 2**depth - 1), jnp.int32, one_chip),
        thresh=_sds((trees, 2**depth - 1), jnp.float32, one_chip),
        leaf=_sds((trees, 2**depth), jnp.float32, one_chip),
        base=_sds((), jnp.float32, one_chip))
    fn = jax.jit(lambda p, x: ops.gbdt_predict(p, x, interpret=False))
    _assert_kernel(fn.lower(
        params, _sds((B, feats), jnp.float32, one_chip)).compile())


def test_sharded_probe_step_compiles_one_chip_mesh(topo):
    from repro.dist import collectives
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((1,), ("model",), devices=topo.devices[:1])
    rep = NamedSharding(mesh, P())
    cap_split = NamedSharding(mesh, P(None, "model"))
    index = ivf.IVFIndex(
        centroids=_sds((NLIST, D), jnp.float32, rep),
        bucket_vecs=_sds((NLIST, CAP, D), jnp.float32,
                         NamedSharding(mesh, P(None, "model", None))),
        bucket_ids=_sds((NLIST, CAP), jnp.int32, cap_split),
        bucket_sqnorm=_sds((NLIST, CAP), jnp.float32, cap_split),
        bucket_sizes=_sds((NLIST,), jnp.int32, rep),
        scale=_sds((D,), jnp.float32, rep),
        offset=_sds((D,), jnp.float32, rep))
    state = jax.eval_shape(
        lambda: ivf.fresh_state(jnp.zeros((B, D)), jnp.zeros((B, 1)),
                                jnp.zeros((B, NLIST), jnp.int32),
                                jnp.zeros((B,)), K))
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, rep), state)
    step = collectives.make_sharded_probe_step(mesh, interpret=False)
    _assert_kernel(step.lower(index, state).compile())
