"""Cross-shard top-k merge: padding + id-masking invariants, and the
multi-shard numerics on a real (placeholder) 8-device mesh."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.dist import collectives
from repro.index import flat
from repro.launch.mesh import auto_mesh


def _mesh1():
    return auto_mesh((1,), ("model",))


def test_merge_topk_masks_padding_ids():
    # +inf candidates (shard padding) must come out as id -1, never a
    # padded row id.
    cand_d = jnp.asarray([[0.5, jnp.inf, 0.1, jnp.inf]], jnp.float32)
    cand_i = jnp.asarray([[7, 999, 3, 998]], jnp.int32)
    d, i = collectives.merge_topk(cand_d, cand_i, k=3)
    np.testing.assert_allclose(np.asarray(d[0, :2]), [0.1, 0.5])
    assert i[0, 0] == 3 and i[0, 1] == 7
    assert i[0, 2] == -1 and not np.isfinite(np.asarray(d[0, 2]))


def test_sharded_search_fewer_rows_than_k():
    # N < k: the tail slots must be (+inf, -1), matching flat.search.
    # (Goes through the flat.search_sharded convenience entry point.)
    mesh = _mesh1()
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    d, i = flat.search_sharded(q, x, 5, mesh)
    d_ref, i_ref = flat.search(q, x, 5)
    np.testing.assert_allclose(np.asarray(d)[:, :3],
                               np.asarray(d_ref)[:, :3], atol=1e-3)
    assert (np.asarray(i)[:, 3:] == -1).all()
    assert not np.isfinite(np.asarray(d)[:, 3:]).any()


def test_cached_search_keys_on_geometry_not_mesh_object():
    """Regression: the sharded-search cache used to key its lru_cache on
    the Mesh object, holding meshes (and through the jit cache, their
    device buffers) alive across tests. Keys must be (axis geometry, k)
    primitives, and equivalent meshes must share one compiled entry."""
    from jax.sharding import Mesh

    collectives._SEARCH_CACHE.clear()
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 4)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)

    mesh_a = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    mesh_b = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    d_a, _ = collectives.sharded_flat_search(q, x, 3, mesh_a)
    d_b, _ = collectives.sharded_flat_search(q, x, 3, mesh_b)
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b))
    assert len(collectives._SEARCH_CACHE) == 1

    def flatten(obj):
        if isinstance(obj, tuple):
            for e in obj:
                yield from flatten(e)
        else:
            yield obj

    for key in collectives._SEARCH_CACHE:
        for leaf in flatten(key):
            assert isinstance(leaf, (str, int)), key
            assert not isinstance(leaf, Mesh)

    # a different k is a different entry, same bounded cache
    collectives.sharded_flat_search(q, x, 2, mesh_a)
    assert len(collectives._SEARCH_CACHE) == 2
    collectives._SEARCH_CACHE.clear()


def test_sharded_search_xla_fallback_matches():
    mesh = _mesh1()
    fn = collectives.make_sharded_flat_search(mesh, k=4, use_kernel=False)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(6, 12)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(97, 12)), jnp.float32)
    d, i = fn(q, x)
    d_ref, i_ref = flat.search(q, x, 4)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), atol=1e-3)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")
from repro.dist import collectives
from repro.index import flat
from repro.launch.mesh import auto_mesh

# N deliberately NOT divisible by the 8-way model axis: 1001 = 8*125 + 1,
# so 7 padded rows exist on the last shard and must never surface.
mesh = auto_mesh((8,), ("model",))
rng = np.random.default_rng(0)
n, d, b, k = 1001, 16, 32, 10
x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)

fn = collectives.make_sharded_flat_search(mesh, k)
ds, is_ = fn(q, x)
dr, ir = flat.search(q, x, k)

ids = np.asarray(is_)
ok_ids = bool(((ids >= 0) & (ids < n)).all())          # no padded-row ids
ok_d = bool(np.allclose(np.asarray(ds), np.asarray(dr), atol=1e-3))
ok_set = bool(np.mean(np.asarray(flat.recall_at_k(is_, ir))) > 0.999)
print(json.dumps({"ok_ids": ok_ids, "ok_d": ok_d, "ok_set": ok_set,
                  "ndev": jax.device_count()}))
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_nondivisible_db_on_8_shards():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ndev"] == 8
    assert res["ok_ids"], res
    assert res["ok_d"], res
    assert res["ok_set"], res


def test_elastic_restore_from_mesh(tmp_path):
    """`restore(shardings=<Mesh>)` re-derives placement from the logical
    spec recorded at save time (degrading axes the new mesh lacks)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import ckpt

    mesh_a = auto_mesh((1, 1), ("data", "model"))
    w = jax.device_put(jnp.arange(32.0).reshape(4, 8),
                       NamedSharding(mesh_a, P("data", "model")))
    ckpt.save(str(tmp_path), 1, {"w": w})

    mesh_b = auto_mesh((1,), ("model",))
    like = {"w": jax.ShapeDtypeStruct((4, 8), jnp.float32)}
    restored, meta = ckpt.restore(str(tmp_path), like, shardings=mesh_b)
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(w))
    assert restored["w"].sharding.mesh.axis_names == ("model",)
    assert meta["shardings"]["w"]["spec"] == ["data", "model"]
