"""Sharded HNSW beam engine: numeric parity with the single-device beam
loop (topk_d / topk_i / ndis / ninserts) on the 1-device mesh in-process,
and on real (placeholder) {1, 2, 4}-shard meshes in a subprocess — with a
node count that does not divide the shard count (place_index pads the
node dim; pad rows must keep sqnorm +inf / neighbor ids -1)."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import dist
from repro.core import darth_search, engines
from repro.index import hnsw
from repro.launch.mesh import auto_mesh


def _mesh1():
    return auto_mesh((1,), ("model",))


@pytest.fixture(scope="module")
def small_hnsw():
    from repro.data import vectors
    ds = vectors.make_dataset(n=1501, d=16, num_learn=64, num_queries=32,
                              clusters=12, cluster_std=1.0, seed=0)
    index = hnsw.build(ds.base, m=8, passes=1, ef_construction=32, seed=0)
    return ds, index


def test_sharded_beam_matches_single_device(small_hnsw):
    ds, index = small_hnsw
    mesh = _mesh1()
    placed = dist.place_index(index, mesh)
    q = jnp.asarray(ds.queries[:16])
    d0, i0, s0 = hnsw.search(index, q, k=5, ef=24)
    d1, i1, s1 = hnsw.search_sharded(placed, q, k=5, ef=24, mesh=mesh)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0.ndis), np.asarray(s1.ndis))
    np.testing.assert_array_equal(np.asarray(s0.ninserts),
                                  np.asarray(s1.ninserts))


def test_sharded_engine_protocol_drivers(small_hnsw):
    """darth_search's plain / budget drivers run the sharded beam engine
    unchanged (Engine protocol) and reproduce single-device results."""
    ds, index = small_hnsw
    mesh = _mesh1()
    placed = dist.place_index(index, mesh)
    q = jnp.asarray(ds.queries[:16])
    eng_ref = engines.hnsw_engine(index, k=5, ef=24)
    eng_sh = engines.sharded_hnsw_engine(placed, mesh, k=5, ef=24)
    assert eng_sh.name == "hnsw-sharded"
    assert eng_sh.max_steps == eng_ref.max_steps == 8 * 24

    plain_ref = darth_search.plain_search(eng_ref, q)
    plain_sh = darth_search.plain_search(eng_sh, q)
    np.testing.assert_array_equal(np.asarray(plain_ref.cand_i[:, :5]),
                                  np.asarray(plain_sh.cand_i[:, :5]))
    np.testing.assert_array_equal(np.asarray(plain_ref.nstep),
                                  np.asarray(plain_sh.nstep))

    budget = float(index.route_ids.shape[0] + 120)
    bud_ref = darth_search.budget_search(eng_ref, q, budget)
    bud_sh = darth_search.budget_search(eng_sh, q, budget)
    np.testing.assert_array_equal(np.asarray(bud_ref.ndis),
                                  np.asarray(bud_sh.ndis))
    np.testing.assert_array_equal(np.asarray(bud_ref.cand_i[:, :5]),
                                  np.asarray(bud_sh.cand_i[:, :5]))


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

sys.path.insert(0, "src")
from repro import dist
from repro.data import vectors
from repro.index import hnsw

# n=1501 is odd AND 1 mod 4: place_index must pad the node dim for both
# the 2- and 4-shard meshes.
ds = vectors.make_dataset(n=1501, d=16, num_learn=64, num_queries=32,
                          clusters=12, cluster_std=1.0, seed=0)
index = hnsw.build(ds.base, m=8, passes=1, ef_construction=32, seed=0)
q = jnp.asarray(ds.queries[:16])
d0, i0, s0 = hnsw.search(index, q, k=5, ef=24)
n = index.num_vectors
out = {"ndev": jax.device_count(), "n": n, "cases": []}
for nsh in (1, 2, 4):
    mesh = Mesh(np.asarray(jax.devices()[:nsh]), ("model",))
    placed = dist.place_index(index, mesh)
    # padding contract on the placed arrays
    sqn_pad = np.asarray(placed.sqnorm)[n:]
    nbr_pad = np.asarray(placed.neighbors)[n:]
    d1, i1, s1 = hnsw.search_sharded(placed, q, k=5, ef=24, mesh=mesh)
    out["cases"].append({
        "shards": nsh, "n_padded": placed.num_vectors,
        "pad_ok": bool(np.isposinf(sqn_pad).all()
                       and (nbr_pad == -1).all()),
        "d_ok": bool(np.allclose(np.asarray(d0), np.asarray(d1),
                                 atol=1e-4)),
        "i_ok": bool(np.array_equal(np.asarray(i0), np.asarray(i1))),
        "ndis_ok": bool(np.array_equal(np.asarray(s0.ndis),
                                       np.asarray(s1.ndis))),
        "nins_ok": bool(np.array_equal(np.asarray(s0.ninserts),
                                       np.asarray(s1.ninserts))),
    })
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_beam_parity_mesh_1_2_4():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ndev"] == 4
    assert len(res["cases"]) == 3
    for case in res["cases"]:
        if case["shards"] > 1:     # 1501 padded up to the shard multiple
            assert case["n_padded"] % case["shards"] == 0, case
            assert case["n_padded"] > res["n"], case
        for key in ("pad_ok", "d_ok", "i_ok", "ndis_ok", "nins_ok"):
            assert case[key], case
