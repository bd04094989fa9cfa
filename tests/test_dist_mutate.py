"""Mutable engine over the SHARDED engines: numeric parity with the
single-device mutable path (topk_d / topk_i / ndis / ninserts) after an
insert/delete burst AND after compaction — on the 1-device mesh
in-process, and on real (placeholder) {1, 2, 4}-shard meshes in a
subprocess. The delta tier is replicated; tombstones travel row-sharded
inside the base arrays (pad convention), so the sharded steps need no
mutation-specific code at all."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import dist, mutate
from repro.core import darth_search, engines
from repro.data import vectors
from repro.index import ivf
from repro.launch.mesh import auto_mesh


def _mesh1():
    return auto_mesh((1,), ("model",))


@pytest.fixture(scope="module")
def mutated_ivf():
    ds = vectors.make_dataset(n=2000, d=16, num_learn=64, num_queries=32,
                              clusters=16, cluster_std=1.0, seed=0)
    index = ivf.build(ds.base, nlist=16, seed=0)
    mut = mutate.MutableIndex(index, capacity=512)
    mut.apply(vectors.mutation_stream(ds, insert_pct=0.2, delete_pct=0.1,
                                      drift=0.3, steps=4, seed=3))
    return ds, mut


def test_sharded_mutable_matches_single_device(mutated_ivf):
    ds, mut = mutated_ivf
    mesh = _mesh1()
    q = jnp.asarray(ds.queries[:16])
    ref = engines.mutable_engine(
        engines.ivf_engine(mut.base, k=5, nprobe=8), mut.delta)
    view = dist.place_index(mut.view(), mesh)
    sh = engines.mutable_engine(
        engines.sharded_ivf_engine(view.base, mesh, k=5, nprobe=8),
        view.delta)
    assert sh.name == "ivf-sharded+delta"
    ws0 = darth_search.plain_search(ref, q)
    ws1 = darth_search.plain_search(sh, q)
    # kernel (sharded) vs einsum (single-device) distances: ids exact,
    # distances to a relative 2e-5 (f32 summation order)
    np.testing.assert_array_equal(np.asarray(ref.topk_i(ws0)),
                                  np.asarray(sh.topk_i(ws1)))
    np.testing.assert_allclose(np.asarray(ref.topk_d(ws0)),
                               np.asarray(sh.topk_d(ws1)), rtol=2e-5, atol=0)
    np.testing.assert_array_equal(np.asarray(ws0.ndis), np.asarray(ws1.ndis))
    np.testing.assert_array_equal(np.asarray(ws0.ninserts),
                                  np.asarray(ws1.ninserts))


def test_place_index_replicates_delta(mutated_ivf):
    ds, mut = mutated_ivf
    mesh = _mesh1()
    view = dist.place_index(mut.view(), mesh)
    for leaf in jax.tree.leaves(view.delta):
        assert leaf.sharding.is_fully_replicated
    # contents untouched by placement
    np.testing.assert_array_equal(np.asarray(view.delta.ids),
                                  np.asarray(mut.delta.ids))


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

sys.path.insert(0, "src")
from repro import dist, mutate
from repro.core import darth_search, engines
from repro.data import vectors
from repro.index import hnsw, ivf

ds = vectors.make_dataset(n=1501, d=16, num_learn=64, num_queries=32,
                          clusters=12, cluster_std=1.0, seed=0)
q = jnp.asarray(ds.queries[:16])
events = vectors.mutation_stream(ds, insert_pct=0.2, delete_pct=0.1,
                                 drift=0.3, steps=4, seed=3)

out = {"ndev": jax.device_count(), "cases": []}
for kind in ("ivf", "hnsw"):
    if kind == "ivf":
        base = ivf.build(ds.base, nlist=16, seed=0, cap_round=1)
        mk = lambda idx: engines.ivf_engine(idx, k=5, nprobe=8)
        mk_sh = lambda idx, mesh: engines.sharded_ivf_engine(
            idx, mesh, k=5, nprobe=8)
    else:
        base = hnsw.build(ds.base, m=8, passes=1, ef_construction=32, seed=0)
        mk = lambda idx: engines.hnsw_engine(idx, k=5, ef=24)
        mk_sh = lambda idx, mesh: engines.sharded_hnsw_engine(
            idx, mesh, k=5, ef=24)
    mut = mutate.MutableIndex(base, capacity=512)
    mut.apply(events)
    for phase in ("burst", "compacted"):
        if phase == "compacted":
            mut.compact(seed=1)
        ref = engines.mutable_engine(mk(mut.base), mut.delta)
        ws0 = darth_search.plain_search(ref, q)
        d0 = np.asarray(ref.topk_d(ws0)); i0 = np.asarray(ref.topk_i(ws0))
        nd0 = np.asarray(ws0.ndis); ni0 = np.asarray(ws0.ninserts)
        for nsh in (1, 2, 4):
            mesh = Mesh(np.asarray(jax.devices()[:nsh]), ("model",))
            view = dist.place_index(mut.view(), mesh)
            sh = engines.mutable_engine(mk_sh(view.base, mesh), view.delta)
            ws1 = darth_search.plain_search(sh, q)
            out["cases"].append({
                "kind": kind, "phase": phase, "shards": nsh,
                "delta_rep": bool(all(
                    l.sharding.is_fully_replicated
                    for l in jax.tree.leaves(view.delta))),
                "d_ok": bool(np.allclose(d0, np.asarray(sh.topk_d(ws1)),
                                         atol=1e-4)),
                "i_ok": bool(np.array_equal(i0,
                                            np.asarray(sh.topk_i(ws1)))),
                "ndis_ok": bool(np.array_equal(nd0, np.asarray(ws1.ndis))),
                "nins_ok": bool(np.array_equal(ni0,
                                               np.asarray(ws1.ninserts))),
            })
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_sharded_mutable_parity_mesh_1_2_4():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ndev"] == 4
    assert len(res["cases"]) == 2 * 2 * 3   # {ivf,hnsw} x {burst,compacted}
    for case in res["cases"]:
        for key in ("delta_rep", "d_ok", "i_ok", "ndis_ok", "nins_ok"):
            assert case[key], case
