"""Multi-host slot-pool serving over the sharded engines: slot-dim
placement specs (dist.sharding "hosts" axis), index placement on a
("hosts", "model") serve mesh (index global per host group, slot dim
split), and exact single-controller parity of the full serve loop at
(hosts, shards) combinations on real placeholder devices — for BOTH
sharded engine families, including through mutable_engine."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro import dist
from repro.launch import mesh as mesh_lib


def _serve_mesh1():
    return mesh_lib.auto_mesh((1, 1), ("hosts", "model"))


class _FakeServeMesh:
    """spec_for only reads axis_names + shape — a fake lets the spec
    rules be tested for >1-sized axes on the 1-device test host."""
    axis_names = ("hosts", "model")
    shape = {"hosts": 2, "model": 2}


class _FakeDataMesh:
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 2}


def test_slot_dim_spec_rules():
    """Slot-dim specs: the leading (slot) dim splits over "hosts" and
    ONLY "hosts" (the device programs key on collectives.BATCH_AXIS, so
    any other axis would split inputs they treat as replicated), and
    replicates when the axis is absent or the slot count does not
    divide."""
    from repro.dist import sharding

    spec = sharding.spec_for(_FakeServeMesh, (8, 16), ("hosts", None))
    assert tuple(spec) == ("hosts", None)
    spec = sharding.spec_for(_FakeDataMesh, (8,), ("hosts",))
    assert tuple(spec) == (None,)
    spec = sharding.spec_for(_FakeServeMesh, (7,), ("hosts",))
    assert tuple(spec) == (None,)


def test_slot_sharding_and_serve_batch_shardings():
    """batch_shardings kind="serve" and slot_sharding build
    NamedShardings on a real serve mesh (the 1-sized hosts axis of the
    test host drops to replication — the divisibility contract)."""
    mesh = _serve_mesh1()
    qb = np.zeros((8, 16), np.float32)
    rt = np.zeros((8,), np.float32)
    sh = dist.batch_shardings({"q": qb, "rt": rt}, mesh, kind="serve")
    assert sh["q"].mesh.axis_names == ("hosts", "model")
    assert all(e is None for e in sh["q"].spec)
    s = dist.slot_sharding(mesh, 8, trailing=1)
    assert all(e is None for e in s.spec)


def test_place_index_on_serve_mesh_keeps_index_global():
    """place_index on a ("hosts", "model") mesh: every sharded dim
    names only "model", so the index replicates across host groups —
    each host group sees the whole sharded index."""
    from repro.data import vectors
    from repro.index import ivf

    ds = vectors.make_dataset(n=1200, d=16, num_learn=32, num_queries=8,
                              clusters=8, cluster_std=1.0, seed=0)
    index = ivf.build(ds.base, nlist=8, seed=0)
    mesh = _serve_mesh1()
    placed = dist.place_index(index, mesh)
    for name in ("bucket_vecs", "bucket_ids", "bucket_sqnorm"):
        spec = tuple(getattr(placed, name).sharding.spec)
        assert "hosts" not in spec, (name, spec)
    np.testing.assert_array_equal(np.asarray(placed.bucket_sizes),
                                  np.asarray(index.bucket_sizes))


def test_make_serve_mesh_validates():
    with pytest.raises(ValueError, match="needs"):
        mesh_lib.make_serve_mesh(hosts=4, shards=4)
    with pytest.raises(ValueError, match="hosts must be"):
        mesh_lib.make_serve_mesh(hosts=0)
    mesh = mesh_lib.make_serve_mesh(hosts=1, shards=1)
    assert mesh.axis_names == ("hosts", "model")


def test_serve_mesh_single_device_serves():
    """The full multi-host serve loop on the (1, 1) serve mesh: the
    slot-dim placement path is exercised (mesh has a "hosts" axis) and
    results match the meshless server exactly."""
    import jax.numpy as jnp
    from repro.core import api, engines
    from repro.data import vectors
    from repro.index import ivf
    from repro.serve import DarthServer

    ds = vectors.make_dataset(n=1500, d=16, num_learn=128, num_queries=32,
                              clusters=12, cluster_std=1.0, seed=0)
    index = ivf.build(ds.base, nlist=12, seed=0)
    d = api.Darth(
        make_engine=lambda **kw: engines.ivf_engine(index, **kw),
        engine=engines.ivf_engine(index, k=5, nprobe=12))
    d.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base), batch=128)
    rts = np.tile([0.8, 0.9], 16).astype(np.float32)

    ref_server = DarthServer(d.engine, d.trained.predictor,
                             d.interval_for_target, num_slots=8,
                             steps_per_sync=2)
    ref, ref_stats = ref_server.serve(ds.queries, rts)

    mesh = _serve_mesh1()
    placed = dist.place_index(index, mesh)
    eng = engines.sharded_ivf_engine(placed, mesh, k=5, nprobe=12)
    server = DarthServer(eng, d.trained.predictor, d.interval_for_target,
                         num_slots=8, steps_per_sync=2, mesh=mesh, hosts=2)
    res, stats = server.serve(ds.queries, rts)
    assert stats.completed == ref_stats.completed == 32
    for a, b in zip(ref, res):
        # kernel (sharded) vs einsum (meshless) distances: ids exact,
        # distances to a relative 2e-5 (f32 summation order)
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_allclose(a[0], b[0], rtol=2e-5, atol=0)
    assert stats.ndis_harvested == ref_stats.ndis_harvested


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import jax, jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")
from repro import dist, mutate
from repro.core import api, engines
from repro.data import vectors
from repro.index import hnsw, ivf
from repro.launch import mesh as mesh_lib
from repro.serve import DarthServer

ds = vectors.make_dataset(n=1501, d=16, num_learn=128, num_queries=48,
                          clusters=12, cluster_std=1.0, seed=0)
rts = np.tile([0.8, 0.9, 0.95], 16).astype(np.float32)
events = vectors.mutation_stream(ds, insert_pct=0.15, delete_pct=0.05,
                                 drift=0.3, steps=3, seed=3)

out = {"ndev": jax.device_count(), "cases": []}
for kind in ("ivf", "hnsw"):
    if kind == "ivf":
        index = ivf.build(ds.base, nlist=12, seed=0, cap_round=1)
        kw = dict(k=5, nprobe=12)
        mk = lambda idx, **k2: engines.ivf_engine(idx, **k2)
        mk_sh = lambda idx, mesh, **k2: engines.sharded_ivf_engine(
            idx, mesh, **k2)
    else:
        index = hnsw.build(ds.base, m=8, passes=1, ef_construction=32,
                           seed=0)
        kw = dict(k=5, ef=24)
        mk = lambda idx, **k2: engines.hnsw_engine(idx, **k2)
        mk_sh = lambda idx, mesh, **k2: engines.sharded_hnsw_engine(
            idx, mesh, **k2)
    for mutated in (False, True):
        if mutated:
            mut = mutate.MutableIndex(index, capacity=512)
            mut.apply(events)
            base_idx = mut.base
            wrap = lambda eng: engines.mutable_engine(eng, mut.delta)
        else:
            base_idx = index
            wrap = lambda eng: eng
        d = api.Darth(make_engine=lambda **k2: wrap(mk(base_idx, **k2)),
                      engine=wrap(mk(base_idx, **kw)))
        d.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base), batch=128)
        ref_server = DarthServer(d.engine, d.trained.predictor,
                                 d.interval_for_target, num_slots=8,
                                 steps_per_sync=2)
        ref, ref_stats = ref_server.serve(ds.queries, rts)
        for hosts, shards in ((1, 4), (2, 2), (4, 1)):
            mesh = mesh_lib.make_serve_mesh(hosts, shards)
            if mutated:
                view = dist.place_index(mut.view(), mesh)
                eng = engines.mutable_engine(
                    mk_sh(view.base, mesh, **kw), view.delta)
            else:
                eng = mk_sh(dist.place_index(index, mesh), mesh, **kw)
            server = DarthServer(eng, d.trained.predictor,
                                 d.interval_for_target, num_slots=8,
                                 steps_per_sync=2, mesh=mesh, hosts=hosts)
            res, stats = server.serve(ds.queries, rts)
            out["cases"].append({
                "kind": kind, "mutated": mutated,
                "hosts": hosts, "shards": shards,
                "completed": stats.completed,
                "all_done": all(r is not None for r in res),
                "d_ok": bool(all(np.allclose(a[0], b[0], atol=1e-4)
                                 for a, b in zip(ref, res))),
                "i_ok": bool(all(np.array_equal(a[1], b[1])
                                 for a, b in zip(ref, res))),
                "ndis_ok": stats.ndis_harvested == ref_stats.ndis_harvested,
                "trunc_ok": stats.truncated == ref_stats.truncated == 0,
            })
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_multi_host_sharded_serve_parity_hosts_1_2_4():
    """Acceptance bar: multi-host serve output exactly matches the
    single-controller server (topk_d/topk_i/ndis/truncated) at host
    counts {1, 2, 4} on real placeholder-device serve meshes, for both
    sharded engines, plain AND through mutable_engine."""
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ndev"] == 4
    # {ivf,hnsw} x {plain,mutable} x {(1,4),(2,2),(4,1)}
    assert len(res["cases"]) == 2 * 2 * 3
    for case in res["cases"]:
        assert case["completed"] == 48, case
        for key in ("all_done", "d_ok", "i_ok", "ndis_ok", "trunc_ok"):
            assert case[key], case
