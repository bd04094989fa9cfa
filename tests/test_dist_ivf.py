"""Sharded IVF probe engine: numeric parity with the single-device probe
loop (topk_d / topk_i / ndis / ninserts) on the 1-device mesh in-process,
and on real (placeholder) {1, 2, 4}-shard meshes in a subprocess — for
both f32 and SQ8 storage, with a bucket cap that does not divide the
shard count (place_index pads; padding must stay +inf / id -1)."""
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
from repro.index import ivf
from repro.launch.mesh import auto_mesh


def _mesh1():
    return auto_mesh((1,), ("model",))


@pytest.fixture(scope="module")
def small_ivf():
    from repro.data import vectors
    ds = vectors.make_dataset(n=2000, d=16, num_learn=128, num_queries=32,
                              clusters=16, cluster_std=1.0, seed=0)
    return ds


@pytest.mark.parametrize("quantize", [False, True])
def test_sharded_probe_matches_single_device(small_ivf, quantize):
    ds = small_ivf
    index = ivf.build(ds.base, nlist=16, seed=0, cap_round=1,
                      quantize=quantize)
    mesh = _mesh1()
    placed = dist.place_index(index, mesh)
    q = jnp.asarray(ds.queries[:16])
    d0, i0, s0 = ivf.search(index, q, k=5, nprobe=6)
    d1, i1, s1 = ivf.search_sharded(placed, q, k=5, nprobe=6, mesh=mesh)
    # The kernel reduces q.x on the VPU, the single-device step with an
    # einsum: the f32 summation order differs and sqn - 2 q.x + ||q||^2
    # cancels, so distances agree to a relative 2e-5 (1.6e-5 seen)
    # while the ids must match exactly.
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=2e-5,
                               atol=0)
    np.testing.assert_array_equal(np.asarray(s0.ndis), np.asarray(s1.ndis))
    np.testing.assert_array_equal(np.asarray(s0.ninserts),
                                  np.asarray(s1.ninserts))


def test_sharded_probe_xla_fallback_matches(small_ivf):
    ds = small_ivf
    index = ivf.build(ds.base, nlist=16, seed=0)
    mesh = _mesh1()
    placed = dist.place_index(index, mesh)
    q = jnp.asarray(ds.queries[:8])
    d0, i0, _ = ivf.search(index, q, k=5, nprobe=4)
    d1, i1, _ = ivf.search_sharded(placed, q, k=5, nprobe=4, mesh=mesh,
                                   use_kernel=False)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_sharded_engine_protocol_drivers(small_ivf):
    """darth_search's plain / budget drivers run the sharded engine
    unchanged (Engine protocol) and reproduce single-device results."""
    ds = small_ivf
    index = ivf.build(ds.base, nlist=16, seed=0)
    mesh = _mesh1()
    placed = dist.place_index(index, mesh)
    q = jnp.asarray(ds.queries[:16])
    eng_ref = engines.ivf_engine(index, k=5, nprobe=6)
    eng_sh = engines.sharded_ivf_engine(placed, mesh, k=5, nprobe=6)
    assert eng_sh.name == "ivf-sharded" and eng_sh.max_steps == 6

    plain_ref = darth_search.plain_search(eng_ref, q)
    plain_sh = darth_search.plain_search(eng_sh, q)
    np.testing.assert_array_equal(np.asarray(plain_ref.topk_i),
                                  np.asarray(plain_sh.topk_i))

    bud_ref = darth_search.budget_search(eng_ref, q, 300.0)
    bud_sh = darth_search.budget_search(eng_sh, q, 300.0)
    np.testing.assert_array_equal(np.asarray(bud_ref.ndis),
                                  np.asarray(bud_sh.ndis))
    np.testing.assert_array_equal(np.asarray(bud_ref.topk_i),
                                  np.asarray(bud_sh.topk_i))


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
from repro.index import ivf

ds = vectors.make_dataset(n=2000, d=16, num_learn=64, num_queries=32,
                          clusters=16, cluster_std=1.0, seed=0)
q = jnp.asarray(ds.queries[:16])
out = {"ndev": jax.device_count(), "cases": []}
for quantize in (False, True):
    # cap_round=1 -> cap is the raw max bucket size (217 for this build
    # seed under jax 0.9), NOT a multiple of 2 or 4: place_index must pad
    # the cap dim.
    index = ivf.build(ds.base, nlist=16, seed=2, cap_round=1,
                      quantize=quantize)
    d0, i0, s0 = ivf.search(index, q, k=5, nprobe=6)
    for nsh in (1, 2, 4):
        mesh = Mesh(np.asarray(jax.devices()[:nsh]), ("model",))
        placed = dist.place_index(index, mesh)
        # padding contract on the placed arrays
        ids_pad = np.asarray(placed.bucket_ids)[:, index.cap:]
        sqn_pad = np.asarray(placed.bucket_sqnorm)[:, index.cap:]
        d1, i1, s1 = ivf.search_sharded(placed, q, k=5, nprobe=6,
                                        mesh=mesh)
        out["cases"].append({
            "quantize": quantize, "shards": nsh,
            "cap": index.cap, "cap_padded": placed.cap,
            "pad_ok": bool((ids_pad == -1).all()
                           and np.isposinf(sqn_pad).all()),
            "d_ok": bool(np.allclose(np.asarray(d0), np.asarray(d1),
                                     rtol=2e-5, atol=0)),
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
def test_sharded_probe_parity_mesh_1_2_4():
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ndev"] == 4
    assert len(res["cases"]) == 6
    for case in res["cases"]:
        if case["shards"] > 1:     # 217 padded up to the shard multiple
            assert case["cap_padded"] % case["shards"] == 0, case
            assert case["cap_padded"] > case["cap"], case
        for key in ("pad_ok", "d_ok", "i_ok", "ndis_ok", "nins_ok"):
            assert case[key], case
