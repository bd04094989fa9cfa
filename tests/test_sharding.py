"""Sharding rules + HLO collective accounting + elastic restore."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs, ckpt
from repro.dist import sharding as sh
from repro.launch.mesh import auto_mesh
from repro.models import model_zoo
from repro.utils import hlo as hlo_lib


def _fake_mesh_161():
    # single-device mesh with production axis names: rules must degrade to
    # replication (divisibility check) without erroring.
    return auto_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", configs.ALL_ARCHS)
def test_param_specs_build_for_all_archs(arch):
    cfg = configs.get_config(arch)
    mesh = _fake_mesh_161()
    tree = model_zoo.abstract_params(cfg)
    specs = sh.param_shardings(tree, mesh)
    n = len(jax.tree.leaves(specs))
    assert n == len(jax.tree.leaves(tree))


def test_opt_sharding_structures():
    cfg = configs.get_config("smollm-360m")
    mesh = _fake_mesh_161()
    tree = model_zoo.abstract_params(cfg)
    from repro.train import step as step_lib
    init_opt, _ = step_lib.make_train_step(cfg)
    opt_abs = jax.eval_shape(init_opt, tree)
    o_sh = sh.opt_shardings(opt_abs, tree, mesh)
    assert set(o_sh.keys()) == set(opt_abs.keys())
    # adafactor variant
    init_opt2, _ = step_lib.make_train_step(cfg, optimizer="adafactor")
    opt_abs2 = jax.eval_shape(init_opt2, tree)
    o_sh2 = sh.opt_shardings(opt_abs2, tree, mesh)
    assert "leaves" in o_sh2


def test_collective_parser_weights_loops():
    hlo = """
HloModule test

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %x = f32[8,8] get-tuple-element(%p), index=1
  %ag = f32[8,8]{1,0} all-gather(%x), dimensions={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ag)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %ar = f32[8,8]{1,0} all-reduce(%a), to_apply=%add
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %ar)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""
    out = hlo_lib.collective_bytes(hlo)
    assert out["all-reduce"] == 8 * 8 * 4           # once
    assert out["all-gather"] == 8 * 8 * 4 * 5       # 5 loop trips


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint saved de-sharded restores under a different mesh's
    shardings (the elastic contract; on 1 device both meshes are (1,1))."""
    mesh = _fake_mesh_161()
    tree = {"w": jnp.arange(64.0).reshape(8, 8)}
    ckpt.save(str(tmp_path), 3, tree)
    shardings = {"w": jax.NamedSharding(mesh, P(None, None))}
    like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    restored, meta = ckpt.restore(str(tmp_path), like, shardings=shardings)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]))


def test_sharded_flat_search_single_device():
    from repro.dist import collectives
    mesh = auto_mesh((1,), ("model",))
    fn = collectives.make_sharded_flat_search(mesh, k=5)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    d, i = fn(q, x)
    from repro.index import flat
    d_ref, i_ref = flat.search(q, x, 5)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), atol=1e-3)
