"""Unit tests for the analysis/infra utilities: meshctx, hlo parser,
metrics, roofline model-flops."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import metrics
from repro.launch.mesh import auto_mesh
from repro.utils import hlo as hlo_lib
from repro.utils import meshctx


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    y = meshctx.constrain(x, "dp", None)
    assert y is x  # literally untouched


def test_constrain_divisibility_degrades():
    mesh = auto_mesh((1, 1), ("data", "model"))
    with meshctx.use_mesh(mesh):
        x = jnp.ones((3, 7))  # nothing divides -> P(None, None)
        y = meshctx.constrain(x, "dp", "tp")
        assert y.shape == x.shape


def test_sp_axis_gated():
    mesh = auto_mesh((1, 1), ("data", "model"))
    with meshctx.use_mesh(mesh, sp=False):
        assert meshctx._resolve(mesh, "sp") is None
    with meshctx.use_mesh(mesh, sp=True):
        assert meshctx._resolve(mesh, "sp") == "model"
    # dpt = all axes
    assert meshctx._resolve(mesh, "dpt") == ("data", "model")


def test_hlo_shape_bytes():
    assert hlo_lib._shape_bytes("f32[8,8]{1,0}") == 256
    assert hlo_lib._shape_bytes("bf16[4]") == 8
    assert hlo_lib._shape_bytes("(f32[2,2], s32[3])") == 16 + 12
    assert hlo_lib._shape_bytes("pred[]") == 1


def test_hlo_dot_flops_weighted():
    hlo = """
HloModule m

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %x = f32[4,4] get-tuple-element(%p), index=1
  %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4,4]) tuple(%i, %d)
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4] parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,4]) tuple(%z, %a)
  %w = (s32[], f32[4,4]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[4,4] get-tuple-element(%w), index=1
}
"""
    out = hlo_lib.analyze(hlo)
    # dot: 2*16*4 = 128 flops x 3 trips
    assert out["flops"] == 128 * 3


def test_metrics_recall_rde_nrs():
    found_i = np.array([[0, 1, 2], [3, 9, 8]])
    true_i = np.array([[0, 1, 3], [3, 4, 5]])
    r = metrics.recall(found_i, true_i)
    np.testing.assert_allclose(r, [2 / 3, 1 / 3])
    assert metrics.rqut(r, 0.5) == 0.5

    found_d = np.array([[1.0, 4.0, 9.0]])
    true_d = np.array([[1.0, 4.0, 4.0]])
    v = metrics.rde(found_d, true_d)          # only slot 3 deviates: (3-2)/2
    np.testing.assert_allclose(v, [0.5 / 3], atol=1e-6)

    gt_wide = np.array([[0, 1, 2, 3, 4]])
    n = metrics.nrs(np.array([[0, 1, 2]]), gt_wide)
    np.testing.assert_allclose(n, [1.0])      # perfect ranks

    es = metrics.error_stats(np.array([0.95, 0.5]), 0.9)
    assert es["worst1pct"] == pytest.approx(0.4)


def test_roofline_model_flops():
    import benchmarks.roofline as rl
    mf_train = rl.model_flops("smollm-360m", "train", 4096, 256)
    counts = rl._param_counts("smollm-360m")
    assert mf_train == 6 * counts["active"] * 4096 * 256
    # MoE active < total
    c = rl._param_counts("qwen3-moe-30b-a3b")
    assert c["active"] < 0.25 * c["total"]
