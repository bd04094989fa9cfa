"""Pallas TPU kernels for DARTH's compute hot spots.

l2_topk       fused squared-L2 distance tiles + running top-k against a
              SHARED DB (flat ground truth, centroid ranking)
bucket_topk   fused IVF probe: per-query gathered bucket distances merged
              into the running top-k (DARTH-on-IVF's hot loop)
gbdt_predict  VMEM-resident GBDT ensemble inference (the recall predictor)

Each kernel has a pure-jnp oracle in ref.py and a jit'd public wrapper in
ops.py. backend.py decides how they run: compiled on a TPU, interpreted
on the CPU; tests sweep shapes/dtypes in interpret mode against the
oracles.
"""
from repro.kernels import ops, ref
from repro.kernels.ops import bucket_topk, gbdt_predict, l2_topk

__all__ = ["ops", "ref", "l2_topk", "bucket_topk", "gbdt_predict"]
