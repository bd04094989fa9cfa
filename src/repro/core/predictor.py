"""Recall predictor wrapper: GBDT params + prediction paths.

Two inference paths, numerically identical (tests assert it):
  * XLA path (gbdt.infer.predict_efficient), the default on every backend
    and the one the serving loop runs: a gather-free descent by
    compare-and-select (`jnp.where` and reductions, never a multiply,
    which turns a degenerate node's infinite threshold into NaN, nor a
    matmul, which rounds to bfloat16 on a TPU), fused into the step's
    `darth.predict` branch,
  * Pallas path (kernels.gbdt_predict, `use_kernel=True`) — VMEM-resident
    ensemble, compiled on a TPU, interpreted on the CPU (kernels.backend).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro import gbdt
from repro.gbdt.model import GBDTParams
from repro.kernels import ops as kernel_ops


@dataclasses.dataclass
class RecallPredictor:
    params: GBDTParams
    use_kernel: bool = False

    def __call__(self, feats: jax.Array) -> jax.Array:
        if self.use_kernel:
            return kernel_ops.gbdt_predict(self.params, feats)
        return gbdt.predict_efficient(self.params, feats)

    def save(self, path: str) -> None:
        sd = gbdt.to_state_dict(self.params)
        np.savez(path, **sd)

    @classmethod
    def load(cls, path: str, use_kernel: bool = False) -> "RecallPredictor":
        with np.load(path) as z:
            sd = {k: z[k] for k in z.files}
        return cls(params=gbdt.from_state_dict(sd), use_kernel=use_kernel)


def regression_metrics(pred: np.ndarray, true: np.ndarray) -> dict:
    pred = np.asarray(pred, np.float64)
    true = np.asarray(true, np.float64)
    mse = float(np.mean((pred - true) ** 2))
    mae = float(np.mean(np.abs(pred - true)))
    ss_res = float(np.sum((pred - true) ** 2))
    ss_tot = float(np.sum((true - true.mean()) ** 2)) + 1e-12
    return {"mse": mse, "mae": mae, "r2": 1.0 - ss_res / ss_tot}
