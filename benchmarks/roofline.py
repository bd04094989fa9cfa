"""Roofline analysis (deliverable g): three terms per (arch x shape x mesh)
from the dry-run's compiled artifacts.

  compute    = HLO_FLOPs / peak_FLOP/s          (per-device, loop-weighted)
  memory     = HLO_bytes / HBM_bw               (per-device kernel traffic)
  collective = collective_bytes / link_bw       (per-device wire bytes)

Hardware peaks come from PEAKS, keyed by jax's `device_kind`; the dry-run
models a TPU v5e (DRYRUN_DEVICE_KIND).
MODEL_FLOPS = 6*N*D (train, dense), 6*N_active*D (MoE), 2*N*D (prefill),
2*N_active*tokens (decode) — the "useful compute" yardstick; the ratio
MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat/redundancy waste.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

import numpy as np

# Published per-chip peaks by `jax.devices()[i].device_kind`. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM);
# link_bw is a conservative single-link model of the 1,600 Gbit/s ICI.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of `device_kind`; an unknown device is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to benchmarks/roofline.py PEAKS with their source")
    return PEAKS[device_kind]

_PARAM_COUNTS: Dict[str, Dict[str, float]] = {}


def _param_counts(arch: str) -> Dict[str, float]:
    if arch in _PARAM_COUNTS:
        return _PARAM_COUNTS[arch]
    import jax
    from repro import configs
    from repro.models import model_zoo
    cfg = configs.get_config(arch)
    shapes = model_zoo.param_shapes(cfg)
    total = 0
    expert = 0
    for path, s in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda x: isinstance(x, tuple))[0]:
        n = int(np.prod(s))
        total += n
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        if "moe/w" in keys:
            expert += n
    active = total - expert
    if cfg.num_experts:
        active += expert * cfg.experts_per_token / cfg.num_experts
    _PARAM_COUNTS[arch] = {"total": float(total), "active": float(active)}
    return _PARAM_COUNTS[arch]


def model_flops(arch: str, kind: str, seq: int, batch: int) -> float:
    counts = _param_counts(arch)
    n_act = counts["active"]
    tokens = seq * batch
    if kind == "train":
        return 6.0 * n_act * tokens
    if kind == "prefill":
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * batch


def analyze_record(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    from repro.configs.base import SHAPES
    peak = peaks(DRYRUN_DEVICE_KIND)
    cell = SHAPES[rec["shape"]]
    chips = rec["num_devices"]
    t_c = rec["hlo_flops"] / peak["flops"]
    t_m = rec["hlo_bytes"] / peak["hbm_bw"]
    t_x = rec["collectives"]["total"] / peak["link_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    mf = model_flops(rec["arch"], cell.kind, cell.seq_len, cell.global_batch)
    useful = mf / max(rec["hlo_flops"] * chips, 1.0)
    bound = max(t_c, t_m, t_x)
    mfu_bound = (mf / chips / peak["flops"]) / max(bound, 1e-12)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": cell.kind,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "model_flops": mf, "useful_ratio": useful,
        "roofline_fraction": mfu_bound,
        "mem_gb": rec.get("memory", {}).get("temp_bytes", 0) / 1e9,
    }


def build_table(path: str = "results/dryrun.json",
                mesh: str = "16x16") -> str:
    with open(path) as f:
        recs = json.load(f)
    rows = []
    for r in recs:
        if r.get("mesh") != mesh:
            continue
        if r.get("status") == "skipped":
            rows.append({"arch": r["arch"], "shape": r["shape"],
                         "mesh": mesh, "skip": r["reason"]})
            continue
        a = analyze_record(r)
        if a:
            rows.append(a)
    lines = [
        "| arch | shape | compute(s) | memory(s) | collective(s) | dominant "
        "| MODEL_FLOPS | useful | roofline frac | mem GB/dev |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    rows.sort(key=lambda r: (r["arch"], order.get(r["shape"], 9)))
    for r in rows:
        if "skip" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"SKIP | — | — | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"{r['dominant']} | {r['model_flops']:.3g} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} | "
            f"{r['mem_gb']:.1f} |")
    return "\n".join(lines)


def main() -> None:
    for mesh, label in (("16x16", "single pod"), ("2x16x16", "multi-pod")):
        table = build_table(mesh=mesh)
        os.makedirs("results", exist_ok=True)
        out = f"results/roofline_{mesh}.md"
        with open(out, "w") as f:
            f.write(f"# Roofline table ({mesh}, {label})\n\n" + table + "\n")
        print(f"[roofline] wrote {out}")
        if mesh == "16x16":
            print(table)


if __name__ == "__main__":
    main()
