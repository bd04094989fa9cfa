"""The benchmark is found by name, refuses to run without a chip, and a
configuration is added by adding a file."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _copy_bench(dst: Path, with_src: bool) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_src:
        (dst / "src").symlink_to(ROOT / "src")


def _run(cwd: Path, *args, timeout=600):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def _result_line(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


@pytest.mark.parametrize("with_src", [True, False],
                         ids=["checkout", "benchmark_files_only"])
def test_no_chip_no_result(tmp_path, with_src):
    _copy_bench(tmp_path, with_src)
    p = _run(tmp_path, "bench/run.py", "--workload", "sift1m-ivf1024.batch",
             "--seed", str(2 ** 32 + 1), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not _result_line(p.stdout)


def test_spec_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (ROOT / "bench" / "references"
                / f"{cfg['reference']}.py").exists()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("key,value", [
    (("metric",), "inner_product"), (("index", "kind"), "hnsw"),
    (("index", "store"), "float16"), (("serving", "engine"),
                                       "sharded_ivf_engine")])
def test_a_deployment_the_harness_does_not_serve_is_refused(key, value):
    from bench import harness
    cfg = json.loads((ROOT / "bench/configs/sift1m-ivf1024.json").read_text())
    harness.served_as(cfg)
    node = cfg
    for k in key[:-1]:
        node = node[k]
    node[key[-1]] = value
    with pytest.raises(ValueError, match=repr(value)):
        harness.served_as(cfg)


def test_an_added_config_and_mix_rehearse_without_edits(tmp_path):
    """A throwaway configuration (an SQ8 store, with a distance limit of
    its own), open-loop traffic mix and cell, added as files and entries,
    run through the unchanged harness."""
    _copy_bench(tmp_path, with_src=True)
    cfg = json.loads((ROOT / "bench/configs/sift1m-ivf1024.json").read_text())
    cfg.update(name="extra-ivf", dim=48)
    cfg["index"]["store"] = "int8"
    cfg["limits"]["dist_gap"] = 1.0
    (tmp_path / "bench/configs/extra-ivf.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/extra-open.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 40, "targets": [0.8, 0.9, 0.95]}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="extra-ivf",
                                file="bench/configs/extra-ivf.json"))
    cell = "extra-ivf.open"
    spec["workloads"].append(dict(spec["workloads"][0], name=cell,
                                  config="extra-ivf", traffic="extra-open"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    for name in ("p50_ms", "p99_ms"):
        spec["end_to_end"].append({"name": name, "unit": "ms",
                                   "better": "lower", "bound": 0.2,
                                   "source": "host_clock",
                                   "workloads": [cell]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    p = _run(tmp_path, "bench/rehearse.py", "--workload", cell)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert f"[rehearsal] {cell} on cpu: correct True" in p.stdout
    assert "store int8" in p.stdout
    assert "p99_ms" in p.stdout and "steps_per_query.batch" in p.stdout
    assert not _result_line(p.stdout)
