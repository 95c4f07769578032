"""BENCHMARK.json against the files it names, and the proof that the
harness is driven by data: a scratch cell made of two new files and one
new entry runs without an edit to any file that was there."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import cells, peaks

BENCH = cells.contract()


def test_every_cell_has_its_files():
    for entry in BENCH["workloads"]:
        spec = cells.load_cell(entry["name"])
        p, q = spec["config"]["grid"]
        assert p * q == entry["chips"]
        assert spec["traffic"]["kind"]
        assert spec["cell"]["tol_eps"] > 0
        assert spec["end_to_end"] and spec["per_layer"]
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("package,key", [("end_to_end", "end_to_end"),
                                         ("layer_metrics", "per_layer")])
def test_every_metric_has_a_reader_that_agrees(package, key):
    for entry in BENCH[key]:
        module = importlib.import_module(
            f"benchmarks.{package}.{entry['name'].replace('.', '_')}")
        for field, value in module.HEADER.items():
            assert entry[field] == value, (entry["name"], field)
        assert callable(module.compute)


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells_all = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells_all):
            assert cell in moved.get("workloads", cells_all)


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_a_scratch_cell_needs_only_new_files(tmp_path):
    """Copy the benchmark, add ``posv_scratch`` as a traffic file, a
    cell file and one entry, and rehearse it."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(cells.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "posv_scratch", "config": "dense_solve_f32_1x1",
        "traffic": "closed_loop_posv_offset7", "chips": 1,
        "why": "scratch: posv_16k_1x1 at another seed offset"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = cells.read_json(os.path.join(
        cells.BENCH, "traffic", "closed_loop_posv.json"))
    traffic["seed_offset"] = 7
    (root / "benchmarks/traffic/closed_loop_posv_offset7.json").write_text(
        json.dumps(traffic))
    shutil.copy(root / "benchmarks/workloads/posv_16k_1x1.json",
                root / "benchmarks/workloads/posv_scratch.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cells.ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "posv_scratch",
         "--seed", "5", "--seconds", "1", "--trace", "0",
         "--rehearse-on-cpu", "--n", "256", "--nb", "64"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 1, done.stderr[-2000:]     # a rehearsal
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] is False
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"solve_s", "solve_p90_s", "setup_s"} <= set(last["metrics"])


def test_without_a_tpu_the_run_exits_non_zero(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "posv_16k_1x1",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
