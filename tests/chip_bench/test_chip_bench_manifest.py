"""The harness finds every piece of a cell by its name, refuses names it
does not know, and takes a new cell from new files and entries alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, TINY_SIZES


def test_every_cell_finds_its_files(manifest):
    for cell in manifest.data["workloads"]:
        conf = manifest.config(cell["config"])
        traffic = manifest.traffic(cell["traffic"])
        assert set(conf["sizes"]) <= set(TINY_SIZES) and traffic
        ref, work = manifest.reference(conf["kind"]), manifest.work(conf["kind"])
        assert callable(ref.init) and callable(ref.logit) and callable(work.step_flops)
        for trace in (False, True):
            for m in manifest.metrics(cell["name"], trace):
                if trace:
                    assert callable(manifest.reader(m["name"]).read)
    assert manifest.peaks("TPU v5 lite")["flops_per_s"] == 197e12


@pytest.mark.parametrize("lookup,name", [
    ("workload", "wide_deep.nope"), ("config", "dlrm_x"), ("traffic", "bursty"),
    ("reader", "no_such_metric"), ("reference", "dcn_v9"), ("work", "dcn_v9"),
    ("peaks", "TPU v9 huge")])
def test_unknown_names_are_refused(manifest, lookup, name):
    with pytest.raises(KeyError):
        getattr(manifest, lookup)(name)


def test_metrics_follow_their_workloads_lists(manifest):
    ckpt = [m["name"] for m in manifest.metrics("wide_deep.zipf.ckpt", False)]
    plain = [m["name"] for m in manifest.metrics("wide_deep.zipf", False)]
    assert ckpt == ["ckpt_stall_ms", "setup_s"]
    assert plain == ["samples_per_s", "setup_s"]
    assert "ckpt_flatten_ms" not in [m["name"] for m in manifest.metrics("xdeepfm.zipf", True)]
    traced = [m["name"] for m in manifest.metrics("wide_deep.zipf.ckpt", True)]
    assert traced == ["ckpt_flatten_ms", "samples_per_s.ckpt"]


def test_a_cell_added_by_files_and_entries_alone_runs(tmp_path, manifest):
    """A new configuration, traffic mix, metric reader and cell: files under
    the benchmark's directories and entries in the manifest, no code."""
    import harness
    from manifest import Manifest
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    conf = dict(manifest.config("wide_deep"), sizes=dict(
        (k, TINY_SIZES[k]) for k in manifest.config("wide_deep")["sizes"]))
    (bench / "configs" / "wide_deep_small.json").write_text(json.dumps(conf))
    (bench / "traffic" / "zipf.hotter.json").write_text(json.dumps(
        {"zipf_alpha": 1.4, "hot_rows": 2, "ring_batches": 8, "ckpt_every": 0}))
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['steps'])\n")
    data = json.loads(json.dumps(manifest.data))
    data["configs"].append(dict(data["configs"][0], name="wide_deep_small",
                                file="benchmarks/chip/configs/wide_deep_small.json"))
    data["workloads"].append({"name": "wide_deep_small.zipf.hotter",
                              "config": "wide_deep_small", "traffic": "zipf.hotter",
                              "chips": 1, "why": "a test cell"})
    rate = next(m for m in data["end_to_end"] if m["name"] == "samples_per_s")
    rate["workloads"].append("wide_deep_small.zipf.hotter")
    data["per_layer"].append({"name": "steps_seen", "unit": "steps", "better": "higher",
                              "source": "host_clock", "layer": "input",
                              "moves": "samples_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    m = Manifest(str(tmp_path), str(bench))
    assert m.reader("steps_seen").read({"steps": 3}) == 3.0
    result = harness.run(m, "wide_deep_small.zipf.hotter", 2**33 + 1, 0.3,
                         log=lambda s: None)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"samples_per_s", "setup_s"}


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "wide_deep.zipf", "--seed", str(2**40), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_means_no_result():
    proc = _run_py(ROOT)
    assert proc.returncode == 2, proc.stderr
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_the_benchmark_alone_gives_no_result(tmp_path, manifest):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest.data["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
