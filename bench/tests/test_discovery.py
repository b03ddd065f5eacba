"""A configuration, a traffic mix and a per-layer metric are added as
files alone: the harness finds them by the names BENCHMARK.json gives."""
import json
import shutil
import subprocess
import sys

import jax

import graphs
import harness
import run

ROOT = harness.ROOT


def _copy(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path / "bench"


def _add_cell(tmp_path):
    """Add a configuration, a traffic mix of two graph sizes, a metric
    reader and the cell that uses them, as files alone."""
    bench = _copy(tmp_path)
    cfg = json.loads((bench / "configs" / "snn_rand_truenorth.json")
                     .read_text())
    cfg.update(name="snn_rand_loihi", omega=1024, delta=4096,
               n_nodes=2048)
    (bench / "configs" / "snn_rand_loihi.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "two_sizes.json").write_text(
        json.dumps({"about": "test", "scales": [1.0, 0.5]}))
    (bench / "metrics" / "levels.py").write_text(
        "def read(run):\n    return len(run.solves[0]['levels'])\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(
        name="snn_rand_loihi.two_sizes", config="snn_rand_loihi",
        traffic="two_sizes", chips=1, why="test"))
    spec["per_layer"].append(dict(
        name="levels", unit="count", better="lower",
        source="program_counter", layer="coarsening", moves="solve_s",
        workloads=["snn_rand_loihi.two_sizes"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def test_new_files_are_found_by_name(tmp_path):
    bench = _add_cell(tmp_path)
    cell = harness.workload("snn_rand_loihi.two_sizes", root=tmp_path)
    cfg = harness.config(cell["config"], bench=bench)
    assert cfg["omega"] == 1024
    mix = harness.traffic(cell["traffic"], bench=bench)
    assert [g["n_nodes"] for g in graphs.requests(cfg, mix, 5)] == \
        [2048, 1024]
    names = [m["name"] for m in
             harness.per_layer_metrics(cell["name"], root=tmp_path)]
    assert names == ["levels"]
    read = harness.metric_reader("levels", bench=bench)

    class Run:
        solves = [dict(levels=[(1, 2, 3)] * 5)]
    assert read(Run) == 5


def test_a_new_mix_changes_what_the_window_sends(tmp_path, monkeypatch,
                                                 capsys):
    """The cell added as files runs through the unchanged harness (JAX on
    the CPU, at a small size): set-up solves both graph sizes and the
    window solves them in turn."""
    bench = _add_cell(tmp_path)
    real = {f: getattr(harness, f) for f in ("workload", "config",
                                             "traffic", "metric_reader")}
    monkeypatch.setattr(harness, "workload",
                        lambda n: real["workload"](n, root=tmp_path))
    monkeypatch.setattr(harness, "config", lambda n: dict(
        real["config"](n, bench=bench), n_nodes=128, omega=64, delta=64))
    monkeypatch.setattr(harness, "traffic",
                        lambda n: real["traffic"](n, bench=bench))
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    sizes = []
    solve = run.solve
    monkeypatch.setattr(run, "solve", lambda hg, cfg: (
        sizes.append(hg.n_nodes), solve(hg, cfg))[1])
    assert run.main(["--workload", "snn_rand_loihi.two_sizes", "--seed",
                     "2147483659", "--seconds", "2", "--trace", "0"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert len(sizes) >= 4 and out["attempted"] == len(sizes)
    assert sizes == [(128, 64)[i % 2] for i in range(len(sizes))]


def test_every_named_file_exists():
    spec = harness.benchmark()
    for c in spec["configs"]:
        cfg = harness.config(c["name"])
        assert (ROOT / c["file"]).exists() and cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
    for w in spec["workloads"]:
        harness.config(w["config"])
        harness.traffic(w["traffic"])
        assert harness.per_layer_metrics(w["name"])
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_run_exits_nonzero_on_the_cpu():
    cell = harness.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "4294967301", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    _copy(tmp_path)
    cell = harness.benchmark()["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert "correct" not in p.stdout
