"""CPU rehearsal: every cell of BENCHMARK.json, driven for a second at the
smoke sizes of its configuration and traffic files, prints the contract's
last line; the real command refuses to run without a TPU; a cell is added
by adding a data file and an entry."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import ROOT, harness

CELLS = [c["name"] for c in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 3_000_000_019                       # more than 31 bits


def cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


def run_cell(capsys, cell: str, trace: int) -> dict:
    rc = harness.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1", "--trace", str(trace)], allow_cpu=True,
                      overrides={"smoke": True})
    assert rc == 0
    return last_json(capsys.readouterr().out)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_the_contract_line(capsys, cell):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = run_cell(capsys, cell, 0)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in harness.metric_entries(spec, {"name": cell},
                                                      False)}
    assert set(out["metrics"]) == want and "setup_s" in want
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run_prints_busy_and_window(capsys):
    out = run_cell(capsys, CELLS[0], 1)
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "setup.compile_s" in out["metrics"]


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=ROOT, env=cpu_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace",
                        "0"], cwd=tmp_path, env=cpu_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_a_cell_is_added_by_a_data_file_and_an_entry(tmp_path):
    """A copy of the benchmark gains a traffic mix (a new JSON file) and a
    cell (a new entry), and runs it with no code changed."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/build-2e24.json").read_text())
    mix.update(clients=2)
    (tmp_path / "bench/traffic/build-2c.json").write_text(json.dumps(mix))
    spec["workloads"].append({"name": "wah.build-2c",
                              "config": "wah-card256",
                              "traffic": "build-2c", "chips": 1,
                              "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "wah.build-2e24" in m.get("workloads", []):
            m["workloads"].append("wah.build-2c")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    code = ("import sys; sys.path.insert(0, '.');"
            "from bench.harness import main;"
            "sys.exit(main(['--workload', 'wah.build-2c', '--seed', "
            "'5', '--seconds', '1', '--trace', '0'], allow_cpu=True, "
            "overrides={'smoke': True}))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       env=dict(cpu_env(), JAX_COMPILATION_CACHE_DIR=str(
                           tmp_path / "cache")),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = last_json(p.stdout)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"calls_per_s", "setup_s"}
