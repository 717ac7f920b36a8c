"""BENCHMARK.json and the files it names: every name resolves, names and
units are well-formed, and a later PR adds a cell, a configuration, a traffic
mix, a generator or a per-layer metric with new files and new entries only."""

import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def run_py(root, *args):
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), *args],
        capture_output=True, text=True, timeout=120)


def test_manifest_is_well_formed():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e and 0 < len(m["layer"]) <= 200
    cells = MANIFEST["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    for entry in MANIFEST["configs"] + cells:
        assert NAME.match(entry["name"]) and 0 < len(entry["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        on_disk = json.loads((ROOT / c["file"]).read_text())
        assert on_disk["name"] == c["name"]
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
        assert on_disk["env"]["DEGRADE_ENABLE"] == "false"


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_resolves_by_name(cell):
    r = run_py(ROOT, "--workload", cell, "--seed", "1", "--seconds", "1",
               "--resolve-only")
    assert r.returncode == 0, r.stdout + r.stderr
    found = json.loads(r.stdout.strip().splitlines()[-1])
    assert found["workload"] == cell
    assert found["per_layer"] == [
        m["name"] for m in MANIFEST["per_layer"]
        if "workloads" not in m or cell in m["workloads"]]


def test_a_name_that_resolves_to_nothing_fails_loudly():
    r = run_py(ROOT, "--workload", "desk1080.nothing", "--seed", "1",
               "--seconds", "1", "--resolve-only")
    assert r.returncode != 0 and "no workload" in r.stdout


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A throw-away configuration, traffic mix, generator, per-layer metric
    and cell in a copy of the benchmark: nothing that is there is edited."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "desk1080.json").read_text())
    cfg.update(name="throw720", source="https://example.org/throw720")
    cfg["env"].update(SIZEW="1280", SIZEH="720")
    (b / "configs" / "throw720.json").write_text(json.dumps(cfg))
    (b / "traffic" / "flat.json").write_text(json.dumps(
        {"generator": "flat", "params": {"grey": 90}}))
    (b / "traffic" / "gen_flat.py").write_text(
        "class Scene:\n"
        "    def __init__(self, grey):\n        self.grey = grey\n"
        "    def render(self, c, out):\n        out[:] = self.grey + c % 2\n"
        "def build(params, width, height, fps, seed):\n"
        "    return Scene(params['grey'])\n")
    (b / "layer_metrics" / "skipped.refreshes.py").write_text(
        "def read(run):\n    return run['display_skipped']\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(
        {"name": "throw720", "source": cfg["source"],
         "file": "benchmark/configs/throw720.json", "reduced": [],
         "why": "throw-away"})
    manifest["workloads"].append(
        {"name": "throw720.flat", "config": "throw720", "traffic": "flat",
         "chips": 1, "why": "throw-away"})
    manifest["per_layer"].append(
        {"name": "skipped.refreshes", "unit": "count", "better": "lower",
         "source": "host_clock", "layer": "load generator (benchmark)",
         "moves": "delivered_fps", "workloads": ["throw720.flat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    r = run_py(tmp_path, "--workload", "throw720.flat", "--seed", "5",
               "--seconds", "1", "--resolve-only")
    assert r.returncode == 0, r.stdout + r.stderr
    found = json.loads(r.stdout.strip().splitlines()[-1])
    assert found["generator"] == "flat" and found["env"]["SIZEW"] == "1280"
    assert found["per_layer"][-1] == "skipped.refreshes"
    # an old cell does not get the new cell's metric, and no file changed
    r = run_py(tmp_path, "--workload", "desk1080.desktop", "--seed", "5",
               "--seconds", "1", "--resolve-only")
    assert "skipped.refreshes" not in r.stdout and r.returncode == 0
    assert all(p.read_bytes() == data for p, data in before.items())


def test_appended_entries_leave_the_manifest_tests_passing(tmp_path):
    """What a ``model_config`` PR does to BENCHMARK.json, a configuration, a
    cell and a per-layer metric with a ``workloads`` list APPENDED, in a
    copy of the benchmark and of its tests: every test of the manifest
    there still passes, so none pins a position in a list."""
    for d in ("benchmark", "tests/benchmark"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmark"
    cfg = json.loads((b / "configs" / "desk1080.json").read_text())
    cfg.update(name="later1080", source="https://example.org/later1080")
    (b / "configs" / "later1080.json").write_text(json.dumps(cfg))
    (b / "layer_metrics" / "later_engine_ms.py").write_text(
        "def read(run):\n    return None\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(
        {"name": "later1080", "source": cfg["source"],
         "file": "benchmark/configs/later1080.json", "reduced": [],
         "why": "a later PR's deployment"})
    manifest["workloads"].append(
        {"name": "later1080.desktop", "config": "later1080",
         "traffic": "desktop", "chips": 1, "why": "a later PR's cell"})
    manifest["per_layer"].append(
        {"name": "later_engine_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "host entropy engine",
         "moves": "g2g_p50_ms", "workloads": ["later1080.desktop"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", "manifest or every_cell_resolves",
         "--deselect", "tests/benchmark/test_benchmark_manifest.py::"
         "test_appended_entries_leave_the_manifest_tests_passing",
         "tests/benchmark/test_benchmark_manifest.py",
         "tests/benchmark/test_benchmark_stages.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    # the cells that were there and the new one, and the tests by name
    assert " passed" in r.stdout and "failed" not in r.stdout
    passed = int(re.search(r"(\d+) passed", r.stdout).group(1))
    assert passed >= 4 + len(MANIFEST["workloads"]) + 9


def test_the_multi_session_branch_is_a_stub():
    src = (ROOT / "benchmark" / "run.py").read_text()
    assert "tpu_sessions > 1" in src and "not built yet" in src
