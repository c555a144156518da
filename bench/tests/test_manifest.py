"""``BENCHMARK.json`` against the benchmark's contract, and the command's
refusal to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import cells, correct

ROOT = cells.ROOT
M = cells.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]
# what a program module provides (bench/programs/__init__.py's docstring)
PROGRAM_API = ("LoopConfig", "run_training", "build", "batch_spec", "step_flops", "regularizer_flops", "faults",
               "tiny")
# the harness's files, which reach the model only through a program module
HARNESS = [ROOT / "bench" / f"{m}.py" for m in ("cells", "harness", "scopes", "calibrate", "correct", "trace", "run")]
HARNESS += sorted((ROOT / "bench" / "metrics").glob("*.py"))
MODEL_WORDS = ("input_dim", "backbone_widths", "projector_width", "view1", '"style"', '"mu"')


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"]
    assert M["command"][1] == "bench/run.py" and len(M["command"]) <= 32
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)


def test_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units():
    named = M["configs"] + M["workloads"] + M["end_to_end"] + M["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for metric in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in M[group]]
        assert len(names) == len(set(names)), group
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == len(M["workloads"])


def test_entries_have_just_their_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert not any(k.endswith(("_dim", "_rank", "width", "widths")) for k in c["reduced"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for e in M["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in M["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve_by_name(cell):
    c = cells.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in M["workloads"] if w["name"] == cell)
    assert c.traffic["name"] == next(w["traffic"] for w in M["workloads"] if w["name"] == cell)
    assert set(c.limits) == set(correct.NUMBERS)
    program = cells.program(c)
    assert all(callable(getattr(program, f)) for f in PROGRAM_API)
    cells.load_module("refs", c.config["reference"])
    cells.load_module("gen", c.traffic["generator"])
    for m in c.per_layer:
        assert callable(cells.load_module("metrics", m["name"]).read)
    e2e = {e["name"] for e in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer


def test_a_configuration_must_name_its_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    conf = M["configs"][0]
    data = json.loads((ROOT / conf["file"]).read_text())
    del data["program"]
    (tmp_path / conf["file"]).parent.mkdir(parents=True)
    (tmp_path / conf["file"]).write_text(json.dumps(data))
    cell = next(w["name"] for w in M["workloads"] if w["config"] == conf["name"])
    with pytest.raises(KeyError, match='"program"'):
        cells.load_cell(cell, tmp_path)


@pytest.mark.parametrize("path", HARNESS, ids=lambda p: p.relative_to(ROOT).as_posix())
def test_harness_names_no_model(path):
    text = path.read_text()
    assert not [w for w in MODEL_WORDS if w in text]


def test_config_files_are_their_own():
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    for c in M["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/") and data["name"] == c["name"]
        assert data["source"] == c["source"] and data["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in M["workloads"])


def test_per_layer_metrics_name_their_cells_and_what_they_move():
    e2e = {e["name"]: e for e in M["end_to_end"]}
    for p in M["per_layer"]:
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        assert 1 <= len(p["layer"]) <= 200 and "\n" not in p["layer"]
        moved_in = e2e[p["moves"]].get("workloads", CELLS)
        for w in p.get("workloads", []):
            assert w in moved_in, (p["name"], w)
        assert (ROOT / "bench" / "metrics" / f"{p['name']}.py").is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reads_every_per_layer_metric_of_what_it_reports(cell):
    c = cells.load_cell(cell)
    reported = {e["name"] for e in c.end_to_end}
    for p in M["per_layer"]:
        due = cell in p["workloads"] if "workloads" in p else p["moves"] in reported
        assert (p in c.per_layer) == due, p["name"]
    assert all(p["moves"] in reported for p in c.per_layer)


def test_layers_are_named_alike():
    layers = {p["name"].split(".")[0]: p["layer"] for p in M["per_layer"]}
    for p in M["per_layer"]:
        assert p["layer"] == layers[p["name"].split(".")[0]]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", str(2**31 + 3),
           "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return isinstance(json.loads(lines[-1]), dict)
    except json.JSONDecodeError:
        return False


def test_command_refuses_the_cpu():
    out = _run(ROOT)
    assert out.returncode != 0 and not _has_result(out.stdout)
    assert "needs 1 TPU" in out.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0 and not _has_result(out.stdout)


def test_import_describes_no_topology():
    code = (
        "import sys; sys.path[:0] = [sys.argv[1], sys.argv[1] + '/src']\n"
        "from bench import cells, harness, trace, peaks, correct, calibrate\n"
        "import bench.programs.ssl_mlp\n"
        "m = cells.manifest()\n"
        "[cells.load_module('metrics', p['name']) for p in m['per_layer']]\n"
        "[cells.load_module('refs', 'bt_mlp'), cells.load_module('gen', 'ssl_views')]\n"
        "bad = [k for k in sys.modules if 'topologies' in k or 'libtpu' in k]\n"
        "assert not bad, bad\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr


def test_paths_hold_only_the_benchmark():
    for p in M["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert Path(ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
