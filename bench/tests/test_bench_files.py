"""Every file the benchmark names is there and resolves by name, each
metric declares what ``BENCHMARK.json`` says of it, no code under
``bench/`` names a cell or a configuration, and off a TPU the command
prints no result."""
import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from lib import common

BENCH = common.BENCH
ROOT = common.ROOT
B = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_names():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    names = ([c["name"] for c in B["configs"]]
             + [w["name"] for w in B["workloads"]]
             + [m["name"] for m in B["end_to_end"] + B["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in B["end_to_end"]}
    assert all(m["bound"] <= 0.25 for m in B["end_to_end"])


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_each_cell_resolves_by_name(w):
    files = common.cell_files(w["name"])
    cell, config, mix = files["cell"], files["config"], files["mix"]
    assert os.path.exists(os.path.join(BENCH, "drivers",
                                       cell["driver"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "traffic", mix["kind"] + ".py"))
    assert config["name"] == w["config"] and len(w["why"]) <= 200
    assert w["chips"] in (1, 4)
    e2e = {m["name"] for m in files["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and files["per_layer"]
    for m in files["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_each_configuration_names_its_own_files(c):
    """A configuration brings its plain reference, its weight maker and its
    map onto the program's config as files it names, so that another
    family needs new files, not edits."""
    config = common.load_json(ROOT, c["file"])
    assert config["name"] == c["name"] and config["source"] == c["source"]
    ref = common.config_module(config, "reference")
    assert callable(ref.hidden) and callable(ref.logits)
    assert callable(common.config_module(config, "weights").make)
    assert callable(common.config_module(config, "program").program_config)
    for key in ("reference", "weights", "program"):
        assert config[key].startswith("bench/")


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_each_metric_reader_declares_its_entry(m):
    mod = common.metric_reader(m["name"])
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        m["layer"], m["unit"], m["source"], m["moves"])
    assert callable(mod.read)


def test_every_metric_file_is_in_the_benchmark():
    declared = {m["name"] for m in B["per_layer"]}
    files = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(BENCH, "metrics", "*.py"))}
    assert {f for f in files if not f.startswith("_")} == declared


def test_no_code_names_a_cell_or_configuration():
    names = [w["name"] for w in B["workloads"]] + [
        c["name"] for c in B["configs"]] + [
        w["traffic"] for w in B["workloads"]]
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True):
        if os.sep + "tests" + os.sep in path:
            continue
        text = open(path).read()
        for n in names:
            assert n not in text, (path, n)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         B["workloads"][0]["name"], "--seed", "3", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr[-2000:]
    json.loads(open(tmp_path / "BENCHMARK.json").read())
