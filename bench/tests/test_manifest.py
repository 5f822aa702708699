"""``BENCHMARK.json`` against the rules it is held to, and the harness's
refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import manifest

BENCH = manifest.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(BENCH) == TOP_KEYS
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)


def test_paths_and_command_stay_inside_the_benchmark():
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(manifest.ROOT, p))
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for w in cmd:
        assert not w.startswith("/") and ".." not in w
        if os.sep in w:
            assert any(w.startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_well_formed(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert len(names) == len(set(names)) and names
    assert all(NAME.match(n) for n in names)


def test_every_metric_is_well_formed():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in E2E
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_configurations_resolve_to_their_files():
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            conf = json.load(f)
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert conf["reduced"] == c["reduced"]
        assert conf["assumed"]
        # the configuration names its plain reference, beside the files
        assert os.path.exists(os.path.join(
            manifest.HERE, "reference", f"{conf['reference']}.py"))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_every_cell_resolves_to_its_configuration_and_traffic():
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        conf = manifest.config(BENCH, w)
        assert conf["name"] == w["config"]
        assert manifest.traffic(w)["shard_order"]
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_every_per_layer_metric_is_read_where_its_target_is_reported():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert os.path.exists(manifest.reader_path(m["name"]))
        assert m["moves"] in E2E
        target = E2E[m["moves"]]
        for name in m.get("workloads", cells):
            assert name in cells
            assert "workloads" not in target or name in target["workloads"]
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        readers = manifest.readers(BENCH, w)
        assert readers


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    w = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", w, "--seed", "3", "--seconds", "1", "--trace",
           "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out):
    for line in out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_the_harness_refuses_to_run_without_a_tpu():
    p = _run_bench(manifest.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_the_harness_needs_the_program_beside_it(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_bench(str(tmp_path))
    assert p.returncode != 0
    assert _no_result(p.stdout)
