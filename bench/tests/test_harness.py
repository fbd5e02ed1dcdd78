"""The harness: BENCHMARK.json against the benchmark's contract, a run
of every cell at a CPU size, and the refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from bench import run
from bench.run import BENCH, ROOT
from bench.tests.small import cell

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert (ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_every_cell_reports_what_its_metrics_move():
    for w in SPEC["workloads"]:
        c = run.load_cell(w["name"])
        e2e = {m["name"] for m in c["e2e"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert c["per_layer"], w["name"]
        for m in c["per_layer"]:
            assert m["moves"] in e2e
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_run_seconds_fits_a_full_check():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_a_run_of_every_cell(name):
    c = cell(name)
    res = run.run_cell(c, 2 ** 31 + 3, 1.0, False,
                       jax.devices()[:c["cell"]["chips"]])
    assert res["correct"], res["compared"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert set(res["metrics"]) == {m["name"] for m in c["e2e"]}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == c["cell"]["chips"]


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _bench(["--workload", "v100.campaign", "--seed", "1",
                "--seconds", "1"], ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(["--workload", "v100.campaign", "--seed", "1",
                "--seconds", "1"], tmp_path)
    assert p.returncode != 0 and p.stdout == ""
