"""The entry point's refusals, and BENCHMARK.json against the files the
harness finds by name."""
import os
import re
import shutil
import subprocess
import sys

import pytest

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CMD = [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload", "inex-dense.serve",
       "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"]


def _run(cmd, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    return any(line.lstrip().startswith("{") for line in stdout.splitlines())


def test_refuses_without_a_tpu():
    p = _run(CMD, run.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable, str(tmp_path / "bench" / "run.py")] + CMD[2:]
    p = _run(cmd, tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))


def test_every_name_finds_its_files(bench):
    assert bench["paths"] == ["bench"]
    names = [c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(run.ROOT, c["file"]))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(run.BENCH, "metrics", m["name"] + ".py"))
    for w in bench["workloads"]:
        spec = run.cell_spec(w["name"], bench)
        kind = spec["traffic"]["kind"]
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec["per_layer"] and all(m["moves"] in e2e for m in spec["per_layer"])
        assert set(spec["config"]["limits"][kind]) >= {"compiles_in_window"}


def test_cell_picks_its_own_metrics(bench):
    spec = run.cell_spec("inex-dense.serve", bench)
    names = {m["name"] for m in spec["per_layer"]}
    assert "nn_topk_roofline" in names and "nn_assign_roofline" not in names
    assert {m["name"] for m in spec["end_to_end"]} == {
        "search_p50_ms", "search_rows_per_s", "setup_s"}
    assert {"client.search_p95_ms", "client.lateness_p95_ms"} <= names
    build = {m["name"] for m in run.cell_spec("rcv1-ell.build", bench)["per_layer"]}
    assert "ell_spmm_roofline" in build and "nn_assign_roofline" not in build
    with pytest.raises(run.BenchError):
        run.cell_spec("no-such.cell", bench)


def test_check_limits():
    ok, out = run.check_limits({"a": 0.0, "b": 2e-6}, {"a": 0, "b": 1e-5})
    assert ok and out["b"] == {"value": 2e-6, "limit": 1e-5}
    ok, _ = run.check_limits({"a": 1}, {"a": 0})
    assert not ok
    with pytest.raises(run.BenchError):
        run.check_limits({"c": 0}, {"a": 0})
