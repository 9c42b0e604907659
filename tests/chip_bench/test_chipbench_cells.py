"""The chip benchmark's cells, rehearsed on the CPU.

Every cell of ``BENCHMARK.json`` runs its set-up and a short window at its
files' ``rehearsal`` sizes, through the same player, traffic and metric
files as on the chip, and prints a well-formed line.  The command itself
refuses a CPU backend, and a checkout without the program.
"""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness  # noqa: E402

BENCH = harness.load_json(harness.BENCHMARK_FILE)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(autouse=True)
def _small_pool(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_WORKERS", "2")


def _well_formed(out: dict, cell: str, trace: bool) -> None:
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    kind = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in BENCH[kind]
                if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) <= set(declared)
    for name, m in out["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], float)
    if trace:
        assert dev["window_s"] > 0 and dev["busy_s"] >= 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
    for k, c in out["checks"].items():
        assert c["value"] <= c["limit"], k
    json.loads(json.dumps(out))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    out = harness.run(cell, 2 ** 31 + 17, 0.3, bool(trace),
                      t_start=time.perf_counter(), require_tpu=False,
                      rehearse=True)
    _well_formed(out, cell, bool(trace))


def test_every_name_is_a_file():
    """Configurations, traffic mixes and metrics are found by name."""
    for c in BENCH["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert harness.load_json(path)["name"] == c["name"]
        assert c["file"].startswith(tuple(BENCH["paths"]))
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.traffic["player"] in ("replay", "campaign")
        harness.load_player(cell.traffic["player"])
        for name in cell.metrics:
            assert callable(harness.load_metric(name))
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert NAME.match(m["name"])
            if kind == "per_layer":
                moved = {e["name"]: e for e in BENCH["end_to_end"]}[m["moves"]]
                assert set(m["workloads"]) <= set(moved.get("workloads",
                                                            CELLS))


def test_a_new_cell_is_files_and_entries(tmp_path):
    """A cell added by a new configuration file and new ``BENCHMARK.json``
    entries runs with no edit to the harness."""
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks/chip/configs/chameleon-nb20-128c16g.json"))
    cfg.update(name="chameleon-nb6-16c2g", nb_blocks=6, platform=[16, 2])
    cfg_file = tmp_path / "chameleon-nb6-16c2g.json"
    cfg_file.write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": str(cfg_file), "reduced": [],
                             "why": "a small deployment"})
    bench["workloads"].append({"name": "replay.nb6", "config": cfg["name"],
                               "traffic": "replay_mc1024", "chips": 1,
                               "why": "a small replay"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "replay.chameleon20.mc1024" in m.get("workloads", []):
                m["workloads"].append("replay.nb6")
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    cell = harness.load_cell("replay.nb6", str(bench_file))
    assert cell.config["nb_blocks"] == 6
    assert "evals_per_s" in cell.metrics
    out = harness.run("replay.nb6", 5, 0.2, False, t_start=time.perf_counter(),
                      bench_file=str(bench_file), require_tpu=False)
    assert out["correct"] and "evals_per_s" in out["metrics"]


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    res = _command(ROOT)
    assert res.returncode != 0
    assert res.stdout == ""
    assert "needs a TPU" in res.stderr


def test_command_needs_the_program(tmp_path):
    """A directory holding only ``BENCHMARK.json`` and the benchmark's own
    paths: the command fails and prints no result."""
    shutil.copy(harness.BENCHMARK_FILE, tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _command(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""


# A parent that adopts whatever its child leaves behind (a subreaper), runs
# the child, and prints the processes still its own once the child exited.
_REAPER = r"""
import ctypes, json, os, subprocess, sys
ctypes.CDLL(None).prctl(36, 1, 0, 0, 0)
subprocess.run(sys.argv[1:], check=True, stdout=subprocess.DEVNULL)
left = []
for d in filter(str.isdigit, os.listdir("/proc")):
    try:
        with open(f"/proc/{d}/stat") as f:
            if int(f.read().rsplit(")", 1)[1].split()[1]) == os.getpid():
                left.append(int(d))
    except OSError:
        pass
for pid in left:
    os.kill(pid, 9)
    os.waitpid(pid, 0)
print(json.dumps(left))
"""

_CELL_RUN = r"""
import sys, time
sys.path[:0] = [{root!r}, {src!r}]
from benchmarks.chip import harness
try:
    out = harness.run({cell!r}, 11, 0.2, False, t_start=time.perf_counter(),
                      require_tpu=False, rehearse=True)
finally:
    harness.stop_processes()
assert out["correct"]
"""


@pytest.mark.skipif(not sys.platform.startswith("linux"),
                    reason="reads /proc and sets a subreaper")
def test_a_run_leaves_no_process_behind():
    """The plan-build pool's workers, its forkserver and the resource
    tracker are all stopped and reaped before a run exits."""
    cell = next(w["name"] for w in BENCH["workloads"]
                if harness.load_cell(w["name"]).traffic["player"] == "replay")
    child = _CELL_RUN.format(root=ROOT, src=os.path.join(ROOT, "src"),
                             cell=cell)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _REAPER, sys.executable,
                          "-c", child], env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert json.loads(res.stdout.splitlines()[-1]) == []
