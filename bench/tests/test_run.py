"""Whole runs of the harness on the CPU at a tiny size: a cell that exists
only as new files and manifest entries runs with no code edit, a run
without a chip prints no result, and ``correct`` comes out false under
the control and under each fault planted in the timed path."""

import json
import os
import subprocess
import sys

import pytest

from bench import check, faults, run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="module")
def sound(root):
    return run.run_cell(root, "tiny.rag", 2 ** 31 + 3, 2.0,
                        require_tpu=False)


def test_a_cell_added_as_files_runs_without_code_edit(root):
    rc, out, _ = run.run_cell(root, "tiny.chat", 17, 2.0, require_tpu=False)
    assert rc == 0
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True and out["attempted"] > 0
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in manifest["end_to_end"]
            if "tiny.chat" in m.get("workloads", ["tiny.chat"])}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert set(out["check"]) == set(tiny.CELLS["tiny.chat"]["limits"])


def test_closed_loop_cell_is_correct(sound):
    rc, out, r = sound
    assert rc == 0 and out["correct"] is True
    assert r.numbers["kv0_err"] <= r.params["limits"]["kv0_err"]


def test_without_a_chip_there_is_no_result(capsys):
    rc, out, _ = run.run_cell(run.ROOT, "smollm-rag", 1, 1.0)
    assert rc == 2 and out is None
    assert run.main(["--workload", "smollm-rag", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("control", ["fp8_lrc", "fp8_kv"])
def test_the_control_is_not_correct(sound, control):
    _, _, r = sound
    cmp = check.compare_control(r.spec, r.weights, r.samples, r.seq_len,
                                control)
    correct, _ = check.verdict(cmp.numbers(), r.params["limits"])
    assert not correct


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_timed_path_is_not_correct(root, fault):
    rc, out, _ = run.run_cell(root, "tiny.rag", 5, 2.0, require_tpu=False,
                              fault=faults.FAULTS[fault])
    assert rc == 0 and out["correct"] is False


MESH_RUN = """
import json, sys
from pathlib import Path
from bench import run
from bench.tests import tiny
root = tiny.make_root(Path(sys.argv[1]))
manifest = json.loads((root / "BENCHMARK.json").read_text())
for cell in manifest["workloads"]:
    if cell["name"] == "tiny.rag":
        cell["chips"] = 2
(root / "BENCHMARK.json").write_text(json.dumps(manifest))
seen = {}
def look(drv):
    seen["mesh"] = dict(drv.eng.mesh.shape)
rc, out, _ = run.run_cell(root, "tiny.rag", 2 ** 31 + 7, 2.0,
                          require_tpu=False, fault=look)
print(json.dumps({"rc": rc, "correct": out["correct"], **seen}))
"""


def test_a_cell_on_two_chips_serves_on_a_mesh_of_them(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "PYTHONPATH": os.pathsep.join([str(run.ROOT), str(run.ROOT / "src"),
                                          os.environ.get("PYTHONPATH", "")])}
    done = subprocess.run([sys.executable, "-c", MESH_RUN, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got == {"rc": 0, "correct": True, "mesh": {"model": 2}}
