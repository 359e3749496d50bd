"""A run that cannot give a result says why where the record can keep it
(PR 26): exit code 1, no result line, ONE line on standard error that
starts ``benchmark failed:``, and ``failure.json`` in the run's directory
with the child that died and the last lines of its log."""

import json
import subprocess
import sys

from conftest import CHIP_DIR, cpu_env


def run_py(*argv):
    return subprocess.run([sys.executable, str(CHIP_DIR / "run.py"), *argv],
                          capture_output=True, text=True, timeout=300,
                          env=cpu_env())


def failed_line(p):
    assert p.returncode == 1
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    lines = [ln for ln in p.stderr.splitlines() if ln.strip()]
    assert sum(ln.startswith("benchmark failed:") for ln in lines) == 1
    assert lines[-1].startswith("benchmark failed:")
    return lines[-1]


def test_a_worker_that_dies_is_named_with_the_end_of_its_log():
    p = run_py("--rehearse", "--workload", "mistral7b.decode_sat", "--seed",
               "2147483999", "--seconds", "3", "--trace", "2",
               "--worker-flag=--no-such-flag")
    line = failed_line(p)
    assert "worker exited with code 2" in line
    assert "no-such-flag" in line and "failure.json" in line
    rec = json.loads((CHIP_DIR / "_run" / "out" / "mistral7b.decode_sat" /
                      "s2147483999-t2-rehearse" / "failure.json").read_text())
    assert rec["child"] == "worker" and rec["kind"] == "BenchFailure"
    assert rec["workload"] == "mistral7b.decode_sat" and rec["trace"] == 2
    assert "no-such-flag" in rec["log_tail"]
    assert ["worker", 2] in [[c["name"], c["returncode"]]
                             for c in rec["children_exited"]]


def test_a_failure_before_the_run_has_a_directory_still_says_why():
    line = failed_line(run_py("--workload", "no.such.cell"))
    assert "no workload 'no.such.cell'" in line
