"""``mistral7b.sessions_shared`` at tiny size on the CPU.  PR 26 measured
the cell on the chip and left it out of BENCHMARK.json (PERF.md §6, §7); so
that its traffic file, its generator and its ladder stay exercised, the
cell is added to a temporary copy of the benchmark exactly as README.md's
worked example "a cell" says — entries in BENCHMARK.json, no file — and
rehearsed there: it reports every metric listed for it that a CPU run can
read, most of its prompt tokens are reused, and a served token altered
where it is produced comes out not correct."""

import json
import os
import subprocess
import sys

import pytest
from conftest import copy_of_the_benchmark, cpu_env
from test_rehearse import rehearse

from harness import reference

CELL = "mistral7b.sessions_shared"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(the copy's benchmarks/chip, its BENCHMARK.json, result line,
    output) of one rehearsal of the cell, for both tests."""
    root = tmp_path_factory.mktemp("sessions")
    chip, bench = copy_of_the_benchmark(root)
    bench["workloads"].append({
        "name": CELL, "config": "mistral-7b-int8",
        "traffic": "sessions_shared", "chips": 1,
        "why": "12 seats, 4 shared 512-token system prompts, +64 in / 48 "
               "out a turn, think 1.5 s: the prefix index, its LRU and "
               "refcounts, admissions whose prefill is mostly a cache hit"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # every metric chat_open lists but the lateness of an open loop's
        # generator: a closed loop has no due time to be late against
        if "mistral7b.chat_open" in m.get("workloads", []) and (
                m["name"] != "client.late_p80_ms"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return (chip, bench, *rehearse(CELL, 2, chip))


def test_the_sessions_cell_reports_what_it_lists_and_reuses_its_prefixes(
        served):
    chip, bench, line, out = served
    listed = {g: {m["name"] for m in bench[g]
                  if CELL in m.get("workloads", [CELL])
                  and m["source"] != "device_trace"}
              for g in ("end_to_end", "per_layer")}
    assert listed["end_to_end"] == {"ttft_p80_ms", "itl_p95_ms", "setup_s"}
    assert listed["end_to_end"] | listed["per_layer"] <= set(line["metrics"])
    assert "client.late_p80_ms" not in line["metrics"]
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["sched.prefix_reuse_share"] > 50.0
    assert m["sched.prefix_reuse_share"] == m["sched.prefix_hit_share"]
    assert "gauges: " in out and "samples inside the" in out


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        served, tmp_path):
    """The rest of a run behind a broken timed path: the sample the run
    above handed to the reference check (``check_input.json`` in its
    directory), with one served token of each reply replaced by its
    neighbour in the vocabulary, goes through the same check process and
    the same limits and comes out not correct — while the sample as it
    was served comes out correct."""
    chip = served[0]
    run_dir = chip / "_run" / "out" / CELL / "s2147483659-t2-rehearse"
    job = json.loads((run_dir / "check_input.json").read_text())
    assert job["rehearse"] and len(job["samples"]) >= 2
    vocab = job["config"]["vocab_size"]
    tol = reference.limits(job["config"]["bench"]["reference"])

    def checked(samples, name):
        (tmp_path / f"{name}.in").write_text(json.dumps(
            {**job, "samples": samples}))
        env = cpu_env(PYTHONPATH=os.pathsep.join(
            [str(chip.parents[1]), str(chip)]))
        subprocess.run(
            [sys.executable, "-m", "harness.reference.check", "--input",
             str(tmp_path / f"{name}.in"), "--output",
             str(tmp_path / f"{name}.out")], cwd=chip, env=env,
            check=True, timeout=300, capture_output=True)
        res = json.loads((tmp_path / f"{name}.out").read_text())
        return all(res[k] <= tol[k] for k in reference.LIMITS), res

    ok, sound = checked(job["samples"], "sound")
    assert ok, sound
    broken = [{**s, "reply_ids": [(t + 1) % vocab if i % 16 == 3 else t
                                  for i, t in enumerate(s["reply_ids"])]}
              for s in job["samples"]]
    ok, res = checked(broken, "broken")
    assert not ok
    assert res["max_deficit"] > 5 * tol["max_deficit"], res
