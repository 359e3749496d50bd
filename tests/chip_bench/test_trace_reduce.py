"""The reduction from a device trace to numbers, on a trace recorded on the
chip (cut down to a text proto by trace_reduce.cut_to_text) and on a
hand-made one whose answer can be worked out by eye."""

import json

import pytest
from conftest import CHIP_DIR

from harness import trace_reduce

FIX = CHIP_DIR / "fixtures"


def test_self_times_charge_a_parent_only_what_its_children_leave():
    ev = [(0.0, 10.0, "while"), (1.0, 5.0, "a"), (2.0, 3.0, "a.inner"),
          (6.0, 9.0, "b"), (20.0, 25.0, "c")]
    assert trace_reduce.self_times(ev) == [3.0, 3.0, 1.0, 3.0, 5.0]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_hand_made_trace():
    r = trace_reduce.reduce(str(FIX / "hand_made.xspace.txt"))
    assert r["window_s"] == pytest.approx(30e-6)
    assert r["busy_s"] == pytest.approx(15e-6)
    assert r["ops"]["while.1"]["self_s"] == pytest.approx(6e-6)
    assert r["ops"]["while.1"]["total_s"] == pytest.approx(10e-6)
    assert r["programs"] == {"jit_step(123)": [pytest.approx(25e-6)]}
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["TransferFromDevice"] == pytest.approx(10e-6)
    assert gaps[trace_reduce.NO_HOST_EVENT] == pytest.approx(5e-6)
    assert trace_reduce.op_time(r, r"^fusion")[0] == pytest.approx(4e-6)
    assert trace_reduce.program_durations(r, "step") == [pytest.approx(25e-6)]


def test_a_trace_with_no_device_plane_is_refused(tmp_path):
    text = (FIX / "hand_made.xspace.txt").read_text().replace(
        "/device:TPU:0", "/host:other")
    p = tmp_path / "cpu.xspace.txt"
    p.write_text(text)
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(str(p))


def test_recorded_trace_reduces_to_its_expected_numbers():
    r = trace_reduce.reduce(str(FIX / "decode_sat.xspace.txt"))
    want = json.loads((FIX / "decode_sat.expected.json").read_text())
    assert r["devices"] == want["devices"] == 1
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert 0.0 < r["busy_s"] <= r["window_s"]
    # self times of all ops add up to busy
    assert sum(v["self_s"] for v in r["ops"].values()) == pytest.approx(
        r["busy_s"])
    for name, secs in want["top_ops"]:
        assert r["ops"][name]["self_s"] == pytest.approx(secs)
    for name, durs in want["programs"].items():
        assert r["programs"][name] == pytest.approx(durs)
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_cut_to_text_round_trips(tmp_path):
    out = tmp_path / "again.xspace.txt"
    trace_reduce.cut_to_text(str(FIX / "hand_made.xspace.txt"), str(out))
    a = trace_reduce.reduce(str(FIX / "hand_made.xspace.txt"))
    b = trace_reduce.reduce(str(out))
    assert a["busy_s"] == pytest.approx(b["busy_s"])
    assert a["ops"].keys() == b["ops"].keys()
