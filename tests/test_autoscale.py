"""Elastic drain/scale loop (crowdllama_tpu/swarm/autoscale.py): the
hysteresis controller that turns the swarm's load gauges into
drain/undrain decisions, its victim selection, the /metrics parser it
feeds from, and its deterministic simulation (``simulate()``)."""

from crowdllama_tpu.swarm import (
    AutoscaleConfig,
    AutoscaleController,
    Sample,
    parse_gauges,
    pick_drain_candidate,
    simulate,
)

CFG = AutoscaleConfig(up_ticks=2, down_ticks=4, cooldown_ticks=5,
                      min_workers=1, max_workers=8)

HOT = Sample(workers=4, pending_depth=6.0, batch_occupancy=0.9)
COLD = Sample(workers=4, pending_depth=0.0, batch_occupancy=0.1)
BAND = Sample(workers=4, pending_depth=1.5, batch_occupancy=0.5)


def test_hot_streak_triggers_undrain_after_up_ticks():
    ctl = AutoscaleController(CFG)
    assert ctl.observe(HOT).action == "hold"       # 1/2
    d = ctl.observe(HOT)                           # 2/2
    assert d.action == "undrain"
    assert "hot" in d.reason


def test_shed_alone_reads_as_hot():
    ctl = AutoscaleController(CFG)
    s = Sample(workers=4, pending_depth=0.0, batch_occupancy=0.2, shed=3.0)
    ctl.observe(s)
    assert ctl.observe(s).action == "undrain"


def test_cold_streak_triggers_drain_after_down_ticks():
    ctl = AutoscaleController(CFG)
    for _ in range(3):
        assert ctl.observe(COLD).action == "hold"
    assert ctl.observe(COLD).action == "drain"


def test_in_band_sample_resets_both_streaks():
    ctl = AutoscaleController(CFG)
    ctl.observe(HOT)
    ctl.observe(BAND)                              # resets the hot run
    assert ctl.observe(HOT).action == "hold"       # back to 1/2
    for _ in range(3):
        ctl.observe(COLD)
    ctl.observe(BAND)                              # resets the cold run
    for _ in range(3):
        assert ctl.observe(COLD).action == "hold"


def test_cooldown_holds_and_swallows_streaks():
    ctl = AutoscaleController(CFG)
    ctl.observe(HOT)
    assert ctl.observe(HOT).action == "undrain"
    # cooldown_ticks of mandatory hold, even under a solid hot streak.
    for _ in range(CFG.cooldown_ticks):
        d = ctl.observe(HOT)
        assert d.action == "hold"
        assert "cooldown" in d.reason
    # After the cooldown the streak starts from zero again.
    assert ctl.observe(HOT).action == "hold"
    assert ctl.observe(HOT).action == "undrain"


def test_min_max_worker_clamps():
    ctl = AutoscaleController(CFG)
    at_max = Sample(workers=CFG.max_workers, pending_depth=9.0,
                    batch_occupancy=1.0)
    ctl.observe(at_max)
    d = ctl.observe(at_max)
    assert d.action == "hold" and "max_workers" in d.reason

    ctl2 = AutoscaleController(CFG)
    at_min = Sample(workers=CFG.min_workers, pending_depth=0.0,
                    batch_occupancy=0.0)
    for _ in range(CFG.down_ticks - 1):
        ctl2.observe(at_min)
    d = ctl2.observe(at_min)
    assert d.action == "hold" and "min_workers" in d.reason


def test_pick_drain_candidate_least_loaded_deterministic_ties():
    gauges = {
        "w-b": {"pending_depth": 0.0, "batch_occupancy": 0.25},
        "w-a": {"pending_depth": 2.0, "batch_occupancy": 0.5},
        "w-c": {"pending_depth": 0.0, "batch_occupancy": 0.25},
    }
    assert pick_drain_candidate(gauges) == "w-b"   # tie -> lexicographic
    assert pick_drain_candidate({}) == ""


def test_parse_gauges_reads_both_surfaces():
    text = ("# TYPE crowdllama_engine_pending_depth gauge\n"
            "crowdllama_engine_pending_depth 3.0\n"
            "# TYPE crowdllama_engine_batch_occupancy gauge\n"
            "crowdllama_engine_batch_occupancy 0.625\n"
            "# TYPE crowdllama_gateway_shed_total counter\n"
            "crowdllama_gateway_shed_total 7\n")
    g = parse_gauges(text)
    assert g == {"pending_depth": 3.0, "batch_occupancy": 0.625,
                 "shed_total": 7.0}
    # Absent families read as zero (a worker has no shed counter).
    assert parse_gauges("") == {"pending_depth": 0.0,
                                "batch_occupancy": 0.0, "shed_total": 0.0}


def test_simulation_deterministic_and_elastic():
    """The committed-artifact scenario: through a 4x load swing the loop
    scales up to absorb the peak without shedding, scales back down after
    it, and two runs produce identical artifacts byte for byte."""
    a = simulate()
    b = simulate()
    assert a.to_json() == b.to_json()

    s = a.summary
    assert s["total_shed"] == 0                    # peak fully absorbed
    assert s["total_served"] == s["total_offered"]
    assert s["peak_active"] > s["start_active"]    # scaled up for the peak
    assert s["final_active"] < s["peak_active"]    # and back down after
    assert s["drains"] >= 1 and s["undrains"] >= 1
    # Scale-down rode the live-migration path: backlog moved, not dropped.
    assert s["total_migrated_backlog"] >= 0
    actions = [(t["tick"], t["action"]) for t in a.ticks
               if t["action"] != "hold"]
    undrain_ticks = [t for t, act in actions if act == "undrain"]
    drain_ticks = [t for t, act in actions if act == "drain"]
    # All the adds happen around the up-ramp, all the removals after the
    # peak has passed (ticks 0-119, peak plateau is 48-72).
    assert all(t < 72 for t in undrain_ticks)
    assert all(t > 72 for t in drain_ticks)
