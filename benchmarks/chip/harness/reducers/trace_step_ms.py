"""Device time of one decode step, in ms: the summed device time of the
traced programs matching ``program`` over the decode steps they ran.  Steps
are counted, not assumed: ``step_op`` matches, by the kernel's own name, an
op that runs exactly once per layer per step (the paged decode attention
kernel, ``%paged_decode_attention``: TRACING.md has the convention), counted
inside those programs alone, so steps = its count / the configuration's
layers — right whatever mix of 8-step and 1-step dispatches the scheduler
made, however many other custom calls a layer has, and whatever prefills
ran beside them."""

from .. import trace_reduce


def steps_traced(s: dict, run) -> float:
    _, calls = trace_reduce.op_time(run.profile, s["step_op"],
                                    s.get("program"))
    return calls / run.profile["devices"] / run.config["num_hidden_layers"]


def step_seconds(s: dict, run) -> float | None:
    if not run.profile:
        return None
    durs = trace_reduce.program_durations(run.profile, s["program"])
    steps = steps_traced(s, run)
    if not durs or not steps:
        return None
    return sum(durs) / run.profile["devices"] / steps


def reduce(s: dict, run) -> float | None:
    t = step_seconds(s, run)
    return None if t is None else 1e3 * t
