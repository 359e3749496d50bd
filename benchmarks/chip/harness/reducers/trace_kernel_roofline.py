"""A kernel's share of its roofline, in percent: the least time the chip
could take for the bytes the kernel has to read (``bytes``: ``module.function`` inside
harness/, e.g. costs.kv_read_bytes, per decode step; the configuration's own
cost module is asked for ``function`` first) at the published bandwidth, over
the kernel's measured device time per step.  Kernel time: the summed self
time of the traced ops matching ``op`` inside the programs matching
``program`` (a prefill's calls of the same matmuls are not a decode
step's); steps: counted as trace_step_ms counts them (``step_op``, in the
same programs).  Decode attention
and the expert layer at decode batch sizes are bound by bytes, not by
operations (a few FLOP per byte read), which is why bytes bound it."""

from .. import costs, trace_reduce
from . import trace_hbm_share, trace_step_ms


def reduce(s: dict, run) -> float | None:
    if not run.profile:
        return None
    t, n = trace_reduce.op_time(run.profile, s["op"], s.get("program"))
    steps = trace_step_ms.steps_traced(s, run)
    if not n or not steps:
        return None
    batch, kv_tokens = trace_hbm_share.live(run)
    need = costs.function(run.config, s["bytes"])(
        run.config, batch, kv_tokens)
    least = need / costs.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (t / run.profile["devices"] / steps)
