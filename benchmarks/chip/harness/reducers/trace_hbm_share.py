"""Least HBM traffic of a decode step (``decode_step_bytes`` of the
configuration's cost module, harness/costs.py where it names none: every
int8 weight once + the live KV once) over what the published bandwidth moves in
the step's measured device time, in percent.  Live tokens: the mean batch
occupancy of the gauge samples (``--trace 2``: taken inside the traced
seconds, harness/profiler.py) x slots; live KV: that x the mean context of
the window's output tokens."""

from .. import costs, metrics
from . import samples, trace_step_ms


def live(run) -> tuple[float, float]:
    """(tokens per step, KV tokens read per step)."""
    occ = [v for text in run.gauge_samples
           for v in samples(text, "crowdllama_engine_batch_occupancy")]
    batch = (sum(occ) / len(occ) if occ else 1.0) * run.config["bench"]["slots"]
    ctx = [r.prompt_len + i for r in run.records
           for i, t in enumerate(metrics.token_times(r))
           if 0.0 <= t < run.seconds]
    return batch, batch * (sum(ctx) / len(ctx) if ctx else 0.0)


def reduce(s: dict, run) -> float | None:
    t = trace_step_ms.step_seconds(s, run)
    if t is None:
        return None
    batch, kv_tokens = live(run)
    need = costs.module_for(run.config).decode_step_bytes(
        run.config, batch, kv_tokens)
    return 100.0 * need / (t * costs.peaks(run.device_kind)["hbm_bytes_per_s"])
