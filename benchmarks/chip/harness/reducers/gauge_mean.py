"""The mean of a worker gauge over the samples taken at 2 Hz: ``--trace 2``
inside the traced seconds and no others (harness/profiler.py), ``--trace 1``
through the window."""

from . import samples


def reduce(s: dict, run) -> float | None:
    vals = [v for text in run.gauge_samples
            for v in samples(text, s["family"], s.get("labels"))]
    if not vals:
        return None
    return s.get("scale", 1.0) * sum(vals) / len(vals)
