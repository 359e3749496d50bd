"""A percentile of a client-side series: ``series`` ttft | gap | late,
``q`` in (0, 1), ``scale`` (1000 = seconds to ms)."""

from .. import metrics


def reduce(s: dict, run) -> float | None:
    series = {"ttft": metrics.ttfts, "gap": metrics.gaps,
              "late": metrics.lateness}[s["series"]](run.records, run.seconds)
    if not series:
        return None
    return s.get("scale", 1.0) * metrics.percentile(
        series, s["q"], run.min_beyond)
