"""A percentile over the window's requests of the summed duration of the
named ``spans`` in one node's span ring (``node`` gateway | worker;
durations in microseconds there, ``scale`` 0.001 = ms).  A request whose
trace has none of the spans is left out; ``q`` as in client_percentile."""

from .. import metrics


def reduce(s: dict, run) -> float | None:
    vals = []
    for tr in run.traces.get(s["node"], []):
        durs = [sp["dur_us"] for sp in tr.get("spans", [])
                if sp["name"] in s["spans"]]
        if durs:
            vals.append(sum(durs))
    if not vals:
        return None
    return s.get("scale", 1.0) * metrics.percentile(
        vals, s["q"], run.min_beyond)
