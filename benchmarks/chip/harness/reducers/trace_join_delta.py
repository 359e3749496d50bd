"""Per request, one node's whole-request time minus the other's, joined on
``trace_id`` (``outer`` minus ``inner``, microseconds; ``scale`` 0.001 =
ms): with the gateway outside and the worker inside, the time a request
spent in the gateway and on the p2p plane, both ways.  A percentile over
the window's requests, ``q`` as in client_percentile."""

from .. import metrics


def reduce(s: dict, run) -> float | None:
    inner = {t["trace_id"]: t["total_us"]
             for t in run.traces.get(s["inner"], []) if t.get("done")}
    vals = [t["total_us"] - inner[t["trace_id"]]
            for t in run.traces.get(s["outer"], [])
            if t.get("done") and t["trace_id"] in inner]
    if not vals:
        return None
    return s.get("scale", 1.0) * metrics.percentile(
        vals, s["q"], run.min_beyond)
