"""A counter's growth over the window: the sum over every sample of
``family`` (labels including ``labels``) in ``node``'s /metrics at the
window's end, minus the same at its start."""

from . import samples


def reduce(s: dict, run) -> float | None:
    sc = run.scrapes.get(s["node"])
    if not sc:
        return None
    end = samples(sc["end"], s["family"], s.get("labels"))
    start = samples(sc["start"], s["family"], s.get("labels"))
    if not end:
        return None
    return (sum(end) - sum(start)) * s.get("scale", 1.0)
