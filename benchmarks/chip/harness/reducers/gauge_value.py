"""A gauge as it stands at the window's end (``reduce`` max | sum over its
samples), e.g. the peak device memory on the fullest chip."""

from . import samples


def reduce(s: dict, run) -> float | None:
    sc = run.scrapes.get(s["node"])
    vals = samples(sc["end"], s["family"], s.get("labels")) if sc else []
    if not vals or (run.rehearse and s.get("device_only")):
        return None
    agg = max(vals) if s.get("reduce", "max") == "max" else sum(vals)
    return agg * s.get("scale", 1.0)
