"""The growth of some counters over the window, over the growth of others:
``num`` and ``den`` are lists of ``{"family": ..., "labels": {...}}`` terms
in ``node``'s /metrics, each list summed; growth is the window's end scrape
minus its start scrape, which every mode of a run takes.  ``scale`` 100 =
percent, 1000 = seconds to ms.  A program without one of the families, or a
window in which the denominator did not grow, gives nothing."""

from . import samples


def growth(sc: dict, terms: list[dict]) -> float | None:
    total = 0.0
    for t in terms:
        end = samples(sc["end"], t["family"], t.get("labels"))
        if not end:
            return None
        total += sum(end) - sum(
            samples(sc["start"], t["family"], t.get("labels")))
    return total


def reduce(s: dict, run) -> float | None:
    sc = run.scrapes.get(s["node"])
    if not sc:
        return None
    num, den = growth(sc, s["num"]), growth(sc, s["den"])
    if num is None or not den or den <= 0:
        return None
    return s.get("scale", 1.0) * num / den
