"""Output tokens that arrived inside the window, over its length."""

from .. import metrics


def reduce(s: dict, run) -> float | None:
    return metrics.tokens_in_window(run.records, run.seconds) / run.seconds
