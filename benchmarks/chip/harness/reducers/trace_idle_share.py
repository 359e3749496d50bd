"""1 - (union of device-op intervals) / traced window, in percent."""


def reduce(s: dict, run) -> float | None:
    p = run.profile
    if not p or p["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
