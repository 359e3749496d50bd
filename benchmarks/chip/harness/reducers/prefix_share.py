"""Share of the window's prompt tokens served from the prefix cache: the
growth of the worker's ``prefix_cache.tokens_reused`` (its engine stats
line, logged every 10 s — the counter has no /metrics series) between a
line written before the timeline started and one written after the drain,
over the prompt tokens of every request sent in between."""


def reduce(s: dict, run) -> float | None:
    p = run.prefix
    if not p or not p.get("prompt_tokens"):
        return None
    return s.get("scale", 100.0) * p["tokens_reused"] / p["prompt_tokens"]
