"""Closed loop, fixed sizes stated as PARTS of the configuration's context:
every request has ``context * prompt_parts // of_parts`` unshared random
words and asks for ``context * output_parts // of_parts`` tokens, and is
otherwise :mod:`closed_fixed`'s (clients, ramp, checked requests).

For a mix whose point is how full the context is — ``long_sat``: 4 and 1
parts of 5, which at the served context of 5120 are prompts of 4096 tokens
and replies of 1024, so that every decode step runs at 4096-5120 tokens of
context — the sizes follow the context the configuration is served at
instead of repeating it, and the mix fits whatever context it is planned
for (tests/chip_bench/test_generators.py plans every mix at 2048).
"""

from __future__ import annotations

from ..loadgen import Plan
from . import closed_fixed


def plan(p: dict, ctx: dict) -> Plan:
    sizes = {key: ctx["context"] * p[f"{kind}_parts"] // p["of_parts"]
             for kind, key in (("prompt", "prompt_tokens"),
                               ("output", "output_tokens"))}
    return closed_fixed.plan({**p, **sizes}, ctx)
