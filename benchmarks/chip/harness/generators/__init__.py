"""Traffic generators, one module per kind.  A traffic file names its kind
under ``generator``; :func:`load` finds the module by that name, so a new
kind is a new file here and no edit."""

from __future__ import annotations

import importlib
import math
import random
from statistics import NormalDist

from ..loadgen import Plan


def load(kind: str):
    try:
        return importlib.import_module(f"{__name__}.{kind}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no traffic generator named {kind!r}") from e


def build_plan(traffic: dict, ctx: dict) -> Plan:
    """``ctx``: seed, seconds, slots, vocab_size, context (tokens) and,
    where the run traces a tail after the window, tail_s.  Closed loops
    need nothing for a tail (their actors go on); an open loop appends a
    segment of its own, so the window's turns stay what they are."""
    return load(traffic["generator"]).plan(traffic, ctx)


# ---- helpers shared by the kinds ------------------------------------------
#
# Every seed gets the SAME set of sizes and arrival gaps, in another order:
# a set is the n evenly spaced quantiles of its distribution, shuffled by the
# seed.  So the work of a run does not depend on the seed, only its order
# and the prompts' contents do.


def rng_for(seed: int, *salt: int) -> random.Random:
    return random.Random(hash((int(seed), *salt)) & 0xFFFFFFFFFFFF)


def random_ids(rng: random.Random, n: int, vocab: int) -> list[int]:
    return [rng.randrange(vocab) for _ in range(n)]


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> list[int]:
    nd = NormalDist()
    return [int(min(hi, max(lo, round(
        median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def exponential_quantiles(n: int, mean: float) -> list[float]:
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def shuffled(values: list, rng: random.Random) -> list:
    out = list(values)
    rng.shuffle(out)
    return out
