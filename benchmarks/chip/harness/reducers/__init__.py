"""Metric readers, one module per kind.  A metric's data file names its kind
under ``reducer``; :func:`compute` finds the module by that name, so a new
kind is a new file here and no edit.  A reader that finds nothing to read
returns None, and the harness leaves the metric out of the line."""

from __future__ import annotations

import importlib
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[2]


@dataclass
class RunData:
    """Everything a reader may read: the client's frame log, the scrapes,
    the span rings and — in a traced run — the trace's reduction."""

    records: list
    seconds: float
    config: dict
    min_beyond: int = 10
    checked: int = 0               # requests chosen for the reference check
    scrapes: dict = field(default_factory=dict)      # node -> {start, end}
    gauge_samples: list = field(default_factory=list)  # worker /metrics texts
    traces: dict = field(default_factory=dict)       # node -> [trace, ...]
    prefix: dict | None = None     # {"tokens_reused": delta, "prompt_tokens"}
    profile: dict | None = None    # trace_reduce.reduce(...)
    device_kind: str = ""
    rehearse: bool = False


def spec(kind: str, name: str) -> dict:
    """The data file of metric ``name``; kind: e2e_metrics | layer_metrics."""
    path = CHIP_DIR / kind / f"{name}.json"
    if not path.exists():
        raise FileNotFoundError(f"metric {name!r} has no data file {path}")
    return json.loads(path.read_text())


def compute(kind: str, name: str, run: RunData) -> float | None:
    s = spec(kind, name)
    try:
        mod = importlib.import_module(f"{__name__}.{s['reducer']}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no reducer named {s['reducer']!r}") from e
    return mod.reduce(s, run)


# ---- helpers shared by the kinds ------------------------------------------

_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def samples(text: str, family: str, labels: dict | None = None
            ) -> list[float]:
    """Values of every sample of ``family`` whose labels include
    ``labels`` in one Prometheus exposition."""
    out = []
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        m = _SAMPLE.match(line)
        if not m or m.group(1) != family:
            continue
        if all(f'{k}="{v}"' in (m.group(2) or "")
               for k, v in (labels or {}).items()):
            out.append(float(m.group(3)))
    return out
