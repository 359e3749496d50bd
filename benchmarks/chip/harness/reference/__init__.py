"""Plain references: each architecture's forward pass in straightforward
float32 ``jax.numpy``, written from the published equations, with no kernel,
no cache and no batching, sharing no code with the program's model file.

A configuration names its reference under ``bench.reference``:
``<name>.py`` here, with ``forward(weights, hf, ids, positions)``.  The
limits the check holds it to are a file of their own beside it,
``<name>.tolerance.json`` (``max_deficit``, ``mean_deficit``, ``set_from``:
the chip readings they were set from), so that a new family is new files;
the first two references' limits are the ``dense`` and ``moe`` entries of
``tolerance.json``."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
LIMITS = ("max_deficit", "mean_deficit")


def module_file(name: str) -> Path:
    return HERE / f"{name}.py"


def limits(name: str) -> dict:
    """The limits of reference ``name``: its own file first, then its entry
    in tolerance.json; FileNotFoundError naming the file to add where there
    are none (run.py makes that a BenchFailure before anything starts)."""
    own = HERE / f"{name}.tolerance.json"
    if own.exists():
        tol = json.loads(own.read_text())
    else:
        tol = json.loads((HERE / "tolerance.json").read_text()).get(name)
    if not isinstance(tol, dict) or any(
            not isinstance(tol.get(k), (int, float)) for k in LIMITS):
        raise FileNotFoundError(
            f"reference {name!r} has no limits: add {own} with "
            f"{', '.join(LIMITS)} and set_from (the chip readings they were "
            f"set from: the sound runs' largest, the control's smallest)")
    return tol
