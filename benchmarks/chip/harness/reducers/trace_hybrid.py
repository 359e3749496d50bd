"""A trace metric of a model whose layers differ in kind: the reader named
under ``of`` (``trace_step_ms``, ``trace_hbm_share``, ``trace_kernel_roofline``)
with decode steps counted as calls of ``step_op`` over the layers that RUN
it — ``attention_layers(config)`` of the configuration's cost module — where
those readers divide by ``num_hidden_layers``: the decode attention kernel
runs once per ATTENTION layer a step, one layer of eleven in the
Nemotron-3-Super cut (TRACING.nemotron_h.md).  A configuration whose cost
module has no ``attention_layers`` gives nothing.  Where the metric names
``op_from`` (``module.function``, as ``bytes``), the ops it takes are that
function's pattern for this configuration: shapes come from the
configuration, not from the metric file."""

import importlib
from dataclasses import replace

from .. import costs


def reduce(s: dict, run) -> float | None:
    if not run.profile:
        return None
    layers = getattr(costs.module_for(run.config), "attention_layers", None)
    if layers is None:
        return None
    if "op_from" in s:
        s = {**s, "op": costs.function(run.config, s["op_from"])(run.config)}
    view = replace(run, config={**run.config,
                                "num_hidden_layers": layers(run.config)})
    return importlib.import_module(f"{__package__}.{s['of']}").reduce(s, view)
