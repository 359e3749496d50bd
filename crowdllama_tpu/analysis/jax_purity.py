"""jax-purity checker: traced code must stay pure and on-device.

Finds every jit entry point in engine/, ops/, parallel/, train/ —
``@jax.jit`` / ``@partial(jax.jit, ...)`` decorated functions, functions
wrapped via ``jax.jit(fn, ...)`` assignments, and Pallas kernels (first
argument of ``pl.pallas_call``) — and flags, inside the traced bodies:

``host-sync``
    Escapes that force a device round-trip or break tracing:
    ``.block_until_ready()``, ``.item()``, ``.tolist()``,
    ``jax.device_get``, ``np.asarray``/``np.array`` (static shape math
    uses ``np.sqrt``/``np.prod`` on Python ints, never ``asarray``), and
    ``float()``/``int()``/``bool()`` applied to a traced *parameter* of
    the jitted function.  The kernel-looping direction (PAPERS, arXiv
    2410.23668) only pays off if no hidden host sync sneaks into the
    decode loop — this is its tripwire.

``impure-host-state``
    Python-side wall-clock or RNG inside traced code: ``time.time`` /
    ``perf_counter``, ``random.*``, ``np.random.*``.  A jitted function
    reading these bakes one sample into the compiled program — the value
    never changes again, which is a miserable bug to find at runtime.

``use-after-donate``
    For callables jitted with ``donate_argnums``, a read of the donated
    buffer after the call (without the call's result being assigned back
    to that name) — the buffer's memory was handed to XLA, its contents
    are garbage (jax guides: buffer donation).

``host-sync-in-decode-loop``
    A ``for``/``while`` loop that both dispatches decode work
    (``decode_steps_device`` / ``ragged_step`` / ``decode_steps``) and
    materializes device values on the host
    (``np.asarray``/``np.array`` — called directly or handed to
    ``run_in_executor`` — or ``.item()``/``.tolist()``).  A per-step
    readback inside the dispatch loop serializes host and device: read
    the packed ``[K, B]`` block back ONCE per flight with
    ``jax.device_get`` instead.  Unlike the other rules this walks every
    function, not just traced ones — the scheduler's dispatch loop is
    plain async Python.
"""

from __future__ import annotations

import ast

from crowdllama_tpu.analysis.base import (
    Finding,
    SourceFile,
    dotted_name,
    load_sources,
)

CHECKER = "jax-purity"

SUBDIRS = ("engine", "ops", "parallel", "train")

_HOST_SYNC_ATTRS = frozenset({"block_until_ready", "item", "tolist"})
_HOST_SYNC_CALLS = frozenset({
    "jax.device_get", "np.asarray", "np.array", "numpy.asarray",
    "numpy.array", "onp.asarray", "onp.array",
})
_IMPURE_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.",
                    "datetime.")

# host-sync-in-decode-loop: decode dispatch entry points (the device-side
# flights the scheduler's loop launches) and the host-materializing calls
# that must not share a loop body with them.
_DISPATCH_CALLS = frozenset({
    "decode_steps_device", "ragged_step", "decode_steps",
})
_LOOP_SYNC_NAMES = frozenset({
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "onp.asarray", "onp.array",
})


def _is_jax_jit(node: ast.AST) -> bool:
    return dotted_name(node) in ("jax.jit", "jit")


def _jit_decorated(fn: ast.FunctionDef) -> bool:
    """@jax.jit or @(functools.)partial(jax.jit, ...)."""
    for dec in fn.decorator_list:
        if _is_jax_jit(dec):
            return True
        if isinstance(dec, ast.Call):
            if _is_jax_jit(dec.func):
                return True
            if dotted_name(dec.func).endswith("partial") and dec.args \
                    and _is_jax_jit(dec.args[0]):
                return True
    return False


def _decorator_donate(fn: ast.FunctionDef) -> tuple[int, ...]:
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            for kw in dec.keywords:
                if kw.arg == "donate_argnums":
                    return _int_tuple(kw.value)
    return ()


def _int_tuple(node: ast.AST) -> tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
        return tuple(out)
    return ()


def _local_functions(tree: ast.Module) -> dict[str, ast.FunctionDef]:
    """Every plain function/method in the module by name (last wins —
    name collisions across classes are rare and benign here)."""
    out: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            out[node.name] = node
    return out


def _traced_functions(src: SourceFile) -> list[ast.FunctionDef]:
    """Functions whose bodies run under trace: jit-decorated, passed to
    jax.jit(...), or passed to pl.pallas_call(...) as the kernel."""
    local = _local_functions(src.tree)
    traced: dict[int, ast.FunctionDef] = {}
    for fn in local.values():
        if _jit_decorated(fn):
            traced[id(fn)] = fn
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        target: ast.AST | None = None
        if _is_jax_jit(node.func) and node.args:
            target = node.args[0]
        elif name.endswith("pallas_call") and node.args:
            target = node.args[0]
        if target is None:
            continue
        tname = dotted_name(target)
        tname = tname.rsplit(".", 1)[-1] if tname else ""
        fn = local.get(tname)
        if fn is not None:
            traced[id(fn)] = fn
    return list(traced.values())


def _param_names(fn: ast.FunctionDef) -> frozenset[str]:
    args = fn.args
    names = [a.arg for a in (args.posonlyargs + args.args
                             + args.kwonlyargs)]
    if args.vararg:
        names.append(args.vararg.arg)
    return frozenset(n for n in names if n != "self")


def _root_name(node: ast.AST) -> str:
    """The leftmost Name of an expr chain (a.b[c].d -> 'a'), or "" when
    the chain passes through static metadata (`.shape`/`.ndim`/`.size`/
    `.dtype`) — `int(x.shape[0])` is trace-time Python, not a sync."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute) and node.attr in (
                "shape", "ndim", "size", "dtype"):
            return ""
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


def _purity_findings(src: SourceFile, fn: ast.FunctionDef) -> list[Finding]:
    out: list[Finding] = []
    params = _param_names(fn)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _HOST_SYNC_ATTRS:
            out.append(Finding(
                CHECKER, "host-sync", src.path, node.lineno, fn.name,
                f"`.{node.func.attr}()` inside traced code forces a "
                "device->host sync (or fails under trace)"))
        elif name in _HOST_SYNC_CALLS:
            out.append(Finding(
                CHECKER, "host-sync", src.path, node.lineno, fn.name,
                f"`{name}(...)` materializes a traced value on the host"))
        elif name in ("float", "int", "bool") and node.args \
                and _root_name(node.args[0]) in params:
            out.append(Finding(
                CHECKER, "host-sync", src.path, node.lineno, fn.name,
                f"`{name}(...)` on traced argument "
                f"`{_root_name(node.args[0])}` concretizes it — "
                "ConcretizationTypeError at best, silent sync at worst"))
        elif name and (name.startswith(_IMPURE_PREFIXES)
                       or name in ("time.time", "time.perf_counter")):
            out.append(Finding(
                CHECKER, "impure-host-state", src.path, node.lineno,
                fn.name,
                f"`{name}(...)` inside traced code bakes ONE host value "
                "into the compiled program — it never updates again"))
    return out


def _donating_wrappers(src: SourceFile) -> dict[str, tuple[int, ...]]:
    """Callable attribute/function names that donate buffers, mapped to
    CALL-SITE positional indices of the donated args.

    ``self._f = jax.jit(self._f_impl, donate_argnums=(1,))`` wraps the
    *bound* method: index 1 is call-site arg 1.  A ``@partial(jax.jit,
    static_argnums=0, donate_argnums=(6, 7))`` *unbound method* counts
    ``self`` as arg 0, so call sites see indices shifted down by one.
    """
    out: dict[str, tuple[int, ...]] = {}
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if not _is_jax_jit(call.func):
                continue
            donate: tuple[int, ...] = ()
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    donate = _int_tuple(kw.value)
            if not donate:
                continue
            for tgt in node.targets:
                tname = dotted_name(tgt)
                if tname:
                    out[tname.rsplit(".", 1)[-1]] = donate
        elif isinstance(node, ast.FunctionDef):
            donate = _decorator_donate(node)
            if donate and _jit_decorated(node):
                is_method = bool(node.args.args) \
                    and node.args.args[0].arg == "self"
                if is_method:
                    donate = tuple(i - 1 for i in donate if i >= 1)
                out[node.name] = donate
    return out


def _use_after_donate(src: SourceFile) -> list[Finding]:
    donors = _donating_wrappers(src)
    if not donors:
        return []
    out: list[Finding] = []
    for fn in ast.walk(src.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # Lexical liveness scan.  Event ordering within a line mirrors
        # runtime order for the `x = self._f(x, ...)` idiom: the call's
        # args are READ first, the buffer dies when the call runs (its
        # end line), and the assignment REVIVES the name after the whole
        # statement — so a rebound donated buffer is live again.
        dead: dict[str, int] = {}
        events: list[tuple[int, int, str, str]] = []  # (line, prio, kind, name)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                cname = dotted_name(node.func).rsplit(".", 1)[-1]
                donate = donors.get(cname)
                if donate is None:
                    continue
                kill_line = node.end_lineno or node.lineno
                for idx in donate:
                    if idx < len(node.args):
                        dn = dotted_name(node.args[idx])
                        if dn and dn != "self":
                            events.append((kill_line, 1, "kill", dn))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                tgts = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                store_line = node.end_lineno or node.lineno
                for tgt in tgts:
                    dn = dotted_name(tgt)
                    if dn:
                        events.append((store_line, 2, "store", dn))
                    elif isinstance(tgt, ast.Tuple):
                        for elt in tgt.elts:
                            edn = dotted_name(elt)
                            if edn:
                                events.append((store_line, 2, "store", edn))
            elif isinstance(node, (ast.Name, ast.Attribute)) \
                    and isinstance(getattr(node, "ctx", None), ast.Load):
                dn = dotted_name(node)
                if dn:
                    events.append((node.lineno, 0, "load", dn))
        events.sort(key=lambda e: (e[0], e[1]))
        events = [(line, kind, name) for line, _, kind, name in events]
        for line, kind, name in events:
            if kind == "kill":
                dead[name] = line
            elif kind == "store":
                dead.pop(name, None)
            elif kind == "load" and name in dead and line > dead[name]:
                out.append(Finding(
                    CHECKER, "use-after-donate", src.path, line, fn.name,
                    f"`{name}` was donated to XLA at line {dead[name]} — "
                    "its buffer is invalid; rebind the call's result"))
                dead.pop(name)  # one finding per death, not per read
    return out


def _loop_sync_findings(src: SourceFile) -> list[Finding]:
    """host-sync-in-decode-loop: see the module docstring.  One finding
    per (function, sync line) — nested loops both containing the pair
    collapse to a single report anchored at the first sync."""
    out: list[Finding] = []
    seen: set[tuple[str, int]] = set()

    def visit(node: ast.AST, fname: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fname = node.name
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            dispatches = False
            syncs: list[tuple[int, str]] = []
            for sub in ast.walk(node):
                if isinstance(sub, ast.Attribute) \
                        and sub.attr in _DISPATCH_CALLS:
                    dispatches = True
                elif isinstance(sub, ast.Name) \
                        and sub.id in _DISPATCH_CALLS:
                    dispatches = True
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in ("item", "tolist"):
                    syncs.append((sub.lineno, f".{sub.func.attr}()"))
                elif isinstance(sub, ast.Attribute) \
                        and dotted_name(sub) in _LOOP_SYNC_NAMES:
                    # Catches both the direct call and the bare reference
                    # handed to run_in_executor (a call's func node IS an
                    # Attribute, so no separate Call case is needed).
                    syncs.append((sub.lineno, dotted_name(sub)))
            if dispatches and syncs:
                line, what = min(syncs)
                if (fname, line) not in seen:
                    seen.add((fname, line))
                    out.append(Finding(
                        CHECKER, "host-sync-in-decode-loop", src.path,
                        line, fname,
                        f"`{what}` in the same loop as a decode dispatch "
                        "serializes host and device per step — read the "
                        "packed [K, B] block back once per flight with "
                        "jax.device_get"))
        for child in ast.iter_child_nodes(node):
            visit(child, fname)

    visit(src.tree, "<module>")
    return out


def check_jax_purity(root: str,
                     subdirs: tuple[str, ...] = SUBDIRS) -> list[Finding]:
    out: list[Finding] = []
    for src in load_sources(root, subdirs):
        for fn in _traced_functions(src):
            out.extend(_purity_findings(src, fn))
        out.extend(_use_after_donate(src))
        out.extend(_loop_sync_findings(src))
    return out
