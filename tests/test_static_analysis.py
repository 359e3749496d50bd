"""Tier-1 gate for swarmlint (crowdllama_tpu/analysis/): the repo itself
must be finding-free modulo the committed baseline, every checker must
still CATCH its bug class (seeded-violation fixtures — a checker that
rots into a no-op is worse than none), must stay quiet on the matching
clean idioms (true-negative fixtures), and the whole run must fit the
CI lint budget.  `make lint` runs the same checkers standalone.
"""

import json
import textwrap
import time

import pytest

from crowdllama_tpu.analysis import load_baseline, repo_root, run_all
from crowdllama_tpu.analysis.async_hotpath import check_async_hotpath
from crowdllama_tpu.analysis.base import Baseline, parse_baseline_toml
from crowdllama_tpu.analysis.contracts import (
    check_config_parity,
    check_fault_sites,
    check_metrics_docs,
    check_oneof,
    collect_metric_families,
)
from crowdllama_tpu.analysis.jax_purity import check_jax_purity
from crowdllama_tpu.testing.faults import FAULT_SITES


def _fake_repo(tmp_path, files):
    """Write {relpath: source} under tmp_path and return it as a root."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text), encoding="utf-8")
    return str(tmp_path)


# ------------------------------------------------------------ the repo


def _repo_run():
    """One timed run of every checker over the real repo, shared by the
    repo-level tests (the full sweep costs seconds; no need to pay it
    per assertion)."""
    if "result" not in _repo_run.__dict__:
        baseline = load_baseline()
        t0 = time.perf_counter()
        findings = run_all(repo_root(), baseline)
        _repo_run.result = (findings, baseline, time.perf_counter() - t0)
    return _repo_run.result


def test_repo_is_clean_within_budget():
    """Zero non-baseline findings across all checkers, inside the lint
    runtime budget ISSUE/CI hold the repo to (<30s; it runs in every
    `make test` / `make tier1`)."""
    findings, _, elapsed = _repo_run()
    assert not findings, "new swarmlint findings:\n" + "\n".join(
        f.render() for f in findings)
    assert elapsed < 30.0, f"lint took {elapsed:.1f}s — over the 30s budget"


def test_baseline_policy():
    """At most 10 waivers, every one with a non-empty reason, none stale."""
    _, baseline, _ = _repo_run()
    assert len(baseline.entries) <= 10, "baseline grew past 10 waivers — " \
        "fix findings instead of waiving them"
    for e in baseline.entries:
        assert e["reason"].strip(), f"waiver without justification: {e}"
    assert not baseline.stale(), f"stale waivers: {baseline.stale()}"


# ------------------------------------------------- baseline machinery


def test_baseline_parser_rejects_reasonless_waivers():
    good = parse_baseline_toml(
        '# comment\n[[waiver]]\nchecker = "async-hotpath"\n'
        'code = "blocking-call"\npath = "crowdllama_tpu/x.py"\n'
        'symbol = "f"\nreason = "startup-only read"\n')
    assert good[0]["symbol"] == "f"
    with pytest.raises(ValueError, match="missing keys"):
        parse_baseline_toml('[[waiver]]\nchecker = "x"\n')
    with pytest.raises(ValueError, match="empty"):
        parse_baseline_toml(
            '[[waiver]]\nchecker = "c"\ncode = "k"\npath = "p"\n'
            'symbol = "s"\nreason = "  "\n')
    with pytest.raises(ValueError, match="unparseable"):
        parse_baseline_toml("checker = 3\n")


def test_baseline_waives_by_key_and_reports_stale():
    from crowdllama_tpu.analysis.base import Finding

    b = Baseline(entries=[
        {"checker": "c", "code": "k", "path": "p.py", "symbol": "f",
         "reason": "r"},
        {"checker": "c", "code": "k", "path": "other.py", "symbol": "g",
         "reason": "r"},
    ])
    hit = Finding("c", "k", "p.py", 42, "f", "m")
    miss = Finding("c", "k", "p.py", 42, "h", "m")
    assert b.waives(hit) and not b.waives(miss)
    # Line number is NOT part of the key: same finding moved 100 lines
    # down is still waived; the unmatched entry reports stale.
    assert b.waives(Finding("c", "k", "p.py", 142, "f", "m"))
    assert [e["path"] for e in b.stale()] == ["other.py"]


# ------------------------------------------------ async-hotpath seeds


_ASYNC_FIXTURE = """
    import asyncio
    import time


    class Manager:
        def __init__(self):
            self._lock = asyncio.Lock()
            self.table = {}

        async def locked_update(self, k, v):
            async with self._lock:
                self.table = {k: v}

        async def racy_update(self):
            self.table = {}


    async def do_work():
        await asyncio.sleep(0)


    async def bad_sleep():
        time.sleep(0.1)


    async def bad_open(path):
        with open(path) as f:
            return f.read()


    async def bad_result(fut):
        return fut.result()


    async def lost():
        do_work()


    async def fine():
        await do_work()
        asyncio.create_task(do_work())
        loop = asyncio.get_running_loop()

        def _blocking():
            time.sleep(1)

        await loop.run_in_executor(None, _blocking)
"""


def test_async_hotpath_catches_seeded_violations(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/gateway/fx.py": _ASYNC_FIXTURE})
    hits = {(f.code, f.symbol)
            for f in check_async_hotpath(root, ("gateway",))}
    assert ("blocking-call", "bad_sleep") in hits
    assert ("blocking-call", "bad_open") in hits
    assert ("blocking-result", "bad_result") in hits
    assert ("unawaited-coroutine", "lost") in hits
    assert ("unlocked-mutation", "Manager.racy_update") in hits


def test_async_hotpath_true_negatives(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/gateway/fx.py": _ASYNC_FIXTURE})
    symbols = {f.symbol for f in check_async_hotpath(root, ("gateway",))}
    # Awaited/task-wrapped coroutines, executor-nested sleep, and the
    # lock-guarded mutation are all clean idioms — zero findings.
    assert "fine" not in symbols
    assert "Manager.locked_update" not in symbols


# --------------------------------------------------- jax-purity seeds


_PURITY_FIXTURE = """
    import time

    import jax
    import numpy as np


    @jax.jit
    def traced_bad(x):
        y = float(x)
        z = np.asarray(x)
        t = time.time()
        x.block_until_ready()
        return x


    @jax.jit
    def traced_ok(x):
        n = int(x.shape[0])
        return x * n


    def untraced(x):
        return float(np.asarray(x).item())
"""

_DONATE_FIXTURE = """
    import jax


    def _step_impl(params, pool):
        return pool


    _step = jax.jit(_step_impl, donate_argnums=(1,))


    def drive_bad(params, pool):
        out = _step(params, pool)
        return pool.tokens


    def drive_ok(params, pool):
        pool = _step(params, pool)
        return pool
"""


def test_jax_purity_catches_seeded_violations(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/engine/fx.py": _PURITY_FIXTURE})
    hits = [(f.code, f.symbol, f.line)
            for f in check_jax_purity(root, ("engine",))]
    codes = [(c, s) for c, s, _ in hits]
    assert codes.count(("host-sync", "traced_bad")) == 3  # float/asarray/bur
    assert ("impure-host-state", "traced_bad") in codes


def test_jax_purity_true_negatives(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/engine/fx.py": _PURITY_FIXTURE})
    symbols = {f.symbol for f in check_jax_purity(root, ("engine",))}
    # Static shape math under trace and host work in untraced helpers
    # are both fine.
    assert "traced_ok" not in symbols
    assert "untraced" not in symbols


def test_use_after_donate_seeded_and_rebind_negative(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/engine/fx.py": _DONATE_FIXTURE})
    findings = [f for f in check_jax_purity(root, ("engine",))
                if f.code == "use-after-donate"]
    assert [f.symbol for f in findings] == ["drive_bad"]
    assert "pool" in findings[0].message


_LOOP_SYNC_FIXTURE = """
    import jax
    import numpy as np


    class Sched:
        async def loop_bad(self, state):
            while True:
                tokens_dev, state = self.runner.decode_steps_device(state, 8)
                tokens = np.asarray(tokens_dev)
                last = tokens[-1, 0].item()

        async def loop_bad_executor(self, loop, state):
            for _ in range(4):
                tokens_dev, state = await loop.run_in_executor(
                    self._exec, self.runner.decode_steps_device, state, 8)
                tokens = await loop.run_in_executor(
                    self._exec, np.asarray, tokens_dev)

        async def loop_bad_ragged(self, loop, state, job):
            while not job.finished:
                tokens_dev, state = self.runner.ragged_step(state, job, 8)
                tokens = np.asarray(tokens_dev)

        async def loop_ok(self, loop, state):
            while True:
                tokens_dev, state = self.runner.decode_steps_device(
                    state, 8)
                tokens, counters = await loop.run_in_executor(
                    self._exec, jax.device_get, (tokens_dev, counters_dev))

        async def loop_ok_ragged(self, loop, state, job):
            while not job.finished:
                tokens_dev, state = self.runner.ragged_step(state, job, 8)
                tokens, counters = await loop.run_in_executor(
                    self._exec, jax.device_get, (tokens_dev, counters_dev))

        def retire_ok(self, fl):
            tokens = np.asarray(fl.tokens_dev)
            for step in range(tokens.shape[0]):
                self.emit(int(tokens[step, 0]))
"""


def test_host_sync_in_decode_loop_seeded(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/engine/fx.py": _LOOP_SYNC_FIXTURE})
    hits = {(f.code, f.symbol) for f in check_jax_purity(root, ("engine",))}
    # Direct per-step readback AND the executor-wrapped form (np.asarray
    # handed to run_in_executor) are both the seeded bug class, and the
    # ragged flight (ragged_step) is covered the same way.
    assert ("host-sync-in-decode-loop", "loop_bad") in hits
    assert ("host-sync-in-decode-loop", "loop_bad_executor") in hits
    assert ("host-sync-in-decode-loop", "loop_bad_ragged") in hits


def test_host_sync_in_decode_loop_true_negatives(tmp_path):
    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/engine/fx.py": _LOOP_SYNC_FIXTURE})
    loop_hits = {f.symbol for f in check_jax_purity(root, ("engine",))
                 if f.code == "host-sync-in-decode-loop"}
    # The sanctioned pattern (one jax.device_get of the packed block per
    # flight) — plain or ragged — and a dispatch-free emit loop stay
    # clean.
    assert "loop_ok" not in loop_hits
    assert "loop_ok_ragged" not in loop_hits
    assert "retire_ok" not in loop_hits


# ----------------------------------------------------- contract seeds


def test_config_parity_catches_seeded_violations(tmp_path):
    root = _fake_repo(tmp_path, {"crowdllama_tpu/config.py": """
        import os


        class Configuration:
            alpha: int = 1
            beta: str = ""
            gamma: int = 2

            @classmethod
            def from_environment(cls, **overrides):
                env = os.environ
                cfg = cls()
                cfg.alpha = int(env.get("CROWDLLAMA_TPU_ALPHA", cfg.alpha))
                cfg.gamma = int(env.get("CROWDLLAMA_TPU_GAMMA", cfg.gamma))
                return cfg

            @classmethod
            def add_flags(cls, ap):
                ap.add_argument("--alpha", type=int)
                ap.add_argument("--gamma", type=int)
                ap.add_argument("--delta", type=int)

            @classmethod
            def from_flags(cls, args):
                cfg = cls.from_environment()
                for name in ("alpha",):
                    setattr(cfg, name, getattr(args, name))
                return cfg
    """})
    hits = {(f.code, f.symbol) for f in check_config_parity(root)}
    assert ("config-no-env", "beta") in hits          # field without env
    assert ("config-unknown-dest", "delta") in hits   # flag without field
    assert ("config-flag-unconsumed", "gamma") in hits
    assert not any(s == "alpha" for _, s in hits)     # fully wired: clean


def test_metrics_docs_catches_seeded_violations(tmp_path):
    root = _fake_repo(tmp_path, {
        "crowdllama_tpu/obs/fx.py": '''
            def expose(key):
                lines = ["# TYPE crowdllama_documented_total counter",
                         "# TYPE crowdllama_undocumented_total counter"]
                lines.append(f"crowdllama_dyn_{key}_total 1")
                return lines
        ''',
        "docs/OBSERVABILITY.md": (
            "`crowdllama_documented_total` and the `crowdllama_dyn_fast`\n"
            "family; `crowdllama_vanished_total` (no longer emitted).\n"),
    })
    hits = {(f.code, f.symbol) for f in check_metrics_docs(root)}
    assert ("metrics-undocumented", "crowdllama_undocumented_total") in hits
    assert ("metrics-stale-doc", "crowdllama_vanished_total") in hits
    # documented exact family + dynamic prefix with a documented member
    # are both clean.
    assert not any("documented_total" == s.replace("crowdllama_", "")
                   for c, s in hits if c == "metrics-undocumented"
                   and "un" not in s)
    assert not any(s.startswith("crowdllama_dyn_") for _, s in hits)


def test_fault_sites_catches_seeded_violations(tmp_path):
    inject_all = "\n".join(
        f'    await faults.inject("{s}")' for s in FAULT_SITES)
    root = _fake_repo(tmp_path, {
        "crowdllama_tpu/fx.py": (
            "from crowdllama_tpu.testing import faults\n\n\n"
            "async def run():\n"
            f"{inject_all}\n"
            '    await faults.inject("bogus.site")\n'),
        "tests/test_fx.py": """
            import pytest

            from crowdllama_tpu.testing.faults import FaultRule


            def test_seed():
                FaultRule(site="nope.site")
                with pytest.raises(ValueError):
                    FaultRule(site="deliberately.bad")
        """,
    })
    hits = {(f.code, f.symbol) for f in check_fault_sites(root)}
    assert ("fault-site-unregistered", "bogus.site") in hits
    assert ("fault-site-unknown-in-test", "nope.site") in hits
    # The pytest.raises-wrapped rule is a deliberate negative fixture —
    # never flagged; with every registered site instrumented above,
    # nothing reports uninstrumented either.
    assert not any(s == "deliberately.bad" for _, s in hits)
    assert not any(c == "fault-site-uninstrumented" for c, _ in hits)


def test_oneof_catches_missing_wiring(tmp_path):
    from crowdllama_tpu.analysis.contracts import RESPONSE_ARMS
    from crowdllama_tpu.core import llama_v1_pb2 as pb

    arms = [f.name for f in
            pb.BaseMessage.DESCRIPTOR.oneofs_by_name["message"].fields]
    requests = [a for a in arms if a not in RESPONSE_ARMS]
    drop_extract, drop_dispatch = arms[0], requests[-1]
    messages = "\n".join(
        [f"mk = lambda: BaseMessage({a}=None)" for a in arms]
        + [f'WHICH = "{a}"' for a in arms if a != drop_extract])
    peer = "\n".join(f'ok = which == "{a}"' for a in requests
                     if a != drop_dispatch)
    root = _fake_repo(tmp_path, {
        "crowdllama_tpu/core/messages.py": messages,
        "crowdllama_tpu/peer/peer.py": peer,
    })
    hits = {(f.code, f.symbol) for f in check_oneof(root)}
    assert ("oneof-extractor", drop_extract) in hits
    assert ("oneof-dispatch", drop_dispatch) in hits
    # Everything still wired stays clean, and no response arm ever
    # demands a dispatch arm.
    assert not any(c == "oneof-dispatch" and s in RESPONSE_ARMS
                   for c, s in hits)
    assert not any(c == "oneof-constructor" for c, _ in hits)


def test_collected_families_look_sane():
    """The static family collector (the doc-parity checker's foundation
    AND test_metrics_lint's completeness source) sees the core families
    and classifies dynamic f-string families as prefixes."""
    exact, prefixes = collect_metric_families(repo_root())
    for fam in ("crowdllama_request_seconds", "crowdllama_ttft_seconds",
                "crowdllama_workers_total",
                "crowdllama_device_memory_bytes_limit"):
        assert fam in exact, fam
    for pref in ("crowdllama_engine_", "crowdllama_kv_ship_",
                 "crowdllama_gossip_", "crowdllama_drain_"):
        assert pref in prefixes, pref
    # Module/protocol identifiers never masquerade as families.
    assert not any(f.startswith("crowdllama_tpu") for f in exact)


# ------------------------------------------------ ffi-contract seeds


_FFI_CPP_FIXTURE = """
    #include <cstdint>
    #include <cstddef>

    // cl_-named but NOT exported: internal linkage, outside extern "C" —
    // must not demand a ctypes declaration (true negative).
    static long cl_fx_internal(int a) { return a; }

    extern "C" {

    void* cl_fx_ok(const uint8_t* key, int flavor) { (void)key; return 0; }

    void cl_fx_void(void* h) { (void)h; }

    long cl_fx_arity(void* h, const uint8_t* buf, size_t len) { return 0; }

    long cl_fx_restype(void* h) { return 0; }

    long cl_fx_undeclared(void* h) { return 0; }

    long cl_fx_half(void* h) { return 0; }

    }  // extern "C"
"""

_FFI_PY_FIXTURE = """
    import ctypes


    def _declare(lib):
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.cl_fx_ok.restype = ctypes.c_void_p
        lib.cl_fx_ok.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.cl_fx_void.restype = None
        lib.cl_fx_void.argtypes = [ctypes.c_void_p]
        # Seeded: one argtypes entry short of the three C parameters.
        lib.cl_fx_arity.restype = ctypes.c_long
        lib.cl_fx_arity.argtypes = [ctypes.c_void_p, u8p]
        # Seeded: C returns long, declared c_int (truncation).
        lib.cl_fx_restype.restype = ctypes.c_int
        lib.cl_fx_restype.argtypes = [ctypes.c_void_p]
        # Seeded: argtypes half missing.
        lib.cl_fx_half.restype = ctypes.c_long
        # Seeded: no such extern "C" symbol.
        lib.cl_fx_ghost.restype = ctypes.c_long
        lib.cl_fx_ghost.argtypes = [ctypes.c_void_p]
        return lib
"""


def _ffi_fixture_root(tmp_path):
    return _fake_repo(tmp_path, {
        "crowdllama_tpu/native/_src/fx.cpp": _FFI_CPP_FIXTURE,
        "crowdllama_tpu/native/__init__.py": _FFI_PY_FIXTURE,
    })


def test_ffi_contract_catches_seeded_violations(tmp_path):
    from crowdllama_tpu.analysis.ffi_contract import check_ffi_contract

    hits = {(f.code, f.symbol)
            for f in check_ffi_contract(_ffi_fixture_root(tmp_path))}
    assert ("ffi-undeclared", "cl_fx_undeclared") in hits
    assert ("ffi-undeclared", "cl_fx_half") in hits
    assert ("ffi-arity", "cl_fx_arity") in hits
    assert ("ffi-restype", "cl_fx_restype") in hits
    assert ("ffi-unknown-symbol", "cl_fx_ghost") in hits


def test_ffi_contract_true_negatives(tmp_path):
    from crowdllama_tpu.analysis.ffi_contract import check_ffi_contract

    symbols = {f.symbol
               for f in check_ffi_contract(_ffi_fixture_root(tmp_path))}
    # Fully-declared functions (incl. restype None for void) are clean;
    # a static cl_-named helper outside extern "C" is not part of the ABI.
    assert "cl_fx_ok" not in symbols
    assert "cl_fx_void" not in symbols
    assert "cl_fx_internal" not in symbols


def test_ffi_contract_repo_has_zero_waivers():
    """ISSUE 19 policy: the ABI seam is never waived — both repo baseline
    hygiene and the checker being clean on the real tree."""
    from crowdllama_tpu.analysis.ffi_contract import (
        c_exports,
        check_ffi_contract,
        py_declarations,
    )

    assert not any(e.get("checker") == "ffi-contract"
                   for e in load_baseline().entries)
    root = repo_root()
    findings = check_ffi_contract(root)
    assert not findings, "\n".join(f.render() for f in findings)
    # The contract is non-trivially exercised: every native symbol the
    # data plane uses is visible to both sides of the seam.
    exports, decls = c_exports(root), py_declarations(root)
    assert len(exports) >= 15 and set(exports) == set(decls)


# ------------------------------------------------------------ the CLI


def test_cli_json_format_is_clean_on_repo(capsys):
    from crowdllama_tpu.analysis.__main__ import main

    rc = main(["--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["findings"] == []
    assert data["checkers"] == ["async-hotpath", "contracts",
                                "ffi-contract", "jax-purity"]
    assert data["elapsed_s"] < 30.0


def test_cli_exits_nonzero_on_seeded_violation(tmp_path, capsys):
    """The `make lint` contract: injecting a violation flips the exit
    code (CI fails), and the finding renders with path:line."""
    from crowdllama_tpu.analysis.__main__ import main

    root = _fake_repo(tmp_path,
                      {"crowdllama_tpu/gateway/fx.py": _ASYNC_FIXTURE})
    rc = main(["--root", root, "--checker", "async-hotpath"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[async-hotpath/blocking-call] bad_sleep" in out


def test_cli_rejects_malformed_baseline(tmp_path, capsys):
    from crowdllama_tpu.analysis.__main__ import main

    bad = tmp_path / "baseline.toml"
    bad.write_text('[[waiver]]\nchecker = "c"\n', encoding="utf-8")
    assert main(["--baseline", str(bad)]) == 2
