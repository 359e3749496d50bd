"""Prometheus exposition lint for BOTH scrape surfaces (gateway /metrics
and the worker ObsServer's /metrics): every series belongs to a declared
# TYPE family (declared once), no duplicate series, label values stay in
the sane charset the obs/ LabelGuard enforces, and histogram families are
internally consistent (monotone cumulative buckets, +Inf == _count).

This is the guard that keeps the two endpoints mirror images: a metric
added to one side with a malformed name/labels — or a family exposed
twice — fails here before a real Prometheus server ever chokes on it.
"""

import re

import aiohttp
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.net.discovery import new_host_and_dht
from crowdllama_tpu.obs.http import ObsServer
from crowdllama_tpu.obs.metrics import DISPATCH_CLASSES
from crowdllama_tpu.peer.peer import Peer

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')
_VALUE_RE = re.compile(r"^[A-Za-z0-9_.:+/\- ]{0,128}$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? (\S+)$")
# OpenMetrics exemplar suffix (--metrics-exemplars): only histogram
# _bucket lines may carry one, and the label set is exactly a trace_id
# in the gateway's 64-bit-hex mint format.
_EXEMPLAR_RE = re.compile(r' # \{trace_id="[0-9a-f]{1,64}"\} \S+$')


def _parse(text):
    """exposition text -> (types, samples); asserts structural validity."""
    types: dict[str, str] = {}
    samples: list[tuple[str, str, float]] = []
    seen: set[tuple[str, str]] = set()
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            assert parts[:2] == ["#", "TYPE"], f"line {ln}: bad comment"
            assert len(parts) == 4, f"line {ln}: malformed TYPE"
            _, _, fam, kind = parts
            assert _NAME_RE.match(fam), f"line {ln}: bad family {fam!r}"
            assert kind in ("counter", "gauge", "histogram"), (
                f"line {ln}: unknown type {kind!r}")
            assert fam not in types, f"line {ln}: duplicate TYPE for {fam}"
            types[fam] = kind
            continue
        if " # " in line:
            sample, sep, _ = line.partition(" # ")
            assert _EXEMPLAR_RE.search(line), (
                f"line {ln}: malformed exemplar {line!r}")
            assert _SAMPLE_RE.match(sample) and \
                _SAMPLE_RE.match(sample).group(1).endswith("_bucket"), (
                f"line {ln}: exemplar on a non-bucket line {line!r}")
            ex_val = float(line.rsplit(" ", 1)[1])
            assert ex_val >= 0, f"line {ln}: negative exemplar value"
            line = sample
        m = _SAMPLE_RE.match(line)
        assert m, f"line {ln}: unparseable sample {line!r}"
        name, _, labels, value = m.groups()
        labels = labels or ""
        key = (name, labels)
        assert key not in seen, f"line {ln}: duplicate series {key}"
        seen.add(key)
        for lname, lval in _LABEL_RE.findall(labels):
            assert _VALUE_RE.match(lval), (
                f"line {ln}: label {lname} has unsane value {lval!r}")
        v = float(value)
        assert v >= 0, f"line {ln}: negative sample {line!r}"
        samples.append((name, labels, v))
    return types, samples


def _family_of(name: str, types: dict[str, str]) -> str:
    if name in types:
        return name
    for suffix in ("_bucket", "_sum", "_count"):
        base = name[: -len(suffix)] if name.endswith(suffix) else ""
        if base in types and types[base] == "histogram":
            return base
    raise AssertionError(f"series {name} has no # TYPE declaration")


def _lint(text: str) -> dict[str, str]:
    types, samples = _parse(text)
    for name, _, _ in samples:
        _family_of(name, types)
    # Histogram consistency per child (labels minus the le pair).
    hists: dict[tuple[str, str], dict] = {}
    for name, labels, v in samples:
        fam = _family_of(name, types)
        if types[fam] != "histogram":
            continue
        mle = re.search(r'le="([^"]*)",?', labels)
        child = re.sub(r'le="[^"]*",?', "", labels).rstrip(",")
        h = hists.setdefault((fam, child),
                             {"buckets": [], "count": None, "sum": None})
        if name.endswith("_bucket"):
            assert mle, f"{name}{{{labels}}} missing le"
            h["buckets"].append((mle.group(1), v))
        elif name.endswith("_count"):
            h["count"] = v
        elif name.endswith("_sum"):
            h["sum"] = v
    for (fam, child), h in hists.items():
        where = f"{fam}{{{child}}}"
        assert h["count"] is not None and h["sum"] is not None, (
            f"{where}: missing _count/_sum")
        assert h["buckets"], f"{where}: histogram with no buckets"
        assert h["buckets"][-1][0] == "+Inf", f"{where}: last le != +Inf"
        counts = [n for _, n in h["buckets"]]
        assert counts == sorted(counts), f"{where}: non-monotone buckets"
        assert counts[-1] == h["count"], (
            f"{where}: +Inf bucket {counts[-1]} != count {h['count']}")
    return types


def _cfg(bootstrap):
    return Configuration(listen_host="127.0.0.1",
                         bootstrap_peers=[bootstrap],
                         metrics_exemplars=True,
                         intervals=Intervals.default())


async def _wait_for(cond, timeout=20.0, what="condition"):
    import asyncio
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


async def test_gateway_and_worker_metrics_lint():
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    worker = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                  engine=FakeEngine(models=["tiny-test"]), worker_mode=True)
    await worker.start()
    obs_srv = ObsServer(worker, port=0)
    await obs_srv.start()

    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    # SLO objectives on so the crowdllama_slo_* families render and get
    # linted (disabled objectives expose nothing by design).
    gateway = Gateway(consumer, port=0, host="127.0.0.1",
                      metrics_exemplars=True,
                      slo_ttft_ms=500.0, slo_decode_ms=200.0)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    try:
        await _wait_for(
            lambda: consumer.peer_manager.find_best_worker("tiny-test")
            is not None, what="worker discovery")
        async with aiohttp.ClientSession() as s:
            # Streamed + non-streamed traffic so the labeled request
            # histograms, TTFT and decode-step series carry samples.
            body = {"model": "tiny-test", "stream": False,
                    "messages": [{"role": "user", "content": "lint me"}]}
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                              json=body) as resp:
                assert resp.status == 200
            body["stream"] = True
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                              json=body) as resp:
                assert resp.status == 200
                async for _ in resp.content:
                    pass
            async with s.get(
                    f"http://127.0.0.1:{gw_port}/metrics") as resp:
                assert resp.status == 200
                gw_text = await resp.text()
            async with s.get(f"http://127.0.0.1:{obs_srv.port}"
                             f"/metrics") as resp:
                assert resp.status == 200
                wk_text = await resp.text()
            # The third scrape surface (PR 13): the cluster fan-in must
            # be lint-clean too — merged worker families keep one TYPE
            # per family and gain a worker label, exemplars stripped.
            async with s.get(f"http://127.0.0.1:{gw_port}"
                             f"/metrics/cluster") as resp:
                assert resp.status == 200
                cl_text = await resp.text()

        gw_types = _lint(gw_text)
        wk_types = _lint(wk_text)
        cl_types = _lint(cl_text)
        # Completeness, closing the loop with swarmlint's static family
        # collector (crowdllama_tpu/analysis/contracts.py): every
        # crowdllama_* family named anywhere in code must be DECLARED on
        # at least one of the two scrape surfaces — a counter that's
        # bumped but never exposed is invisible to oncall.
        from crowdllama_tpu.analysis.base import repo_root
        from crowdllama_tpu.analysis.contracts import collect_metric_families

        exact, _ = collect_metric_families(repo_root())
        declared = set(gw_types) | set(wk_types) | set(cl_types)
        missing = sorted(f for f in exact if f not in declared)
        assert not missing, (
            f"families named in code but declared on no scrape "
            f"surface: {missing}")
        # The swarm-uniform families exist on BOTH scrape surfaces, with
        # the engine/scheduler gauges next to them.
        for types in (gw_types, wk_types):
            for fam in ("crowdllama_request_seconds",
                        "crowdllama_ttft_seconds",
                        "crowdllama_decode_step_seconds",
                        "crowdllama_kv_fetch_seconds"):
                assert types.get(fam) == "histogram", f"{fam} missing"
            for c in ("bytes", "fetches", "fallbacks", "retries"):
                fam = f"crowdllama_kv_ship_{c}_total"
                assert types.get(fam) == "counter", f"{fam} missing"
            # Live-migration families (docs/ROBUSTNESS.md) are swarm
            # uniform too: drain counters on the worker that drains,
            # migrated/replayed on whichever side moved the stream.
            for c in ("initiated", "migrated_slots", "rejected_requests"):
                fam = f"crowdllama_drain_{c}_total"
                assert types.get(fam) == "counter", f"{fam} missing"
            for fam in ("crowdllama_migrated_streams_total",
                        "crowdllama_replayed_prefill_tokens_total"):
                assert types.get(fam) == "counter", f"{fam} missing"
            # Replicated-gateway families (docs/ROBUSTNESS.md): gossip
            # anti-entropy + per-tenant admission, present (at zero) on
            # BOTH scrape surfaces like every swarm-uniform family.
            for c in ("frames_sent", "frames_received", "entries_applied",
                      "entries_stale", "full_syncs", "send_failures",
                      "snapshot_saves"):
                fam = f"crowdllama_gossip_{c}_total"
                assert types.get(fam) == "counter", f"{fam} missing"
            for g in ("map_entries", "snapshot_entries_loaded"):
                fam = f"crowdllama_gossip_{g}"
                assert types.get(fam) == "gauge", f"{fam} missing"
            for fam, kind in (("crowdllama_tenant_admitted_total",
                               "counter"),
                              ("crowdllama_tenant_shed_total", "counter"),
                              ("crowdllama_tenant_inflight", "gauge")):
                assert types.get(fam) == kind, f"{fam} missing"
            for g in ("pending_depth", "active_slots", "batch_occupancy",
                      "kv_cache_utilization",
                      # Host-dispatch accounting: what the last
                      # retired flight emitted.
                      "tokens_per_dispatch"):
                assert types.get(f"crowdllama_engine_{g}") == "gauge"
            # host_dispatches_total is monotone — it must render as a
            # counter (the `_total` suffix drives the TYPE line).
            assert types.get(
                "crowdllama_engine_host_dispatches_total") == "counter"
            # Engine flight-recorder telemetry (docs/OBSERVABILITY.md):
            # XLA compile timing/counters + padding-waste accounting +
            # device memory, present on BOTH surfaces (zero-valued on a
            # node that never compiled).
            assert types.get(
                "crowdllama_xla_compile_seconds") == "histogram"
            for fam in ("crowdllama_xla_compiles_total",
                        "crowdllama_padding_waste_tokens_total",
                        "crowdllama_useful_tokens_total",
                        "crowdllama_engine_flights_total",
                        "crowdllama_moe_assignments_total",
                        "crowdllama_moe_banks_total",
                        "crowdllama_moe_banks_fetched_total",
                        "crowdllama_attn_grid_steps_total"):
                assert types.get(fam) == "counter", f"{fam} missing"
            for fam in ("crowdllama_device_memory_bytes_in_use",
                        "crowdllama_device_memory_bytes_limit"):
                assert types.get(fam) == "gauge", f"{fam} missing"
            # Swarm observatory (PR 13): dial-ladder attempts, the
            # host-gap histogram and the per-dispatch-class duty cycle
            # are swarm-uniform (zeros on nodes that never dialed a
            # ladder rung or dispatched that class).
            assert types.get(
                "crowdllama_dial_ladder_attempts_total") == "counter"
            assert types.get("crowdllama_host_gap_seconds") == "histogram"
            assert types.get("crowdllama_engine_duty_cycle") == "gauge"
        # All eight (rung, outcome) ladder series pre-render at zero.
        for text in (gw_text, wk_text):
            for rung in ("direct", "reverse", "punch", "splice"):
                for outcome in ("ok", "fail"):
                    assert (f'crowdllama_dial_ladder_attempts_total{{'
                            f'rung="{rung}",outcome="{outcome}"}}') in text
        # Duty cycle: one labeled child per dispatch class and no other
        # (pre-rendered at zero from boot so dashboards see the series
        # before the first flight).
        assert set(re.findall(
            r'^crowdllama_engine_duty_cycle\{dispatch="(\w+)"\}', gw_text,
            re.M)) == set(DISPATCH_CLASSES)
        # The benchmark's step.decode_wall_ms names both flight counters'
        # dispatch="megastep" series: a worker's scrape carries them, at 0
        # (obs/metrics.py DISPATCH_CLASSES has why).
        for fam in ("seconds", "steps"):
            assert re.search(
                rf'^crowdllama_engine_flight_{fam}_total'
                r'\{dispatch="megastep"\} 0(\.0+)?$', wk_text, re.M), fam
        # SLO burn-rate plane (gateway-only; objectives were configured).
        for fam, kind in (("crowdllama_slo_objective_ms", "gauge"),
                          ("crowdllama_slo_requests_total", "counter"),
                          ("crowdllama_slo_burn_rate", "gauge"),
                          ("crowdllama_slo_fast_burn", "gauge"),
                          ("crowdllama_slo_fast_burn_episodes_total",
                           "counter")):
            assert gw_types.get(fam) == kind, f"{fam} missing"
        # Cluster rollups on the fan-in surface.
        for fam, kind in (("crowdllama_cluster_workers_total", "gauge"),
                          ("crowdllama_cluster_workers_scraped", "gauge"),
                          ("crowdllama_cluster_scrapes_total", "counter"),
                          ("crowdllama_cluster_scrape_misses_total",
                           "counter"),
                          ("crowdllama_cluster_tokens_per_second",
                           "gauge"),
                          ("crowdllama_cluster_batch_occupancy", "gauge"),
                          ("crowdllama_cluster_kv_cache_utilization",
                           "gauge"),
                          ("crowdllama_cluster_inflight", "gauge")):
            assert cl_types.get(fam) == kind, f"{fam} missing"
        # Gateway-side routing counters for the KV-ship plane.
        for fam in ("crowdllama_gateway_affinity_evicted_total",
                    "crowdllama_gateway_affinity_repointed_total",
                    "crowdllama_gateway_kv_hints_total",
                    "crowdllama_gateway_gossip_affinity_hits_total"):
            assert gw_types.get(fam) == "counter", f"{fam} missing"
        # Traffic landed in BOTH sides' request histograms.
        for text in (gw_text, wk_text):
            assert re.search(r'crowdllama_request_seconds_count\{'
                             r'model="tiny-test"\} [1-9]', text), (
                "no tiny-test request samples recorded")
        # Exemplars on: the routed requests must have attached a trace_id
        # exemplar to at least one gateway request_seconds bucket (and the
        # suffix passed the OpenMetrics shape check in _parse above).
        assert re.search(r'crowdllama_request_seconds_bucket\{[^}]*\}'
                         r' \S+ # \{trace_id="[0-9a-f]+"\} ', gw_text), (
            "no trace_id exemplar on the gateway request histogram")
    finally:
        await gateway.stop()
        await consumer.stop()
        await obs_srv.stop()
        await worker.stop()
        await boot_host.close()


def test_flight_length_counter_lint():
    """crowdllama_engine_flights_total{length}: one counter family, both
    lengths rendered at 0 before any flight, each retired flight counted
    under exactly one."""
    from crowdllama_tpu.obs.metrics import EngineTelemetry

    tele = EngineTelemetry()
    text = "\n".join(tele.expose())
    assert _lint(text)["crowdllama_engine_flights_total"] == "counter"
    for length in ("short", "full"):
        assert (f'crowdllama_engine_flights_total{{length="{length}"}} 0'
                in text.splitlines())
    tele.flight_inc("plain", seconds=0.01, steps=1, useful=1, waste=7,
                    short=True)
    tele.flight_inc("plain", seconds=0.08, steps=8, useful=64, waste=0,
                    short=False)
    tele.flight_inc("plain", seconds=0.08, steps=8, useful=64, waste=0,
                    short=False)
    lines = tele.expose()
    _lint("\n".join(lines))
    assert 'crowdllama_engine_flights_total{length="short"} 1' in lines
    assert 'crowdllama_engine_flights_total{length="full"} 2' in lines


def test_moe_bank_counters_lint():
    """crowdllama_moe_banks_total{dispatch,state} and
    crowdllama_moe_banks_fetched_total{dispatch}: every dispatch class
    rendered at 0 before any flight; a flight's counts land under its own
    class, a bank as routed or as unrouted; the assignment counter beside
    them keeps its two series."""
    from crowdllama_tpu.obs.metrics import EngineTelemetry

    tele = EngineTelemetry()
    lines = tele.expose()
    types = _lint("\n".join(lines))
    assert types["crowdllama_moe_banks_total"] == "counter"
    assert types["crowdllama_moe_banks_fetched_total"] == "counter"
    for cls in DISPATCH_CLASSES:
        for state in ("routed", "unrouted"):
            assert (f'crowdllama_moe_banks_total{{dispatch="{cls}",'
                    f'state="{state}"}} 0') in lines
        assert (f'crowdllama_moe_banks_fetched_total{{dispatch="{cls}"}} 0'
                in lines)
    # [rows held, rows left out, banks routed, banks fetched, banks held]
    tele.moe_counts_inc("plain", [5, 3, 4, 8, 8])
    tele.moe_counts_inc("plain", [6, 2, 3, 8, 8])
    tele.moe_counts_inc("ragged", [50, 30, 8, 8, 8])
    lines = tele.expose()
    _lint("\n".join(lines))
    for line in ('crowdllama_moe_banks_total{dispatch="plain",state="routed"} 7',
                 'crowdllama_moe_banks_total{dispatch="plain",'
                 'state="unrouted"} 9',
                 'crowdllama_moe_banks_fetched_total{dispatch="plain"} 16',
                 'crowdllama_moe_banks_total{dispatch="ragged",'
                 'state="routed"} 8',
                 'crowdllama_moe_banks_total{dispatch="ragged",'
                 'state="unrouted"} 0',
                 'crowdllama_moe_banks_fetched_total{dispatch="ragged"} 8',
                 'crowdllama_moe_banks_fetched_total{dispatch="spec"} 0',
                 'crowdllama_moe_assignments_total{held="yes"} 61',
                 'crowdllama_moe_assignments_total{held="no"} 35'):
        assert line in lines, line


def test_weight_layout_gauge_lint():
    """crowdllama_weight_layout{leaf,layout}: one gauge family, an info
    series a leaf (value 1) with the layout the placed array reported —
    both label values — and the ``none`` row at 0 before any engine."""
    from crowdllama_tpu.obs.metrics import EngineTelemetry

    tele = EngineTelemetry()
    lines = tele.expose()
    assert _lint("\n".join(lines))["crowdllama_weight_layout"] == "gauge"
    assert 'crowdllama_weight_layout{leaf="none",layout="none"} 0' in lines
    tele.weight_layouts_set({"wq": "input_minor", "wk": "default"})
    lines = tele.expose()
    _lint("\n".join(lines))
    for series in ('crowdllama_weight_layout{leaf="wq",layout="input_minor"}',
                   'crowdllama_weight_layout{leaf="wk",layout="default"}'):
        assert f"{series} 1" in lines
    assert sum(ln.startswith("crowdllama_weight_layout{")
               for ln in lines) == 2


def test_latent_cache_row_width_gauge_lint():
    """crowdllama_latent_cache_row_width{part}: one gauge family beside
    crowdllama_latent_cache_bytes — the entries a token's latent row fills
    (``row``) and the zero columns that round it up to whole lanes
    (``pad``); both 0 from boot and for a runner without a latent pool."""
    from crowdllama_tpu.obs.metrics import EngineTelemetry

    tele = EngineTelemetry()
    lines = tele.expose()
    types = _lint("\n".join(lines))
    assert types["crowdllama_latent_cache_row_width"] == "gauge"
    assert types["crowdllama_latent_cache_bytes"] == "gauge"
    for part in ("row", "pad"):
        assert f'crowdllama_latent_cache_row_width{{part="{part}"}} 0' in lines
    pool = 7 * 385 * 128 * 640 * 2
    tele.state_bytes_set({"latent_cache": pool}, latent_row=(576, 64))
    lines = tele.expose()
    _lint("\n".join(lines))
    assert f"crowdllama_latent_cache_bytes {pool}" in lines
    assert 'crowdllama_latent_cache_row_width{part="row"} 576' in lines
    assert 'crowdllama_latent_cache_row_width{part="pad"} 64' in lines
    tele.state_bytes_set({"kv_pool": 1 << 20})      # the next runner's
    lines = tele.expose()
    assert "crowdllama_latent_cache_bytes 0" in lines
    assert 'crowdllama_latent_cache_row_width{part="row"} 0' in lines


def test_startup_phases_lint():
    """crowdllama_startup_seconds{phase}: every phase rendered from boot;
    ``process`` counts from the operating system's record of the process's
    start, which lies before the import that ``ready`` counts from."""
    import time

    from crowdllama_tpu.obs.metrics import (
        ENGINE_TELEMETRY, STARTUP_PHASES, EngineTelemetry,
        process_age_seconds)

    assert STARTUP_PHASES == ("weights", "warmup", "ready", "process")
    tele = EngineTelemetry()
    lines = tele.expose()
    assert _lint("\n".join(lines))["crowdllama_startup_seconds"] == "gauge"
    for phase in STARTUP_PHASES:
        assert f'crowdllama_startup_seconds{{phase="{phase}"}} 0.000' in lines
    age = process_age_seconds()
    since_import = time.monotonic() - ENGINE_TELEMETRY.t_import
    # the kernel's clock ticks a hundred times a second
    assert age is not None and since_import - 0.02 <= age < 86400
    tele.startup_set("process", age)
    assert (f'crowdllama_startup_seconds{{phase="process"}} {age:.3f}'
            in tele.expose())


def test_spec_gauges_lint():
    """The adaptive-speculation gauges (scheduler.telemetry_gauges) render
    as lint-clean crowdllama_engine_* families — the exact lines both
    /metrics surfaces emit for a spec-decode worker."""
    import jax
    import jax.numpy as jnp

    from crowdllama_tpu.engine.scheduler import Scheduler
    from crowdllama_tpu.engine.spec import SpecModelRunner
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.obs.metrics import engine_gauge_lines

    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = SpecModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                           dtype=jnp.float32, draft_len=4)
    sched = Scheduler(spec, spec_draft_max=8)
    types = _lint("\n".join(engine_gauge_lines(sched.telemetry_gauges())))
    for g in ("spec_steps", "spec_emitted", "spec_accept_echo",
              "spec_accept_gen", "spec_draft_len"):
        assert types.get(f"crowdllama_engine_{g}") == "gauge", g


def test_host_dispatch_gauges_lint():
    """The host-dispatch accounting pair (scheduler.telemetry_gauges)
    renders lint-clean: host_dispatches_total as a counter (monotone,
    `_total`-suffixed), tokens_per_dispatch as a gauge."""
    import asyncio

    from crowdllama_tpu.engine.scheduler import Scheduler
    from crowdllama_tpu.obs.metrics import engine_gauge_lines

    class _Runner:  # gauge rendering needs no device work
        max_slots = 2
        max_seq = 128

    sched = Scheduler.__new__(Scheduler)
    sched.runner = _Runner()
    sched.slots = [None, None]
    sched.pending = asyncio.Queue()
    sched._deferred = []
    sched._admitting = 0
    sched._chunking = None
    sched.host_dispatches = 17
    sched._tokens_per_dispatch = 6.0
    types = _lint("\n".join(engine_gauge_lines(sched.telemetry_gauges())))
    assert types.get(
        "crowdllama_engine_host_dispatches_total") == "counter"
    assert types.get(
        "crowdllama_engine_tokens_per_dispatch") == "gauge"


def test_multi_engine_fans_out_obs_to_children():
    """Assigning `engine.obs` (peer.py does this at construction) must
    reach the child engines — they do the serving, so a container-only
    handle means kv_ship/replayed_prefill/migrated_slots counters stay
    zero on every multi-model CLI worker."""
    from crowdllama_tpu.engine.multi import MultiEngine

    class _Child:
        obs = None

    me = MultiEngine.__new__(MultiEngine)
    me._engines = {"a": _Child(), "b": _Child()}
    me._obs = None
    sentinel = object()
    me.obs = sentinel
    assert me.obs is sentinel
    assert all(e.obs is sentinel for e in me._engines.values())


def test_multi_engine_forwards_spec_gauges():
    """MultiEngine (the CLI's engine container, even for one model) must
    FORWARD child scheduler gauges to the worker /metrics surface —
    counters summed, point-in-time gauges (occupancy/utilization/
    spec_draft_len) maxed — or every worker scrapes zeros and the spec
    telemetry never leaves the process."""
    from crowdllama_tpu.engine.multi import MultiEngine
    from crowdllama_tpu.obs.metrics import engine_gauge_lines

    class _Child:
        def __init__(self, g):
            self._g = g

        def obs_gauges(self):
            return dict(self._g)

    me = MultiEngine.__new__(MultiEngine)
    me._engines = {
        "a": _Child({"pending_depth": 1.0, "batch_occupancy": 0.5,
                     "kv_cache_utilization": 0.125, "spec_draft_len": 2.0,
                     "spec_steps": 10.0, "spec_accept_gen": 7.0}),
        "b": _Child({"pending_depth": 2.0, "batch_occupancy": 0.25,
                     "kv_cache_utilization": 0.5, "spec_draft_len": 3.0,
                     "spec_steps": 4.0, "spec_accept_gen": 1.0}),
    }
    g = me.obs_gauges()
    assert g["pending_depth"] == 3.0          # counters sum
    assert g["spec_steps"] == 14.0
    assert g["spec_accept_gen"] == 8.0
    assert g["batch_occupancy"] == 0.5        # point-in-time gauges max
    assert g["kv_cache_utilization"] == 0.5
    assert g["spec_draft_len"] == 3.0
    _lint("\n".join(engine_gauge_lines(g)))
