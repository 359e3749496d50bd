"""Fixed-bucket histograms + label hygiene for the /metrics exposition.

Replaces the counters-only exposition of PR 1 with latency distributions:

- ``crowdllama_request_seconds``     end-to-end per request, labeled by model
- ``crowdllama_ttft_seconds``        time to first token
- ``crowdllama_decode_step_seconds`` per decode step

Both the gateway and the worker-side ObsServer render the same families
through :class:`NodeMetrics`, so a scraper sees one schema swarm-wide.

:class:`LabelGuard` is the generalized form of the gateway's path
allowlist: every labeled series (paths, model names, phase names) goes
through a guard so a client cannot mint unbounded series by varying a
request field (label-cardinality DoS on the scrape pipeline).
"""

from __future__ import annotations

import os
import re
import threading
import time
from typing import Iterable

# Bucket upper bounds in seconds.  Request/TTFT cover loopback FakeEngine
# (sub-ms) through big-model TPU prefill (tens of seconds); decode steps
# cover fused-kernel steps (sub-ms) through CPU-interpreted tiny models.
REQUEST_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0)
DECODE_STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                       0.05, 0.1, 0.25, 0.5, 1.0)
# XLA compile wall time per (program, bucket) first dispatch: CPU-jitted
# tiny test models compile in tens of ms, big-model TPU prefill programs in
# minutes.
COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 30.0, 60.0, 120.0)

_LABEL_VALUE_RE = re.compile(r"^[A-Za-z0-9_.:/\-]{1,64}$")

# The scheduler's decode dispatch classes (docs/OBSERVABILITY.md duty
# cycle): how a flight reached the device — plain per-step chunk,
# unified ragged step, or speculative verify; every per-class family
# renders one series per class from the first scrape.  No flight is a
# "megastep" any more: its series stay, constant 0, because
# benchmarks/chip/layer_metrics/step.decode_wall_ms.json sums
# crowdllama_engine_flight_{seconds,steps}_total over it by name and reads
# nothing if one is absent.  ROADMAP W0(h) removes that term, then this
# entry goes.
DISPATCH_CLASSES = ("plain", "megastep", "ragged", "spec")
# Phases of a worker's start that crowdllama_startup_seconds reports.
STARTUP_PHASES = ("weights", "warmup", "ready", "process")


def process_age_seconds() -> float | None:
    """Seconds since the operating system started this process: before the
    interpreter, every import and — under a launcher that asks JAX for its
    devices first — reaching the chip, none of which a clock started in
    Python can see.  From /proc (Linux); None where there is none."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command's closing parenthesis; the
            # 22nd of the line is the start, in ticks since boot
            ticks = int(f.read().rpartition(")")[2].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _fmt(v: float) -> str:
    """Exposition number format: integers without a trailing .0."""
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return repr(float(v))


class LabelGuard:
    """Bound the value space of one metric label.

    A value passes when it matches the explicit allowlist (if given) or,
    with no allowlist, when it looks like a sane identifier AND the number
    of distinct values seen so far is under ``max_values``.  Everything
    else collapses to ``fallback`` so series cardinality stays bounded no
    matter what strings arrive from the network.
    """

    def __init__(self, allowed: Iterable[str] | None = None,
                 max_values: int = 64, fallback: str = "other") -> None:
        self._allowed = frozenset(allowed) if allowed is not None else None
        self._max = max(1, int(max_values))
        self._fallback = fallback
        self._seen: set[str] = set()
        self._lock = threading.Lock()

    def value(self, raw: object) -> str:
        s = str(raw) if raw else ""
        if self._allowed is not None:
            return s if s in self._allowed else self._fallback
        if not _LABEL_VALUE_RE.match(s):
            return self._fallback
        with self._lock:
            if s not in self._seen:
                if len(self._seen) >= self._max:
                    return self._fallback
                self._seen.add(s)
        return s


class Histogram:
    """Fixed-bucket histogram, rendered cumulatively at exposition time.

    Observations may carry a trace_id *exemplar* — the last one lands on
    the bucket it fell into and, when exemplar rendering is enabled, is
    emitted in OpenMetrics syntax (`` # {trace_id="..."} <value>``) so a
    dashboard spike links straight to a stitched trace."""

    def __init__(self, buckets: Iterable[float]) -> None:
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("histogram needs at least one bucket")
        self._counts = [0] * (len(self.buckets) + 1)  # +1 = overflow (+Inf)
        self._exemplars: list[tuple[str, float] | None] = \
            [None] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float, exemplar: str = "") -> None:
        v = float(value)
        idx = len(self.buckets)
        for i, b in enumerate(self.buckets):
            if v <= b:
                idx = i
                break
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            if exemplar:
                self._exemplars[idx] = (exemplar, v)

    @property
    def count(self) -> int:
        with self._lock:
            return sum(self._counts)

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def snapshot_counts(self) -> list[int]:
        """Non-cumulative per-bucket counts (last = overflow); benchmarks
        diff two snapshots to get a per-window distribution."""
        with self._lock:
            return list(self._counts)

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile estimate (PromQL histogram_quantile
        semantics).  Benchmarks read their percentiles from here so the
        published number is the same one a dashboard would compute from
        the scraped series."""
        return quantile_from_counts(self.buckets, self.snapshot_counts(), q)

    def lines(self, name: str, labels: str = "",
              exemplars: bool = False) -> list[str]:
        """Series lines (no TYPE header) for one child of a family.

        ``labels`` is a pre-rendered ``key="value"`` list without braces.
        With ``exemplars`` each bucket that captured one gets the
        OpenMetrics exemplar suffix on its _bucket line.
        """
        with self._lock:
            counts = list(self._counts)
            exs = list(self._exemplars)
            total_sum = self._sum
        sep = "," if labels else ""

        def _ex(i: int) -> str:
            if not exemplars or exs[i] is None:
                return ""
            tid, v = exs[i]
            return f' # {{trace_id="{tid}"}} {_fmt(v)}'

        out: list[str] = []
        cum = 0
        for i, (b, c) in enumerate(zip(self.buckets, counts)):
            cum += c
            out.append(f'{name}_bucket{{{labels}{sep}le="{_fmt(b)}"}} '
                       f'{cum}{_ex(i)}')
        cum += counts[-1]
        out.append(f'{name}_bucket{{{labels}{sep}le="+Inf"}} '
                   f'{cum}{_ex(len(counts) - 1)}')
        out.append(f"{name}_sum{{{labels}}} {_fmt(total_sum)}"
                   if labels else f"{name}_sum {_fmt(total_sum)}")
        out.append(f"{name}_count{{{labels}}} {cum}"
                   if labels else f"{name}_count {cum}")
        return out


def quantile_from_counts(buckets: tuple[float, ...], counts: list[int],
                         q: float) -> float:
    """Quantile of a (buckets, non-cumulative counts) pair: linear
    interpolation inside the bucket, the overflow bucket clamps to the
    highest finite bound.  Counts may be a DELTA of two snapshots."""
    q = min(1.0, max(0.0, float(q)))
    total = sum(counts)
    if total == 0:
        return 0.0
    rank = q * total
    cum = 0
    lo = 0.0
    for b, c in zip(buckets, counts):
        cum += c
        if cum >= rank:
            if c == 0:
                return b
            return lo + (b - lo) * (1 - (cum - rank) / c)
        lo = b
    return buckets[-1]


class HistogramVec:
    """Histogram family keyed by one guarded label."""

    def __init__(self, buckets: Iterable[float], label: str,
                 guard: LabelGuard | None = None) -> None:
        self._buckets = tuple(buckets)
        self._label = label
        self._guard = guard or LabelGuard(max_values=32)
        self._children: dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def labels(self, value: object) -> Histogram:
        key = self._guard.value(value)
        with self._lock:
            h = self._children.get(key)
            if h is None:
                h = Histogram(self._buckets)
                self._children[key] = h
            return h

    def expose(self, name: str, exemplars: bool = False) -> list[str]:
        out = [f"# TYPE {name} histogram"]
        with self._lock:
            children = sorted(self._children.items())
        for key, h in children:
            out.extend(h.lines(name, f'{self._label}="{key}"',
                               exemplars=exemplars))
        return out


class NodeMetrics:
    """The three per-node histogram families, one instance per node."""

    def __init__(self, exemplars: bool = False) -> None:
        # OpenMetrics trace_id exemplars on the request-path histograms
        # (--metrics-exemplars): off by default — classic Prometheus text
        # parsers reject the suffix.
        self.exemplars = bool(exemplars)
        self.model_guard = LabelGuard(max_values=32)
        self.request_seconds = HistogramVec(
            REQUEST_BUCKETS, "model", self.model_guard)
        self.ttft_seconds = Histogram(TTFT_BUCKETS)
        self.decode_step_seconds = Histogram(DECODE_STEP_BUCKETS)
        # KV shipping (docs/KV_TRANSFER.md): fetch latency observed by the
        # fetching worker; bytes/fetches/fallbacks count page traffic on
        # whichever side moved it (a donor's exports land in the same
        # families).  Part of NodeMetrics so every node — gateway included —
        # exposes the series at zero rather than absent.
        self.kv_fetch_seconds = Histogram(TTFT_BUCKETS)
        self.kv_ship = {"bytes": 0, "fetches": 0, "fallbacks": 0,
                        "retries": 0}
        # Graceful drain + live migration (docs/ROBUSTNESS.md): drain_*
        # count control-plane events on the node that drained; the two
        # flat families count the request plane's view of migration —
        # migrated_streams on whichever side moved a stream (the gateway
        # re-routing it, the worker handing it off),
        # replayed_prefill_tokens on the successor worker: prompt tokens a
        # migrate-flagged request recomputed even though the donor could
        # have served them (0 == the KV handoff was complete).
        self.drain = {"initiated": 0, "migrated_slots": 0,
                      "rejected_requests": 0}
        self.migrated_streams = 0
        self.replayed_prefill_tokens = 0
        # Replicated gateway plane (docs/ROBUSTNESS.md "replicated
        # gateway"): gossip anti-entropy traffic + LWW map health, and
        # per-tenant admission outcomes.  In NodeMetrics (not gateway-only
        # state) so both scrape surfaces — gateway /metrics and the
        # worker-side ObsServer — expose the families at zero.
        self.gossip = {"frames_sent": 0, "frames_received": 0,
                       "entries_applied": 0, "entries_stale": 0,
                       "full_syncs": 0, "send_failures": 0,
                       "snapshot_saves": 0,
                       # gauges
                       "map_entries": 0, "snapshot_entries_loaded": 0}
        self.tenant_guard = LabelGuard(max_values=32)
        self.tenant_admitted: dict[str, int] = {}
        self.tenant_shed: dict[str, int] = {}
        self.tenant_inflight: dict[str, int] = {}

    def kv_ship_inc(self, key: str, n: int = 1) -> None:
        self.kv_ship[key] = self.kv_ship.get(key, 0) + int(n)

    def drain_inc(self, key: str, n: int = 1) -> None:
        self.drain[key] = self.drain.get(key, 0) + int(n)

    def gossip_inc(self, key: str, n: int = 1) -> None:
        self.gossip[key] = self.gossip.get(key, 0) + int(n)

    def tenant_inc(self, family: dict, tenant: str, n: int = 1) -> None:
        key = self.tenant_guard.value(tenant or "default")
        family[key] = family.get(key, 0) + int(n)

    def expose(self) -> list[str]:
        ex = self.exemplars
        out = self.request_seconds.expose("crowdllama_request_seconds",
                                          exemplars=ex)
        out.append("# TYPE crowdllama_ttft_seconds histogram")
        out.extend(self.ttft_seconds.lines("crowdllama_ttft_seconds",
                                           exemplars=ex))
        out.append("# TYPE crowdllama_decode_step_seconds histogram")
        out.extend(self.decode_step_seconds.lines(
            "crowdllama_decode_step_seconds", exemplars=ex))
        for key in ("bytes", "fetches", "fallbacks", "retries"):
            name = f"crowdllama_kv_ship_{key}_total"
            out.append(f"# TYPE {name} counter")
            out.append(f"{name} {self.kv_ship.get(key, 0)}")
        out.append("# TYPE crowdllama_kv_fetch_seconds histogram")
        out.extend(self.kv_fetch_seconds.lines("crowdllama_kv_fetch_seconds"))
        for key in ("initiated", "migrated_slots", "rejected_requests"):
            name = f"crowdllama_drain_{key}_total"
            out.append(f"# TYPE {name} counter")
            out.append(f"{name} {self.drain.get(key, 0)}")
        out.append("# TYPE crowdllama_migrated_streams_total counter")
        out.append(f"crowdllama_migrated_streams_total "
                   f"{self.migrated_streams}")
        out.append("# TYPE crowdllama_replayed_prefill_tokens_total counter")
        out.append(f"crowdllama_replayed_prefill_tokens_total "
                   f"{self.replayed_prefill_tokens}")
        for key in ("frames_sent", "frames_received", "entries_applied",
                    "entries_stale", "full_syncs", "send_failures",
                    "snapshot_saves"):
            name = f"crowdllama_gossip_{key}_total"
            out.append(f"# TYPE {name} counter")
            out.append(f"{name} {self.gossip.get(key, 0)}")
        for key in ("map_entries", "snapshot_entries_loaded"):
            name = f"crowdllama_gossip_{key}"
            out.append(f"# TYPE {name} gauge")
            out.append(f"{name} {self.gossip.get(key, 0)}")
        for fam, kind, series in (
            ("crowdllama_tenant_admitted_total", "counter",
             self.tenant_admitted),
            ("crowdllama_tenant_shed_total", "counter", self.tenant_shed),
            ("crowdllama_tenant_inflight", "gauge", self.tenant_inflight),
        ):
            out.append(f"# TYPE {fam} {kind}")
            if not series:
                out.append(f'{fam}{{tenant="default"}} 0')
            for tenant in sorted(series):
                out.append(f'{fam}{{tenant="{tenant}"}} {series[tenant]}')
        return out


def engine_gauge_lines(gauges: dict) -> list[str]:
    """Render Engine.obs_gauges() as crowdllama_engine_* series.

    Keys are gauges except ``*_total``, which declare as counters (the
    Prometheus suffix convention — e.g. host_dispatches_total counts
    device programs launched and only ever grows).  A ``base|label=value``
    key renders as a labeled child of the ``base`` family (one TYPE line
    per family) — the duty-cycle gauges use this to keep one family
    across the dispatch classes."""
    out: list[str] = []
    typed: set[str] = set()
    for key in sorted(gauges):
        try:
            val = float(gauges[key])
        except (TypeError, ValueError):
            continue
        base, _, label = key.partition("|")
        name = f"crowdllama_engine_{base}"
        kind = "counter" if base.endswith("_total") else "gauge"
        if name not in typed:
            typed.add(name)
            out.append(f"# TYPE {name} {kind}")
        if label:
            lname, _, lval = label.partition("=")
            out.append(f'{name}{{{lname}="{lval}"}} {_fmt(val)}')
        else:
            out.append(f"{name} {_fmt(val)}")
    return out


class EngineTelemetry:
    """Process-wide XLA compile + padding accounting (PR 8 tentpole).

    Module-level (like net/secure's aead counters) rather than hung off
    NodeObs: the runners compile during engine construction and warmup,
    BEFORE the peer wires ``engine.obs`` — a per-node object would miss
    exactly the compiles the operator most wants to see.  Thread-safe:
    the scheduler's jax-dispatch thread records while the event loop
    scrapes.

    Compile detection is first-dispatch timing: the first call of a jitted
    program per static signature (program name + bucket) pays trace +
    lower + XLA compile synchronously, so its wall time IS the compile
    cost to within one dispatch — deterministic, backend-agnostic, and
    exactly the recompile-storm signal (a retuned spec draft_len or an
    unexpected prefill bucket shows up as a new (program, bucket) count).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compile_seconds = Histogram(COMPILE_BUCKETS)
        self.program_guard = LabelGuard(max_values=64)
        self.bucket_guard = LabelGuard(max_values=256)
        self._compiles: dict[tuple[str, str], int] = {}
        self._seen: set[tuple[str, str]] = set()
        # Cached-hit witness: dispatches whose (program, bucket)
        # signature was already claimed — the proof that a chunk size
        # seen before costs no recompile.  Keyed by
        # program only: the interesting fact is "this entry point reused
        # a signature", not which bucket did.
        self._cache_hits: dict[str, int] = {}
        self._padding = {"waste": 0, "useful": 0}
        # Prefix-cache accounting at admission (the scheduler holds the
        # numbers): reused / prompt tokens is the share of prefill the
        # cache saved, hits counts admissions that reused anything.
        self._prefix = {"tokens_reused": 0, "hits": 0, "prompt_tokens": 0}
        # Per dispatch class, the wall time and decode steps of every
        # retired flight: seconds / steps is the wall time of one step.
        self._flight_seconds = {cls: 0.0 for cls in DISPATCH_CLASSES}
        self._flight_steps = {cls: 0 for cls in DISPATCH_CLASSES}
        # An expert layer that holds a share of its experts (models/
        # hybrid.py): token-expert rows it computed ("yes") and rows it left
        # to the ranks that hold their expert ("no"), counted on the device
        # and read back with each flight's tokens.
        self._moe_assignments = {"yes": 0, "no": 0}
        # The same layer's banks (one held expert's matrices in one layer
        # in one step), by the flight's dispatch class: those some row was
        # routed to, those none was, and those the grouped matmuls read
        # from HBM whichever — counted and read back the same way.
        self._moe_banks = {cls: {"routed": 0, "unrouted": 0, "fetched": 0}
                           for cls in DISPATCH_CLASSES}
        # Grid steps of the GQA decode attention kernel (ops/pallas/paged.py),
        # by the kind of layer ("full" pool | "window" ring): those its
        # calls walked — one a live (slot, page pair) — and those of the
        # slots x table rectangle they would have walked without the list;
        # booked at dispatch from the lengths the runner holds on the host.
        self._attn_grid_steps = {kind: {"live": 0, "rectangle": 0}
                                 for kind in ("full", "window")}
        # Bytes of each kind of per-request state the newest runner's
        # init_state allocated (engine/hybrid.py: KV pool, state-space
        # state, convolution tail).
        self._state_bytes: dict[str, int] = {}
        # Entries of one stored row of that runner's latent pool: those a
        # token's row [c ; k_rope] fills and the zero columns that round it
        # up to whole lanes (engine/paged.py pool_row_width); 0, 0 for a
        # runner without a latent pool.
        self._latent_row = {"row": 0, "pad": 0}
        # Seconds each phase of the start took ("ready": from the import
        # of this module, which the CLI does first, to the engine serving).
        self.t_import = time.monotonic()
        self._startup: dict[str, float] = {}
        # program -> "pallas" | "pallas_interpret" | "jnp": which attention
        # implementation the newest runner built in this process dispatches
        # (set at runner build; a refused kernel must be visible to a
        # scrape, not just to whoever reads the worker's log).
        self._attention_paths: dict[str, str] = {}
        # "int8_kernel" | "dequant_ragged_dot" | "ragged_dot": how the
        # newest engine's expert layers multiply their banks (ops/quant.py
        # qragged_dot; set at JaxEngine.start, "" without expert layers).
        self._moe_matmul_path = ""
        # "pallas" | "xla": how the newest engine's Mamba layers update the
        # carried state in a decode step (ops/ssm.py ssm_update_at; set at
        # JaxEngine.start, "" without Mamba layers).
        self._ssm_update_path = ""
        # The same for the KDA layers' delta-rule update ("pallas" | "xla":
        # ops/kda.py kda_update_at) and for what the decode attention reads
        # ("mla": one latent row a token, key and value both; "gqa": K and V
        # pools), "" where the model has neither.
        self._kda_update_path = ""
        self._attn_decode_path = ""
        # leaf ("wq", "wk") -> "input_minor" | "default": how the newest
        # engine's attention projections lie on the device, read from the
        # placed arrays (parallel/sharding.py placed_layouts; set at
        # JaxEngine.start).
        self._weight_layouts: dict[str, str] = {}
        # Decode duty-cycle profiler (PR 13, docs/OBSERVABILITY.md): the
        # host-side gap between one flight's retire and the next flight's
        # dispatch, per dispatch class.  Children pre-created so every
        # class renders a zero histogram from the first scrape (absent()-
        # style alerts, and the fixed allowlist IS the LabelGuard).
        self.host_gap_seconds = HistogramVec(
            DECODE_STEP_BUCKETS, "dispatch",
            LabelGuard(allowed=DISPATCH_CLASSES))
        for cls in DISPATCH_CLASSES:
            self.host_gap_seconds.labels(cls)
        # Admissions by where their first token was when the scheduler
        # placed them (Scheduler._place): "device" — prefill's scalar went
        # into the insert unread and the next flight was queued before the
        # host read anything; "host" — the runner handed back an int (the
        # chunked and ragged finishes, the multi-host wrapper).
        self._admissions = {"device": 0, "host": 0}
        # Retired decode flights by length (Scheduler._chunk_size): "short"
        # — fewer steps than decode_chunk, which outside spec probes and
        # gateway-paced rounds means a slot was free; "full" — decode_chunk
        # steps: every slot was taken.
        self._flights = {"short": 0, "full": 0}

    def _key(self, program: str, bucket: object) -> tuple[str, str]:
        return (self.program_guard.value(program),
                self.bucket_guard.value(str(bucket)))

    def compile_begin(self, program: str, bucket: object) -> float:
        """0.0 when (program, bucket) already dispatched; otherwise claim
        the signature and return a perf_counter() start for compile_end.
        The membership probe is the only cost on the steady-state path."""
        key = self._key(program, bucket)
        with self._lock:
            if key in self._seen:
                self._cache_hits[key[0]] = \
                    self._cache_hits.get(key[0], 0) + 1
                return 0.0
            self._seen.add(key)
        return time.perf_counter()

    def compile_end(self, program: str, bucket: object, t0: float) -> None:
        if not t0:
            return
        dt = max(0.0, time.perf_counter() - t0)
        key = self._key(program, bucket)
        with self._lock:
            self._compiles[key] = self._compiles.get(key, 0) + 1
        self.compile_seconds.observe(dt)

    def attention_paths_set(self, paths: dict[str, str]) -> None:
        with self._lock:
            self._attention_paths = dict(paths)

    def weight_layouts_set(self, layouts: dict[str, str]) -> None:
        with self._lock:
            self._weight_layouts = dict(layouts)

    def moe_matmul_path_set(self, path: str) -> None:
        with self._lock:
            self._moe_matmul_path = path

    def ssm_update_path_set(self, path: str) -> None:
        with self._lock:
            self._ssm_update_path = path

    def kda_update_path_set(self, path: str) -> None:
        with self._lock:
            self._kda_update_path = path

    def attn_decode_path_set(self, path: str) -> None:
        with self._lock:
            self._attn_decode_path = path

    def padding_inc(self, useful: int, waste: int) -> None:
        """Account one padded dispatch: ``useful`` real tokens rode it,
        ``waste`` were padding (bucket rounding, inactive decode slots)."""
        with self._lock:
            self._padding["useful"] += max(0, int(useful))
            self._padding["waste"] += max(0, int(waste))

    def flight_inc(self, cls: str, seconds: float, steps: int,
                   useful: int, waste: int, short: bool) -> None:
        """Account one retired decode flight under one lock: its wall time
        and steps under its dispatch class, its padding as
        :meth:`padding_inc` would, and its length (``short``: dispatched
        with fewer steps than decode_chunk)."""
        with self._lock:
            self._flights["short" if short else "full"] += 1
            self._flight_seconds[cls] += max(0.0, float(seconds))
            self._flight_steps[cls] += max(0, int(steps))
            self._padding["useful"] += max(0, int(useful))
            self._padding["waste"] += max(0, int(waste))

    def prefix_inc(self, prompt_tokens: int, tokens_reused: int,
                   hits: int) -> None:
        """Account one admission: its prompt tokens, and how many of them
        the prefix cache already held."""
        with self._lock:
            self._prefix["prompt_tokens"] += max(0, int(prompt_tokens))
            self._prefix["tokens_reused"] += max(0, int(tokens_reused))
            self._prefix["hits"] += max(0, int(hits))

    def admission_inc(self, first_token: str) -> None:
        with self._lock:
            self._admissions[first_token] += 1

    def moe_counts_inc(self, dispatch: str, counts) -> None:
        """One flight's expert-layer counts (models/hybrid.py ``COUNTS``),
        already on the host."""
        held, left_out, routed, fetched, banks = (
            max(0, int(c)) for c in counts)
        with self._lock:
            self._moe_assignments["yes"] += held
            self._moe_assignments["no"] += left_out
            by = self._moe_banks[dispatch]
            by["routed"] += routed
            by["unrouted"] += banks - routed
            by["fetched"] += fetched

    def attn_grid_steps_inc(self, kind: str, live: int,
                            rectangle: int) -> None:
        with self._lock:
            by = self._attn_grid_steps[kind]
            by["live"] += max(0, int(live))
            by["rectangle"] += max(0, int(rectangle))

    def state_bytes_set(self, by_kind: dict[str, int],
                        latent_row: tuple[int, int] = (0, 0)) -> None:
        with self._lock:
            self._state_bytes = {k: int(v) for k, v in by_kind.items()}
            self._latent_row = dict(zip(("row", "pad"), map(int, latent_row)))

    def startup_set(self, phase: str, seconds: float) -> None:
        with self._lock:
            self._startup[phase] = max(0.0, float(seconds))

    def snapshot_compiles(self) -> dict[tuple[str, str], int]:
        """(program, bucket) -> count; tests diff two snapshots to assert
        e.g. a draft_len retune added exactly one new decode bucket."""
        with self._lock:
            return dict(self._compiles)

    def snapshot_cache_hits(self) -> dict[str, int]:
        """program -> cached-signature dispatch count; the retune test
        diffs two snapshots to prove a dial revert recompiled nothing."""
        with self._lock:
            return dict(self._cache_hits)

    def padding_snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._padding)

    def expose(self) -> list[str]:
        out = ["# TYPE crowdllama_xla_compile_seconds histogram"]
        out.extend(self.compile_seconds.lines(
            "crowdllama_xla_compile_seconds"))
        with self._lock:
            compiles = sorted(self._compiles.items())
            padding = dict(self._padding)
            cache_hits = sorted(self._cache_hits.items())
            attention = sorted(self._attention_paths.items())
            moe_path = self._moe_matmul_path
            ssm_path = self._ssm_update_path
            kda_path = self._kda_update_path
            attn_decode = self._attn_decode_path
            weight_layouts = sorted(self._weight_layouts.items())
            prefix = dict(self._prefix)
            flight_seconds = dict(self._flight_seconds)
            flight_steps = dict(self._flight_steps)
            startup = dict(self._startup)
            moe = dict(self._moe_assignments)
            moe_banks = {cls: dict(by) for cls, by in self._moe_banks.items()}
            admissions = dict(self._admissions)
            flights = dict(self._flights)
            state_bytes = sorted(self._state_bytes.items())
            latent_row = dict(self._latent_row)
            grid_steps = {kind: dict(by)
                          for kind, by in self._attn_grid_steps.items()}
        out.append("# TYPE crowdllama_engine_attention_path gauge")
        if not attention:
            out.append('crowdllama_engine_attention_path{program="none",'
                       'path="none"} 0')
        for program, path in attention:
            out.append(f'crowdllama_engine_attention_path{{'
                       f'program="{program}",path="{path}"}} 1')
        out.append("# TYPE crowdllama_moe_matmul_path gauge")
        out.append(f'crowdllama_moe_matmul_path{{path="{moe_path or "none"}"'
                   f'}} {1 if moe_path else 0}')
        out.append("# TYPE crowdllama_ssm_update_path gauge")
        out.append(f'crowdllama_ssm_update_path{{path="{ssm_path or "none"}"'
                   f'}} {1 if ssm_path else 0}')
        out.append("# TYPE crowdllama_kda_update_path gauge")
        out.append(f'crowdllama_kda_update_path{{path="{kda_path or "none"}"'
                   f'}} {1 if kda_path else 0}')
        out.append("# TYPE crowdllama_attn_decode_path gauge")
        out.append(f'crowdllama_attn_decode_path{{path="'
                   f'{attn_decode or "none"}"}} {1 if attn_decode else 0}')
        out.append("# TYPE crowdllama_weight_layout gauge")
        if not weight_layouts:
            out.append('crowdllama_weight_layout{leaf="none",layout="none"} 0')
        for leaf, layout in weight_layouts:
            out.append(f'crowdllama_weight_layout{{leaf="{leaf}",'
                       f'layout="{layout}"}} 1')
        out.append("# TYPE crowdllama_xla_compiles_total counter")
        if not compiles:
            out.append('crowdllama_xla_compiles_total{program="none",'
                       'bucket="0"} 0')
        for (program, bucket), n in compiles:
            out.append(f'crowdllama_xla_compiles_total{{'
                       f'program="{program}",bucket="{bucket}"}} {n}')
        # Cached-hit witness: signature reuse per jit entry point — a
        # chunk size seen before shows up here instead of as a new
        # crowdllama_xla_compiles_total child.
        out.append("# TYPE crowdllama_xla_compile_cache_hits_total counter")
        if not cache_hits:
            out.append('crowdllama_xla_compile_cache_hits_total{'
                       'program="none"} 0')
        for program, n in cache_hits:
            out.append(f'crowdllama_xla_compile_cache_hits_total{{'
                       f'program="{program}"}} {n}')
        out.append("# TYPE crowdllama_padding_waste_tokens_total counter")
        out.append(f"crowdllama_padding_waste_tokens_total "
                   f"{padding['waste']}")
        out.append("# TYPE crowdllama_useful_tokens_total counter")
        out.append(f"crowdllama_useful_tokens_total {padding['useful']}")
        out.append("# TYPE crowdllama_prefix_tokens_reused_total counter")
        out.append(f"crowdllama_prefix_tokens_reused_total "
                   f"{prefix['tokens_reused']}")
        out.append("# TYPE crowdllama_prefix_hits_total counter")
        out.append(f"crowdllama_prefix_hits_total {prefix['hits']}")
        out.append("# TYPE crowdllama_prompt_tokens_total counter")
        out.append(f"crowdllama_prompt_tokens_total "
                   f"{prefix['prompt_tokens']}")
        out.append("# TYPE crowdllama_engine_flight_seconds_total counter")
        for cls in DISPATCH_CLASSES:
            out.append(f'crowdllama_engine_flight_seconds_total{{'
                       f'dispatch="{cls}"}} {flight_seconds[cls]:.6f}')
        out.append("# TYPE crowdllama_engine_flight_steps_total counter")
        for cls in DISPATCH_CLASSES:
            out.append(f'crowdllama_engine_flight_steps_total{{'
                       f'dispatch="{cls}"}} {flight_steps[cls]}')
        out.append("# TYPE crowdllama_moe_assignments_total counter")
        for held, n in moe.items():
            out.append(f'crowdllama_moe_assignments_total{{held="{held}"}} '
                       f'{n}')
        out.append("# TYPE crowdllama_moe_banks_total counter")
        for cls in DISPATCH_CLASSES:
            for state in ("routed", "unrouted"):
                out.append(f'crowdllama_moe_banks_total{{dispatch="{cls}",'
                           f'state="{state}"}} {moe_banks[cls][state]}')
        out.append("# TYPE crowdllama_moe_banks_fetched_total counter")
        for cls in DISPATCH_CLASSES:
            out.append(f'crowdllama_moe_banks_fetched_total{{dispatch="{cls}"'
                       f'}} {moe_banks[cls]["fetched"]}')
        out.append("# TYPE crowdllama_attn_grid_steps_total counter")
        for kind, by in grid_steps.items():
            for walk, n in by.items():
                out.append(f'crowdllama_attn_grid_steps_total{{kind="{kind}",'
                           f'walk="{walk}"}} {n}')
        out.append("# TYPE crowdllama_engine_state_bytes gauge")
        if not state_bytes:
            out.append('crowdllama_engine_state_bytes{kind="none"} 0')
        for kind, n in state_bytes:
            out.append(f'crowdllama_engine_state_bytes{{kind="{kind}"}} {n}')
        # what the slots' state costs beside the KV: the recurrent layers'
        # (kind ssm | kda | conv), and a latent cache's rows
        out.append("# TYPE crowdllama_recurrent_state_bytes gauge")
        recurrent = [(k, n) for k, n in state_bytes
                     if k in ("ssm", "kda", "conv")]
        for kind, n in recurrent or [("none", 0)]:
            out.append(f'crowdllama_recurrent_state_bytes{{kind="{kind}"}} '
                       f'{n}')
        out.append("# TYPE crowdllama_latent_cache_bytes gauge")
        out.append(f"crowdllama_latent_cache_bytes "
                   f"{dict(state_bytes).get('latent_cache', 0)}")
        out.append("# TYPE crowdllama_latent_cache_row_width gauge")
        for part, n in latent_row.items():
            out.append(f'crowdllama_latent_cache_row_width{{part="{part}"}} '
                       f'{n}')
        out.append("# TYPE crowdllama_startup_seconds gauge")
        for phase in STARTUP_PHASES:
            out.append(f'crowdllama_startup_seconds{{phase="{phase}"}} '
                       f'{startup.get(phase, 0.0):.3f}')
        out.extend(self.host_gap_seconds.expose(
            "crowdllama_host_gap_seconds"))
        out.append("# TYPE crowdllama_admissions_total counter")
        for where, n in admissions.items():
            out.append(f'crowdllama_admissions_total{{first_token="{where}"'
                       f'}} {n}')
        out.append("# TYPE crowdllama_engine_flights_total counter")
        for length, n in flights.items():
            out.append(f'crowdllama_engine_flights_total{{length="{length}"'
                       f'}} {n}')
        return out


# The process-wide engine profiling plane; runners and schedulers record
# into it directly, both scrape surfaces render it.
ENGINE_TELEMETRY = EngineTelemetry()


def device_memory_lines(on_device: bool) -> list[str]:
    """Per-device memory gauges from jax.local_devices()[*].memory_stats(),
    sampled at scrape time.  Only a node whose engine runs on the device
    (``Engine.on_device``) asks the runtime — a scrape must not make a
    gateway or DHT process initialize a backend and take the chip from the
    worker beside it.  Everyone else, and platforms without the API (CPU),
    report zeros: the series must exist for absent()-style alerts either
    way."""
    per_device: list[dict] = []
    if on_device:
        import jax

        # memory_stats() is None on platforms without the API (CPU).
        per_device = [d.memory_stats() or {} for d in jax.local_devices()]
    out = ["# TYPE crowdllama_device_memory_bytes_in_use gauge",
           "# TYPE crowdllama_device_memory_peak_bytes_in_use gauge",
           "# TYPE crowdllama_device_memory_bytes_limit gauge"]
    for i, stats in enumerate(per_device or [{}]):
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        for family, value in (("bytes_in_use", stats.get("bytes_in_use")),
                              ("peak_bytes_in_use",
                               stats.get("peak_bytes_in_use")),
                              ("bytes_limit", limit)):
            out.append(f'crowdllama_device_memory_{family}{{device="{i}"}} '
                       f'{int(value or 0)}')
    return out
