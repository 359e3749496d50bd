"""Worker-side observability HTTP server: /metrics, /debug/trace, /drain
and the profiler control (POST /debug/profile/start|stop).

Workers have no consumer-facing HTTP surface (that is the gateway's job),
but the tracing plane needs every node scrapeable: :class:`ObsServer` is a
minimal aiohttp listener serving the same metric families as the gateway
(``crowdllama_request_seconds`` / ``crowdllama_ttft_seconds`` /
``crowdllama_decode_step_seconds`` + engine gauges + host stream counters)
and the node's trace ring buffer as JSON.

Enabled via ``--worker-metrics-port`` (0 = disabled, the default; tests
pass ``port=0`` explicitly through ``ObsServer`` to bind an ephemeral
port).
"""

from __future__ import annotations

import logging

from aiohttp import web

from crowdllama_tpu.engine.engine import ProfileBusy, ProfileDisabled
from crowdllama_tpu.obs.metrics import (
    ENGINE_TELEMETRY,
    device_memory_lines,
    engine_gauge_lines,
)

log = logging.getLogger("crowdllama.obs")


def native_metric_lines() -> list[str]:
    """Native data-plane health (docs/NATIVE.md): a gauge for whether the
    C++ fast path is active in this process, plus a per-component counter
    of every degradation to the pure-Python path.  A fleet where
    ``crowdllama_native_enabled`` is 0 (or fallbacks are climbing) is
    silently paying ~an order of magnitude more CPU per request — these
    series make that visible instead of a mystery regression."""
    from crowdllama_tpu import native

    st = native.stats()
    lines = [
        "# TYPE crowdllama_native_enabled gauge",
        f"crowdllama_native_enabled {1 if st['enabled'] else 0}",
        "# TYPE crowdllama_native_fallbacks_total counter",
    ]
    # Always-present component labels so dashboards can rate() without
    # sparse-series gaps; extra components recorded at runtime still show.
    components = {"aead": 0, "envelope": 0, "frame_scan": 0}
    components.update(st["fallbacks"])
    for comp, v in sorted(components.items()):
        lines.append(
            f'crowdllama_native_fallbacks_total{{component="{comp}"}} {v}')
    return lines


def host_stat_lines(host) -> list[str]:
    """Host stream-path counters, identical series on gateway and worker."""
    lines = ["# TYPE crowdllama_host_streams_total counter"]
    for k, v in sorted(host.stats.items()):
        if k.startswith("streams_"):
            lines.append(f'crowdllama_host_streams_total{{kind="{k}"}} {v}')
    lines.append("# TYPE crowdllama_host_rejected_total counter")
    lines.append(
        f"crowdllama_host_rejected_total {host.stats.get('rejected', 0)}")
    lines.append("# TYPE crowdllama_host_handshake_seconds_total counter")
    lines.append(
        f"crowdllama_host_handshake_seconds_total "
        f"{host.stats.get('handshake_ns', 0) / 1e9:.6f}")
    # Dial-ladder outcomes (docs/OBSERVABILITY.md): one counter per
    # (rung, outcome) the connect path attempted — direct, then the relay
    # escalation ladder (reverse / punch / splice).  Always present at
    # zero for the rungs a node never climbs, so dashboards can rate()
    # without sparse-series gaps.
    lines.append("# TYPE crowdllama_dial_ladder_attempts_total counter")
    ladder = getattr(host, "dial_ladder", {})
    for rung in ("direct", "reverse", "punch", "splice"):
        for outcome in ("ok", "fail"):
            v = ladder.get((rung, outcome), 0)
            lines.append(
                f'crowdllama_dial_ladder_attempts_total'
                f'{{rung="{rung}",outcome="{outcome}"}} {v}')
    return lines


def node_metric_lines(peer) -> list[str]:
    """The full worker-side exposition — the exact lines ObsServer's
    /metrics serves AND the payload a MetricsSnapshot carries over the p2p
    plane (docs/OBSERVABILITY.md swarm observatory): one composition, so
    the two scrape surfaces cannot drift."""
    obs = peer.obs
    lines = obs.metrics.expose()
    engine = getattr(peer, "engine", None)
    if engine is not None:
        try:
            lines.extend(engine_gauge_lines(engine.obs_gauges()))
        except Exception as e:  # a sick engine must not break the scrape
            log.debug("engine gauges unavailable: %s", e)
    # XLA compile/padding telemetry + device memory (PR 8): process
    # singletons, real numbers on the node that actually compiles.
    lines.extend(ENGINE_TELEMETRY.expose())
    lines.extend(device_memory_lines(getattr(engine, "on_device", False)))
    lines.extend(host_stat_lines(peer.host))
    lines.extend(native_metric_lines())
    return lines


class ObsServer:
    """Per-worker metrics/trace endpoint, mirroring the gateway's."""

    def __init__(self, peer, host: str = "127.0.0.1", port: int = 0,
                 sock=None) -> None:
        self.peer = peer
        self.host = host
        self.port = port
        # A socket already bound to host:port (net/host.py
        # ``bind_listener``), which ``start`` serves on; None: bind there.
        self._sock = sock
        self._runner: web.AppRunner | None = None
        self.app = web.Application()
        self.app.router.add_get("/metrics", self.handle_metrics)
        self.app.router.add_get("/debug/trace", self.handle_trace)
        # Operator drain hook (docs/ROBUSTNESS.md): same graceful path as
        # SIGTERM, for orchestrators that reach workers over HTTP (e.g.
        # a preStop hook) instead of signaling the process.
        self.app.router.add_post("/drain", self.handle_drain)
        # The profiler control: only this process — the one that holds the
        # chip — can trace it (the gateway maps no jaxlib at all).
        self.app.router.add_post("/debug/profile/start",
                                 self.handle_profile_start)
        self.app.router.add_post("/debug/profile/stop",
                                 self.handle_profile_stop)

    async def start(self) -> None:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = (web.SockSite(self._runner, self._sock)
                if self._sock is not None
                else web.TCPSite(self._runner, self.host, self.port))
        await site.start()
        # Resolve the bound port (port=0 binds ephemeral).
        self.port = self._runner.addresses[0][1]
        log.info("worker obs endpoint on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def handle_metrics(self, request: web.Request) -> web.Response:
        return web.Response(
            text="\n".join(node_metric_lines(self.peer)) + "\n",
            content_type="text/plain")

    async def handle_trace(self, request: web.Request) -> web.Response:
        """``?trace_id=`` filters to one trace, ``?limit=N`` keeps the N
        newest records (PR 8 satellite — same contract as the gateway's)."""
        try:
            limit = max(0, int(request.query.get("limit", "0") or 0))
        except ValueError:
            limit = 0
        return web.json_response(self.peer.obs.trace.snapshot(
            trace_id=request.query.get("trace_id", ""), limit=limit))

    async def handle_drain(self, request: web.Request) -> web.Response:
        drain = getattr(self.peer, "drain", None)
        if drain is None:
            return web.json_response(
                {"error": "peer does not support drain"}, status=501)
        already = bool(getattr(self.peer, "_draining", False))
        migrated = await drain()
        return web.json_response({
            "draining": True,
            "already_draining": already,
            "migrated_streams": migrated,
        })

    async def _profile(self, action: str) -> web.Response:
        """``start`` / ``stop`` of the engine's profiler trace.  501 where
        the node cannot be traced (its engine runs no JAX program here, or
        no ``--profile-dir``), 409 against the single flight (a second
        start, a stop with nothing running).  The profiler calls run on a
        thread inside the engine, never on this loop."""
        engine = getattr(self.peer, "engine", None)
        control = getattr(engine, f"profile_{action}", None)
        if control is None or not getattr(engine, "on_device", False):
            return web.json_response(
                {"error": "this node's engine holds no device to trace"},
                status=501)
        try:
            return web.json_response(await control())
        except ProfileDisabled as e:
            return web.json_response({"error": str(e)}, status=501)
        except ProfileBusy as e:
            return web.json_response({"error": str(e)}, status=409)
        except Exception as e:
            log.exception("profiler %s failed", action)
            return web.json_response(
                {"error": f"profiler {action} failed: {e}"}, status=500)

    async def handle_profile_start(self, request: web.Request
                                   ) -> web.Response:
        return await self._profile("start")

    async def handle_profile_stop(self, request: web.Request) -> web.Response:
        return await self._profile("stop")
