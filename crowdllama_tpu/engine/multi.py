"""MultiEngine: one worker serving several models (Ollama-style).

The reference's workers advertise a *list* of supported models because
Ollama hosts many; a single-model JAX engine would under-serve that
surface.  ``MultiEngine`` runs one child ``JaxEngine`` per model name
(``--model a,b,c``) behind the same ``Engine`` seam and routes each
request by its ``model`` field.  Children share the device: their
schedulers' dispatch threads interleave at the device queue, so serving
stays single-flight per child while models multiplex the chip.
"""

from __future__ import annotations

import asyncio
import logging
from dataclasses import replace as _dc_replace
from typing import AsyncIterator

from crowdllama_tpu.engine.engine import Chunk, Engine, JaxEngine

log = logging.getLogger("crowdllama.engine.multi")


class MultiEngine(Engine):
    supports_kv_donor = True
    on_device = True

    def __init__(self, config):
        self.config = config
        names = [m.strip() for m in config.model.split(",") if m.strip()]
        if not names:
            raise ValueError("MultiEngine needs >= 1 model name")
        self._engines: dict[str, JaxEngine] = {}
        for i, name in enumerate(names):
            # model_path names ONE checkpoint: it belongs to the first
            # listed model only — later children random-init rather than
            # silently loading (and re-sharing) the wrong model's bytes.
            child_cfg = _dc_replace(config, model=name,
                                    model_path=config.model_path if i == 0
                                    else "")
            self._engines[name] = JaxEngine(child_cfg)
        self.models = names
        self._peer = None
        self._obs = None

    # The peer hands its NodeObs to `engine.obs`; the children do the
    # actual serving, so the handle must fan out or every child-side
    # counter (kv_ship, replayed_prefill, migrated_slots, fetch
    # latency) silently stays zero on multi-model CLI workers.
    @property
    def obs(self):
        return self._obs

    @obs.setter
    def obs(self, value) -> None:
        self._obs = value
        for eng in self._engines.values():
            eng.obs = value

    def _child(self, model: str) -> JaxEngine:
        if not model:
            # Single-model clients may omit the name; unambiguous only
            # when one child exists (guarded in __init__) — require it.
            raise ValueError(
                f"model is required (serving {sorted(self._engines)})")
        eng = self._engines.get(model)
        if eng is None:
            raise ValueError(
                f"model {model!r} not served (have {sorted(self._engines)})")
        return eng

    async def start(self) -> None:
        import jax

        if jax.process_count() > 1 and len(self._engines) > 1:
            # Each child would wrap its runner in a ReplicatedRunner and
            # interleave frame streams the single follower replay loop
            # (parallel/replicated.py) cannot represent — programmatic
            # twin of the CLI's --dist-coordinator shape check.  A
            # SINGLE-model container is fine: one child, one stream.
            raise ValueError(
                "multi-model workers do not compose with multi-host "
                "serving (one replicated engine per cluster)")
        # Sequential start: children compile on the same device; parallel
        # starts would interleave big compilations for no wall-clock win.
        for name, eng in self._engines.items():
            log.info("starting child engine for %s", name)
            await eng.start()

    async def stop(self) -> None:
        await asyncio.gather(*(e.stop() for e in self._engines.values()),
                             return_exceptions=True)

    async def drain(self, timeout: float = 30.0) -> bool:
        results = await asyncio.gather(
            *(e.drain(timeout) for e in self._engines.values()))
        return all(results)

    async def migrate(self) -> int:
        moved = await asyncio.gather(
            *(e.migrate() for e in self._engines.values()))
        return sum(moved)

    def attach_peer(self, peer) -> None:
        self._peer = peer
        for eng in self._engines.values():
            eng.attach_peer(peer)

    def model_dir(self, model: str) -> str | None:
        eng = self._engines.get(model)
        return eng.model_dir(model) if eng is not None else None

    async def add_model(self, name: str, path: str = "") -> None:
        """Hot-register a model (swarm pull landing, net/model_share.py):
        build + start a child engine, then advertise the new list."""
        if name in self._engines:
            return
        child_cfg = _dc_replace(self.config, model=name,
                                model_path=path or self.config.model_path)
        eng = JaxEngine(child_cfg)
        eng.obs = self._obs
        await eng.start()
        self._engines[name] = eng
        self.models = list(self._engines)
        if self._peer is not None:
            self._peer.update_metadata()  # advertise without waiting a tick
        log.info("hot-registered model %s from %s", name, path or "<default>")

    # Point-in-time gauges (spec_draft_len is the controller's CURRENT k,
    # the ratios a per-child fullness): max across children.  Everything
    # else (depths, counts, spec acceptance totals) sums.
    _GAUGE_MAX = frozenset(
        {"batch_occupancy", "kv_cache_utilization", "spec_draft_len",
         "tokens_per_dispatch"})

    def obs_gauges(self) -> dict:
        out: dict = {}
        for eng in self._engines.values():
            for k, v in eng.obs_gauges().items():
                # duty_cycle|dispatch=... is a ratio, not a depth: max,
                # like the other point-in-time gauges.
                if k in self._GAUGE_MAX or k.startswith("duty_cycle"):
                    out[k] = max(out.get(k, 0.0), v)
                else:
                    out[k] = out.get(k, 0.0) + v
        return out or super().obs_gauges()

    def describe(self) -> dict:
        per = {name: e.describe() for name, e in self._engines.items()}
        return {
            "models": self.models,
            "embeddings": any(d.get("embeddings", True)
                              for d in per.values()),
            "throughput": round(sum(d["throughput"] for d in per.values()), 2),
            "load": round(max(d["load"] for d in per.values()), 3),
            "engines": per,
        }

    def _format_chat(self, messages: list[dict], model: str = "") -> str:
        return self._child(model)._format_chat(messages, model=model)

    def _migrate_export_meta(self, req) -> tuple[list[bytes], int]:
        eng = self._engines.get(req.model)
        return eng._migrate_export_meta(req) if eng is not None else ([], 0)

    def generate(self, prompt: str, model: str = "", max_tokens: int = 128,
                 temperature: float = 0.0, top_p: float = 1.0, seed: int = 0,
                 stop: list[str] | None = None, top_k: int = 0,
                 repeat_penalty: float = 1.0, kv_donor: str = "",
                 kv_trace: str = "", migrate: bool = False
                 ) -> AsyncIterator[Chunk]:
        return self._child(model).generate(
            prompt, model=model, max_tokens=max_tokens,
            temperature=temperature, top_p=top_p, seed=seed, stop=stop,
            top_k=top_k, repeat_penalty=repeat_penalty, kv_donor=kv_donor,
            kv_trace=kv_trace, migrate=migrate)

    async def export_kv_pages(self, model: str, chain_hashes: list[bytes],
                              page_size: int) -> dict | None:
        eng = self._engines.get(model)
        if eng is None:
            return None
        return await eng.export_kv_pages(model, chain_hashes, page_size)

    async def embed(self, texts: list[str], model: str = "",
                    truncate: bool = True) -> tuple[list[list[float]], int]:
        return await self._child(model).embed(texts, model=model,
                                              truncate=truncate)

    # The profiler session is global to the process, so one child's control
    # is the control (and its latch the single flight).

    async def profile_start(self) -> dict:
        return await next(iter(self._engines.values())).profile_start()

    async def profile_stop(self) -> dict:
        return await next(iter(self._engines.values())).profile_stop()

    async def capture_profile(self, seconds: float = 3.0) -> str:
        return await next(iter(self._engines.values())).capture_profile(seconds)
