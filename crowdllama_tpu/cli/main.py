"""Unified node CLI: ``crowdllama-tpu start [--worker-mode] | version |
network-status``.

Counterpart of /root/reference/cmd/crowdllama/main.go: one binary, two roles —
``start --worker-mode`` runs a worker (engine + stream handlers),
plain ``start`` runs a consumer (gateway HTTP server) (main.go:184-190);
optional IPC server from config/env (main.go:133-143); periodic stats logging
(main.go:391-427); SIGINT/SIGTERM graceful shutdown (main.go:450-460).
The reference's embedded Ollama CLI surface (main.go:49-78) maps to native
subcommands: ``run`` (streaming chat), ``pull`` (swarm checkpoint fetch),
``list`` / ``show`` / ``rm`` (local checkpoint management; ``list
--gateway`` for the swarm view) — the engine is in-process JAX, so there
is nothing to embed or shell out to.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import logging
import re
import signal
import sys

from crowdllama_tpu.config import Configuration
from crowdllama_tpu.logutil import new_app_logger
from crowdllama_tpu.utils.keys import KeyManager
from crowdllama_tpu.version import version_string

log = logging.getLogger("crowdllama.cli")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="crowdllama-tpu",
                                description="TPU-native p2p LLM inference swarm")
    sub = p.add_subparsers(dest="command")
    start = sub.add_parser("start", help="run a swarm node")
    start.add_argument("--worker-mode", action="store_true",
                       help="serve inference (default: consumer/gateway mode)")
    Configuration.add_flags(start)
    sub.add_parser("version", help="print version")
    status = sub.add_parser("network-status", help="probe a gateway's health endpoint")
    status.add_argument("--gateway", default="http://127.0.0.1:9001")
    trace = sub.add_parser(
        "trace", help="fetch a cross-node stitched trace from a gateway "
                      "and print it as a waterfall")
    trace.add_argument("trace_id", help="trace id (from a response header, "
                                        "exemplar, or /debug/flightrecorder)")
    trace.add_argument("--gateway", default="http://127.0.0.1:9001")
    top = sub.add_parser(
        "top", help="live per-worker swarm table from a gateway's "
                    "/metrics/cluster scrape")
    top.add_argument("--gateway", default="http://127.0.0.1:9001")
    top.add_argument("--watch", type=float, default=0.0, metavar="SECONDS",
                     help="refresh every N seconds (default: one shot)")
    run = sub.add_parser(
        "run", help="chat with a model through a gateway (ollama-run style)")
    run.add_argument("model", help="model name (see /api/tags)")
    run.add_argument("prompt", nargs="?", default="",
                     help="one-shot prompt; omit for an interactive REPL")
    run.add_argument("--gateway", default="http://127.0.0.1:9001")
    run.add_argument("--temperature", type=float, default=0.7)
    run.add_argument("--top-p", type=float, default=0.95)
    run.add_argument("--max-tokens", type=int, default=0)
    pull = sub.add_parser(
        "pull", help="fetch a model's checkpoint from a swarm peer "
                     "(hash-verified safetensors transfer)")
    pull.add_argument("model", help="model name advertised by some worker")
    pull.add_argument("--bootstrap-peers", required=True,
                      help="comma-separated host:port bootstrap addresses")
    pull.add_argument("--models-dir", default="",
                      help="destination root (default ~/.crowdllama-tpu/models)")
    pull.add_argument("--key-path", default="")
    # Model management (the reference rides the embedded Ollama CLI's
    # list/show/rm, cmd/crowdllama/main.go:49-78).
    lst = sub.add_parser("list", help="list local checkpoints (or the "
                                      "swarm's models with --gateway)")
    lst.add_argument("--models-dir", default="")
    lst.add_argument("--gateway", default="",
                     help="query this gateway's /api/tags instead")
    show = sub.add_parser("show", help="model config + local checkpoint "
                                       "details")
    show.add_argument("model")
    show.add_argument("--models-dir", default="")
    rm = sub.add_parser("rm", help="delete a local pulled checkpoint")
    rm.add_argument("model")
    rm.add_argument("--models-dir", default="")
    distill = sub.add_parser(
        "distill-draft",
        help="distill a small draft model from a main model's logits for "
             "--spec-decode draft (train/distill.py, docs/SPECULATIVE.md)")
    distill.add_argument("--teacher", default="tiny-test",
                         help="main-model registry name")
    distill.add_argument("--teacher-path", default="",
                         help="teacher checkpoint dir (empty = random init, "
                              "matching a checkpoint-less serving node)")
    distill.add_argument("--out", required=True,
                         help="checkpoint dir to write (becomes "
                              "--spec-draft-path)")
    distill.add_argument("--draft-layers", type=int, default=2)
    distill.add_argument("--steps", type=int, default=1200)
    distill.add_argument("--batch", type=int, default=16)
    distill.add_argument("--seq-len", type=int, default=64)
    distill.add_argument("--corpus-seqs", type=int, default=256,
                         help="teacher-rollout sequences to synthesize")
    distill.add_argument("--corpus", default="",
                         help="optional text file: seeds rollout prefixes "
                              "(the prompt distribution) and joins the "
                              "corpus as raw chunks")
    distill.add_argument("--max-prefix", type=int, default=32,
                         help="longest rollout prefix length")
    distill.add_argument("--sample-temperature", type=float, default=0.0,
                         help="rollout sampling temperature (0 = greedy, "
                              "the verify-time trajectory distribution)")
    distill.add_argument("--no-tie-embeddings", action="store_true",
                         help="random-init embed/lm_head instead of "
                              "copying the teacher's")
    distill.add_argument("--lr", type=float, default=3e-3)
    distill.add_argument("--kl-weight", type=float, default=0.5)
    distill.add_argument("--kl-temperature", type=float, default=2.0)
    distill.add_argument("--seed", type=int, default=0)
    distill.add_argument("--verbose", action="store_true")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "version":
        print(version_string())
        return 0
    if args.command == "network-status":
        return asyncio.run(_network_status(args.gateway))
    if args.command == "trace":
        return asyncio.run(_trace(args))
    if args.command == "top":
        return asyncio.run(_top(args))
    if args.command == "run":
        try:
            return asyncio.run(_run_chat(args))
        except KeyboardInterrupt:
            print(file=sys.stderr)
            return 0
    if args.command == "pull":
        try:
            return asyncio.run(_pull(args))
        except KeyboardInterrupt:
            return 1
    if args.command == "list":
        return asyncio.run(_list(args)) if args.gateway else _list_local(args)
    if args.command == "show":
        return _show(args)
    if args.command == "rm":
        return _rm(args)
    if args.command == "distill-draft":
        return _distill_draft(args)
    if args.command == "start":
        cfg = Configuration.from_flags(args)
        new_app_logger("crowdllama", cfg.verbose)
        logging.getLogger().setLevel(
            logging.DEBUG if cfg.verbose else logging.INFO)
        logging.basicConfig(stream=sys.stderr)
        if args.worker_mode and cfg.engine_backend != "fake":
            # Before the first compile (leader engine and follower alike).
            # Gateway/consumer and fake-engine nodes never compile — and
            # never touch JAX at all (one process per chip).
            from crowdllama_tpu.utils.jaxcache import enable_compile_cache

            log.info("jax compile cache: %s", enable_compile_cache())
        if cfg.dist_coordinator:
            # Multi-host pod-slice serving (parallel/replicated.py):
            # initialize the global mesh BEFORE any backend touch, then
            # process 0 runs the full node (its engine broadcasts every
            # device-touching call) and every other process replays the
            # frame stream.  v1 replicates exactly ONE JaxEngine's frame
            # stream — refuse shapes that would start other engines
            # (consumer FakeEngine path, sharded groups, multi-model
            # lists) instead of deadlocking the first collective.
            if (not args.worker_mode or cfg.shard_count > 1
                    or "," in cfg.model):
                print("error: --dist-coordinator serves exactly one "
                      "worker-mode model per cluster (no consumer mode, "
                      "--shard-count, or model lists)", file=sys.stderr)
                return 2
            # A swarm-pull hot-registering a SECOND engine would emit
            # frames the single-runner follower loop cannot represent.
            cfg.allow_swarm_pull = False
            from crowdllama_tpu.parallel.multihost import (
                initialize_from_config,
                is_leader,
            )

            initialize_from_config(cfg)
            if not is_leader():
                from crowdllama_tpu.parallel.replicated import run_follower

                run_follower(cfg)
                return 0
        try:
            asyncio.run(run_node(cfg, worker_mode=args.worker_mode))
            return 0
        except KeyboardInterrupt:
            return 0
    build_parser().print_help()
    return 1


def _distill_draft(args) -> int:
    """Train + save a speculative draft checkpoint (train/distill.py);
    prints the flags that load it back into a serving node."""
    logging.basicConfig(
        stream=sys.stderr,
        level=logging.DEBUG if args.verbose else logging.INFO)
    from crowdllama_tpu.train.distill import DistillConfig, distill_draft

    dc = DistillConfig(
        teacher=args.teacher, teacher_path=args.teacher_path,
        draft_layers=args.draft_layers, steps=args.steps, batch=args.batch,
        seq_len=args.seq_len, corpus_seqs=args.corpus_seqs,
        corpus_path=args.corpus, sample_temperature=args.sample_temperature,
        max_prefix=args.max_prefix,
        tie_embeddings=not args.no_tie_embeddings,
        lr=args.lr, kl_weight=args.kl_weight,
        kl_temperature=args.kl_temperature, seed=args.seed, out=args.out)
    result = distill_draft(dc)
    print(f"checkpoint: {result['checkpoint']}")
    print(f"final loss: {result['losses'][-1]:.4f}  "
          f"greedy agreement: {result['agreement']:.3f}")
    print("serve with: crowdllama-tpu start --worker-mode "
          f"--model {args.teacher} --spec-decode draft "
          f"--spec-draft-path {result['checkpoint']}")
    return 0


async def _pull(args) -> int:
    """Standalone swarm pull: discover a peer advertising the model, fetch
    its checkpoint with hash verification, print the local path.  The
    swarm-native `ollama pull` (the reference embeds Ollama's,
    /root/reference/cmd/crowdllama/main.go:49-78)."""
    from crowdllama_tpu.core.protocol import namespace_key
    from crowdllama_tpu.net.discovery import discover_peers, new_host_and_dht
    from crowdllama_tpu.net.model_share import fetch_model
    from crowdllama_tpu.utils.keys import KeyManager

    logging.basicConfig(stream=sys.stderr, level=logging.INFO)
    cfg = Configuration.from_environment()
    models_dir = args.models_dir or cfg.models_dir
    key = KeyManager(args.key_path or None).get_or_create_private_key("pull")
    host, dht = await new_host_and_dht(key, listen_host="127.0.0.1")
    try:
        boots = [a.strip() for a in args.bootstrap_peers.split(",") if a.strip()]
        await dht.bootstrap(boots)
        resources = await discover_peers(host, dht)
        sources = [r for r in resources
                   if r.worker_mode and args.model in r.supported_models]
        if not sources:
            print(f"no swarm peer advertises model {args.model!r} "
                  f"(discovered {len(resources)} peers)", file=sys.stderr)
            return 1
        last_err = None
        for r in sources:
            contact = await dht.find_peer(r.peer_id)
            if contact is None:
                last_err = RuntimeError(
                    f"cannot resolve peer {r.peer_id[:8]}")
                continue
            try:
                dest = await fetch_model(host, contact, args.model, models_dir)
                print(dest)
                return 0
            except Exception as e:
                last_err = e
                log.warning("pull from %s failed: %s", r.peer_id[:8], e)
        print(f"pull failed from every source: {last_err}", file=sys.stderr)
        return 1
    finally:
        await host.close()


def _models_root(args):
    from pathlib import Path

    cfg = Configuration.from_environment()
    return Path(args.models_dir or cfg.models_dir).expanduser()


def _dir_size(d) -> int:
    return sum(p.stat().st_size for p in d.rglob("*") if p.is_file())


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024
    return f"{n:.1f} GiB"


def _list_local(args) -> int:
    """``list`` — local checkpoints under the models dir (the reference's
    embedded `ollama list`, cmd/crowdllama/main.go:49-78)."""
    root = _models_root(args)
    rows = []
    if root.is_dir():
        for d in sorted(root.iterdir()):
            if d.is_dir() and not d.name.endswith(".partial"):
                st = list(d.glob("*.safetensors"))
                if st:
                    rows.append((d.name, _fmt_bytes(_dir_size(d)), len(st)))
    if not rows:
        print(f"no local checkpoints under {root}")
        return 0
    w = max(len(r[0]) for r in rows)
    print(f"{'NAME'.ljust(w)}  SIZE        SHARDS")
    for name, size, shards in rows:
        print(f"{name.ljust(w)}  {size:<10}  {shards}")
    return 0


async def _list(args) -> int:
    """``list --gateway`` — the swarm's served models via /api/tags."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{args.gateway}/api/tags",
                             timeout=aiohttp.ClientTimeout(total=5)) as resp:
                body = await resp.json()
    except Exception as e:
        print(f"gateway unreachable: {e}", file=sys.stderr)
        return 1
    models = body.get("models", [])
    if not models:
        print("no models served by the swarm")
        return 0
    for m in models:
        print(m.get("name", m.get("model", "?")))
    return 0


def _show(args) -> int:
    """``show MODEL`` — registry config + local checkpoint details."""
    from crowdllama_tpu.models.config import get_config, list_models
    from crowdllama_tpu.net.model_share import dest_under_root

    try:
        cfg = get_config(args.model)
    except KeyError:
        cfg = None
    if cfg is not None:
        print(f"model:        {cfg.name} (family {cfg.family})")
        print(f"layers:       {cfg.num_layers}")
        print(f"hidden:       {cfg.hidden_size} "
              f"(heads {cfg.num_heads}/{cfg.num_kv_heads} kv)")
        print(f"context:      {cfg.max_context_length}")
        if cfg.is_moe:
            print(f"experts:      {cfg.num_experts} "
                  f"(top-{cfg.num_experts_per_tok})")
    else:
        print(f"model:        {args.model} (not in the builtin registry; "
              f"known: {', '.join(list_models())})")
    try:
        d = dest_under_root(_models_root(args), args.model)
    except ValueError as e:
        print(f"invalid model name: {e}", file=sys.stderr)
        return 1
    if d.is_dir() and list(d.glob("*.safetensors")):
        print(f"checkpoint:   {d} ({_fmt_bytes(_dir_size(d))})")
    else:
        print("checkpoint:   none local (use `crowdllama-tpu pull`)")
    return 0


def _rm(args) -> int:
    """``rm MODEL`` — delete a local pulled checkpoint (name-validated and
    containment-checked like every other models-dir path)."""
    import shutil

    from crowdllama_tpu.net.model_share import dest_under_root

    try:
        d = dest_under_root(_models_root(args), args.model)
    except ValueError as e:
        print(f"invalid model name: {e}", file=sys.stderr)
        return 1
    if not d.is_dir():
        print(f"no local checkpoint for {args.model!r} under {d.parent}",
              file=sys.stderr)
        return 1
    shutil.rmtree(d)
    print(f"removed {d}")
    return 0


async def _network_status(gateway: str) -> int:
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{gateway}/api/health",
                             timeout=aiohttp.ClientTimeout(total=5)) as resp:
                body = await resp.json()
    except Exception as e:
        print(f"gateway unreachable: {e}", file=sys.stderr)
        return 1
    print(f"gateway: {gateway}")
    print(f"peer id: {body.get('peer_id', '?')}")
    workers = body.get("workers", {})
    print(f"workers: {len(workers)}")
    for pid, w in workers.items():
        mark = "healthy" if w.get("is_healthy") else "unhealthy"
        print(f"  {pid[:12]} [{mark}] models={','.join(w.get('supported_models', []))} "
              f"tput={w.get('tokens_throughput', 0)} accel={w.get('accelerator', '?')}")
    return 0


async def _trace(args) -> int:
    """``trace <trace_id>`` — ask the gateway's collector to stitch the
    cross-node trace and render it as an indented waterfall
    (docs/OBSERVABILITY.md: debug a slow request in 3 commands)."""
    import aiohttp

    from crowdllama_tpu.obs.collector import render_waterfall

    url = f"{args.gateway}/debug/trace/{args.trace_id}"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(url,
                             timeout=aiohttp.ClientTimeout(total=15)) as resp:
                body = await resp.json()
                if resp.status != 200:
                    print(f"error: {body.get('error', resp.status)}",
                          file=sys.stderr)
                    return 1
    except Exception as e:
        print(f"gateway unreachable: {e}", file=sys.stderr)
        return 1
    print(render_waterfall(body))
    return 0


def _parse_exposition(text: str) -> list[tuple[str, dict, float]]:
    """Prometheus text → [(family, labels, value)] — just enough parsing
    for the ``top`` table; TYPE/HELP/exemplar noise is skipped."""
    out: list[tuple[str, dict, float]] = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? (\S+)",
                     line)
        if m is None:
            continue
        name, _, inner, value = m.groups()
        labels: dict = {}
        for part in (inner or "").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k.strip()] = v.strip().strip('"')
        try:
            out.append((name, labels, float(value)))
        except ValueError:
            continue
    return out


def render_top(text: str) -> str:
    """``/metrics/cluster`` exposition → the per-worker table.

    Joins the gateway's routing view (``crowdllama_worker_*``, keyed by
    ``peer``) with each worker's scraped engine gauges (keyed by
    ``worker`` — same 16-char peer-id head)."""
    samples = _parse_exposition(text)
    rows: dict[str, dict] = {}
    rollups: dict[str, float] = {}
    for name, labels, value in samples:
        if name.startswith("crowdllama_cluster_"):
            rollups[name[len("crowdllama_cluster_"):]] = value
            continue
        wid = labels.get("peer") or labels.get("worker")
        if not wid:
            continue
        row = rows.setdefault(wid, {})
        if name == "crowdllama_worker_throughput_tokens_per_sec":
            row["tok/s"] = value
        elif name == "crowdllama_worker_load":
            row["load"] = value
        elif name == "crowdllama_worker_healthy":
            row["ok"] = value
        elif name == "crowdllama_engine_batch_occupancy":
            row["occ"] = value
        elif name == "crowdllama_engine_kv_cache_utilization":
            row["kv"] = value
        elif name == "crowdllama_engine_pending_depth":
            row["pend"] = value
        elif name == "crowdllama_engine_active_slots":
            row["act"] = value
        elif name == "crowdllama_engine_duty_cycle":
            # highest-duty dispatch class is the one that matters
            row["duty"] = max(row.get("duty", 0.0), value)
    lines = [
        f"workers {rollups.get('workers_total', 0):g} "
        f"(scraped {rollups.get('workers_scraped', 0):g})   "
        f"tok/s {rollups.get('tokens_per_second', 0):g}   "
        f"occupancy {rollups.get('batch_occupancy', 0):.2f}   "
        f"kv {rollups.get('kv_cache_utilization', 0):.2f}   "
        f"inflight {rollups.get('inflight', 0):g}",
        f"{'WORKER':<18}{'OK':>3}{'LOAD':>7}{'TOK/S':>8}{'ACT':>5}"
        f"{'PEND':>6}{'OCC':>6}{'KV':>6}{'DUTY':>6}",
    ]
    for wid in sorted(rows):
        r = rows[wid]
        lines.append(
            f"{wid:<18}{'y' if r.get('ok', 0) else 'n':>3}"
            f"{r.get('load', 0.0):>7.2f}{r.get('tok/s', 0.0):>8.1f}"
            f"{r.get('act', 0.0):>5.0f}{r.get('pend', 0.0):>6.0f}"
            f"{r.get('occ', 0.0):>6.2f}{r.get('kv', 0.0):>6.2f}"
            f"{r.get('duty', 0.0):>6.2f}")
    if not rows:
        lines.append("(no workers visible)")
    return "\n".join(lines)


async def _top(args) -> int:
    """``top`` — the swarm observatory table (docs/OBSERVABILITY.md).

    One GET /metrics/cluster per refresh; ``--watch N`` loops until ^C."""
    import aiohttp

    url = f"{args.gateway}/metrics/cluster"
    while True:
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(
                        url,
                        timeout=aiohttp.ClientTimeout(total=30)) as resp:
                    text = await resp.text()
                    if resp.status != 200:
                        print(f"error: HTTP {resp.status}", file=sys.stderr)
                        return 1
        except Exception as e:
            print(f"gateway unreachable: {e}", file=sys.stderr)
            return 1
        if args.watch > 0:
            print("\x1b[2J\x1b[H", end="")  # clear screen between frames
        print(render_top(text))
        if args.watch <= 0:
            return 0
        try:
            await asyncio.sleep(args.watch)
        except (KeyboardInterrupt, asyncio.CancelledError):
            return 0


async def _run_chat(args) -> int:
    """``run <model>`` — the ollama-run-style chat client.

    The reference gets this surface by embedding the Ollama CLI
    (main.go:49-78); here it is a thin NDJSON client of the gateway's
    /api/chat, streaming tokens as they arrive.  One-shot with a prompt
    argument, REPL without."""
    import json

    import aiohttp

    history: list[dict] = []
    options = {"temperature": args.temperature, "top_p": args.top_p}
    if args.max_tokens:
        options["num_predict"] = args.max_tokens

    async def turn(http: aiohttp.ClientSession, content: str) -> bool:
        history.append({"role": "user", "content": content})
        try:
            async with http.post(
                f"{args.gateway}/api/chat",
                json={"model": args.model, "messages": history,
                      "stream": True, "options": options},
                timeout=aiohttp.ClientTimeout(total=600),
            ) as resp:
                if resp.status != 200:
                    body = await resp.text()
                    print(f"error: {body.strip()}", file=sys.stderr)
                    history.pop()
                    return False
                parts = []
                async for line in resp.content:
                    if not line.strip():
                        continue
                    frame = json.loads(line)
                    if frame.get("done_reason") == "error":
                        print(f"\nerror: {frame.get('error', 'worker failed')}",
                              file=sys.stderr)
                        history.pop()
                        return False
                    text = frame.get("message", {}).get("content", "")
                    if text:
                        parts.append(text)
                        print(text, end="", flush=True)
                    if frame.get("done"):
                        break
                print()
                history.append({"role": "assistant",
                                "content": "".join(parts)})
                return True
        except (aiohttp.ClientError, asyncio.TimeoutError,
                json.JSONDecodeError) as e:
            print(f"gateway error: {e or type(e).__name__}", file=sys.stderr)
            history.pop()
            return False

    async with aiohttp.ClientSession() as http:
        if args.prompt:
            return 0 if await turn(http, args.prompt) else 1
        print(f"chatting with {args.model} via {args.gateway} "
              "(/bye or Ctrl-D to exit)", file=sys.stderr)
        # Read stdin on a dedicated DAEMON thread, one line per turn (the
        # event gates it so ">>> " never interleaves with streamed tokens).
        # The default executor would hang Ctrl-C: asyncio.run joins its
        # threads on shutdown, and one would still be blocked in input().
        import threading

        loop = asyncio.get_running_loop()
        lines: asyncio.Queue[str | None] = asyncio.Queue()
        ready = threading.Event()

        def reader() -> None:
            while True:
                ready.wait()
                ready.clear()
                try:
                    line = input(">>> ")
                except (EOFError, KeyboardInterrupt):
                    loop.call_soon_threadsafe(lines.put_nowait, None)
                    return
                loop.call_soon_threadsafe(lines.put_nowait, line)

        threading.Thread(target=reader, daemon=True).start()
        while True:
            ready.set()
            try:
                line = await lines.get()
            except (KeyboardInterrupt, asyncio.CancelledError):
                print(file=sys.stderr)
                return 0
            if line is None:
                print(file=sys.stderr)
                return 0
            line = line.strip()
            if line in ("/bye", "/exit", "/quit"):
                return 0
            if not line:
                continue
            await turn(http, line)


def _make_engine(cfg: Configuration, worker_mode: bool):
    from crowdllama_tpu.engine.engine import FakeEngine, JaxEngine

    if not worker_mode:
        # Consumers never run inference locally (reference uses an echo stub,
        # api.go:163-189).
        return FakeEngine(models=[])
    names = [m.strip() for m in cfg.model.split(",") if m.strip()]
    if cfg.engine_backend == "fake":
        return FakeEngine(models=names)
    if len(names) > 1 and cfg.shard_count > 1:
        raise ValueError("multi-model workers cannot combine with "
                         "--shard-count (shard one model per worker group)")
    if cfg.shard_count > 1:
        from crowdllama_tpu.engine.sharded import ShardedEngine

        return ShardedEngine(cfg)
    # Always the multi-model container (even for one model): swarm pull
    # hot-registers via MultiEngine.add_model, and a single-model JaxEngine
    # cannot grow.
    from crowdllama_tpu.engine.multi import MultiEngine

    cfg.model = ",".join(names) if names else cfg.model
    return MultiEngine(cfg)


async def run_node(cfg: Configuration, worker_mode: bool) -> None:
    """Worker: engine + peer.  Consumer: peer + gateway.  Either may add IPC."""
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.ipc.server import IPCServer
    from crowdllama_tpu.net.host import bind_listener
    from crowdllama_tpu.peer.peer import Peer

    km = KeyManager(cfg.key_path or None)
    component = "worker" if worker_mode else "consumer"
    key = km.get_or_create_private_key(component)

    # The node's ports are taken BEFORE the engine starts and served on
    # after it: an engine start lasts minutes, and a port that a launcher
    # found free before it (the benchmark's bind-and-close) may not be
    # free after (ROADMAP W0(k)).  Until the node listens a dial is
    # refused as by a closed port.
    with contextlib.ExitStack() as held:    # closed if the start fails
        listen_sock = held.enter_context(
            bind_listener(cfg.listen_host, cfg.listen_port))
        obs_sock = None
        if worker_mode and cfg.worker_metrics_port:
            obs_sock = held.enter_context(
                bind_listener(cfg.listen_host, cfg.worker_metrics_port))
        engine = _make_engine(cfg, worker_mode)
        log.info("starting %s node (%s)", component, version_string())
        await engine.start()
        held.pop_all()

    peer = Peer(key, cfg, engine=engine, worker_mode=worker_mode)
    await peer.start(listen_sock)

    gateway = None
    gossip = None
    obs_server = None
    if not worker_mode:
        # Replicated gateway plane (docs/ROBUSTNESS.md): gossip routing
        # state with the other replicas (--gateway-peers) and/or enforce
        # per-tenant quotas (--tenant-quota).  The gossip node is built
        # even with no peers when a snapshot path is set, so a bounced
        # single gateway still rehydrates its affinity map.
        from crowdllama_tpu.swarm.gossip import (
            GossipNode,
            TenantQuotas,
            parse_tenant_quotas,
        )

        quotas = None
        if cfg.tenant_quota:
            quotas = TenantQuotas(parse_tenant_quotas(cfg.tenant_quota),
                                  node_id=peer.peer_id)
        if cfg.gateway_peers or cfg.gossip_snapshot_path or quotas:
            gossip = GossipNode(peer, peers=cfg.gateway_peers,
                                interval=cfg.gossip_interval,
                                snapshot_path=cfg.gossip_snapshot_path,
                                quotas=quotas)
        gateway = Gateway(peer, port=cfg.gateway_port,
                          trace_buffer=cfg.trace_buffer,
                          request_timeout=cfg.request_timeout,
                          admission_max_inflight=cfg.admission_max_inflight,
                          retry_after_s=cfg.retry_after_s,
                          kv_ship=cfg.kv_ship,
                          gossip=gossip, tenant_quotas=quotas,
                          flight_recorder=cfg.flight_recorder,
                          trace_ttl=cfg.trace_ttl,
                          metrics_exemplars=cfg.metrics_exemplars,
                          slo_ttft_ms=cfg.slo_ttft_ms,
                          slo_decode_ms=cfg.slo_decode_ms,
                          stream_stall_ms=cfg.stream_stall_ms,
                          hedge_ttft_ms=cfg.hedge_ttft_ms,
                          spec_pipeline=cfg.gateway_spec_pipeline,
                          spec_draft_path=cfg.spec_draft_path)
        if gossip is not None:
            gossip.metrics = gateway.obs.metrics
            await gossip.start()
        await gateway.start()
    elif cfg.worker_metrics_port:
        from crowdllama_tpu.obs.http import ObsServer
        obs_server = ObsServer(peer, host=cfg.listen_host,
                               port=cfg.worker_metrics_port, sock=obs_sock)
        await obs_server.start()

    ipc = None
    if cfg.ipc_socket:
        ipc = IPCServer(cfg.ipc_socket, engine, peer=peer)
        await ipc.start()

    stop = asyncio.Event()
    got_sig: list[int] = []
    loop = asyncio.get_running_loop()

    def _on_signal(signum: int) -> None:
        got_sig.append(signum)
        stop.set()

    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, _on_signal, sig)

    async def stats_loop() -> None:
        while True:
            await asyncio.sleep(10)
            pm = peer.peer_manager
            if pm is not None:
                log.info("peers: %d total, %d healthy, %d workers | engine: %s",
                         len(pm.peers), len(pm.get_healthy_peers()),
                         len(pm.get_workers()), engine.describe())

    stats = asyncio.create_task(stats_loop())
    try:
        await stop.wait()
    finally:
        log.info("shutting down")
        stats.cancel()
        if signal.SIGTERM in got_sig and worker_mode:
            # SIGTERM on a worker = live-migration drain
            # (docs/ROBUSTNESS.md): advertise draining, migrate in-flight
            # streams to the swarm, then stay up as a KV donor for their
            # successors through the drain window.  A second signal (or an
            # earlier POST /drain having already moved everything) cuts
            # the window short.
            migrated = await peer.drain()
            if migrated:
                log.info("migrated %d in-flight streams; serving KV "
                         "fetches for %.0fs (signal again to exit now)",
                         migrated, cfg.drain_timeout)
                stop.clear()
                try:
                    await asyncio.wait_for(stop.wait(), cfg.drain_timeout)
                except asyncio.TimeoutError:
                    pass
        else:
            # SIGINT (operator foreground stop) / consumer: finish
            # in-flight requests in place, then tear down.
            await peer.stop_advertising()
            drained = await engine.drain(cfg.drain_timeout)
            if not drained:
                log.warning("drain timed out after %.0fs; dropping "
                            "in-flight requests", cfg.drain_timeout)
        if ipc is not None:
            await ipc.stop()
        if obs_server is not None:
            await obs_server.stop()
        if gossip is not None:
            # Snapshot-on-shutdown (docs/ROBUSTNESS.md): the LWW map —
            # affinity pins + quarantines — lands in
            # cfg.gossip_snapshot_path, and the restarted gateway
            # rehydrates it so a bounce keeps its affinity hit-rate.
            await gossip.stop(save=True)
        if gateway is not None:
            await gateway.stop()
        await peer.stop()
        await engine.stop()


if __name__ == "__main__":
    sys.exit(main())
