#!/usr/bin/env python3
"""The chip benchmark's command.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1|2>

One run of one cell of BENCHMARK.json: starts DHT node, worker and gateway
as child processes, warms up, drives the cell's traffic at the gateway's
``/api/generate`` for ``--seconds``, checks a seeded sample of the outputs
against the plain reference on the chip, and prints the contract's one JSON
object as the last line of standard output (``--trace 0``: the cell's
end-to-end metrics; ``--trace 1``: its per-layer metrics, from spans,
counters and a device trace of a few seconds of the window; ``--trace 2``:
both — a ``--trace 0`` run up to the moment the window closes, then a few
traced seconds of the same traffic, one last line with the end-to-end
metrics of the window and the per-layer metrics side by side).  A run that
cannot give a result exits 1, prints no result, says why on standard error
in one line that starts ``benchmark failed:`` and leaves ``failure.json``
(the message, the children that had exited, the last lines of their logs)
in its directory under ``_run/out/``.

    --rehearse      the same flow at tiny size on the CPU (Pallas in
                    interpret mode), on the rehearsal configuration the
                    cell's configuration names (``bench.rehearsal``, else
                    rehearsal-tiny-mistral); prints "device" as the CPU and
                    never a device metric
    --sweep R1,R2   open-loop cells: one set-up, one window per rate, a
                    table instead of a result (how the knee was found)

This process never imports JAX: the chip belongs to the worker, and after
the worker has exited to the reference check.  See README.md beside this
file for the layout and for how to add a configuration, a traffic mix, a
cell or a metric as files.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

from harness import (costs, generators, launcher, metrics,  # noqa: E402
                     profiler, reducers, reference)
from harness.launcher import BenchFailure  # noqa: E402
from harness.loadgen import LoadGen  # noqa: E402

ROOT = launcher.ROOT
REHEARSAL_CONFIG = "rehearsal-tiny-mistral"
TRACE_AT, TRACE_LEN = 0.4, 3.0     # --trace 1: where in the window, how long
# --trace 2 traces after the window: a second for the window's last requests
# to get their first token undisturbed, the profiler's calls (a first start
# and stop whose trace is thrown away, then the real ones), the trace.
TAIL_GRACE, TAIL_PROFILER = 1.0, 8.0


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, rehearse: bool) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) by the names in
    BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json "
                           f"({[w['name'] for w in bench['workloads']]})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(CHIP_DIR / "traffic" / f"{cell['traffic']}.json")
    if rehearse:
        tiny = config["bench"].get("rehearsal", REHEARSAL_CONFIG)
        path = CHIP_DIR / "configs" / f"{tiny}.json"
        if not path.exists():
            raise BenchFailure(
                f"configuration {cell['config']!r} names the rehearsal "
                f"configuration {tiny!r}: no file {path}")
        config = load_json(path)
        traffic.update(traffic.get("rehearsal") or {})
    check_pieces(config)
    return bench, cell, config, traffic


def check_pieces(config: dict) -> None:
    """A configuration's reference, its limits and its cost module, found
    and tried before anything starts: a family that lacks one fails here in
    a second, with the file to add, not after minutes of set-up — and never
    by being counted as another family."""
    b = config["bench"]
    ref = reference.module_file(b["reference"])
    if not ref.exists():
        raise BenchFailure(f"configuration {b['name']!r} names the "
                           f"reference {b['reference']!r}: no file {ref}")
    try:
        tol = reference.limits(b["reference"])
        mod = costs.module_for(config)
        need = mod.decode_step_bytes(config, b["slots"],
                                     b["slots"] * b["context"] / 2)
    except (FileNotFoundError, costs.CostsMisread) as e:
        raise BenchFailure(str(e)) from e
    say(f"info: pieces: reference {b['reference']} (limits "
        f"{ {k: tol[k] for k in reference.LIMITS} }), costs "
        f"{mod.__name__.rpartition('.')[2]}: a step of {b['slots']} tokens "
        f"at half the context reads at least {need / 1e9:.3f} GB")


def cell_metrics(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------- worker io


def device_of(worker_metrics: str) -> tuple[int, int]:
    """(devices with memory the worker reports, peak bytes on the fullest):
    a worker that is not on an accelerator reports limit 0."""
    limits = reducers.samples(
        worker_metrics, "crowdllama_device_memory_bytes_limit")
    peaks = reducers.samples(
        worker_metrics, "crowdllama_device_memory_peak_bytes_in_use")
    return sum(1 for x in limits if x > 0), int(max(peaks, default=0))


# ------------------------------------------------------------------ one run


class Run:
    def __init__(self, args, cell, config, traffic) -> None:
        self.args, self.cell = args, cell
        self.config, self.traffic = config, traffic
        self.traced = bool(args.trace)
        self.profile: dict | None = None    # the worker's answer to "stop"
        self.traced_at: tuple[float, float] | None = None   # --trace 2
        self.out = launcher.RUN_DIR / "out" / cell["name"] / (
            f"s{args.seed}-t{args.trace}" + ("-rehearse" if args.rehearse
                                             else ""))
        shutil.rmtree(self.out, ignore_errors=True)
        self.out.mkdir(parents=True)
        self.nodes = launcher.Nodes(self.out)
        self.window_unix0 = 0.0

    def plan_ctx(self, seconds: float, tail: float) -> dict:
        b = self.config["bench"]
        return {"seed": self.args.seed, "seconds": seconds, "tail_s": tail,
                "slots": b["slots"], "context": b["context"],
                "vocab_size": self.config["vocab_size"]}

    def start_system(self) -> None:
        model_dir = launcher.write_model_dir(self.config)
        self.model_dir = model_dir
        self.nodes.start(self.config, model_dir,
                         extra_worker_flags=self.args.worker_flag or [],
                         require_chips=0 if self.args.rehearse
                         else self.cell["chips"])
        health = self.nodes.wait_ready(self.config["bench"]["ready_timeout_s"])
        say(f"info: ready after {time.monotonic() - T_PROCESS_START:.1f}s; "
            f"workers: " + json.dumps([
                {k: w.get(k) for k in ("accelerator", "tpu_chip_count",
                                       "supported_models")}
                for w in (health.get("workers") or {}).values()]))
        self.device = load_json(self.out / "device.json")
        m = self.scrape("metrics")
        n_dev, _ = device_of(m)
        paths = [ln for ln in m.splitlines()
                 if ln.startswith("crowdllama_engine_attention_path")]
        say("info: attention paths: " + " ".join(paths))
        if not self.args.rehearse:
            if n_dev < self.cell["chips"]:
                raise BenchFailure(
                    f"the worker reports {n_dev} accelerator device(s), the "
                    f"cell needs {self.cell['chips']}: no TPU, no result")
            if any('path="pallas"' not in p for p in paths):
                raise BenchFailure("an attention path is not the Pallas "
                                   "kernel on the chip: " + " ".join(paths))

    def scrape(self, node: str, path: str = "/metrics") -> str:
        return launcher.http_get(self.nodes.ports[node], path, timeout=30)

    async def ascrape(self, node: str, path: str = "/metrics") -> str:
        return await asyncio.get_running_loop().run_in_executor(
            None, self.scrape, node, path)

    async def profile_for(self, length: float, gauges: list | None = None
                          ) -> dict:
        """Trace the worker's chip for ``length`` seconds through its own
        control; the answer to "stop" (artifact directory, host clocks).
        ``gauges`` takes the worker's /metrics sampled over exactly the
        traced seconds (harness/profiler.py)."""
        def post(action: str) -> dict:
            return json.loads(launcher.http_request(
                "POST", self.nodes.ports["metrics"],
                f"/debug/profile/{action}", timeout=120))

        stopped, samples = await profiler.profile_for(
            post, length,
            None if gauges is None else lambda: self.ascrape("metrics"))
        if gauges is not None:
            gauges.extend(text for _, _, text in samples)
        return stopped

    async def measure(self, traffic: dict, seconds: float) -> reducers.RunData:
        """One timeline (ladder, ramp, window, [traced tail,] drain) against
        the running system; everything a metric reader may want, gathered.
        Up to the moment the window closes every mode does the same."""
        b = self.config["bench"]
        mode = self.args.trace
        trace_len = min(TRACE_LEN, 0.5 * seconds)
        tail = TAIL_GRACE + TAIL_PROFILER + trace_len if mode == 2 else 0.0
        plan = generators.build_plan(traffic, self.plan_ctx(seconds, tail))
        gen = LoadGen(self.nodes.ports["gateway"], b["name"], b["sampling"],
                      self.args.seed, seconds, tail)
        run = reducers.RunData(
            records=gen.records, seconds=seconds, config=self.config,
            min_beyond=0 if self.args.rehearse else 10,
            rehearse=self.args.rehearse, checked=plan.checked)
        scr = {"worker": {}, "gateway": {}}
        tasks: dict[str, asyncio.Task] = {}

        async def sample_gauges() -> None:
            while True:
                run.gauge_samples.append(await self.ascrape("metrics"))
                await asyncio.sleep(0.5)

        async def trace_in_window() -> None:
            await asyncio.sleep(TRACE_AT * seconds)
            self.profile = await self.profile_for(trace_len)

        async def trace_tail() -> None:
            await asyncio.sleep(TAIL_GRACE)
            # the first start of the profiler costs more than the later
            # ones: made once and thrown away, it falls into no number
            first = await self.profile_for(0.0)
            shutil.rmtree(first["artifact"], ignore_errors=True)
            self.profile = await self.profile_for(trace_len,
                                                  run.gauge_samples)
            say(f"info: gauges: {len(run.gauge_samples)} samples inside the "
                f"{trace_len:.1f}s traced; the profiler's stop answered "
                f"{self.profile.get('written_monotonic', 0.0) - self.profile['stopped_monotonic']:.1f}s "
                f"after it was asked")
            # the worker's monotonic clock is this machine's, as gen.t0 is
            self.traced_at = (self.profile["started_monotonic"] - gen.t0,
                              self.profile["stopped_monotonic"] - gen.t0)
            if self.traced_at[1] > seconds + tail:
                raise BenchFailure(
                    f"the traced window ended {self.traced_at[1] - seconds:.1f}"
                    f"s after the measured one, past the {tail:.1f}s of "
                    f"traffic that follow it")

        async def window_start() -> None:
            self.window_unix0 = time.time()
            self.setup_s = time.monotonic() - T_PROCESS_START
            scr["worker"]["start"] = await self.ascrape("metrics")
            scr["gateway"]["start"] = await self.ascrape("gateway")
            if mode == 1 and self.profile is None:   # one trace to a worker
                tasks["gauges"] = asyncio.create_task(sample_gauges())
                tasks["profile"] = asyncio.create_task(trace_in_window())

        async def window_end() -> None:
            if "gauges" in tasks:
                tasks["gauges"].cancel()
            scr["worker"]["end"] = await self.ascrape("metrics")
            scr["gateway"]["end"] = await self.ascrape("gateway")
            if mode == 2:
                await trace_tail()

        await gen.run(plan, None, window_start, window_end)
        if "profile" in tasks:
            await tasks["profile"]
        self.nodes.assert_alive()
        run.scrapes = scr
        lo, hi = self.window_unix0, self.window_unix0 + seconds
        for node, port in (("worker", "metrics"), ("gateway", "gateway")):
            snap = json.loads(await self.ascrape(port, "/debug/trace"))
            run.traces[node] = [t for t in snap.get("traces", [])
                                if lo <= t.get("started_at", 0) < hi]
        def grown(family: str) -> float | None:
            end = reducers.samples(scr["worker"]["end"], family)
            start = reducers.samples(scr["worker"]["start"], family)
            return sum(end) - sum(start) if end else None

        prompt_tokens = grown("crowdllama_prompt_tokens_total")
        if prompt_tokens is not None:
            run.prefix = {
                "tokens_reused": grown("crowdllama_prefix_tokens_reused_total"),
                "prompt_tokens": prompt_tokens}
        scr["worker"]["final"] = await self.ascrape("metrics")
        return run

    # ---- after the window ------------------------------------------------

    def reference_check(self, run: reducers.RunData) -> tuple[bool, dict]:
        """The plain reference on the chip, in a process of its own after
        the worker has exited.  False also when a sampled request did not
        end with a ``done`` frame."""
        sample = [r for r in run.records if r.check]
        problems = [f"checked request actor {r.actor} turn {r.turn}: "
                    f"status {r.status} {r.error or 'no done frame'}"
                    for r in sample if not r.ok]
        sample = [r for r in sample if r.ok and r.reply_ids]
        if len(sample) < run.checked:
            problems.append(f"only {len(sample)} of the {run.checked} "
                            f"requests chosen for the check completed")
        job = {"config": self.config, "model_dir": str(self.model_dir),
               "rehearse": self.args.rehearse,
               "samples": [{"prompt_ids": r.prompt_ids,
                            "reply_ids": r.reply_ids} for r in sample]}
        (self.out / "check_input.json").write_text(json.dumps(job))
        env = launcher.child_env(
            {"JAX_PLATFORMS": "cpu"} if self.args.rehearse else None)
        log = self.out / "check.log"
        with log.open("w") as f:
            rc = subprocess.run(
                [sys.executable, "-m", "harness.reference.check",
                 "--input", str(self.out / "check_input.json"),
                 "--output", str(self.out / "check_output.json")],
                cwd=CHIP_DIR, env=env, stdout=f, stderr=subprocess.STDOUT,
                timeout=900).returncode
        if rc != 0:
            raise BenchFailure(f"reference check exited {rc}",
                               child="reference check", log=log)
        res = load_json(self.out / "check_output.json")
        tol = reference.limits(self.config["bench"]["reference"])
        ok = (not problems and res["max_deficit"] <= tol["max_deficit"]
              and res["mean_deficit"] <= tol["mean_deficit"])
        say("info: reference check: " + json.dumps(
            {**{k: res[k] for k in (
                "tokens", "max_deficit", "mean_deficit", "argmax_agree_share",
                "weights_s", "forward_s")},
             "limits": {k: tol[k] for k in ("max_deficit", "mean_deficit")},
             "problems": problems}))
        return ok, res

    def run_trace_reduce(self) -> dict | None:
        """The device trace's reduction, in a child on the CPU backend;
        the trace itself is deleted once reduced."""
        pdir = Path(self.profile["artifact"])
        outp = self.out / "profile_reduced.json"
        with (self.out / "trace_reduce.log").open("w") as f:
            rc = subprocess.run(
                [sys.executable, "-m", "harness.trace_reduce", str(pdir),
                 "--json", str(outp)], cwd=CHIP_DIR, stdout=f,
                stderr=subprocess.STDOUT, timeout=600,
                env=launcher.child_env({"JAX_PLATFORMS": "cpu"})).returncode
        if rc != 0:
            if self.args.rehearse:
                say("info: rehearsal: the CPU trace has no device plane; "
                    "no device metric is printed")
                shutil.rmtree(pdir, ignore_errors=True)
                return None
            raise BenchFailure(f"trace reduction exited {rc}",
                               child="trace reduction",
                               log=self.out / "trace_reduce.log")
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(pdir, ignore_errors=True)
        return load_json(outp)


def result_line(run: reducers.RunData, names: list[dict], group: str,
                setup_s: float) -> dict:
    kind = "e2e_metrics" if group == "end_to_end" else "layer_metrics"
    out = {}
    for m in names:
        if m["name"] == "setup_s":      # the harness's own clock, no reader
            out["setup_s"] = {"value": setup_s, "unit": "s"}
            continue
        try:
            v = reducers.compute(kind, m["name"], run)
        except metrics.TooFewSamples as e:
            if group == "end_to_end":
                raise
            say(f"info: {m['name']} left out: {e}")
            continue
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def write_rows(out: Path, run: reducers.RunData) -> None:
    with (out / "requests.jsonl").open("w") as f:
        for r in run.records:
            f.write(json.dumps(r.to_json(with_ids=r.check)) + "\n")
    for node, sc in run.scrapes.items():
        for when, text in sc.items():
            (out / f"{node}_metrics_{when}.txt").write_text(text)
    (out / "traces.json").write_text(json.dumps(run.traces))


def info_lines(run: reducers.RunData) -> None:
    recs, w = run.records, run.seconds
    win = metrics.window_records(recs, w)
    tt = [t for t in metrics.ttfts(recs, w) if t != float("inf")]
    late = metrics.lateness(recs, w)
    say(f"info: requests: {len(recs)} sent in all, {len(win)} due in the "
        f"window, {sum(1 for r in win if r.ok)} of those completed; "
        f"frames/token {metrics.frames_per_token(recs):.4f}; tokens in "
        f"window {metrics.tokens_in_window(recs, w)}; gaps "
        f"{len(metrics.gaps(recs, w))}")
    if tt:
        tt.sort()
        say(f"info: ttft ms min/median/max {tt[0] * 1e3:.1f}/"
            f"{tt[len(tt) // 2] * 1e3:.1f}/{tt[-1] * 1e3:.1f} over {len(tt)}")
    if late:
        late.sort()
        hist = [sum(1 for x in late if lo <= x * 1e3 < hi) for lo, hi in
                ((-1e9, 1), (1, 5), (5, 20), (20, 100), (100, 1e9))]
        say(f"info: generator lateness ms histogram [<1, 1-5, 5-20, 20-100, "
            f">=100]: {hist}; max {late[-1] * 1e3:.2f}")
    duty = [ln for ln in run.scrapes["worker"].get("end", "").splitlines()
            if ln.startswith("crowdllama_engine_duty_cycle") and
            not ln.endswith(" 0")]
    if duty:
        say("info: worker's own host-clock duty cycle (NOT a device metric; "
            "beside the trace's busy share for ROADMAP S6): "
            + "; ".join(duty))


def profiler_cost_line(run: reducers.RunData, traced_at: tuple) -> None:
    """What the profiler costs while it is on: the gap tail and the tokens
    per second of the traced seconds after the window, beside the
    window's own (printed, never a metric)."""
    a, b = traced_at
    gaps, tokens = [], 0
    for r in run.records:
        ts = metrics.token_times(r)
        gaps.extend(y - x for x, y in zip(ts, ts[1:]) if a <= y < b)
        tokens += sum(1 for t in ts if a <= t < b)
    w = run.seconds
    if gaps:
        say(f"info: profiler on for {b - a:.2f}s from {a - w:.2f}s after the "
            f"window: itl_p95_ms "
            f"{1e3 * metrics.percentile(gaps, 0.95, 0):.1f} over "
            f"{len(gaps)} gaps, out_tokens_per_s {tokens / (b - a):.1f}; "
            f"the window's: itl_p95_ms "
            f"{1e3 * metrics.percentile(metrics.gaps(run.records, w), 0.95, 0):.1f}"
            f", out_tokens_per_s "
            f"{metrics.tokens_in_window(run.records, w) / w:.1f}")


async def sweep(r: Run, rates: list[float], seconds: float) -> None:
    """One set-up, one timeline per rate, lowest first; stops at the first
    rate at which a request fails (past its capacity the worker starts to
    refuse requests, and what it holds afterwards is no longer a clean
    state to measure the next rate on)."""
    say("sweep: rate_per_s offered completed_share drain_s failed ttft_p50_ms "
        "ttft_p80_ms ttft_p90_ms itl_p95_ms queue_wait_ms_first_half "
        "queue_wait_ms_second_half tokens_per_s")
    for rate in sorted(rates):
        run = await r.measure({**r.traffic, "rate_per_s": rate}, seconds)
        (r.out / f"rate{rate}").mkdir(exist_ok=True)
        write_rows(r.out / f"rate{rate}", run)
        win = metrics.window_records(run.records, seconds)
        done = sum(1 for x in win if x.ok)
        drain = max((x.done_t for x in win if x.ok), default=0.0) - seconds
        failed = sum(1 for x in run.records if not x.ok)
        tt = metrics.ttfts(run.records, seconds)
        halves = [[], []]
        for t in run.traces["worker"]:
            q = sum(sp["dur_us"] for sp in t["spans"]
                    if sp["name"] == "worker_queue") / 1e3
            halves[t["started_at"] - r.window_unix0 >= seconds / 2].append(q)
        mean = lambda xs: sum(xs) / len(xs) if xs else float("nan")
        say(f"sweep: {rate} {len(win)} {done / max(1, len(win)):.3f} {drain:.1f} {failed} "
            f"{1e3 * metrics.percentile(tt, 0.5, 0):.1f} "
            f"{1e3 * metrics.percentile(tt, 0.8, 0):.1f} "
            f"{1e3 * metrics.percentile(tt, 0.9, 0):.1f} "
            f"{1e3 * metrics.percentile(metrics.gaps(run.records, seconds), 0.95, 0):.1f} "
            f"{mean(halves[0]):.1f} {mean(halves[1]):.1f} "
            f"{metrics.tokens_in_window(run.records, seconds) / seconds:.1f}")
        if failed:
            say(f"sweep: stopped: {failed} request(s) failed at {rate}/s")
            break
        await asyncio.sleep(2.0)


def report_failure(e: Exception, args, run: "Run | None") -> None:
    """Why the run gave no result, where the record can keep it: one line
    on standard error that starts ``benchmark failed:`` and, once the run
    has a directory, ``failure.json`` in it with the last lines of the
    logs of the child the failure names and of every child that had
    exited."""
    log = getattr(e, "log", None)
    exited = run.nodes.died if run else []
    rec = {
        "message": str(e), "kind": type(e).__name__,
        "workload": args.workload, "seed": args.seed, "trace": args.trace,
        "rehearse": args.rehearse,
        "after_s": round(time.monotonic() - T_PROCESS_START, 1),
        "child": getattr(e, "child", None),
        "log": str(log) if log else None,
        "log_tail": launcher.tail(log) if log else "",
        "children_exited": [
            {"name": n, "returncode": rc, "log_tail": launcher.tail(lg)}
            for n, rc, lg in exited],
        "traceback": "" if isinstance(e, BenchFailure)
        else traceback.format_exc(),
    }
    parts = [f"{rec['kind']}: {rec['message']}"]
    if exited:
        parts.append("children that had exited: " + ", ".join(
            f"{n} (code {rc})" for n, rc, _ in exited))
    last = [ln for ln in (rec["log_tail"] or "\n".join(
        c["log_tail"] for c in rec["children_exited"])).splitlines()
        if ln.strip()][-3:]
    if last:
        parts.append("last log lines: " + " / ".join(last))
    if run:
        path = run.out / "failure.json"
        path.write_text(json.dumps(rec, indent=1))
        parts.append(f"see {path.relative_to(ROOT)}")
    if rec["traceback"]:        # not a failure the harness foresaw
        print(rec["traceback"], file=sys.stderr, end="")
    line = "; ".join(parts).replace("\r", " ").replace("\n", " / ")
    print("benchmark failed: " + line[:4000], file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--worker-flag", action="append",
                    help="extra worker CLI flag (experiments only)")
    args = ap.parse_args()
    # a terminated run still stops its children (the finally below)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    r: Run | None = None
    try:
        bench, cell, config, traffic = load_cell(args.workload, args.rehearse)
        seconds = args.seconds or float(bench["run_seconds"])
        r = Run(args, cell, config, traffic)
        try:
            r.start_system()
            if args.sweep:
                asyncio.run(sweep(r, [float(x) for x in
                                      args.sweep.split(",")], seconds))
                return 0
            run = asyncio.run(r.measure(traffic, seconds))
        finally:
            r.nodes.stop()
        write_rows(r.out, run)
        info_lines(run)
        if r.traced_at:
            profiler_cost_line(run, r.traced_at)
        one = metrics.one_frame_streams(run.records)
        if one:
            raise BenchFailure(
                f"{len(one)} streams of more than one token arrived as ONE "
                f"frame: the client cannot time their tokens")
        n_dev, peak = device_of(run.scrapes["worker"]["final"])
        if r.traced:
            run.profile = r.run_trace_reduce()
            if run.profile:
                say(f"info: trace: device busy {run.profile['busy_s']:.4f}s of "
                    f"{run.profile['window_s']:.4f}s traced")
        correct, check = r.reference_check(run)
        run.device_kind = r.device["kind"]
        groups = {0: ["end_to_end"], 1: ["per_layer"],
                  2: ["end_to_end", "per_layer"]}[args.trace]
        window = metrics.window_records(run.records, seconds)
        device = dict(r.device, memory_peak_bytes=peak)
        if check["device"] != r.device or (
                not args.rehearse and device["count"] != n_dev):
            raise BenchFailure(
                f"devices disagree: worker {r.device} (memory on {n_dev}), "
                f"reference check {check['device']}")
        line = {
            "correct": bool(correct),
            "attempted": len(window),
            "failed": sum(1 for x in window if not x.ok),
            "metrics": {k: v for group in groups for k, v in result_line(
                run, cell_metrics(bench, group, cell["name"]), group,
                r.setup_s).items()},
            "device": device,
        }
        if run.profile:
            device["busy_s"] = run.profile["busy_s"]
            device["window_s"] = run.profile["window_s"]
            line["breakdown"] = run.profile["breakdown"]
        (r.out / "result.json").write_text(json.dumps(line, indent=1))
        print(json.dumps(line), flush=True)
        return 0
    except Exception as e:      # the command's boundary: say why, exit 1
        report_failure(e, args, r)
        return 1


if __name__ == "__main__":
    sys.exit(main())
