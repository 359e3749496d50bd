"""What stands where bench.py's fallback machinery was: a run that wants
the chip and finds none fails, a failed phase makes the exit code non-zero,
every result line names its device, nothing assumes a device it cannot ask,
the compile cache goes where it is placed, a consumer node never touches
JAX, and the no-checkpoint quantized init is seeded and never builds the
bf16 tree."""

import json
import subprocess
import sys
import types
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))

import bench  # noqa: E402


@pytest.fixture
def bench_run(monkeypatch, tmp_path):
    """Run bench.main() with the given phases; returns (SystemExit code or
    None, emitted result dicts)."""
    monkeypatch.setattr(bench, "PARTIAL_PATH", tmp_path / "partial.jsonl")

    def run(phases: str, capsys):
        monkeypatch.setenv("CROWDLLAMA_BENCH_PHASES", phases)
        code = None
        try:
            bench.main()
        except SystemExit as e:
            code = e.code
        lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("{")]
        return code, lines

    return run


def test_run_that_wants_the_chip_and_finds_none_fails(
        bench_run, monkeypatch, capsys):
    # Not pinned to cpu == the operator wants the chip; JAX here has none.
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ran = []
    monkeypatch.setattr(bench, "_swarm_phase", lambda: ran.append(1) or {})
    code, lines = bench_run("swarm", capsys)
    assert code and "wants the chip" in str(code)
    assert not ran and not lines  # failed at once: no phase, no result


def test_chip_phase_on_a_cpu_pinned_run_fails(bench_run, monkeypatch,
                                              capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code, lines = bench_run("decode8b,swarm", capsys)
    assert code and "decode8b" in str(code)
    assert not lines  # no stand-in number under the chip metric's name


def test_failed_phase_makes_the_exit_code_nonzero_and_lines_name_the_device(
        bench_run, monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    def boom():
        raise RuntimeError("phase blew up")

    monkeypatch.setattr(bench, "_swarm_phase", boom)
    monkeypatch.setattr(bench, "_ep_dispatch_phase",
                        lambda: {"metric": "m", "value": 1.0})
    code, lines = bench_run("swarm,ep_dispatch", capsys)
    assert code and "swarm" in str(code)  # non-zero, names the failure
    # The later phase still ran, and its line names the device.
    assert [ln["metric"] for ln in lines] == ["m"]
    d = jax.devices()
    assert lines[0]["device"] == {"platform": "cpu",
                                  "device_kind": d[0].device_kind,
                                  "count": len(d)}
    # Persisted as it was printed.
    assert json.loads(bench.PARTIAL_PATH.read_text())["device"]["count"] \
        == len(d)


def test_benchmark_scripts_stamp_their_device(capsys):
    import _common

    _common.emit({"metric": "x", "value": 2})
    line = json.loads(capsys.readouterr().out.strip())
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "device_kind", "count"}


def test_capacity_and_roofline_fail_on_a_device_they_do_not_know():
    import capacity

    # The CPU device reports no HBM size: an error, not an assumed v5e.
    with pytest.raises(RuntimeError, match="reports no HBM"):
        capacity.report()
    # No practical ceiling recorded for device_kind "cpu".
    with pytest.raises(KeyError, match="device_kind"):
        bench._roofline_accounting(
            types.SimpleNamespace(params={}, max_slots=1), None, "bf16",
            1.0, 1, 1.0, 1)


def test_compile_cache_helper_honours_env_else_fixed_checkout_path(
        monkeypatch):
    from crowdllama_tpu.utils import jaxcache

    def configured():
        return getattr(jax.config, jaxcache.CACHE_OPTION)

    before = configured()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    assert jaxcache.enable_compile_cache() == "/somewhere/placed"
    assert configured() == before  # JAX's own handling stands
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert jaxcache.enable_compile_cache() == str(REPO / ".jax_cache")
        assert configured() == str(REPO / ".jax_cache")
    finally:
        jax.config.update(jaxcache.CACHE_OPTION, before)  # as found


_CONSUMER_SCRIPT = """
import asyncio, sys
from crowdllama_tpu.config import Configuration
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.obs.http import node_metric_lines
from crowdllama_tpu.peer.peer import Peer
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

async def main():
    cfg = Configuration(listen_host="127.0.0.1")
    peer = Peer(Ed25519PrivateKey.generate(), cfg, engine=FakeEngine(models=[]),
                worker_mode=False)
    await peer.start()
    try:
        peer.update_metadata()
        assert peer.resource.accelerator == "", peer.resource.accelerator
        gw = Gateway(peer, port=0)
        resp = await gw.handle_metrics(None)
        text = resp.text + "\\n".join(node_metric_lines(peer))
        assert 'crowdllama_device_memory_bytes_limit{device="0"} 0' in text
    finally:
        await peer.stop()

asyncio.run(main())
if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), "backend initialized"
print("clean")
"""


def test_consumer_peer_start_leaves_jax_backends_uninitialised():
    """Gateway/consumer nodes (and their /metrics) never initialize a JAX
    backend: on the chip it belongs to the worker process beside them."""
    proc = subprocess.run([sys.executable, "-c", _CONSUMER_SCRIPT],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("clean")


def test_load_params_for_random_init_is_seeded_and_never_builds_bf16_tree(
        monkeypatch):
    import numpy as np

    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.engine import weights
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.ops import quant

    cfg = weights.resolve_clamped_model_config(
        Configuration(model="tiny-test", quantize="int8"))
    real_init = T.init_params

    def abstract_only(cfg_, key, *a, **kw):
        assert isinstance(key, jax.core.Tracer), (
            "init_params ran concretely: the bf16 tree was materialized")
        return real_init(cfg_, key, *a, **kw)

    monkeypatch.setattr(T, "init_params", abstract_only)
    monkeypatch.setattr(quant, "quantize_params", lambda *a, **k: (
        pytest.fail("quantize-after-init ran")))
    config = Configuration(model="tiny-test", quantize="int8")
    a = weights.load_params_for(config, cfg)
    b = weights.load_params_for(config, cfg)
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb) > 0
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert any(x.dtype == np.int8 for x in la)
