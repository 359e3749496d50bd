"""CLI surface tests: parser wiring, version, config layering from env,
where the compile cache goes, and a consumer node that never touches JAX."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from crowdllama_tpu.cli.dht import main as dht_main
from crowdllama_tpu.cli.main import build_parser, main
from crowdllama_tpu.config import Configuration

REPO = Path(__file__).resolve().parent.parent


def test_version_command(capsys):
    assert main(["version"]) == 0
    assert "crowdllama-tpu" in capsys.readouterr().out


def test_dht_version(capsys):
    assert dht_main(["version"]) == 0
    assert "crowdllama-tpu" in capsys.readouterr().out


def test_no_command_prints_help(capsys):
    assert main([]) == 1
    assert "start" in capsys.readouterr().out


def test_start_flags_parse():
    args = build_parser().parse_args([
        "start", "--worker-mode", "--model", "llama-3-8b",
        "--bootstrap-peers", "10.0.0.1:9000,10.0.0.2:9000",
        "--mesh", "1x8", "--gateway-port", "9005",
    ])
    cfg = Configuration.from_flags(args)
    assert args.worker_mode
    assert cfg.model == "llama-3-8b"
    assert cfg.bootstrap_peers == ["10.0.0.1:9000", "10.0.0.2:9000"]
    assert cfg.mesh_shape == "1x8"
    assert cfg.gateway_port == 9005


def test_model_management_commands(tmp_path, capsys):
    """list/show/rm against a local models dir (the reference rides the
    embedded Ollama CLI's list/show/rm, cmd/crowdllama/main.go:49-78)."""
    root = tmp_path / "models"
    ck = root / "tiny-test"
    ck.mkdir(parents=True)
    (ck / "model.safetensors").write_bytes(b"x" * 2048)
    (ck / "config.json").write_text("{}")
    (root / "leftover.partial").mkdir()  # staging dirs must not list

    assert main(["list", "--models-dir", str(root)]) == 0
    out = capsys.readouterr().out
    assert "tiny-test" in out and "leftover" not in out

    assert main(["show", "tiny-test", "--models-dir", str(root)]) == 0
    out = capsys.readouterr().out
    assert "family llama" in out and str(ck) in out

    # rm validates names (no traversal) and deletes only real checkpoints.
    assert main(["rm", "..", "--models-dir", str(root)]) == 1
    assert main(["rm", "absent", "--models-dir", str(root)]) == 1
    capsys.readouterr()
    assert main(["rm", "tiny-test", "--models-dir", str(root)]) == 0
    assert not ck.exists() and root.exists()

    assert main(["list", "--models-dir", str(root)]) == 0
    assert "no local checkpoints" in capsys.readouterr().out


def test_env_layering(monkeypatch):
    monkeypatch.setenv("CROWDLLAMA_TPU_MODEL", "mixtral-8x7b")
    monkeypatch.setenv("CROWDLLAMA_TPU_BOOTSTRAP_PEERS", "a:1, b:2 ,")
    monkeypatch.setenv("CROWDLLAMA_TPU_VERBOSE", "1")
    cfg = Configuration.from_environment()
    assert cfg.model == "mixtral-8x7b"
    assert cfg.bootstrap_peers == ["a:1", "b:2"]
    assert cfg.verbose is True
    # flags override env
    args = build_parser().parse_args(["start", "--model", "tiny-test"])
    cfg = Configuration.from_flags(args)
    assert cfg.model == "tiny-test"


def test_network_status_unreachable(capsys):
    assert main(["network-status", "--gateway", "http://127.0.0.1:1"]) == 1
    assert "unreachable" in capsys.readouterr().err


async def test_run_chat_one_shot_and_history(capsys):
    """``run`` streams a chat turn through a live gateway (FakeEngine echo)
    and keeps multi-turn history."""
    import argparse

    from crowdllama_tpu.cli.main import _run_chat
    from tests.test_integration import _topology, _wait_for

    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: any(p.peer_id == worker.peer_id
                        for p in consumer.peer_manager.get_healthy_peers()),
            what="discovery",
        )
        args = argparse.Namespace(
            model="tiny-test", prompt="hello swarm",
            gateway=f"http://127.0.0.1:{gw_port}",
            temperature=0.0, top_p=1.0, max_tokens=0,
        )
        assert await _run_chat(args) == 0
        out = capsys.readouterr().out
        assert "echo:" in out and "hello swarm" in out

        # Unknown model: clean failure, non-zero exit.
        args.model = "missing-model"
        assert await _run_chat(args) == 1
    finally:
        await teardown()


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


@pytest.mark.parametrize("flag", [
    ["--megastep-k", "4"], ["--autotune"], ["--autotune-interval", "8"],
    ["--autotune-megastep-max", "8"], ["--autotune-draft-max", "4"],
    ["--autotune-budget-max", "512"], ["--autotune-prefill-max", "256"]])
def test_a_flag_of_the_second_dispatch_or_the_tuner_is_refused(flag, capsys):
    """Decode has one dispatch, ``decode_chunk`` steps a flight, and nothing
    moves it at run time: the flags that chose otherwise are no flags."""
    with pytest.raises(SystemExit) as e:
        build_parser().parse_args(["start", "--worker-mode", *flag])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


def test_their_environment_variables_choose_nothing(monkeypatch):
    import dataclasses

    want = Configuration.from_environment()
    for name in ("MEGASTEP_K", "AUTOTUNE", "AUTOTUNE_INTERVAL",
                 "AUTOTUNE_MEGASTEP_MAX", "AUTOTUNE_DRAFT_MAX",
                 "AUTOTUNE_BUDGET_MAX", "AUTOTUNE_PREFILL_MAX",
                 "AUTOTUNE_DEPTH_MAX"):
        monkeypatch.setenv(f"CROWDLLAMA_TPU_{name}", "1")
    assert Configuration.from_environment() == want
    assert not [f.name for f in dataclasses.fields(Configuration)
                if "megastep" in f.name or "autotune" in f.name]
