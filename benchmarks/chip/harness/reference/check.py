"""The reference check, as a process of its own (it needs the chip, so it
runs after the worker has exited): rebuilds the worker's seeded weights,
teacher-forces each sampled request's prompt + emitted ids through the
plain reference, and writes every emitted token's deficit (see dense.py).

    python -m harness.reference.check --input IN.json --output OUT.json

IN: {"config": <configuration file's content>, "model_dir": ..., "rehearse":
bool, "samples": [{"prompt_ids": [...], "reply_ids": [...]}, ...]}.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time


def seeded_weights(config: dict, model_dir: str):
    """The weights the worker serves: its own no-checkpoint init from its
    fixed key, made on the device in ONE jitted call (the worker makes them
    leaf by leaf; the values are a function of the key alone)."""
    import jax

    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    b = config["bench"]
    cfg = resolve_model_config(b["name"], model_dir)
    make = jax.jit(lambda key: random_quantized_params(
        cfg, key, mode=b["quantize"]))
    return jax.block_until_ready(make(jax.random.PRNGKey(0)))


def deficits(forward, weights, hf: dict, prompt: list[int],
             reply: list[int], pad_to: int):
    """Per emitted token: (reference's best logit - reference's logit of
    the emitted token) / std of that position's logits."""
    import jax.numpy as jnp

    ids = list(prompt) + list(reply)
    positions = list(range(len(prompt) - 1, len(ids) - 1))
    padded = ids + [0] * (-len(ids) % pad_to)     # causal: padding is unseen
    logits = forward(weights, hf, padded, positions)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(reply)[:, None], axis=-1)[:, 0]
    return (jnp.max(logits, -1) - picked) / jnp.std(logits, -1)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--input", required=True)
    ap.add_argument("--output", required=True)
    args = ap.parse_args()
    with open(args.input) as f:
        job = json.load(f)
    t0 = time.monotonic()
    import jax

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu" and not job["rehearse"]:
        print(f"reference check: no TPU ({device})", file=sys.stderr)
        return 3
    config = job["config"]
    hf = {k: v for k, v in config.items() if k != "bench"}
    ref = importlib.import_module(
        f"harness.reference.{config['bench']['reference']}")
    with jax.default_matmul_precision("highest"):
        weights = seeded_weights(config, job["model_dir"])
        t_w = time.monotonic()
        out = []
        for s in job["samples"]:
            d = deficits(ref.forward, weights, hf, s["prompt_ids"],
                         s["reply_ids"], 32 if job["rehearse"] else 256)
            out.append([float(x) for x in d])
    flat = [x for d in out for x in d]
    result = {
        "device": device,
        "tokens": len(flat),
        "max_deficit": max(flat, default=0.0),
        "mean_deficit": sum(flat) / max(1, len(flat)),
        "argmax_agree_share": sum(x == 0.0 for x in flat) / max(1, len(flat)),
        "per_sample_max": [max(d, default=0.0) for d in out],
        "weights_s": t_w - t0, "forward_s": time.monotonic() - t_w,
    }
    with open(args.output, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
