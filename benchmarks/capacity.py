"""Largest-model-per-chip capacity report (BASELINE config 2/3 feasibility).

Static accounting of parameter + KV-cache bytes for every registry model
against the attached accelerator's HBM, in bf16 and int8 (ops/quant.py).
Answers "which BASELINE configs fit one chip" without downloading weights —
the same accounting the scheduler needs for placement.

Prints ONE JSON line; value is the largest-servable model's parameter count
(billions) on one chip under int8.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import _common  # noqa: E402 - repo path + compile cache bootstrap

import json
import os


def model_bytes(cfg, quant: bool, bits: int = 8) -> tuple[int, int]:
    """(param_bytes, kv_bytes_per_slot_at_max_ctx)."""
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, nl = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    dh = cfg.resolved_head_dim()
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    if cfg.is_moe:
        mlp = cfg.num_experts * 3 * d * f + d * cfg.num_experts
    else:
        mlp = 3 * d * f
    norms = 2 * d + (2 * d if cfg.post_norms else 0)
    per_layer = attn + mlp + norms
    embed = v * d
    head = 0 if cfg.tie_word_embeddings else d * v
    matmul_params = nl * (attn + mlp)  # quantizable
    other_params = nl * norms + embed + head + d
    wbytes = (bits / 8) if quant else 2
    param_bytes = int(matmul_params * wbytes) + other_params * 2
    if quant:
        if bits == 8:  # per-output-channel bf16 scales
            per_ch = nl * (h * dh + 2 * hkv * dh + d
                           + (3 * f if not cfg.is_moe
                              else cfg.num_experts * 3 * f))
            param_bytes += per_ch * 2
        else:  # int4: one bf16 scale per 64-weight group
            param_bytes += (matmul_params // 64) * 2
    kv_bytes = nl * hkv * cfg.max_context_length * dh * 2 * 2  # k+v bf16
    return param_bytes, kv_bytes


def report() -> dict:
    """The capacity result for the device this process holds.  A device
    that does not report its HBM is an error, not an assumed v5e."""
    from crowdllama_tpu.models.config import get_config, list_models
    from crowdllama_tpu.peer.peer import _tpu_capabilities

    caps = _tpu_capabilities()
    hbm_gb = caps["hbm_gb_per_chip"]
    if not hbm_gb:
        raise RuntimeError(
            f"device {caps['accelerator']!r} reports no HBM size; capacity "
            f"accounting needs the chip it is asked about")
    budget = hbm_gb * (1 << 30) * 0.9  # leave 10% for XLA scratch
    slots = int(os.environ.get("CROWDLLAMA_BENCH_SLOTS", "8"))

    rows, best = [], None
    for name in list_models():
        if name.startswith("tiny-test"):
            continue
        cfg = get_config(name)
        pb16, kv = model_bytes(cfg, quant=False)
        pb8, _ = model_bytes(cfg, quant=True)
        pb4, _ = model_bytes(cfg, quant=True, bits=4)
        kv_per_tok = kv / cfg.max_context_length
        fits16 = pb16 + slots * kv < budget
        fits8 = pb8 + slots * kv < budget
        # Largest power-of-two context at which params + slots*KV fit (int8).
        ctx_fit = 0
        c = cfg.max_context_length
        while c >= 128:
            if pb8 + slots * kv_per_tok * c < budget:
                ctx_fit = c
                break
            c //= 2
        params_b = round((pb16 / 2) / 1e9, 2)
        rows.append({"model": name, "params_b": params_b,
                     "bf16_gb": round(pb16 / 2**30, 1),
                     "int8_gb": round(pb8 / 2**30, 1),
                     "int4_gb": round(pb4 / 2**30, 1),
                     "fits_int4": pb4 + slots * kv < budget,
                     "kv_gb_at_max_ctx_x%d" % slots: round(slots * kv / 2**30, 1),
                     "fits_bf16": fits16, "fits_int8": fits8,
                     "max_ctx_fit_int8": ctx_fit})
        if ctx_fit and (best is None or params_b > best[1]):
            best = (name, params_b)
        print(f"# {name}: {params_b}B params, bf16 {pb16/2**30:.1f} GiB "
              f"(fits={fits16}), int8 {pb8/2**30:.1f} GiB (fits={fits8}, "
              f"ctx<={ctx_fit})", file=sys.stderr)

    return {
        "metric": f"largest model servable on one chip ({hbm_gb:.0f} GiB HBM, int8)",
        "value": best[1] if best else 0.0,
        "unit": "B params",
        "vs_baseline": None,
        "extra": {"model": best[0] if best else None, "slots": slots,
                  "accelerator": caps.get("accelerator"), "rows": rows},
    }


if __name__ == "__main__":
    _common.emit(report())
