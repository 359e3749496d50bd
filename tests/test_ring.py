"""Sequence-parallel attention correctness on the virtual 8-device mesh.

Ring attention (prefill) and distributed flash-decoding (decode) must match
the dense single-device ops bit-for-bit up to fp32 reduction order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.ops.attention import decode_attention, prefill_attention
from crowdllama_tpu.ops.ring import ring_prefill_attention, sp_decode_attention
from crowdllama_tpu.parallel.mesh import build_mesh


def _qkv(rng, b, t, h, hkv, dh):
    q = jnp.asarray(rng.standard_normal((b, t, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, hkv, dh)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("spec,h,hkv,softcap,window", [
    ("1x4x1x2", 4, 2, 0.0, 0),     # sp=4, tp=2, local kv = 1
    ("2x2x1x2", 8, 4, 0.0, 0),     # dp=2, sp=2, tp=2, local kv = 2 (GQA)
    ("1x8x1x1", 4, 2, 30.0, 16),   # sp=8, softcap + sliding window
])
def test_ring_prefill_matches_dense(spec, h, hkv, softcap, window):
    b, t, dh = 2, 64, 8
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, b, t, h, hkv, dh)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    # Mark a padding tail on sequence 1 to exercise kv_valid.
    kv_valid = jnp.asarray(np.stack([
        np.ones(t, bool),
        np.arange(t) < t - 10,
    ]))
    scale = dh ** -0.5

    # Dense reference takes head-major KV; ring takes sequence-major.
    want = prefill_attention(q, k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), positions, scale,
                             softcap=softcap, sliding_window=window,
                             kv_valid=kv_valid)

    mesh = build_mesh(spec)
    got = jax.jit(
        lambda *a: ring_prefill_attention(
            *a, scale, mesh, softcap=softcap, sliding_window=window,
            kv_valid=kv_valid,
        )
    )(q, k, v, positions)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("spec,h,hkv,softcap,window", [
    ("1x4x1x2", 4, 2, 0.0, 0),
    ("1x2x1x2", 8, 4, 0.0, 0),     # local kv = 2 (GQA under tp)
    ("2x4x1x1", 4, 2, 50.0, 12),
])
def test_sp_decode_matches_dense(spec, h, hkv, softcap, window):
    b, s, dh = 2, 32, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, h, dh)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, hkv, s, dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, hkv, s, dh)), jnp.float32)
    seq_lens = jnp.asarray([s, 17], jnp.int32)  # one full, one partial
    scale = dh ** -0.5

    want = decode_attention(q, kc, vc, seq_lens, scale, softcap=softcap,
                            sliding_window=window)

    mesh = build_mesh(spec)
    got = jax.jit(
        lambda *a: sp_decode_attention(
            *a, scale, mesh, softcap=softcap, sliding_window=window,
        )
    )(q, kc, vc, seq_lens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_runner_sp_matches_dense_greedy():
    """End-to-end: a sequence-parallel ModelRunner generates the same greedy
    tokens as the unsharded one."""
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=64)
    params = T.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    prompt = list(range(1, 20))

    def run(mesh_spec):
        r = ModelRunner(cfg, params=dict(params), mesh_spec=mesh_spec,
                        max_slots=2, max_seq=64, dtype=jnp.float32)
        state = r.init_state()
        first, ks, vs, plen = r.prefill(prompt, 0.0, 1.0, jax.random.PRNGKey(0))
        state = r.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
        toks, state = r.decode_steps(state, 8)
        return [int(first)] + [int(t) for t in toks[:, 0]]

    base = run("1x1x1x1")
    sp = run("1x4x1x2")  # sp=4, tp=2
    assert base == sp, f"greedy mismatch: {base} vs {sp}"
