"""Pipeline parallelism correctness on the virtual 8-device mesh.

pp-sharded layer stacks + ppermute microbatch pipeline must match the dense
single-device forward exactly (same math, different schedule).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import get_config
from crowdllama_tpu.parallel.mesh import build_mesh
from crowdllama_tpu.parallel.pipeline import pp_decode_step, pp_prefill
from crowdllama_tpu.parallel.sharding import cache_sharding, shard_params

B, SEQ, S = 4, 8, 16


def _setup(name, spec):
    cfg = get_config(name)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mesh = build_mesh(spec)
    sharded = shard_params(params, cfg, mesh)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, SEQ)))
    pos = jnp.broadcast_to(jnp.arange(SEQ), (B, SEQ))
    return cfg, params, sharded, mesh, tokens, pos, rng


@pytest.mark.parametrize("name,spec", [
    ("tiny-test", "1x2x1x1x2"),        # pp=2, tp=2
    ("tiny-test", "2x2x1x1x1"),        # dp=2, pp=2
    ("tiny-test-moe", "1x2x1x2x2"),    # pp=2, ep=2, tp=2
    ("tiny-test-gemma", "1x4x1x1x2"),  # pp=4 (4 layers), tp=2
])
def test_pp_prefill_matches_dense(name, spec):
    cfg, params, sharded, mesh, tokens, pos, _ = _setup(name, spec)
    want, want_ks, _ = T.prefill(params, cfg, tokens, pos)

    got, ks, vs = jax.jit(
        lambda p, t, po: pp_prefill(p, cfg, t, po, mesh)
    )(sharded, tokens, pos)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(want_ks),
                               atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("name,spec", [
    ("tiny-test", "1x2x1x1x2"),
    ("tiny-test-moe", "1x2x1x2x1"),
])
def test_pp_decode_matches_dense(name, spec):
    cfg, params, sharded, mesh, tokens, pos, rng = _setup(name, spec)
    L, hkv, dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim()

    _, ks, vs = T.prefill(params, cfg, tokens, pos)
    kc = jnp.zeros((L, B, hkv, S, dh), jnp.float32).at[:, :, :, :SEQ].set(ks)
    vc = jnp.zeros((L, B, hkv, S, dh), jnp.float32).at[:, :, :, :SEQ].set(vs)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)))
    decode_pos = jnp.full((B,), SEQ)
    lens = jnp.full((B,), SEQ + 1)

    want, want_kc, _ = T.decode_step(params, cfg, nxt, decode_pos, kc, vc, lens)

    kc_s = jax.device_put(kc, cache_sharding(mesh))
    vc_s = jax.device_put(vc, cache_sharding(mesh))
    got, got_kc, _ = jax.jit(
        lambda p, t, po, k, v, sl: pp_decode_step(p, cfg, t, po, k, v, sl, mesh)
    )(sharded, nxt, decode_pos, kc_s, vc_s, lens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_kc), np.asarray(want_kc),
                               atol=2e-4, rtol=1e-4)


def test_runner_pp_matches_dense_greedy():
    """End-to-end: a pipeline-parallel ModelRunner generates the same greedy
    tokens as the unsharded one."""
    from crowdllama_tpu.engine.runner import ModelRunner

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

    base = run("1x1x1x1x1")
    pp = run("1x2x1x1x2")  # pp=2, tp=2
    assert base == pp, f"greedy mismatch: {base} vs {pp}"


def test_pick_n_microbatches():
    from crowdllama_tpu.parallel.pipeline import pick_n_microbatches
    assert pick_n_microbatches(8, 2) == 2
    assert pick_n_microbatches(3, 2) == 1   # non-divisible → sequential
    assert pick_n_microbatches(6, 4) == 3
    assert pick_n_microbatches(1, 8) == 1


def test_runner_pp_odd_slots():
    """max_slots not divisible by pp must still decode (n_mb falls back to a
    divisor), not crash at trace time."""
    from crowdllama_tpu.engine.runner import ModelRunner

    cfg = get_config("tiny-test", max_context_length=32)
    r = ModelRunner(cfg, mesh_spec="1x2x1x1x1", max_slots=3, max_seq=32,
                    dtype=jnp.float32)
    state = r.init_state()
    first, ks, vs, plen = r.prefill([1, 2, 3], 0.0, 1.0, jax.random.PRNGKey(0))
    state = r.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
    toks, _ = r.decode_steps(state, 2)
    assert toks.shape == (2, r.max_slots)


def test_pp_prefill_single_microbatch():
    """B=1 serving prefill: correct (sequential stages, no overlap)."""
    cfg = get_config("tiny-test")
    params = T.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    mesh = build_mesh("1x2x1x1x2")
    sharded = shard_params(params, cfg, mesh)
    tokens = jnp.asarray([[5, 9, 2, 11, 3, 1, 8, 4]])
    pos = jnp.arange(8)[None, :]
    want, _, _ = T.prefill(params, cfg, tokens, pos)
    # Partial-manual shard_map requires a jit context (as in the runner).
    got, _, _ = jax.jit(
        lambda p, t, po: pp_prefill(p, cfg, t, po, mesh))(sharded, tokens, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)
