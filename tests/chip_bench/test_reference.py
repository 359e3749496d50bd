"""The plain references against the program's model file at tiny size on
the CPU, on the worker's own seeded int8 weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness.reference import check, dense, moe

#: float32 reference against the program's bf16 forward at tiny size:
#: logits have a standard deviation of ~0.6 and the two differ by bf16's
#: rounding through two layers; measured 0.020 (dense) and 0.014 (MoE).
TOL = 0.06

CASES = {
    "tiny-test-mistral": (dense, {"sliding_window": 16}),
    "tiny-test-moe": (moe, {"num_local_experts": 4,
                            "num_experts_per_tok": 2}),
}


def setup(name):
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    cfg = get_config(name)
    hf = {"hidden_size": cfg.hidden_size,
          "num_attention_heads": cfg.num_heads,
          "num_key_value_heads": cfg.num_kv_heads,
          "num_hidden_layers": cfg.num_layers, "rope_theta": cfg.rope_theta,
          "rms_norm_eps": cfg.rms_norm_eps, **CASES[name][1]}
    return cfg, hf, random_quantized_params(cfg, jax.random.PRNGKey(0),
                                            mode="int8")


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_agrees_with_the_programs_forward(name):
    from crowdllama_tpu.models import transformer as T

    cfg, hf, w = setup(name)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, 48)
    with jax.default_matmul_precision("highest"):
        ref = CASES[name][0].forward(w, hf, ids.tolist(), list(range(48)))
    got, _, _ = T.prefill(w, cfg, jnp.asarray(ids)[None],
                          jnp.arange(48)[None])
    assert float(jnp.max(jnp.abs(ref - got[0]))) < TOL
    # 48 > window 16: the mask binds in the dense case
    assert float(jnp.mean(jnp.argmax(ref, -1) == jnp.argmax(got[0], -1))) > 0.9


def test_a_wrong_window_is_seen():
    cfg, hf, w = setup("tiny-test-mistral")
    ids = list(range(3, 51))
    with jax.default_matmul_precision("highest"):
        a = dense.forward(w, hf, ids, [47])
        b = dense.forward(w, {**hf, "sliding_window": 0}, ids, [47])
    assert float(jnp.max(jnp.abs(a - b))) > TOL


def test_jitted_init_gives_the_workers_weights():
    cfg, _, w = setup("tiny-test-mistral")
    from crowdllama_tpu.ops.quant import random_quantized_params

    j = jax.jit(lambda k: random_quantized_params(cfg, k, mode="int8"))(
        jax.random.PRNGKey(0))
    same = jax.tree_util.tree_map(lambda a, b: bool(jnp.array_equal(a, b)),
                                  w, j)
    assert jax.tree_util.tree_all(same)


@pytest.mark.parametrize("name", sorted(CASES))
def test_deficit_is_zero_on_the_argmax_and_positive_off_it(name):
    cfg, hf, w = setup(name)
    fwd = CASES[name][0].forward
    prompt = list(range(5, 25))
    with jax.default_matmul_precision("highest"):
        logits = fwd(w, hf, prompt + [0] * 12, [19])
        best = int(jnp.argmax(logits[0]))
        worst = int(jnp.argmin(logits[0]))
        d_best = check.deficits(fwd, w, hf, prompt, [best], 32)
        d_worst = check.deficits(fwd, w, hf, prompt, [worst], 32)
    assert float(d_best[0]) == 0.0
    assert float(d_worst[0]) > 3.0      # several standard deviations


def test_router_weights_are_a_softmax_over_the_top_two():
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    router = jnp.asarray([[3.0, 1.0, 2.0, 0.0], [0.0, 0.0, 0.0, 5.0]])
    w = moe.router_weights(x, router, 2)
    e = np.exp([3.0, 2.0])
    assert np.allclose(w[0], [e[0] / e.sum(), 0, e[1] / e.sum(), 0])
    assert np.allclose(w.sum(-1), 1.0) and float(w[1, 3]) > 0.99
