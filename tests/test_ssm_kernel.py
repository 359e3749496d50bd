"""The Pallas ``ssm_update`` (ops/pallas/ssm.py), in interpret mode on the
CPU, against ``ops/ssm.py`` ``ssm_update`` — which it replaces in the decode
step's programs on one TPU device — on float32 inputs: the new state and
``y`` within float32 rounding (the kernel multiplies and adds in another
order), rows with ``dt = 0`` and the other layers' slabs bit-identical, and
the gate that sends a shape the kernel refuses down the XLA path.

The chip compiler's view of the same kernel (its tiles, VMEM, the stack
updated in place inside the step programs) is tests/test_tpu_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.ops import ssm
from crowdllama_tpu.ops.pallas import ssm as kernel

# (layers, slots, heads, head dim, state, groups): a small shape, one with
# several head blocks a slot and heads that are not whole lanes, and the
# benchmark cell's tile geometry [.., 128, 64, 128] with fewer slots
SHAPES = {
    "small": (2, 4, 8, 16, 128, 2),
    "blocks": (3, 3, 48, 64, 128, 4),
    "cell": (5, 2, 128, 64, 128, 8),
}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")


def operands(shape, seed: int = 0):
    m, s, h, p, n, g = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (s, g, n))
    c = jax.random.normal(ks[4], (s, g, n))
    d = jax.random.normal(ks[5], (h,))
    stack = jax.random.normal(ks[6], (m, s, h, p, n))
    return (x, dt, a, b, c, d), stack


@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_matches_the_xla_update(shape):
    args, stack = operands(SHAPES[shape])
    layer = SHAPES[shape][0] - 1
    y_ref, state_ref = ssm.ssm_update(*args, stack[layer])
    y, out = kernel.ssm_update(*args, stack, layer)
    assert y.shape == y_ref.shape and y.dtype == out.dtype == jnp.float32
    # float32 rounding of sums of ~N products of unit scale
    np.testing.assert_allclose(out[layer], state_ref, rtol=0, atol=4e-6)
    np.testing.assert_allclose(y, y_ref, rtol=0,
                               atol=2e-5 * float(jnp.max(jnp.abs(y_ref))))


def test_head_blocks_of_the_cell_are_one_megabyte():
    assert kernel.choose_head_block(128, 64, 128) == 32
    assert kernel.choose_head_block(48, 64, 128) == 24
    assert kernel.choose_head_block(8, 16, 128) == 8


@pytest.mark.parametrize("shape", list(SHAPES))
def test_rows_with_dt_zero_keep_their_tiles_bit_identical(shape):
    (x, dt, a, b, c, d), stack = operands(SHAPES[shape], seed=1)
    dt = dt.at[1].set(0.0).at[0, ::3].set(0.0)
    _, out = kernel.ssm_update(x, dt, a, b, c, d, stack, 0)
    assert np.array_equal(out[0, 1], stack[0, 1])
    assert np.array_equal(out[0, 0, ::3], stack[0, 0, ::3])
    assert not np.array_equal(out[0, 0, 1], stack[0, 0, 1])


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("layer", ["first", "last"])
def test_the_other_layers_slabs_come_back_bit_identical(shape, layer):
    args, stack = operands(SHAPES[shape], seed=2)
    m = SHAPES[shape][0]
    i = 0 if layer == "first" else m - 1
    # the index is traced, as a program's call sites hand it over
    _, out = jax.jit(kernel.ssm_update)(*args, stack, jnp.int32(i))
    for other in range(m):
        same = np.array_equal(out[other], stack[other])
        assert same is (other != i), other


@pytest.mark.parametrize("shape,reason", [
    ((2, 4, 8, 16, 16, 2), "state size 16"),      # the tiny test model's
    ((2, 4, 8, 16, 192, 2), "state size 192"),
    ((2, 4, 8, 12, 128, 2), "head dim 12"),
])
def test_a_refused_shape_takes_the_xla_path_and_says_so(shape, reason,
                                                        monkeypatch):
    path, why = ssm.ssm_update_path(shape[1:-1])
    assert path == "xla" and reason in why
    monkeypatch.setattr(kernel, "ssm_update", None)   # would raise if called
    args, stack = operands(shape)
    y, out = ssm.ssm_update_at(*args, stack, 1)
    y_ref, state_ref = ssm.ssm_update(*args, stack[1])
    assert np.array_equal(y, y_ref) and np.array_equal(out[1], state_ref)
    assert np.array_equal(out[0], stack[0])


def test_the_gate_reads_the_backend_and_the_shape_alone(monkeypatch):
    shape = SHAPES["cell"][1:-1]
    assert ssm.ssm_update_path(shape) == ("pallas", "")
    monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET")
    assert ssm.ssm_update_path(shape) == ("xla", "backend is cpu, not tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.ssm_update_path(shape) == ("pallas", "")
    monkeypatch.setenv("CROWDLLAMA_NO_PALLAS", "1")
    assert ssm.ssm_update_path(shape)[0] == "xla"


def test_update_at_hands_an_accepted_shape_to_the_kernel():
    args, stack = operands(SHAPES["small"], seed=3)
    text = jax.jit(ssm.ssm_update_at, static_argnums=(7,)).lower(
        *args, stack, 1).as_text()
    assert "_ssm_update" in text
    y, out = ssm.ssm_update_at(*args, stack, 1)
    y_ref, state_ref = ssm.ssm_update(*args, stack[1])
    np.testing.assert_allclose(out[1], state_ref, rtol=0, atol=4e-6)
    np.testing.assert_allclose(y, y_ref, rtol=0, atol=1e-4)
