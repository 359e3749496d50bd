"""The int8 grouped-matmul kernel (ops/pallas/moe.py) in interpret mode on
the CPU, against ``lax.ragged_dot`` over the dequantized bank computed in
float32, and the paths ``qragged_dot`` takes.

Widths are the cells' divided by 8 or 16, so the tile structure is the
cells': Mixtral's 4096 x 14336 becomes 512 x 1792 (``d_out`` = 14 lane
tiles, not a power of two), Nemotron's 1024 x 2688 becomes 128 x 384 with
``d_in`` 384 = three chunks on the way back.  A case's ``tiles`` stand in
for ``choose_tiles`` where several ``d_in`` and column tiles are wanted and
the chosen ones would be the whole matrix.

This is the gate on the arithmetic (float32 accumulation, the scale
applied to the float32 product): the chip's ``correct`` compares emitted
tokens and passes a lower precision unseen (PERF.md §7 item 2).
"""

import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from crowdllama_tpu.models import transformer as T  # noqa: E402
from crowdllama_tpu.models.config import ModelConfig  # noqa: E402
from crowdllama_tpu.ops import quant  # noqa: E402
from crowdllama_tpu.ops.pallas import moe  # noqa: E402
from crowdllama_tpu.ops.quant import (  # noqa: E402
    LayerOf,
    QTensor,
    dequant,
    qragged_dot,
    quantize_weight,
    quantize_weight_int4,
    ragged_dot_path,
)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")


def _bank(e, d_in, d_out, layers=None, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (e, d_in, d_out) if layers is None else (layers, e, d_in, d_out)
    q = jax.random.randint(k[0], shape, -127, 128, dtype=jnp.int8)
    s = (jax.random.uniform(k[1], shape[:-2] + (d_out,), minval=0.5,
                            maxval=1.5) / (127 * np.sqrt(d_in)))
    return QTensor(q=q, s=s.astype(jnp.bfloat16))


def _reference(xs, w: QTensor, group_sizes):
    """``lax.ragged_dot`` over ``q * s`` in float32, scale unrounded."""
    bank = w.q.astype(jnp.float32) * w.s.astype(jnp.float32)[..., None, :]
    with jax.default_matmul_precision("highest"):
        return jax.lax.ragged_dot(xs.astype(jnp.float32), bank,
                                  jnp.asarray(group_sizes, jnp.int32))


def _spread(total, groups, seed, empty=0):
    """``total`` rows over ``groups`` groups at random, ``empty`` of them
    forced to none."""
    rng = np.random.default_rng(seed)
    open_ = rng.permutation(groups)[empty:]
    return np.bincount(rng.choice(open_, total), minlength=groups)


# (rows, groups, d_in, d_out, group sizes, tiles, stacked (layers, layer))
CASES = {
    "mixtral_decode_32_rows_8_groups":
        (32, 8, 512, 1792, _spread(32, 8, 1), None, None),
    "mixtral_decode_down":
        (32, 8, 1792, 512, _spread(32, 8, 2), None, None),
    "nemotron_decode_704_rows_176_live_empty_groups_zero_tail":
        (704, 128, 128, 384, _spread(176, 128, 3, empty=31), None, None),
    "nemotron_decode_down_three_chunks":
        (704, 128, 384, 128, _spread(176, 128, 4, empty=31), None, None),
    "mixtral_prefill_256_rows":
        (256, 8, 512, 1792, _spread(256, 8, 5), (32, 256, 896), None),
    "nemotron_prefill_2816_rows_704_live":
        (2816, 128, 128, 384, _spread(704, 128, 6), None, None),
    "one_group_holds_every_row":
        (64, 8, 256, 256, [0, 0, 0, 64, 0, 0, 0, 0], (32, 128, 128), None),
    "a_group_straddles_a_row_tile":
        (64, 4, 256, 256, [20, 30, 1, 13], (32, 256, 128), None),
    "zero_live_rows":
        (64, 8, 256, 256, [0] * 8, None, None),
    "rows_not_a_multiple_of_the_row_tile":
        (48, 8, 256, 256, [5, 0, 3, 0, 20, 0, 0, 2], None, None),
    "long_prefill_row_tile_128":
        (3072, 8, 256, 256, _spread(3072, 8, 7), None, None),
    "stacked_leaf_with_a_layer_index":
        (64, 4, 256, 384, [1, 22, 3, 14], (32, 128, 128), (3, 2)),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_ragged_dot_over_the_dequantized_bank(
        interpret, monkeypatch, name):
    m, e, d_in, d_out, sizes, tiles, stacked = CASES[name]
    if tiles:
        monkeypatch.setattr(moe, "choose_tiles", lambda *a: tiles)
    sizes = jnp.asarray(sizes, jnp.int32)
    xs = jax.random.normal(jax.random.PRNGKey(9), (m, d_in),
                           jnp.float32).astype(jnp.bfloat16)
    if stacked is None:
        w = _bank(e, d_in, d_out)
        got = jax.jit(lambda xs, w, g: moe.moe_grouped_matmul(
            xs, w.q, w.s, g))(xs, w, sizes)
        ref = _reference(xs, w, sizes)
    else:
        layers, layer = stacked
        w = _bank(e, d_in, d_out, layers=layers)
        got = jax.jit(lambda xs, w, g, li: moe.moe_grouped_matmul(
            xs, w.q, w.s, g, li))(xs, w, sizes, jnp.int32(layer))
        ref = _reference(
            xs, jax.tree_util.tree_map(lambda a: a[layer], w), sizes)
    assert got.shape == (m, d_out) and got.dtype == xs.dtype
    live = int(sizes.sum())
    got = np.asarray(got.astype(jnp.float32))
    ref = np.asarray(ref)
    assert not got[live:].any(), "rows past the last group must be zero"
    # float32 accumulation, float32 scale, ONE rounding to bf16 at the
    # store: half a bf16 ulp (2^-9) of the value, and nothing else
    np.testing.assert_allclose(got, ref, rtol=2 ** -8, atol=1e-6)
    # and closer to the float32 reference than today's path, which rounds
    # q * s to bf16 before the dot
    if live:
        old = jax.lax.ragged_dot(xs, dequant(
            w if stacked is None else jax.tree_util.tree_map(
                lambda a: a[stacked[1]], w)), sizes)
        old = np.asarray(old.astype(jnp.float32))
        assert np.abs(got - ref).mean() <= np.abs(old - ref).mean() * 1.05


@pytest.mark.parametrize("shape", [
    (32, 8, 4096, 14336, 32), (32, 8, 14336, 4096, 32),      # Mixtral decode
    (256, 8, 4096, 14336, 64), (3072, 8, 14336, 4096, 128),  # and prefills
    (704, 128, 1024, 2688, 32), (704, 128, 2688, 1024, 32),  # Nemotron
    (2816, 128, 1024, 2688, 64), (2816, 128, 2688, 1024, 64),
], ids=lambda s: "x".join(map(str, s)))
def test_tiles_come_from_the_shapes(shape):
    """The cells' shapes (PERF.md §4): a 32-row tile at decode, 64 at a
    128-token prefill, 128 in a long prefill; bank tiles that divide the
    matrix, under the budget the kernel states."""
    m, e, d_in, d_out, row_tile = shape
    tm, tk, tn = moe.choose_tiles(m, e, d_in, d_out)
    assert tm == row_tile
    assert d_in % tk == 0 and d_out % tn == 0 and tk % 128 == 0
    assert tn % 128 == 0 and tk * tn <= moe._BANK_TILE_ELEMS
    assert tk % moe._k_chunk(tk) == 0
    assert moe._vmem_bytes(tm, tk, tn, 2) <= moe._VMEM_BUDGET_BYTES


def _has_kernel(fn, *args) -> bool:
    # a fresh function: the trace cache does not key on the environment
    return "moe_grouped_matmul" in str(
        jax.make_jaxpr(lambda *a: fn(*a))(*args))


def _int8(e=4, d_in=128, d_out=256, **kw):
    return replace(_bank(e, d_in, d_out), **kw)


FALLBACKS = {
    # name: (bank, interpret forced, path, kernel in the program)
    "int8_on_one_device_forced_interpret":
        (lambda: _int8(), True, "int8_kernel"),
    "int8_backend_not_tpu":
        (lambda: _int8(), False, "dequant_ragged_dot"),
    "int8_placed_on_a_mesh_of_four":
        (lambda: _int8(mesh_devices=4), True, "dequant_ragged_dot"),
    "int8_dims_not_multiples_of_128":
        (lambda: _int8(d_in=64, d_out=96), True, "dequant_ragged_dot"),
    "int4_group_wise_scales":
        (lambda: quantize_weight_int4(jax.random.normal(
            jax.random.PRNGKey(0), (4, 128, 256))), True,
         "dequant_ragged_dot"),
    "bf16_bank":
        (lambda: jax.random.normal(jax.random.PRNGKey(0), (4, 128, 256),
                                   jnp.bfloat16), True, "ragged_dot"),
}


@pytest.mark.parametrize("name", list(FALLBACKS))
def test_each_input_takes_the_path_the_table_names(monkeypatch, name):
    make, forced, path = FALLBACKS[name]
    if forced:
        monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET", raising=False)
    w = make()
    got, why = ragged_dot_path(w)
    assert got == path and bool(why) == (path != "int8_kernel")
    d_in = w.shape[-2]
    xs = jnp.ones((32, d_in), jnp.bfloat16)
    sizes = jnp.asarray([8, 0, 20, 4], jnp.int32)
    assert _has_kernel(qragged_dot, xs, w, sizes) == (path == "int8_kernel")
    # every path is the same product
    out = qragged_dot(xs, w, sizes)
    ref = jax.lax.ragged_dot(xs.astype(jnp.float32),
                             dequant(w).astype(jnp.float32), sizes)
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), rtol=0.02, atol=0.02)


def test_fallback_reason_is_logged_once(monkeypatch, caplog):
    monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(quant, "_logged_fallbacks", set())
    w = _int8()
    xs = jnp.ones((32, 128), jnp.bfloat16)
    sizes = jnp.asarray([8, 0, 20, 4], jnp.int32)
    with caplog.at_level("INFO", logger=quant.__name__):
        qragged_dot(xs, w, sizes)
        qragged_dot(xs, w, sizes)
    lines = [r for r in caplog.records if "not read as int8" in r.message]
    assert len(lines) == 1 and "backend is cpu" in lines[0].getMessage()


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_placement_on_a_mesh_keeps_the_bank_off_the_kernel(interpret, mode):
    """``shard_params`` is where a placed ``QTensor`` is built: it stamps
    the mesh's size on the weight, ``tree_map``, ``jit`` and a scanned
    slice keep it, and a bank on several devices never takes the kernel
    (GSPMD does not partition a ``pallas_call``).  ``QTensor4`` is re-built
    there too and takes no kernel wherever it lies."""
    from crowdllama_tpu.parallel.mesh import build_mesh
    from crowdllama_tpu.parallel.sharding import shard_params

    params = quant.quantize_params(
        T.init_params(MIXTRAL, jax.random.PRNGKey(1), dtype=jnp.bfloat16),
        mode=mode)
    one = shard_params(params, MIXTRAL,
                       build_mesh("1", devices=jax.devices()[:1]))
    four = shard_params(params, MIXTRAL,
                        build_mesh((1, 1, 1, 2, 2), devices=jax.devices()[:4]))
    want_one = "int8_kernel" if mode == "int8" else "dequant_ragged_dot"
    for placed, n, path in ((one, 1, want_one),
                            (four, 4, "dequant_ragged_dot")):
        bank = placed["layers"]["w_gate"]
        if mode == "int8":
            assert bank.mesh_devices == n
            bank = jax.tree_util.tree_map(lambda a: a, bank)
            assert bank.mesh_devices == n
            seen = []
            jax.jit(lambda b: jax.lax.scan(
                lambda c, lp: (seen.append(lp.mesh_devices) or c, None),
                0, b)[0])(bank)
            assert seen == [n]
        assert ragged_dot_path(bank)[0] == path
        riding = set(placed["layers"]) - set(
            quant.ride_banks(placed["layers"])[0])
        assert riding == ({"w_gate", "w_up", "w_down"}
                          if path == "int8_kernel" else set())


# ------------------------------------------- through the two expert layers

# A router that chooses ALL its experts, as tests/test_hybrid.py's bf16 and
# int8 rows do: where 2 of 8 are chosen, one rounding sends a token whose
# second and third scores tie to another expert, and the comparison reads
# the choice, not the arithmetic.
MIXTRAL = ModelConfig(
    name="moe-int8-128", family="mixtral", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_layers=3, num_heads=4, num_kv_heads=2,
    num_experts=8, num_experts_per_tok=8, max_context_length=64)


def _mixtral_params():
    p = T.init_params(MIXTRAL, jax.random.PRNGKey(1), dtype=jnp.bfloat16)
    return quant.quantize_params(p)


def _prefill_logits(params, cfg=MIXTRAL):
    tokens = jnp.asarray([[257, 3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9]])
    pos = jnp.arange(tokens.shape[1])[None, :]
    return jax.jit(lambda p: T.prefill(p, cfg, tokens, pos)[0])(params)


@pytest.mark.parametrize("program", ["prefill_scan", "layer"])
def test_mixtral_int8_layer_through_the_kernel(monkeypatch, program):
    """The scanned prefill hands the kernel the stacked leaf and the layer
    index (no ``[E, d_in, d_out]`` slice in the program), and the result is
    the dequant path's to bf16 rounding."""
    params = _mixtral_params()
    if program == "layer":
        lp = T._layer_params(params["layers"], 1)
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 128), jnp.bfloat16)
        run = lambda: jax.jit(lambda lp, x: T._moe_sorted(lp, MIXTRAL, x))(
            lp, x)
    else:
        run = lambda: _prefill_logits(params)
    monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET", raising=False)
    old = np.asarray(run().astype(jnp.float32))
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    jax.clear_caches()
    new = np.asarray(run().astype(jnp.float32))
    assert np.abs(new - old).max() <= 0.05 * np.abs(old).std() + 0.02
    if program == "prefill_scan":
        tokens = jnp.zeros((1, 16), jnp.int32)
        text = str(jax.make_jaxpr(lambda p: T.prefill(
            p, MIXTRAL, tokens, jnp.arange(16)[None, :]))(params))
        assert text.count("moe_grouped_matmul") >= 3
        # the banks are not among what the scan slices a layer at a time:
        # no [E, d_in, d_out] int8 value exists in the program
        assert "i8[8,128,256]" not in text and "i8[8,256,128]" not in text
        assert "i8[3,8,128,256]" in text


def test_ride_banks_leaves_other_layers_alone(interpret):
    params = _mixtral_params()
    scanned, bind = quant.ride_banks(params["layers"])
    assert set(params["layers"]) - set(scanned) == {"w_gate", "w_up",
                                                    "w_down"}
    # the layer's index is scanned in the banks' place
    (index,) = set(scanned) - set(params["layers"])
    assert scanned[index].tolist() == [0, 1, 2]
    lp = bind({"ln1": 1, index: jnp.int32(2)})
    assert isinstance(lp["w_up"], LayerOf) and lp["w_up"].layer == 2
    assert lp["ln1"] == 1 and index not in lp
    dense = {"w_gate": quantize_weight(jnp.ones((3, 128, 256))), "ln1": 1}
    same, bind = quant.ride_banks(dense)
    assert same is dense and bind(dense) is dense


# The parity oracle for ``_moe_sorted``, and what one would run on the chip
# to debug the kernel: the banks ride the loop there too (the kernel is
# eligible), and ``_moe_dense`` reads them through ``qeinsum``.
DENSE = replace(MIXTRAL, moe_dispatch="dense")


@pytest.mark.parametrize("program", ["scan_prefill_layers",
                                     "scan_decode_layers",
                                     "paged_decode_layers"])
def test_dense_dispatch_reads_a_riding_bank(monkeypatch, program):
    """int8 banks with ``moe_dispatch="dense"`` and the kernel eligible:
    every layer loop that lets the banks ride still runs, no kernel is
    called, and the result is what the same program gives where nothing
    rides (the CPU without interpret mode: the parent's program)."""
    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.parallel.mesh import build_mesh

    params = _mixtral_params()
    prompt = [257, 3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9]
    riding = []  # whether the banks of the program's own params ride

    def rides(layers) -> bool:
        return "w_gate" not in quant.ride_banks(layers)[0]

    def serve(cls, **kw):
        # one device, as on the chip: the default mesh takes all eight
        r = cls(DENSE, params=params, max_slots=2, max_seq=64,
                dtype=jnp.bfloat16,
                mesh=build_mesh("1", devices=jax.devices()[:1]), **kw)
        assert r.moe_matmul_path == ""  # dense dispatch takes no kernel
        riding.append(rides(r.params["layers"]))
        state = r.init_state()
        first, ks, vs, plen = r.prefill(prompt, 0.0, 1.0,
                                        jax.random.PRNGKey(1), state=state)
        state = r.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
        out, state = r.decode_steps(state, 4)
        return [first] + [int(t) for t in out[:, 0]]

    def logits():
        riding.append(rides(params["layers"]))
        return np.asarray(_prefill_logits(params, DENSE).astype(jnp.float32))

    run = {
        "scan_prefill_layers": logits,
        "scan_decode_layers": lambda: serve(ModelRunner),
        "paged_decode_layers": lambda: serve(PagedModelRunner, page_size=16),
    }[program]
    monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET", raising=False)
    old = run()
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    jax.clear_caches()
    new = run()
    assert riding == [False, True]  # the second run's banks ride, as on the chip
    if program == "scan_prefill_layers":
        assert np.abs(new - old).max() <= 0.05 * np.abs(old).std() + 0.02
        tokens = jnp.zeros((1, 16), jnp.int32)
        text = str(jax.make_jaxpr(lambda p: T.prefill(
            p, DENSE, tokens, jnp.arange(16)[None, :]))(params))
        assert "moe_grouped_matmul" not in text
    else:
        assert new == old


HYBRID_PATHS = ("prefill", "decode", "ragged")


@pytest.mark.parametrize("path", HYBRID_PATHS)
def test_hybrid_int8_rows_hold_their_limit_through_the_kernel(monkeypatch,
                                                              path):
    """tests/test_hybrid.py's int8 rows, its own drive and its own limit,
    at expert widths the kernel takes (latent 128, intermediate 256; the
    tiny configuration's 32 and 48 are refused as unaligned and keep the
    dequant path in that file): the distance to the float32 reference
    stays under the limit and does not grow against the dequant path's."""
    import test_hybrid as TH

    cfg = replace(TH.ALL_CHOSEN, moe_latent_size=128,
                  moe_intermediate_size=256)

    def read(expected):
        jax.clear_caches()
        r = TH.Probe(cfg, params=TH.make_params("int8", cfg), max_slots=4,
                     max_seq=256, page_size=16, step_token_budget=36,
                     dtype=jnp.bfloat16)
        assert r.moe_matmul_path == expected
        got = [TH.distance(logits, ids, positions, r)
               for _, logits, ids, positions in TH.run_path(r, path)]
        return max(w for w, _ in got), max(m for _, m in got)

    monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET", raising=False)
    old = read("dequant_ragged_dot")
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    new = read("int8_kernel")
    worst, mean = TH.LIMITS["int8"]
    assert new[0] <= worst and new[1] <= mean, (new, old)
    assert new[0] <= 1.25 * old[0] + 0.01 and new[1] <= 1.25 * old[1] + 0.005
    print(f"# hybrid int8 {path}: dequant {old}, kernel {new}")
