"""Deviceless TPU compiles of the main-path kernels, and of the runner's
decode and ragged step programs, at Mistral-7B widths — and, for the
expert layers, at Mixtral-8x7B's and the Nemotron configuration's.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``v5e:2x2``): what it refuses — a slice not aligned
to the tiling, too much VMEM, a kernel that cannot be partitioned — would be
refused on the chip too, and interpret-mode tests cannot see it.  Nothing
runs, so these say nothing about results or times (``chip_smoke.py`` does).

All of it lives in this ONE file, and the topology is described inside a
module-scoped fixture: only one process at a time may load the TPU's
library, so nothing here touches it at import or collection time.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from crowdllama_tpu.ops.pallas.flash import flash_prefill_attention
from crowdllama_tpu.ops.pallas.paged import (
    flash_paged_decode_attention,
    flash_paged_decode_attention_tp,
    flash_ragged_paged_attention,
)
from crowdllama_tpu.parallel.mesh import AXIS_TP

# mistral-7b (models/config.py) under the serving defaults: page 128,
# 8 slots, context 2048 (16 pages per slot), ragged chunk 512.  The pool is
# the engine's stack of layers; a few are enough to see what a layer costs.
H, HKV, DH = 32, 8, 128
PAGE, SLOTS, PAGES_PER_SLOT = 128, 8, 16
POOL_PAGES = SLOTS * PAGES_PER_SLOT + 1
LAYERS = 3
PREFILL_T, CHUNK = 2048, 512
SCALE = DH ** -0.5
WINDOW = 4096


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A deviceless compile is written to the persistent cache but cannot
    be read back without a chip (the next run would warn and recompile)."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def expert_kernel(monkeypatch):
    """The expert banks take the grouped-matmul kernel, as on the chip:
    here the backend is the CPU and the gate would refuse it."""
    from crowdllama_tpu.ops.pallas import moe

    monkeypatch.setattr(moe, "grouped_matmul_refusal",
                        lambda q_shape, n_devices=1: "")


@pytest.fixture
def state_kernel(monkeypatch):
    """The Mamba layers' decode-step update takes the ``ssm_update`` kernel,
    as on the chip (the same steering, for the same reason)."""
    from crowdllama_tpu.ops.pallas import ssm as ssm_kernel

    monkeypatch.setattr(ssm_kernel, "ssm_update_refusal",
                        lambda state_shape: "")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(shapes, one_chip, rule=True):
    """The parameters' shapes on the described chip as ``shard_params``
    places the arrays: each int8 payload in the order the SAME rule gives
    it (parallel/sharding.py ``weight_layout``, asked about the described
    device), so these tests compile what a worker on the chip serves.
    ``rule=False``: every leaf in the default layout, as before PR 41."""
    from crowdllama_tpu.ops.quant import QTensor
    from crowdllama_tpu.parallel.sharding import weight_layout

    devices = list(one_chip.device_set)

    def place(path, a):
        if not isinstance(a, QTensor):
            return _sds(a.shape, a.dtype, one_chip)
        order = rule and weight_layout(getattr(path[-1], "key", ""), a,
                                       devices)
        where = Format(Layout(major_to_minor=order),
                       one_chip) if order else one_chip
        return QTensor(q=_sds(a.q.shape, a.q.dtype, where),
                       s=_sds(a.s.shape, a.s.dtype, one_chip),
                       mesh_devices=a.mesh_devices)

    return jax.tree_util.tree_map_with_path(
        place, shapes, is_leaf=lambda x: isinstance(x, QTensor))


def _on_chip(tree, one_chip):
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)


def _pool(kv, sharding, scale_sharding=None):
    """(pool_k, pool_v, k_scale, v_scale) shapes for a bf16 or int8 pool."""
    dtype = jnp.int8 if kv == "int8" else jnp.bfloat16
    pool = _sds((LAYERS, POOL_PAGES, HKV, PAGE, DH), dtype, sharding)
    if kv != "int8":
        return pool, pool, None, None
    sc = _sds((LAYERS, POOL_PAGES, HKV, PAGE), jnp.bfloat16,
              scale_sharding or sharding)
    return pool, pool, sc, sc


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_prefill_kernel_compiles(one_chip):
    q = _sds((1, PREFILL_T, H, DH), jnp.bfloat16, one_chip)
    kv = _sds((1, HKV, PREFILL_T, DH), jnp.bfloat16, one_chip)
    pos = _sds((1, PREFILL_T), jnp.int32, one_chip)
    valid = _sds((1, PREFILL_T), jnp.bool_, one_chip)

    def f(q, k, v, pos, valid):
        return flash_prefill_attention(q, k, v, pos, SCALE,
                                       sliding_window=WINDOW, kv_valid=valid)

    _assert_kernel(jax.jit(f).lower(q, kv, kv, pos, valid).compile())


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_kernel_compiles(one_chip, kv):
    q = _sds((SLOTS, H, DH), jnp.bfloat16, one_chip)
    pk, pv, ks, vs = _pool(kv, one_chip)
    table = _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip)
    lens = _sds((SLOTS,), jnp.int32, one_chip)
    layer = _sds((), jnp.int32, one_chip)

    def f(q, pk, pv, layer, table, lens, ks, vs):
        return flash_paged_decode_attention(
            q, pk, pv, layer, table, lens, SCALE, sliding_window=WINDOW,
            k_scale=ks, v_scale=vs)

    _assert_kernel(jax.jit(f).lower(
        q, pk, pv, layer, table, lens, ks, vs).compile())


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_ragged_v2_kernel_compiles(one_chip, kv):
    q = _sds((SLOTS + CHUNK, H, DH), jnp.bfloat16, one_chip)
    pk, pv, ks, vs = _pool(kv, one_chip)
    table = _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip)
    lens = _sds((SLOTS + 1,), jnp.int32, one_chip)
    slot = _sds((), jnp.int32, one_chip)

    def f(q, pk, pv, layer, table, q_lens, kv_lens, slot, ks, vs):
        return flash_ragged_paged_attention(
            q, pk, pv, layer, table, q_lens, kv_lens, slot, SCALE,
            sliding_window=WINDOW, k_scale=ks, v_scale=vs)

    _assert_kernel(jax.jit(f).lower(
        q, pk, pv, slot, table, lens, lens, slot, ks, vs).compile())


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_tp4_wrapped_decode_kernel_compiles(topo, kv):
    """The shard_map wrapper the auto tp=4 mesh of a four-chip host takes:
    one kernel per shard over its own kv heads, no collective."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("dp", AXIS_TP))
    rep = NamedSharding(mesh, P())
    heads = NamedSharding(mesh, P(None, AXIS_TP, None))
    q = _sds((SLOTS, H, DH), jnp.bfloat16, heads)
    pk, pv, ks, vs = _pool(
        kv, NamedSharding(mesh, P(None, None, AXIS_TP, None, None)),
        NamedSharding(mesh, P(None, None, AXIS_TP, None)))
    table = _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, rep)
    lens = _sds((SLOTS,), jnp.int32, rep)
    layer = _sds((), jnp.int32, rep)

    def f(q, pk, pv, layer, table, lens, ks, vs):
        return flash_paged_decode_attention_tp(
            q, pk, pv, layer, table, lens, SCALE, mesh,
            sliding_window=WINDOW, k_scale=ks, v_scale=vs)

    compiled = jax.jit(f).lower(
        q, pk, pv, layer, table, lens, ks, vs).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    for op in ("all-gather", "all-reduce", "all-to-all"):
        assert f" {op}(" not in text, f"unexpected {op} in the tp kernel"
    # Per device: a quarter of the pool (kv heads are the sharded dim).
    ma = compiled.memory_analysis()
    per_dev_pool = 2 * LAYERS * POOL_PAGES * (HKV // 4) * PAGE * DH * (
        1 if kv == "int8" else 2)
    assert ma.argument_size_in_bytes < 1.5 * per_dev_pool


@pytest.mark.parametrize("shape", [
    (32, 8, 4096, 14336, 4), (32, 8, 14336, 4096, 4),     # Mixtral decode
    (256, 8, 4096, 14336, 4), (3072, 8, 14336, 4096, 4),  # and prefills
    (704, 128, 1024, 2688, None), (704, 128, 2688, 1024, None),  # Nemotron
    (2816, 128, 1024, 2688, None), (2816, 128, 2688, 1024, None),
], ids=lambda s: "x".join(map(str, s)))
def test_moe_grouped_matmul_compiles(one_chip, shape):
    """The int8 grouped matmul at the cells' row counts and widths, with
    the tiles it chooses: stacked leaf + layer index (Mixtral) or one
    layer's bank (Nemotron)."""
    from crowdllama_tpu.ops.pallas.moe import moe_grouped_matmul

    m, e, d_in, d_out, layers = shape
    lead = () if layers is None else (layers,)
    args = [_sds((m, d_in), jnp.bfloat16, one_chip),
            _sds((*lead, e, d_in, d_out), jnp.int8, one_chip),
            _sds((*lead, e, d_out), jnp.bfloat16, one_chip),
            _sds((e,), jnp.int32, one_chip)]
    if layers is not None:
        args.append(_sds((), jnp.int32, one_chip))
    compiled = jax.jit(moe_grouped_matmul).lower(*args).compile()
    _assert_kernel(compiled)
    # nothing bank-sized beside the kernel: metadata, padding, the mask
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * m * d_out + (
        1 << 20)


# ------------------------------------------------- the runner's own programs


@pytest.fixture
def mistral_runner(one_chip, monkeypatch):
    """``(kv, layers=LAYERS) -> (runner, params, state, page table)``: a
    PagedModelRunner at Mistral-7B widths with ``layers`` layers of int8
    weights, as the chip benchmark serves it, built from shapes alone —
    nothing is allocated — and the shapes of its arguments on the described
    chip, the parameters' laid out as ``shard_params`` places them."""
    from crowdllama_tpu.engine import runner as runner_mod
    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    # The runner would place real parameters on the CPU's devices and, on
    # this backend, gate its kernels off: steer both here, in the test.
    monkeypatch.setattr(runner_mod, "shard_params", lambda p, cfg, mesh: p)

    def build(kv, layers=LAYERS):
        cfg = get_config("mistral-7b", num_layers=layers,
                         max_context_length=PREFILL_T)
        shapes = jax.eval_shape(lambda: random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        r = PagedModelRunner(cfg, params=shapes, mesh_spec="1x1",
                             max_slots=SLOTS, max_seq=PREFILL_T,
                             page_size=PAGE, kv_dtype=kv)
        r.attention_paths = {**r.attention_paths, "decode": "pallas",
                             "ragged_step": "pallas"}

        table = _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip)
        return (r, _placed(shapes, one_chip),
                _on_chip(jax.eval_shape(r.init_state), one_chip), table)

    return build


def _assert_pool_stays_in_place(compiled, kv, kernels=1):
    """The step updates the donated pool where it lies: no temporary as
    large as ONE layer's K+V slice, no ``copy`` whose result has the shape
    of the pool, of a layer's slice of it or of the scales, and one
    attention kernel per layer per DECODE step (the benchmark's readers
    divide the traced custom calls by the layers to count steps; the layer
    and step loops are rolled, so that is one call site in the text) — two
    in a ragged step: the decode rows' and the chunk's."""
    itemsize = 1 if kv == "int8" else 2
    layer_kv = 2 * POOL_PAGES * HKV * PAGE * DH * itemsize
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < layer_kv, (
        ma.temp_size_in_bytes, layer_kv)
    # Every donated byte is handed back as the new state.
    pool = LAYERS * layer_kv + (
        2 * LAYERS * POOL_PAGES * HKV * PAGE * 2 if kv == "int8" else 0)
    assert ma.alias_size_in_bytes >= pool
    text = compiled.as_text()
    pool_dims = f"{POOL_PAGES},{HKV},{PAGE}"
    for line in text.splitlines():
        head = line.split(" copy(")[0] if " copy(" in line else ""
        assert pool_dims not in head, f"pool-shaped copy: {line.strip()[:200]}"
    assert text.count("custom_call_target=\"tpu_custom_call\"") == kernels


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("steps", [1, 8])
def test_decode_program_keeps_the_pool_in_place(mistral_runner, kv, steps):
    r, params, state, table = mistral_runner(kv)
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    assert "jit__decode_paged_impl" in compiled.as_text()[:200]
    _assert_pool_stays_in_place(compiled, kv)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_ragged_step_program_keeps_the_pool_in_place(mistral_runner, one_chip,
                                                     kv):
    r, params, state, table = mistral_runner(kv)
    assert r.ragged_chunk == CHUNK

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    compiled = jax.jit(
        r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
    ).lower(params, state, table, i32(1, CHUNK), i32(1), i32(), i32(),
            1).compile()
    _assert_pool_stays_in_place(compiled, kv, kernels=2)


def _lowered(r, params, state, table, one_chip, program: str):
    """One of a paged runner's programs, traced anew and lowered for the
    described chip: ``decode`` (one step) or ``decode_<steps>``,
    ``ragged_step`` (one step), ``prefill`` (the first bucket) or
    ``prefill_<bucket>``."""
    from crowdllama_tpu.engine.runner import REPEAT_LAST_N

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    def f32():
        return _sds((), jnp.float32, one_chip)

    name, _, size = program.partition("_")
    jax.clear_caches()  # a bound method's trace is cached by equality
    if name == "decode":
        return jax.jit(
            r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
        ).lower(params, state, table, int(size or 1))
    if program == "ragged_step":
        return jax.jit(
            r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
        ).lower(params, state, table, i32(1, r.ragged_chunk), i32(1), i32(),
                i32(), 1)
    assert name == "prefill", program
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return jax.jit(r._prefill_impl).lower(
        params, i32(1, int(size or r.buckets[0])), i32(), f32(), f32(),
        i32(), f32(), i32(REPEAT_LAST_N), _sds(key.shape, key.dtype, one_chip))


def _lower_program(r, params, state, table, one_chip, program: str) -> str:
    return _lowered(r, params, state, table, one_chip, program).as_text()


@pytest.mark.parametrize("program", ["decode", "ragged_step", "prefill"])
def test_dense_programs_are_what_they_were_without_riding_banks(
        mistral_runner, one_chip, monkeypatch, program):
    """``ride_banks`` stands in every layer loop, and a model without a
    kernel-bound int8 expert bank must not see it: the layers come back as
    they are (no ``LayerOf``, no scanned layer index beside them), and the
    lowered program is, to the byte, the one a loop without it lowers."""
    from crowdllama_tpu.engine import paged
    from crowdllama_tpu.models import transformer
    from crowdllama_tpu.ops.quant import ride_banks

    r, params, state, table = mistral_runner("bf16")
    assert r.ragged_chunk == CHUNK
    layers, bind = ride_banks(params["layers"])
    assert layers is params["layers"] and bind(layers) is layers

    with_it = _lower_program(r, params, state, table, one_chip, program)
    for mod in (paged, transformer):
        monkeypatch.setattr(mod, "ride_banks",
                            lambda layers: (layers, lambda lp: lp))
    assert _lower_program(r, params, state, table, one_chip,
                          program) == with_it


@pytest.mark.parametrize("program", ["decode", "ragged_step", "prefill"])
@pytest.mark.parametrize("model", ["mistral", "mixtral"])
def test_programs_without_mamba_layers_never_meet_the_state_kernel(
        request, one_chip, monkeypatch, model, program):
    """The ``ssm_update`` kernel and its gate serve the ``nemotron_h``
    family alone: with the gate steered open as on the chip, Mistral's and
    Mixtral's decode, ragged-step and prefill programs lower to the same
    text as with it shut, and lowering them enters neither ``ops/ssm.py``
    nor the kernel's file — their step programs are the parent's."""
    from crowdllama_tpu.ops import ssm
    from crowdllama_tpu.ops.pallas import ssm as ssm_kernel

    build = request.getfixturevalue(f"{model}_runner")
    r, params, state, table = build(*(["bf16"] if model == "mistral" else []))
    assert not hasattr(r, "ssm_update_path")

    def never(*args, **kwargs):
        raise AssertionError("a model without Mamba layers met ops/ssm.py")

    def lowered(steered: bool) -> str:
        if steered:
            monkeypatch.setattr(ssm_kernel, "ssm_update_refusal",
                                lambda shape: "")
            for mod, names in ((ssm, ("ssm_update_at", "ssm_update",
                                      "ssd_scan", "causal_conv",
                                      "ssm_update_path")),
                               (ssm_kernel, ("ssm_update", "_ssm_update"))):
                for name in names:
                    monkeypatch.setattr(mod, name, never)
        return _lower_program(r, params, state, table, one_chip, program)

    # one call site: a kernel's serialized body carries its call stack
    shut, steered = (lowered(k) for k in (False, True))
    assert steered == shut


@pytest.mark.parametrize("program,differs", [
    ("decode_1", False), ("decode_8", False), ("ragged_step", False),
    ("prefill", False), ("insert", True)])
def test_only_the_insert_program_takes_the_first_token_into_the_ring(
        mistral_runner, one_chip, monkeypatch, program, differs):
    """An admission's first token stays on the device: the insert program
    writes it into the slot's repeat-penalty ring, at ``plen % N``, where
    the host used to hand over a ring with the token already in it.  That
    write is the WHOLE difference on the device: with it taken out, the
    insert program is another module and the decode (1 and 8 steps),
    ragged-step and prefill programs lower to the same text — so neither
    a step nor a warm-up is a program more or another program."""
    from crowdllama_tpu.engine import paged
    from crowdllama_tpu.engine.runner import REPEAT_LAST_N

    r, params, state, table = mistral_runner("bf16")

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    def f32():
        return _sds((), jnp.float32, one_chip)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = _sds(key.shape, key.dtype, one_chip)
    bucket = r.buckets[0]

    def lowered() -> str:
        jax.clear_caches()  # a bound method's trace is cached by equality
        if program.startswith("decode"):
            return jax.jit(
                r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
            ).lower(params, state, table, int(program[-1])).as_text()
        if program == "ragged_step":
            return jax.jit(
                r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
            ).lower(params, state, table, i32(1, CHUNK), i32(1), i32(), i32(),
                    1).as_text()
        if program == "prefill":
            return jax.jit(r._prefill_impl).lower(
                params, i32(1, bucket), i32(), f32(), f32(), i32(), f32(),
                i32(REPEAT_LAST_N), key).as_text()
        kv = _sds((LAYERS, 1, HKV, PAGE, DH), jnp.bfloat16, one_chip)
        return jax.jit(r._insert_paged_impl, donate_argnums=(0,)).lower(
            state, i32(1), kv, kv, i32(), i32(), i32(), f32(),
            f32(), i32(), f32(), i32(REPEAT_LAST_N), key).as_text()

    with_it = lowered()
    monkeypatch.setattr(paged, "ring_with_first",
                        lambda ring, plen, first_token: ring)
    assert (lowered() != with_it) is differs


# ------------------------ the expert layer, at Mixtral-8x7B widths (4 layers)

MOE_SLOTS, MOE_LAYERS = 16, 4


@pytest.fixture
def mixtral_runner(one_chip, monkeypatch, expert_kernel):
    """``() -> (runner, params, state, page table)`` at the widths, depth
    and slots of ``mixtral-8x7b-d4-int8``, int8, from shapes alone."""
    from crowdllama_tpu.engine import runner as runner_mod
    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    monkeypatch.setattr(runner_mod, "shard_params", lambda p, cfg, mesh: p)

    def build():
        cfg = get_config("mixtral-8x7b", num_layers=MOE_LAYERS,
                         max_context_length=PREFILL_T)
        shapes = jax.eval_shape(lambda: random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        r = PagedModelRunner(cfg, params=shapes, mesh_spec="1x1",
                             max_slots=MOE_SLOTS, max_seq=PREFILL_T,
                             page_size=PAGE)
        assert r.moe_matmul_path == "int8_kernel"
        r.attention_paths = {**r.attention_paths, "decode": "pallas",
                             "ragged_step": "pallas"}

        table = _sds((MOE_SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip)
        return (r, _placed(shapes, one_chip),
                _on_chip(jax.eval_shape(r.init_state), one_chip), table)

    return build


def _assert_banks_are_read_in_place(compiled, state):
    """The scan body calls the grouped-matmul kernel three times (gate, up,
    down; layer and step loops are rolled) on the STACKED int8 leaves: no
    buffer, fusion or ``copy`` of one layer's bank exists, int8 or bf16, and
    the temporaries are far below one bank; the pools still stay in place."""
    import re

    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if " custom-call(" in ln
             and "%moe_grouped_matmul" in ln.split(" = ")[0]]
    assert len(calls) == 3, calls
    bank = re.compile(r"= (s8|bf16)\[(1,)?8,(4096,14336|14336,4096)\]")
    for line in text.splitlines():
        assert not bank.search(line), f"a layer's bank: {line.strip()[:200]}"
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 8 * 4096 * 14336 // 4, ma.temp_size_in_bytes
    pools = sum(a.size * a.dtype.itemsize
                for a in (state.pool_k, state.pool_v))
    assert ma.alias_size_in_bytes >= pools
    pool_dims = ",".join(map(str, state.pool_k.shape[1:4]))
    for line in text.splitlines():
        head = line.split(" copy(")[0] if " copy(" in line else ""
        assert pool_dims not in head, f"pool-shaped copy: {line.strip()[:200]}"


@pytest.mark.parametrize("steps", [1, 8])
def test_moe_decode_program_reads_the_int8_banks_in_place(mixtral_runner,
                                                          steps):
    r, params, state, table = mixtral_runner()
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    _assert_banks_are_read_in_place(compiled, state)


def test_moe_ragged_step_program_reads_the_int8_banks_in_place(
        mixtral_runner, one_chip):
    r, params, state, table = mixtral_runner()

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    compiled = jax.jit(
        r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
    ).lower(params, state, table, i32(1, r.ragged_chunk), i32(1), i32(),
            i32(), 1).compile()
    _assert_banks_are_read_in_place(compiled, state)


# ---------- where the attention projections lie (PR 41, ``weight_layout``)

_PROJECTION_WRITTEN = re.compile(
    r" = s8\[(\d+,)?4096,(4096|1024)\]\S* "
    r"(copy|copy-done|slice-done|fusion)\(")


def _projections_written(text: str) -> list[str]:
    """The lines of a compiled program that WRITE an int8 attention
    projection — a layer's ``wq``/``wo`` (4096 x 4096) or ``wk``/``wv``
    (4096 x 1024), or a whole stack of them: a ``copy``, an async
    ``slice-done`` / ``copy-done``, or a fusion with such a result (XLA's
    ``constant_dynamic-slice_fusion`` of a layer).  A dot that reads its
    weight where it lies has the product as its result and is not here."""
    return [ln.strip()[:120] for ln in text.splitlines()
            if _PROJECTION_WRITTEN.search(ln)]


def _order(leaf) -> tuple[int, ...] | None:
    layout = leaf.q.format.layout
    return None if layout is None else layout.major_to_minor


@pytest.mark.parametrize("program", ["decode_1", "decode_8", "ragged_step"])
@pytest.mark.parametrize("model", ["mistral", "mixtral"])
def test_step_programs_read_the_attention_projections_where_they_lie(
        request, one_chip, model, program):
    """XLA wants the int8 ``wq`` and ``wk`` of the layer loop with the
    input dimension minor.  Placed row-major they were copied before every
    read — ``constant_dynamic-slice_fusion.4`` + ``copy.273`` (``wq``) and
    ``.5`` + ``copy.276`` (``wk``) a layer in the one-step and the ragged
    program, ``copy.231`` / ``copy.230`` of the whole stacks a dispatch in
    the 8-step one (Mixtral: ``copy.770``, ``copy.773``; four
    ``slice-done``), 17% of a one-step Mistral decode on the chip (PERF.md
    §6, PR 41).  Placed as ``weight_layout`` says, no program writes a
    projection at all; ``wv`` and ``wo`` are read where they always lay."""
    build = request.getfixturevalue(f"{model}_runner")
    r, params, state, table = build(*(["bf16"] if model == "mistral" else []))
    layers = params["layers"]
    assert _order(layers["wq"]) == _order(layers["wk"]) == (0, 2, 1)
    assert _order(layers["wv"]) is None and _order(layers["wo"]) is None
    compiled = _lowered(r, params, state, table, one_chip, program).compile()
    assert _projections_written(compiled.as_text()) == []


def test_eight_step_program_holds_no_relaid_stack_at_full_depth(
        mistral_runner, one_chip):
    """All 32 layers of Mistral-7B: the 8-step program used to keep
    ``copy.231 = s8[32,4096,4096]{1,2,0}`` and ``copy.230 =
    s8[32,4096,1024]{1,2,0}`` as HBM temporaries for the length of a
    flight, 672.8 MB; now its temporaries are under ONE layer's ``wq``."""
    r, params, state, table = mistral_runner("bf16", layers=32)
    compiled = _lowered(r, params, state, table, one_chip,
                        "decode_8").compile()
    assert _projections_written(compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 4096 * 4096


@pytest.mark.parametrize("bucket", [32, 512])
@pytest.mark.parametrize("model", ["mistral", "mixtral"])
def test_prefill_programs_still_copy_wv_a_layer(request, one_chip, model,
                                                bucket):
    """What is knowingly left: a prefill program wants ``wv`` input-minor
    too and copies it a layer (a slice fusion and a ``copy`` of
    ``s8[1,4096,1024]``, 4.2 MB; three such pairs before PR 41).  An
    input-minor ``wv`` would clear it and put a copy of the whole ``wv``
    stack into every 8-step decode dispatch instead, twice the bytes where
    four such flights fly for each prefill (PERF.md §6, PR 41)."""
    build = request.getfixturevalue(f"{model}_runner")
    r, params, state, table = build(*(["bf16"] if model == "mistral" else []))
    compiled = _lowered(r, params, state, table, one_chip,
                        f"prefill_{bucket}").compile()
    written = _projections_written(compiled.as_text())
    assert len(written) == 2, written
    sliced, copied = sorted(written, key=lambda ln: " copy(" in ln)
    assert " = s8[1,4096,1024]{2,1,0" in sliced and " fusion(" in sliced
    assert " = s8[1,4096,1024]{1,2,0" in copied and " copy(" in copied


# ------------- a model whose layers differ in kind, at the benchmark's cut


@pytest.fixture
def nemotron_runner(one_chip, monkeypatch, tmp_path, expert_kernel,
                    state_kernel):
    """``() -> (runner, params, state, page table)``: the hybrid runner at
    the widths, depth and share of ``nemotron-3-super-p1-ep4-int8`` (the
    benchmark's configuration file, read as the worker reads it), int8,
    32 slots, built from shapes alone."""
    import json
    from pathlib import Path

    from crowdllama_tpu.engine import runner as runner_mod
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    monkeypatch.setattr(runner_mod, "shard_params", lambda p, cfg, mesh: p)
    doc = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                      / "chip" / "configs"
                      / "nemotron-3-super-p1-ep4-int8.json").read_text())
    slots = doc["bench"]["slots"]
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in doc.items() if k != "bench"}))

    def build():
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path),
                                   max_context_length=PREFILL_T)
        shapes = jax.eval_shape(lambda: random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        r = HybridPagedModelRunner(cfg, params=shapes, mesh_spec="1x1",
                                   max_slots=slots, max_seq=PREFILL_T,
                                   page_size=PAGE)
        assert r.ssm_update_path == "pallas"
        r.attention_paths = {**r.attention_paths, "decode": "pallas",
                             "ragged_step": "pallas"}

        table = _sds((slots, PAGES_PER_SLOT), jnp.int32, one_chip)
        return (r, _placed(shapes, one_chip),
                _on_chip(jax.eval_shape(r.init_state), one_chip), table)

    return build


def _assert_both_states_stay_in_place(compiled, r, state,
                                      decode_kernel=True):
    """Every byte of the donated state — the attention layer's pools, the
    Mamba layers' state and tail — is handed back where it lay; the expert
    banks go to the grouped-matmul kernel as int8 (ten call sites: five
    expert layers, two matrices), so NO dequantized bank is among the
    temporaries: a decode step's are under a sixteenth of one bf16 bank
    (28 MB of 705), and a ragged step's are its 11,968 expert rows'
    activations (342 MB), under half of one; no ``copy`` has the state's
    shape; the decode attention kernel has one call site (one attention
    layer)."""
    cfg = r.cfg
    bank = 2 * (cfg.experts_held * cfg.moe_latent_size
                * cfg.moe_intermediate_size)              # bf16
    kept = sum(a.size * a.dtype.itemsize for a in (
        state.pool_k, state.pool_v, state.ssm, state.conv))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= kept, (ma.alias_size_in_bytes, kept)
    limit = bank // 16 if decode_kernel else bank // 2
    assert ma.temp_size_in_bytes < limit, (ma.temp_size_in_bytes, bank)
    text = compiled.as_text()
    assert len([ln for ln in text.splitlines()
                if " custom-call(" in ln and "%moe_grouped_matmul"
                in ln.split(" = ")[0]]) == 10
    state_dims = ",".join(map(str, state.ssm.shape[1:]))
    bank_dims = (f"[{cfg.experts_held},{cfg.moe_latent_size},"
                 f"{cfg.moe_intermediate_size}]",
                 f"[{cfg.experts_held},{cfg.moe_intermediate_size},"
                 f"{cfg.moe_latent_size}]")
    for line in text.splitlines():
        head = line.split(" copy(")[0] if " copy(" in line else ""
        assert state_dims not in head, f"state-shaped copy: {line.strip()[:200]}"
        if " fusion(" in line or " copy(" in line:
            assert not any(f"= bf16{d}" in line or f"= s8{d}" in line
                           for d in bank_dims), line.strip()[:200]
    if decode_kernel:
        assert len([ln for ln in text.splitlines()
                    if " custom-call(" in ln and "%paged_decode_attention"
                    in ln.split(" = ")[0]]) == 1


@pytest.mark.parametrize("steps", [1, 8])
def test_hybrid_decode_program_keeps_both_states_in_place(nemotron_runner,
                                                          steps):
    r, params, state, table = nemotron_runner()
    assert state.pool_k.shape[0] == 1 and state.ssm.shape == (
        5, 32, 128, 64, 128) and state.conv.shape == (5, 32, 10240, 3)
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    _assert_both_states_stay_in_place(compiled, r, state)


def test_hybrid_ragged_step_program_keeps_both_states_in_place(
        nemotron_runner, one_chip):
    r, params, state, table = nemotron_runner()
    assert r.ragged_chunk == CHUNK

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    compiled = jax.jit(
        r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
    ).lower(params, state, table, i32(1, CHUNK), i32(1), i32(), i32(),
            1).compile()
    _assert_both_states_stay_in_place(compiled, r, state,
                                      decode_kernel=False)



def test_hybrid_decode_step_lowers_one_kernel_body_per_shape(nemotron_runner):
    """The layers are a Python list, so a step program holds ten call sites
    of the grouped matmul, and every warm-up program is traced and lowered
    anew at every start, compile cache or not: the kernel's entry is its
    own ``jit``, so the ten sites CALL two lowered bodies — one a distinct
    (rows, ``d_in``, ``d_out``) — where ten were 8 s of a warm start
    (PERF.md §6, PR 29).  XLA inlines them: the compiled step above holds
    ten ``%moe_grouped_matmul`` custom calls all the same."""
    import re

    r, params, state, table = nemotron_runner()
    text = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, 2).as_text()
    bodies = re.findall(
        r"func\.func private @(_grouped_matmul\w*)\(%arg0: tensor<(\d+)x(\d+)"
        r"xbf16>, %arg1: tensor<\d+x\d+x(\d+)xi8>", text)
    assert sorted((int(m), int(k), int(n)) for _, m, k, n in bodies) == [
        (704, 1024, 2688), (704, 2688, 1024)]
    for name, *_ in bodies:
        assert len(re.findall(rf"call @{name}\(", text)) == 5
    # and the five Mamba layers' state update is one body, called five times
    (update,) = re.findall(r"func\.func private @(_ssm_update\w*)\(", text)
    assert len(re.findall(rf"call @{update}\(", text)) == 5
    # two grouped-matmul kernels, the state update and the attention layer's
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 4


@pytest.mark.parametrize("steps", [1, 2])
def test_hybrid_decode_program_passes_over_the_state_once(nemotron_runner,
                                                          steps):
    """The decode step's state-space update is the ``ssm_update`` kernel on
    the WHOLE carried stack, aliased in to out, inside the step loop: five
    custom calls a step (the cell serves with 1- and 2-step programs), and
    nothing else that touches the state — no XLA fusion with the stack or a
    layer's slab of it among its operands (the benchmark's
    ``kernel.ssm_state_roofline`` would charge it to the share, rightly) and
    no ``copy`` of either: one copy of the 671 MB stack is 1.6 ms on the
    chip, more than the kernel gains."""
    r, params, state, table = nemotron_runner()
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    lines = compiled.as_text().splitlines()
    calls = [ln for ln in lines if " custom-call(" in ln
             and "%ssm_update" in ln.split(" = ")[0]]
    assert len(calls) == 5, calls
    stack = "f32[" + ",".join(map(str, state.ssm.shape)) + "]"
    slab = "f32[" + ",".join(map(str, state.ssm.shape[1:])) + "]"
    for ln in calls:
        assert stack in ln.split(" custom-call(")[0], ln.strip()[:200]
    for ln in lines:
        if any(f" {op}(" in ln for op in ("fusion", "copy", "copy-start")):
            assert stack not in ln and slab not in ln, ln.strip()[:200]
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        state.ssm.size * 4)


def test_ssm_update_kernel_compiles_at_the_cell_shape(one_chip):
    """The kernel alone, with the tile it chooses at the cell's state
    ``[5, 32, 128, 64, 128]``: no temporary, the stack handed back."""
    from crowdllama_tpu.ops.pallas.ssm import ssm_update

    def f32(*shape):
        return _sds(shape, jnp.float32, one_chip)

    m, s, h, p, n, g = 5, 32, 128, 64, 128, 8
    compiled = jax.jit(ssm_update, donate_argnums=(6,)).lower(
        f32(s, h, p), f32(s, h), f32(h), f32(s, g, n), f32(s, g, n), f32(h),
        f32(m, s, h, p, n), _sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == m * s * h * p * n * 4
    assert ma.temp_size_in_bytes < (1 << 20)


# ------------- delta-rule linear attention and latent attention (PR 37)


@pytest.fixture
def kda_kernel(monkeypatch):
    """The KDA layers' decode-step update takes the ``kda_update`` kernel,
    as on the chip (the same steering as ``state_kernel``)."""
    from crowdllama_tpu.ops.pallas import kda as kda_kernel

    monkeypatch.setattr(kda_kernel, "kda_update_refusal",
                        lambda state_shape: "")


@pytest.fixture
def kimi_runner(one_chip, monkeypatch, tmp_path, expert_kernel, kda_kernel):
    """``() -> (runner, params, state, page table)``: the hybrid runner at
    the widths, depth and share of ``kimi-linear-48b-p1-ep8-int8`` (the
    benchmark's configuration file, read as the worker reads it), int8, 32
    slots, context 1536, built from shapes alone."""
    import json
    from pathlib import Path

    from crowdllama_tpu.engine import runner as runner_mod
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    monkeypatch.setattr(runner_mod, "shard_params", lambda p, cfg, mesh: p)
    doc = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                      / "chip" / "configs"
                      / "kimi-linear-48b-p1-ep8-int8.json").read_text())
    slots, ctx = doc["bench"]["slots"], doc["bench"]["context"]
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in doc.items() if k != "bench"}))

    def build():
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path),
                                   max_context_length=ctx)
        shapes = jax.eval_shape(lambda: random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        r = HybridPagedModelRunner(cfg, params=shapes, mesh_spec="1x1",
                                   max_slots=slots, max_seq=ctx,
                                   page_size=PAGE)
        assert r.kda_update_path == "pallas" and r.ssm_update_path == ""
        r.attention_paths = {**r.attention_paths, "decode": "pallas",
                             "ragged_step": "pallas"}

        table = _sds((slots, ctx // PAGE), jnp.int32, one_chip)
        return (r, _placed(shapes, one_chip),
                _on_chip(jax.eval_shape(r.init_state), one_chip), table)

    return build


def _calls(text: str, name: str) -> list[str]:
    return [ln for ln in text.splitlines() if " custom-call(" in ln
            and f"%{name}" in ln.split(" = ")[0]]


# the cell's latent pool as it is allocated: 7 MLA layers, 32 slots x 12
# pages + the dump page, rows [c ; k_rope] of 576 rounded up to whole lanes
KIMI_POOL = (7, 32 * 12 + 1, 1, PAGE, 640)


def _assert_latent_pool_is_worked_where_it_lies(compiled, state,
                                                pool_shape=KIMI_POOL):
    """The program takes the latent pool in the layout its loop uses (row
    minor) and hands it back so: wherever an array of the pool's shape
    stands in the compiled text with a layout it lies row-minor, no ``copy``,
    ``copy-start`` or ``transpose`` yields one and no fusion but a
    ``dynamic-update-slice`` of the buffer itself (a row write; a ragged
    chunk's page, merged under its row mask, is one such fusion), and the
    temporaries are under the pool's own bytes — a 576-wide pool's program
    held a 640-wide working copy of all of it, 441.5 MB, and converted the
    buffer on the way in and again on the way out (PERF.md §6, PR 49)."""
    assert state.pool_v is None and state.pool_k.shape == pool_shape
    ma = compiled.memory_analysis()
    kept = sum(a.size * a.dtype.itemsize
               for a in (state.pool_k, state.kda, state.conv)
               if a is not None)
    assert ma.alias_size_in_bytes >= kept, (ma.alias_size_in_bytes, kept)
    pool_bytes = state.pool_k.size * state.pool_k.dtype.itemsize
    assert pool_bytes == math.prod(pool_shape) * 2
    assert ma.temp_size_in_bytes < pool_bytes, (ma.temp_size_in_bytes,
                                                pool_bytes)
    text = compiled.as_text()
    pool = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert "bf16[" + ",".join(map(str, pool_shape[:-1])) + ",576]" not in text
    roots, name = {}, None      # computation -> its ROOT instruction
    for ln in text.splitlines():
        if ln.startswith("%") and ln.endswith("{"):
            name = ln.split(" ", 1)[0]
        elif ln.lstrip().startswith("ROOT "):
            roots[name] = ln
    lines = [ln for ln in text.splitlines() if pool in ln]
    assert any(" parameter(" in ln.split(pool, 1)[1] for ln in lines)
    for ln in lines:
        for at in ln.split(pool)[1:]:
            assert at.startswith("{4,3,2,1,0") or at[0] != "{", (
                ln.strip()[:200])
        result = ln.split(" = ", 1)[-1]
        for op in ("copy", "copy-start", "transpose", "fusion"):
            if f" {op}(" not in result or pool not in result.split(
                    f" {op}(")[0]:
                continue
            called = re.search(r"calls=(%[\w.\-]+)", ln)
            assert op == "fusion" and called, ln.strip()[:200]
            assert re.search(r" dynamic-update-slice\(%param_0[., ]",
                             roots[called.group(1)]), ln.strip()[:200]


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_kimi_decode_program_keeps_its_state_in_place(kimi_runner, steps):
    """All 27 layers of the cut in one step program, at a short flight's
    length, at two steps and at the cell's own flight (``decode_chunk`` 4):
    the latent pool (no V twin), the KDA matrices and the convolutions'
    tails are handed back where they lay; twenty ``kda_update`` calls a
    step on the WHOLE stack, seven ``paged_decode_attention_mla``, 78
    grouped matmuls (26 expert layers, three int8 banks); no fusion or copy
    with the state or a bank among its operands, none that yields the
    pool."""
    r, params, state, table = kimi_runner()
    assert state.kda.shape == (20, 32, 32, 128, 128)
    assert state.conv.shape == (20, 32, 3, 12288)
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    _assert_latent_pool_is_worked_where_it_lies(compiled, state)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert 7.2e9 < weights < 7.4e9
    text = compiled.as_text()
    assert len(_calls(text, "kda_update")) == 20
    assert len(_calls(text, "paged_decode_attention_mla")) == 7
    assert len(_calls(text, "moe_grouped_matmul")) == 78
    stack = "f32[20,32,32,128,128]"
    for ln in _calls(text, "kda_update"):
        assert stack in ln.split(" custom-call(")[0], ln.strip()[:200]
    for ln in text.splitlines():
        if any(f" {op}(" in ln for op in ("fusion", "copy", "copy-start")):
            assert stack not in ln and "f32[32,32,128,128]" not in ln, (
                ln.strip()[:200])
            assert not any(f"= {t}[32,{d}]" in ln for t in ("bf16", "s8")
                           for d in ("2304,1024", "1024,2304")), (
                ln.strip()[:200])


def test_kimi_ragged_step_program_compiles_with_its_state_in_place(
        kimi_runner, one_chip):
    """Decode rows beside a 512-token chunk: the v2 ragged kernel over one
    latent row a token (key and value both, 640 wide as stored), the
    chunk's rows through the chunkwise delta rule into the slot's own
    slab; the pool worked where it lies, as in the decode program."""
    r, params, state, table = kimi_runner()
    assert r.ragged_chunk == CHUNK

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    compiled = jax.jit(
        r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
    ).lower(params, state, table, i32(1, CHUNK), i32(1), i32(), i32(),
            1).compile()
    _assert_latent_pool_is_worked_where_it_lies(compiled, state)
    text = compiled.as_text()
    assert len(_calls(text, "kda_update")) == 20
    assert len(_calls(text, "moe_grouped_matmul")) == 78
    # the decode rows beside the chunk: the GQA decode kernel on the one
    # latent pool (key and value both), not the decode STEP's _mla kernel
    assert len(_calls(text, "paged_decode_attention")) == 7
    assert not _calls(text, "paged_decode_attention_mla")


def test_kimi_insert_program_writes_its_pages_in_place(kimi_runner,
                                                       one_chip):
    """A prefilled 256-token prompt's rows, 576 wide as the cache-less
    prefill left them, go into columns ``0:576`` of two pages of the pool,
    two ``dynamic-update-slice`` on the buffer itself: the pad columns are
    not written, nothing of the pool's size is made."""
    from crowdllama_tpu.engine.hybrid import HybridPrefill
    from crowdllama_tpu.models import hybrid as HY

    r, _, state, _ = kimi_runner()
    t = 256
    rec = _on_chip(jax.eval_shape(
        lambda: HY.zero_recurrent(r.cfg, 1, jnp.bfloat16)), one_chip)
    ks = HybridPrefill(_sds((7, 1, 1, t, 576), jnp.bfloat16, one_chip),
                       None, rec)

    def scalar(dtype=jnp.int32):
        return _sds((), dtype, one_chip)

    compiled = jax.jit(r._insert_paged_impl, donate_argnums=(0,)).lower(
        state, _sds((t // PAGE,), jnp.int32, one_chip), ks, None, scalar(),
        scalar(), scalar(), scalar(jnp.float32), scalar(jnp.float32),
        scalar(), scalar(jnp.float32),
        _sds(state.recent.shape[1:], jnp.int32, one_chip),
        _sds(state.keys.shape[1:], state.keys.dtype, one_chip)).compile()
    _assert_latent_pool_is_worked_where_it_lies(compiled, state)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert compiled.as_text().count(" dynamic-update-slice(") >= 2


@pytest.mark.parametrize("model,pools", [
    ("mistral", {"pool_k": (LAYERS, POOL_PAGES, HKV, PAGE, DH),
                 "pool_v": (LAYERS, POOL_PAGES, HKV, PAGE, DH)}),
    ("mixtral", {"pool_k": (4, 16 * 16 + 1, 8, PAGE, 128),
                 "pool_v": (4, 16 * 16 + 1, 8, PAGE, 128)}),
    ("nemotron", {"pool_k": (1, 32 * 16 + 1, 2, PAGE, 128),
                  "pool_v": (1, 32 * 16 + 1, 2, PAGE, 128)}),
    ("trinity", {"pool_k": (1, 32 * 40 + 1, 8, PAGE, 128),
                 "pool_v": (1, 32 * 40 + 1, 8, PAGE, 128),
                 "wpool_k": (4, 32 * 37 + 1, 8, PAGE, 128),
                 "wpool_v": (4, 32 * 37 + 1, 8, PAGE, 128)}),
    ("kimi", {"pool_k": KIMI_POOL, "pool_v": None}),
])
def test_only_a_latent_pool_takes_a_wider_row(request, model, pools):
    """``engine/paged.py`` ``pool_row_width`` engages for a latent row that
    is not whole lanes alone: a pool of K and V heads (128 wide in every
    other configuration) keeps its shape to the byte, the kernels' gates
    and the grid-step counter see the shape the pool has."""
    from crowdllama_tpu.engine.paged import pool_row_width

    build = request.getfixturevalue(f"{model}_runner")
    r, _, state, _ = build("bf16") if model == "mistral" else build()
    for name, shape in pools.items():
        got = getattr(state, name)
        assert (got is None) if shape is None else got.shape == shape, name
    row = pool_row_width(r.cfg)
    assert row == state.pool_k.shape[-1] == r._pool_shard.shape[-1]
    assert (row == r.cfg.resolved_head_dim()) == (model != "kimi")
    assert r.cfg.resolved_head_dim() == {"kimi": 576}.get(model, 128)


def test_kda_update_kernel_compiles_at_the_cell_shape(one_chip):
    """The kernel alone, with the tile it chooses at the cell's state
    ``[20, 32, 32, 128, 128]``: the stack handed back, and what rides
    beside the tile (four columns a head) under 3 MB."""
    from crowdllama_tpu.ops.pallas.kda import choose_head_block, kda_update

    def f32(*shape):
        return _sds(shape, jnp.float32, one_chip)

    m, s, h, dk = 20, 32, 32, 128
    assert choose_head_block(h, dk, dk) == 16
    compiled = jax.jit(kda_update, donate_argnums=(5,)).lower(
        f32(s, h, dk), f32(s, h, dk), f32(s, h, dk), f32(s, h, dk), f32(s, h),
        f32(m, s, h, dk, dk), _sds((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes == m * s * h * dk * dk * 4
    assert ma.temp_size_in_bytes < 3 * (1 << 20)


def test_latent_attention_kernels_compile_at_the_cell_shape(one_chip):
    """One shared kv head, 32 query heads, rows of 576 — 640 as the pool
    stores them, the value the first 512: the latent decode kernel and the
    accepted ragged kernel on the stored row, the accepted prefill kernel
    (cache-less: it never meets the pool) on the row as computed, each with
    the row as key AND value (the prefill kernel's query block shrinks to
    fit VMEM)."""
    from crowdllama_tpu.ops.pallas.paged import (paged_decode_attention_mla,
                                                 ragged_paged_attention)

    bf16, i32 = jnp.bfloat16, jnp.int32
    slots, heads, row, latent, np_ = 32, 32, 576, 512, 12
    stored = KIMI_POOL[-1]
    pool = _sds(KIMI_POOL, bf16, one_chip)
    table = _sds((slots, np_), i32, one_chip)
    scale = 192 ** -0.5

    def decode(q, pool, li, table, lens):
        return paged_decode_attention_mla(q, pool, li, table, lens, scale,
                                          latent)

    compiled = jax.jit(decode).lower(
        _sds((slots, heads, stored), bf16, one_chip), pool,
        _sds((), i32, one_chip), table, _sds((slots,), i32, one_chip)
    ).compile()
    assert len(_calls(compiled.as_text(), "paged_decode_attention_mla")) == 1

    for t in (256, 1536):
        def prefill(q, k, pos, valid):
            return flash_prefill_attention(q, k, k, pos, scale,
                                           kv_valid=valid)

        _assert_kernel(jax.jit(prefill).lower(
            _sds((1, t, heads, row), bf16, one_chip),
            _sds((1, 1, t, row), bf16, one_chip),
            _sds((1, t), i32, one_chip),
            _sds((1, t), jnp.bool_, one_chip)).compile())

    def ragged(q, ck, pool, li, table, ql, kl, cs):
        return ragged_paged_attention(q, ck, ck, pool, pool, li, table, ql,
                                      kl, cs, scale, use_pallas=True)

    _assert_kernel(jax.jit(ragged).lower(
        _sds((slots + CHUNK, heads, stored), bf16, one_chip),
        _sds((1, 1, CHUNK, stored), bf16, one_chip), pool,
        _sds((), i32, one_chip), table, _sds((slots + 1,), i32, one_chip),
        _sds((slots + 1,), i32, one_chip), _sds((), i32, one_chip)
    ).compile())


@pytest.mark.parametrize("model", ["nemotron", "kimi"])
def test_list_of_layers_models_are_placed_as_they_were(request, one_chip,
                                                       model):
    """``weight_layout`` matches a STACKED rank-3 int8 ``wq``/``wk``.  A
    model whose parameters are a list of layers of rank-2 leaves
    (models/hybrid.py) takes no layout at all — Nemotron's attention layer
    has a ``wq`` and a ``wk``, Kimi has neither — so every shape is what it
    was without the rule and the one-step decode program lowers to the same
    text: the two hybrid cells are PR 41's controls."""
    r, params, state, table = request.getfixturevalue(f"{model}_runner")()
    plain = _placed(params, one_chip, rule=False)
    assert jax.tree_util.tree_leaves(params) == jax.tree_util.tree_leaves(
        plain)
    assert all(leaf.format.layout is None
               for leaf in jax.tree_util.tree_leaves(params))
    # one call site: a kernel's serialized body carries its call stack
    with_rule, without = (
        _lower_program(r, p, state, table, one_chip, "decode")
        for p in (params, plain))
    assert with_rule == without


# ---- family afmoe: window and full layers on a cache of two kinds of page

@pytest.fixture
def trinity_runner(one_chip, monkeypatch, tmp_path, expert_kernel):
    """``() -> (runner, params, state, page table)``: the hybrid runner at
    the widths, depth and share of ``trinity-large-p1-ep8-int8`` (the
    benchmark's configuration file, read as the worker reads it), int8, 32
    slots, the served context (5120), built from shapes alone."""
    import json
    from pathlib import Path

    from crowdllama_tpu.engine import runner as runner_mod
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    monkeypatch.setattr(runner_mod, "shard_params", lambda p, cfg, mesh: p)
    doc = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                      / "chip" / "configs"
                      / "trinity-large-p1-ep8-int8.json").read_text())
    slots, ctx = doc["bench"]["slots"], doc["bench"]["context"]
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in doc.items() if k != "bench"}))

    def build():
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path),
                                   max_context_length=ctx)
        shapes = jax.eval_shape(lambda: random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        r = HybridPagedModelRunner(cfg, params=shapes, mesh_spec="1x1",
                                   max_slots=slots, max_seq=ctx,
                                   page_size=PAGE)
        r.attention_paths = {**r.attention_paths, "decode": "pallas",
                             "ragged_step": "pallas"}
        table = _sds((slots, ctx // PAGE), jnp.int32, one_chip)
        return (r, _placed(shapes, one_chip),
                _on_chip(jax.eval_shape(r.init_state), one_chip), table)

    return build


@pytest.mark.parametrize("steps", [1, 2])
def test_trinity_decode_program_keeps_both_pools_in_place(trinity_runner,
                                                          steps):
    """All five layers of the cut in one step program: the full layer's
    pool grows with the context (40 pages a slot), the four window layers'
    does not (a ring of 37: 4096 + 512 + 128 tokens), both are handed back
    where they lay; four ``paged_decode_attention_window`` calls a step and
    one ``paged_decode_attention`` — six query heads a kv head through the
    Pallas kernel, no fall to XLA; twelve grouped matmuls; the temporaries
    under a tenth of the weights."""
    r, params, state, table = trinity_runner()
    assert r.ring == (37, 4096) and r.attn_decode_path == "gqa+window"
    assert state.pool_k.shape == (1, 32 * 40 + 1, 8, PAGE, 128)
    assert state.wpool_k.shape == (4, 32 * 37 + 1, 8, PAGE, 128)
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    ma = compiled.memory_analysis()
    kept = sum(a.size * a.dtype.itemsize for a in (
        state.pool_k, state.pool_v, state.wpool_k, state.wpool_v))
    assert 3.14e9 < kept < 3.17e9
    assert ma.alias_size_in_bytes >= kept, (ma.alias_size_in_bytes, kept)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert 4.39e9 < weights < 4.42e9 and ma.temp_size_in_bytes < weights // 10
    text = compiled.as_text()
    assert len(_calls(text, "paged_decode_attention_window")) == 4
    assert len(_calls(text, "paged_decode_attention")) == 5   # both names
    assert len(_calls(text, "moe_grouped_matmul")) == 12
    # a window layer's call walks the list of a table of the 33 pages its
    # window reaches: 17 page pairs a slot
    for ln in _calls(text, "paged_decode_attention_window"):
        assert "s32[544]" in ln and "bf16[4,1185,8,128,128]" in ln
    for ln in text.splitlines():
        if any(f" {op}(" in ln for op in ("fusion", "copy", "copy-start")):
            head = ln.split(" = ")[1][:60] if " = " in ln else ""
            assert "[4,1185,8,128,128]" not in head, ln.strip()[:200]
            assert "[1,1281,8,128,128]" not in head, ln.strip()[:200]


@pytest.mark.parametrize("steps", [1, 2])
def test_trinity_ragged_step_program_compiles_with_both_pools_in_place(
        trinity_runner, one_chip, steps):
    """Decode rows beside a 512-token chunk, the program EVERY admission of
    the long-prompt cell takes: the v2 ragged kernel over the full pool and,
    under its own name, over each block's own view of its slot's ring."""
    r, params, state, table = trinity_runner()
    assert r.ragged_chunk == CHUNK

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    compiled = jax.jit(
        r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
    ).lower(params, state, table, i32(steps, CHUNK), i32(steps), i32(),
            i32(), steps).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in (
            state.pool_k, state.pool_v, state.wpool_k, state.wpool_v))
    text = compiled.as_text()
    assert len(_calls(text, "ragged_paged_attention_window")) == 4
    assert len(_calls(text, "ragged_paged_attention")) == 5
    # the decode rows beside the chunk take the decode kernels
    assert len(_calls(text, "paged_decode_attention_window")) == 4
    assert len(_calls(text, "paged_decode_attention")) == 5
    assert len(_calls(text, "moe_grouped_matmul")) == 12


# ------- the decode kernel's list of live page pairs (PR 44): once a step


def _walk(jaxpr, loops=()):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, each with
    the lengths of the ``scan`` loops around it (outermost first)."""
    for eqn in jaxpr.eqns:
        yield eqn, loops
        inner = loops + ((eqn.params["length"],)
                         if eqn.primitive.name == "scan" else ())
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, inner)


@pytest.mark.parametrize("program", ["decode_1", "decode_chunk",
                                     "ragged_step"])
@pytest.mark.parametrize("model,lists,kernels", [
    # full layers: one rolled call site; Nemotron: its one attention layer
    ("mistral", 1, {"paged_decode_attention": 1}),
    ("nemotron", 1, {"paged_decode_attention": 1}),
    # every attention layer latent: the MLA kernel takes no list; the ragged
    # step's decode rows meet the GQA kernel on the one latent pool
    ("kimi", 0, {"paged_decode_attention_mla": 7}),
    # two lists a step: the full layer's and the four window layers' shared
    ("trinity", 2, {"paged_decode_attention": 1,
                    "paged_decode_attention_window": 4}),
])
def test_decode_work_is_built_once_a_step(request, model, lists, kernels,
                                          program):
    """The list of live page pairs is built beside the lengths, inside the
    step loop and OUTSIDE the layer loop (the ``cumsum`` under its scope's
    name is the witness), once for every kind of table the
    step's GQA decode layers read — and every such layer's kernel call walks
    it under a run-time grid bound; the calls' names and their count a step
    are what the benchmark's readers divide by."""
    build = request.getfixturevalue(f"{model}_runner")
    r, params, state, table = build("bf16") if model == "mistral" else build()
    chunk = {"mistral": 8, "nemotron": 2, "kimi": 4, "trinity": 2}[model]
    steps = chunk if program == "decode_chunk" else 1
    if program == "ragged_step":
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        jaxpr = jax.make_jaxpr(r._ragged_step_impl, static_argnums=(7,))(
            params, state, table, i32(1, r.ragged_chunk), i32(1), i32(),
            i32(), 1)
        if model == "kimi":
            lists, kernels = 1, {"paged_decode_attention": 7}
    else:
        jaxpr = jax.make_jaxpr(r._decode_paged_impl, static_argnums=(3,))(
            params, state, table, steps)
    eqns = list(_walk(jaxpr.jaxpr))
    # the rolled layer loop of a stacked model; a hybrid's layers are unrolled
    layer_loop = (r.cfg.num_layers,) if model == "mistral" else ()
    built = [loops for eqn, loops in eqns
             if eqn.params.get("name") == "cumsum"    # jnp.cumsum's own jit
             and "decode_work" in str(eqn.source_info.name_stack)]
    assert built == [(steps,)] * lists, built
    calls = {}
    for eqn, loops in eqns:
        name = (eqn.params.get("name") or ""
                ) if eqn.primitive.name == "pallas_call" else ""
        if name.startswith("paged_decode_attention"):
            assert loops == (steps,) + layer_loop, (name, loops)
            calls[name] = calls.get(name, 0) + 1
            if not name.endswith("_mla"):
                # the grid's one dimension is the list's length, read at run
                # time (a dynamic bound is the call's first operand)
                assert eqn.params["grid_mapping"].num_dynamic_grid_bounds == 1
    assert calls == kernels


# ---- family sarvam_mla: latent attention in EVERY layer, 64 heads a row

@pytest.fixture
def sarvam_runner(one_chip, monkeypatch, tmp_path, expert_kernel):
    """``() -> (runner, params, state, page table)``: the hybrid runner at
    the widths, depth and share of ``sarvam-105b-p1-ep8-int8`` (the
    benchmark's configuration file, read as the worker reads it), int8, 32
    slots, the served context (5120), built from shapes alone."""
    import json
    from pathlib import Path

    from crowdllama_tpu.engine import runner as runner_mod
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.ops.quant import random_quantized_params

    monkeypatch.setattr(runner_mod, "shard_params", lambda p, cfg, mesh: p)
    doc = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                      / "chip" / "configs"
                      / "sarvam-105b-p1-ep8-int8.json").read_text())
    slots, ctx = doc["bench"]["slots"], doc["bench"]["context"]
    (tmp_path / "config.json").write_text(json.dumps(
        {k: v for k, v in doc.items() if k != "bench"}))

    def build():
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path),
                                   max_context_length=ctx)
        shapes = jax.eval_shape(lambda: random_quantized_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
        r = HybridPagedModelRunner(cfg, params=shapes, mesh_spec="1x1",
                                   max_slots=slots, max_seq=ctx,
                                   page_size=PAGE)
        assert r.kda_update_path == r.ssm_update_path == ""
        # the gates let every kernel through (asked past the backend test,
        # which here sees the CPU)
        with monkeypatch.context() as m:
            m.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
            assert not any(r._attention_refusals().values())
        r.attention_paths = {**r.attention_paths, "decode": "pallas",
                             "ragged_step": "pallas"}
        table = _sds((slots, ctx // PAGE), jnp.int32, one_chip)
        return (r, _placed(shapes, one_chip),
                _on_chip(jax.eval_shape(r.init_state), one_chip), table)

    return build


# the cell's latent pool as it is allocated: 9 layers, 32 slots x 40 pages +
# the dump page, rows [c ; k_rope] of 576 rounded up to whole lanes
SARVAM_POOL = (9, 32 * 40 + 1, 1, PAGE, 640)


@pytest.mark.parametrize("steps", [1, 2])
def test_sarvam_decode_program_keeps_its_pool_in_place(sarvam_runner, steps):
    """All nine layers of the cut in one step program, at a short flight's
    length and at the cell's own (``decode_chunk`` 2): the one latent pool
    (no V twin, no state beside it) is handed back where it lay — no
    ``copy`` of its shape — nine ``paged_decode_attention_mla`` a step, 24
    grouped matmuls (8 expert layers, three int8 banks), 4.89 GB of
    weights."""
    r, params, state, table = sarvam_runner()
    assert state.kda is None and state.ssm is None and state.wpool_k is None
    compiled = jax.jit(
        r._decode_paged_impl, donate_argnums=(1,), static_argnums=(3,)
    ).lower(params, state, table, steps).compile()
    _assert_latent_pool_is_worked_where_it_lies(compiled, state, SARVAM_POOL)
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    assert 4.85e9 < weights < 4.95e9
    text = compiled.as_text()
    assert len(_calls(text, "paged_decode_attention_mla")) == 9
    assert len(_calls(text, "moe_grouped_matmul")) == 24
    for ln in text.splitlines():
        if any(f" {op}(" in ln for op in ("fusion", "copy", "copy-start")):
            assert not any(f"= {t}[16,{d}]" in ln for t in ("bf16", "s8")
                           for d in ("4096,2048", "2048,4096")), (
                ln.strip()[:200])


@pytest.mark.parametrize("steps", [1, 2])
def test_sarvam_ragged_step_program_compiles_with_its_pool_in_place(
        sarvam_runner, one_chip, steps):
    """Decode rows beside a 512-token chunk, at both flight lengths the
    warm-up compiles: the v2 ragged kernel over one latent row a token (key
    and value both, 640 wide as stored) in blocks of 16 queries of 64
    heads, the decode rows through the GQA decode kernel on the same pool;
    the pool worked where it lies, as in the decode program."""
    from crowdllama_tpu.ops.pallas.paged import chunk_query_block

    r, params, state, table = sarvam_runner()
    assert r.ragged_chunk == CHUNK and r.ragged_width_fixed
    assert r._ragged_window() == r.max_pages_per_slot == 40
    assert chunk_query_block(1, 64, 640) == 16

    def i32(*shape):
        return _sds(shape, jnp.int32, one_chip)

    compiled = jax.jit(
        r._ragged_step_impl, donate_argnums=(1,), static_argnums=(7,)
    ).lower(params, state, table, i32(steps, CHUNK), i32(steps), i32(),
            i32(), steps).compile()
    _assert_latent_pool_is_worked_where_it_lies(compiled, state, SARVAM_POOL)
    text = compiled.as_text()
    assert len(_calls(text, "moe_grouped_matmul")) == 24
    assert len(_calls(text, "ragged_paged_attention")) == 9
    assert f"bf16[{CHUNK // 16},1,16,64,640]" in text
    # the decode rows beside the chunk: the GQA decode kernel on the one
    # latent pool (key and value both), not the decode STEP's _mla kernel
    assert len(_calls(text, "paged_decode_attention")) == 9
    assert not _calls(text, "paged_decode_attention_mla")


def test_sixty_four_head_latent_kernels_compile_at_the_cell_shape(one_chip):
    """One shared kv head, 64 query heads, rows of 576 — 640 as the pool
    stores them: the latent decode kernel, the ragged kernel (refused at 32
    queries a block: 18.7 MB of scoped VMEM against 16) and the cache-less
    prefill kernel at the largest bucket a prompt takes whole (512: a longer
    prompt is admitted in chunks, and rows of 576 stay in VMEM up to 1,820)."""
    from crowdllama_tpu.ops.pallas.paged import (paged_decode_attention_mla,
                                                 ragged_paged_attention)

    bf16, i32 = jnp.bfloat16, jnp.int32
    slots, heads, row, latent, np_ = 32, 64, 576, 512, 40
    stored = SARVAM_POOL[-1]
    pool = _sds(SARVAM_POOL, bf16, one_chip)
    table = _sds((slots, np_), i32, one_chip)
    scale = 0.135234

    def decode(q, pool, li, table, lens):
        return paged_decode_attention_mla(q, pool, li, table, lens, scale,
                                          latent)

    compiled = jax.jit(decode).lower(
        _sds((slots, heads, stored), bf16, one_chip), pool,
        _sds((), i32, one_chip), table, _sds((slots,), i32, one_chip)
    ).compile()
    assert len(_calls(compiled.as_text(), "paged_decode_attention_mla")) == 1

    def prefill(q, k, pos, valid):
        return flash_prefill_attention(q, k, k, pos, scale, kv_valid=valid)

    t = 512
    _assert_kernel(jax.jit(prefill).lower(
        _sds((1, t, heads, row), bf16, one_chip),
        _sds((1, 1, t, row), bf16, one_chip),
        _sds((1, t), i32, one_chip),
        _sds((1, t), jnp.bool_, one_chip)).compile())

    def ragged(q, ck, pool, li, table, ql, kl, cs):
        return ragged_paged_attention(q, ck, ck, pool, pool, li, table, ql,
                                      kl, cs, scale, use_pallas=True)

    _assert_kernel(jax.jit(ragged).lower(
        _sds((slots + CHUNK, heads, stored), bf16, one_chip),
        _sds((1, 1, CHUNK, stored), bf16, one_chip), pool,
        _sds((), i32, one_chip), table, _sds((slots + 1,), i32, one_chip),
        _sds((slots + 1,), i32, one_chip), _sds((), i32, one_chip)
    ).compile())
