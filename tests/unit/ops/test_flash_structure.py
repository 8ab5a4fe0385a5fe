"""What the gradient of causal GQA flash attention is made of at the
train cell's shape (``m7b-zero3-train-4chip``: one 4096-token sequence a
chip, 32 query and 8 KV heads of 128, four layers), read from its jaxpr,
from its lowering for the TPU and from the program the TPU's compiler
makes of it for a described v5e. Nothing runs and no clock is read.

* the grids walk the tiles on and below the diagonal and no others, and
  their index maps are a few ``lax`` primitives: no ``sign``, ``rem`` or
  ``floor`` (which Pallas lowers by tracing a helper each: 2.5 s of a
  start-up once, PERF.md PR 33) and no call of a jitted ``jnp`` wrapper;
* four layers trace and lower each kernel once;
* the backward reads K and V by KV head: nothing derived from K or V
  alone is ever as large as ``H`` heads of them;
* the compiled calls keep the shapes the benchmark's
  ``flash_attn_roofline`` finds them by (its own patterns, applied to
  the instructions' text): q the first operand of each in ``[B, H, T,
  D]``, the forward and dkv with a ``[B, H, T, D]`` first result of two,
  dq the only call with a single result.
"""

import itertools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from hcache_deepspeed_tpu.ops.flash_attention import pallas_attention

B, T, H, KV, D = 1, 4096, 32, 8, 128
LAYERS = 4
KERNELS = ["hds_flash_attention_" + k for k in ("fwd", "bwd_dq", "bwd_dkv")]


def _gradient(layers=LAYERS, **tiling):
    def loss(q, k, v, w):
        x = q
        for _ in range(layers):
            x = pallas_attention(x, k, v, causal=True, interpret=False,
                                 **tiling)
        return jnp.sum(x.astype(jnp.float32) * w.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def _shapes(T=T, H=H, KV=KV, sharding=None):
    return [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in ((B, T, H, D), (B, T, KV, D), (B, T, KV, D),
                      (B, T, H, D))]


def _inner(eqn):
    """The jaxprs an equation carries in its parameters."""
    for value in eqn.params.values():
        for x in value if isinstance(value, (list, tuple)) else [value]:
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _pallas_calls(jaxpr, seen=None):
    """Every distinct ``pallas_call`` equation under ``jaxpr``, in
    order. Four layers share one jaxpr of each kind, so each is met
    once however many layers call it."""
    seen = {} if seen is None else seen
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            seen.setdefault(id(eqn), eqn)
        else:
            for inner in _inner(eqn):
                _pallas_calls(inner, seen)
    return list(seen.values())


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for inner in _inner(eqn):
            yield from _primitives(inner)


def _index(mapping, *position):
    closed = mapping.index_map_jaxpr
    return tuple(int(x) for x in jax.core.eval_jaxpr(
        closed.jaxpr, closed.consts, *position))


# ------------------------------------------------------------------ #
# the walk: every grid position of every kernel against the mask
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("T,tiling,spare", [
    (T, {}, False),                  # what the cell runs: 1024 x 512, 1024^2
    (T, dict(block_q=512, block_k=512), False),
    (1024, dict(block_q=256, block_k=128), False),
    (1024, dict(block_q=128, block_k=256), False),
    (640, dict(block_q=128, block_k=128), True),     # five lines
    (768, dict(block_q=384, block_k=256), True),     # 384 and 256
], ids=["cell", "512", "256x128", "128x256", "odd", "undivided"])
def test_grids_hold_no_tile_above_the_diagonal(T, tiling, spare):
    closed = jax.make_jaxpr(_gradient(1, **tiling))(*_shapes(T, 4, 2))
    calls = _pallas_calls(closed.jaxpr)
    assert [c.params["name"] for c in calls] == KERNELS
    spares = []
    for call in calls:
        grid = call.params["grid_mapping"].grid
        q_map, k_map = call.params["grid_mapping"].block_mappings[:2]
        block_q, block_k = q_map.block_shape[2].block_size, \
            k_map.block_shape[2].block_size
        wanted = {(r, c) for r in range(T // block_q)
                  for c in range(T // block_k)
                  if r * block_q + block_q - 1 >= c * block_k}
        assert grid[:2] == (B, 4)
        walked = []
        for slot, step in itertools.product(*map(range, grid[2:])):
            b, h, row, zero = _index(q_map, 0, 3, slot, step)
            assert (b, h, zero) == (0, 3, 0)
            b, kv, col, zero = _index(k_map, 0, 3, slot, step)
            assert (b, kv, zero) == (0, 3 // 2, 0)    # h // rep
            walked.append((row, col))
        # no position stands on a tile above the diagonal and every tile
        # on or below it is stood on; where one block size divides the
        # other and the lines pair off, a position for each and no more
        assert set(walked) == wanted, call.params["name"]
        # a spare step stays where the step before it stood: the
        # pipeline fetches nothing for it
        moves = [a for a, b in zip(walked, walked[1:]) if a != b]
        assert len(moves) + 1 == len(wanted)
        spares.append(len(walked) - len(wanted))
    assert any(spares) == spare, spares


def test_index_maps_are_a_few_lax_primitives():
    closed = jax.make_jaxpr(_gradient(1))(*_shapes())
    calls = _pallas_calls(closed.jaxpr)
    assert len(calls) == 3
    for call in calls:
        for mapping in call.params["grid_mapping"].block_mappings:
            names = list(_primitives(mapping.index_map_jaxpr.jaxpr))
            assert not {"sign", "rem", "floor", "jit", "pjit",
                        "closed_call", "custom_jvp_call"} & set(names), names
            assert len(names) < 40, names
        # and the same walk in the body: no floor division of a grid
        # position there either
        body = set(_primitives(call.params["jaxpr"]))
        assert not {"sign", "rem", "floor"} & body, call.params["name"]


# ------------------------------------------------------------------ #
# four layers: one jaxpr and one lowered function of each kind
# ------------------------------------------------------------------ #
def test_four_layers_trace_and_lower_each_kernel_once():
    traced = jax.jit(_gradient()).trace(*_shapes())
    assert [c.params["name"]
            for c in _pallas_calls(traced.jaxpr.jaxpr)] == KERNELS
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == \
        sorted(KERNELS)
    assert text.count("tpu_custom_call") == 3


# ------------------------------------------------------------------ #
# K and V by KV head, from the jaxpr
# ------------------------------------------------------------------ #
def _visit(jaxpr, sources, calls, values):
    """``sources``: for each input of ``jaxpr``, which of the gradient's
    arguments it was computed from. Appends every Pallas call met to
    ``calls`` and ``(shape, sources)`` of every value to ``values``;
    returns the outputs' sources."""
    env = dict(zip(jaxpr.invars, sources))

    def read(atom):
        return env.get(atom, frozenset()) if hasattr(atom, "count") \
            else frozenset()

    for eqn in jaxpr.eqns:
        ins = [read(x) for x in eqn.invars]
        inner = None if eqn.primitive.name == "pallas_call" \
            else next(_inner(eqn), None)
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
        if inner is not None:
            outs = _visit(inner, ins, calls, values)
        else:
            outs = [frozenset().union(*ins)] * len(eqn.outvars)
        for var, src in zip(eqn.outvars, outs):
            env[var] = src
            values.append((tuple(var.aval.shape), src))
    return [read(x) for x in jaxpr.outvars]


def test_backward_reads_k_and_v_by_kv_head():
    closed = jax.make_jaxpr(_gradient(1))(*_shapes())
    calls, values = [], []
    _visit(closed.jaxpr, [frozenset(n) for n in "qkvw"], calls, values)
    kv_only = [shape for shape, src in values if src and src <= {"k", "v"}]
    assert kv_only, "the walk lost track of K and V"
    assert max(math.prod(s) for s in kv_only) == B * T * KV * D
    assert [c.params["name"] for c in calls] == KERNELS
    for call in calls:
        assert [tuple(x.aval.shape) for x in call.invars[:3]] == \
            [(B, H, T, D), (B, KV, T, D), (B, KV, T, D)]


# ------------------------------------------------------------------ #
# the TPU compiler's program, for a chip that is described and not
# attached
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def instructions(one_chip):
    """The compiled four-layer gradient's instructions, one text each,
    operand shapes printed as a device trace holds them."""
    from jax._src.lib import _jax
    from jax.experimental.compilation_cache import compilation_cache

    # a described device's program cannot be read back from the
    # persistent cache, so keep it out
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(_gradient()).lower(
            *_shapes(sharding=one_chip)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    options = _jax.HloPrintOptions()
    options.print_operand_shape = True
    options.print_metadata = False
    options.print_backend_config = False
    module, = compiled.runtime_executable().hlo_modules()
    return [line.strip().removeprefix("ROOT ")
            for line in module.to_string(options).splitlines()
            if " = " in line]


def _shape_list(text):
    return [tuple(int(n) for n in dims.split(","))
            for dims in re.findall(r"(?:bf16|f32)\[([\d,]+)\]", text)]


def test_compiled_kernels_take_k_and_v_by_kv_head(instructions):
    calls = [i for i in instructions if "tpu_custom_call" in i]
    assert len(calls) == 3 * LAYERS
    for text in calls:
        assert text.startswith(tuple("%" + k for k in KERNELS)), text[:80]
        operands = _shape_list(
            text.split("custom-call(", 1)[1].split("custom_call_target")[0])
        assert operands[:3] == [(B, H, T, D), (B, KV, T, D),
                                (B, KV, T, D)], text[:80]
    # and no operation of the program makes H heads of K or V: a
    # broadcast's result is never [.., H, .., D]-sized
    for text in instructions:
        if re.search(r"\bbroadcast\(", text) and "bf16[" in text:
            result = _shape_list(text.split(" broadcast(")[0])
            assert all(math.prod(s) < B * T * H * D for s in result), text


def test_compiled_calls_keep_the_shapes_the_roofline_metric_finds(
        instructions):
    import benchmarks
    with open(os.path.join(os.path.dirname(benchmarks.__file__), "metrics",
                           "flash_attn_roofline.json")) as f:
        spec = json.load(f)
    fill = {"flash_q": f"{B},{H},{T},{D}"}
    found = [i.split(" = ")[0] for i in instructions
             if re.search(spec["pattern"].format_map(fill), i)]
    # every kernel call and nothing else; a backward counted for each dq
    assert sorted(re.sub(r"\.\d+$", "", name) for name in found) == \
        sorted("%" + k for k in KERNELS * LAYERS), found
    counted = [i.split(" = ")[0] for i in instructions
               if re.search(spec["count_pattern"].format_map(fill), i)]
    assert len(counted) == LAYERS and all(
        name.startswith("%hds_flash_attention_bwd_dq") for name in counted)
