"""The KV pool's path through a serving forward (tier-1: see
``_TIER1_IN_SLOW_DIRS`` in ``tests/conftest.py``).

The pool is several times the weights of a deployment, so a forward that
forms a second copy of it, or of one layer of it, spends its time copying
cache. These tests read that off the compiled program: both pools aliased
input to output and nothing pool-sized among the temporaries. What costs
the time is what the TPU's compiler makes of the program (a ``[KV, D]``-window
scatter makes it keep the carried pool token-major and transpose it whole
for the kernel in every layer), so the same question is put to it too: here
for a described v5e at the serve cell's real shapes, and by
``chip_smoke.py`` on the chip.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hcache_deepspeed_tpu.inference.model import PagedInferenceModel
from hcache_deepspeed_tpu.inference.ragged.kv_cache import (
    BlockedKVCache, pool_scatters, pool_sized_copies, stacked_layer_copies)
from hcache_deepspeed_tpu.inference.ragged.lanes import lanes_width
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny

BS, NBLK, NB = 16, 2048, 8       # 32,768 slots; a sequence holds 128


@pytest.fixture(scope="module")
def model():
    cfg = llama_tiny(n_layer=4, max_positions=128, use_flash=False)
    params = LlamaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
        train=False)["params"]
    return PagedInferenceModel(cfg, params, block_size=BS,
                               max_blocks_per_seq=NB)


def _pool(model, dtype=jnp.float32):
    cfg = model.cfg
    return jax.ShapeDtypeStruct(
        (cfg.n_layer, cfg.n_kv_head, NBLK * BS, cfg.head_dim), dtype)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.mark.parametrize("B,T", [(8, 1), (1, 64)],
                         ids=["decode", "prefill"])
def test_compiled_forward_holds_one_buffer_a_pool(model, B, T):
    pool = _pool(model)
    compiled = model._fwd.lower(
        model.params, pool, pool, _i32(B, lanes_width(T, NB))).compile()
    layer_bytes = int(np.prod(pool.shape[1:])) * pool.dtype.itemsize
    pool_bytes = model.cfg.n_layer * layer_bytes
    mem = compiled.memory_analysis()
    # (a) each pool's output is its (donated) input's buffer
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    header = compiled.as_text().split("\n", 1)[0]
    assert "input_output_alias" in header
    n_leaves = len(jax.tree.leaves(model.params))
    for out, arg in ((0, n_leaves), (1, n_leaves + 1)):
        assert f"{{{out}}}: ({arg}, {{}}" in header, header
    # (b) and nothing the size of one layer of one pool is a temporary
    assert mem.temp_size_in_bytes < layer_bytes, (
        f"{mem.temp_size_in_bytes} bytes of temporaries: a layer of the "
        f"pool ({layer_bytes} bytes) is sliced out or copied again")


def test_forwards_change_only_the_slots_they_write(model):
    """Three forwards (a prompt, its continuation chunk, a decode step
    beside another sequence) on a pool pre-filled with other data: in
    every layer exactly the scattered slots change, and they change."""
    cfg = model.cfg
    cache = BlockedKVCache(cfg.n_layer, 64, BS, cfg.n_kv_head,
                           cfg.head_dim, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    before_k = rng.standard_normal(cache.k.shape).astype(np.float32)
    before_v = rng.standard_normal(cache.v.shape).astype(np.float32)
    cache.replace(jnp.asarray(before_k), jnp.asarray(before_v))

    tables = np.zeros((2, NB), np.int32)
    tables[0, :3] = [5, 9, 2]       # the sequence under test
    tables[1, :1] = [7]             # its neighbour in the decode step
    tok = lambda *shape: rng.integers(0, cfg.vocab_size, shape)
    # (tokens [B,T], start, t_len, rows of `tables`): the first chunk's
    # last 12 positions and one decode lane are padding, to be dropped
    forwards = [
        (tok(1, 32), [0], [20], [0]),
        (tok(1, 16), [20], [16], [0]),
        (tok(2, 1), [36, 3], [1, 0], [0, 1]),
    ]
    written = set()
    for tokens, start, t_len, rows in forwards:
        model.forward_chunk(cache, tokens, np.asarray(start),
                            tables[rows], np.asarray(t_len))
        for row, s, n in zip(rows, start, t_len):
            written.update(int(tables[row, p // BS]) * BS + p % BS
                           for p in range(s, s + n))
    assert len(written) == 37
    slots = np.zeros(cache.k.shape[2], bool)
    slots[sorted(written)] = True
    for before, after in ((before_k, np.asarray(cache.k)),
                          (before_v, np.asarray(cache.v))):
        changed = (before != after).any(axis=-1)        # [L, KV, P]
        assert not changed[:, :, ~slots].any()
        assert changed[:, :, slots].all()


# ------------------------------------------------------------------ #
# the TPU compiler's program, compiled for a chip that is described and
# not attached (this file and ops/test_flash_structure.py describe a
# topology, each inside a fixture: where only one process may load the
# TPU's library, the second to ask skips its compiled cases)
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def one_chip():
    import os
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


#: the text each v5e program lowers to, before the compiler has it:
#: ``(trunk, B, T) -> text`` (``test_causal_programs_lower_as_on_the_parent``)
_LOWERED = {}
#: and the static grid of the paged kernel's call in it
#: (``test_v5e_paged_kernel_steps_over_no_table_slot``)
_PAGED_GRID = {}


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(inner)


def _paged_grid(jaxpr):
    (grid,) = {eqn.params["grid_mapping"].grid
               for eqn in _pallas_calls(jaxpr)
               if "paged_attention" in str(eqn.params["name"])}
    return grid


class _ShapesOnly(PagedInferenceModel):
    """The model over ``ShapeDtypeStruct``s: a described device holds no
    array."""

    def load_params(self, params):
        self.params = params


def _step_lanes(B, T, n_blocks, slot=False):
    """Length of the flat operand of a step program over ``B`` decode
    lanes and one slice lane of ``T`` (``ragged/lanes.py pack_step``)."""
    return B * lanes_width(1, n_blocks, slot) + lanes_width(T, n_blocks,
                                                            slot)


@functools.lru_cache(maxsize=None)
def _v5e_program(one_chip, B, T, restore=False, step=False):
    """Mistral-7B widths, 2 layers, the serve cell's block size and pool
    (2560 blocks of 64), compiled for the described chip: ``(compiled,
    pool, params)``. ``restore``: the program that replays both layers'
    K and V from ``[2, B, T, H]`` latents, in place of the forward.
    ``step``: the program of a step of ``B`` decode lanes and one slice
    lane of ``T``."""
    from jax.experimental.compilation_cache import compilation_cache
    from hcache_deepspeed_tpu import platform
    from hcache_deepspeed_tpu.inference.model import stack_layer_params
    from hcache_deepspeed_tpu.models.llama import LlamaConfig

    # the registry picks the Pallas side; a described device's program
    # cannot be read back from the persistent cache, so keep it out
    platform.set_platform("tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=4096,
                          intermediate_size=14336, n_layer=2, n_head=32,
                          n_kv_head=8, max_positions=4096,
                          tie_word_embeddings=False, use_flash=False,
                          dtype=jnp.bfloat16)
        tree = jax.eval_shape(lambda: LlamaForCausalLM(cfg).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 8), np.int32)},
            train=False))["params"]

        def on_chip(x, dtype=jnp.bfloat16):   # served in the compute dtype
            return jax.ShapeDtypeStruct(x.shape, dtype, sharding=one_chip)

        params = jax.tree.map(on_chip, {
            "embed": tree["embed_tokens"]["embedding"],
            "norm": tree["norm"]["weight"],
            "layers": jax.eval_shape(
                lambda p: stack_layer_params(p, cfg.n_layer), tree),
            "lm_head": tree["lm_head"]["kernel"]})
        model = _ShapesOnly(cfg, params, block_size=64,
                            max_blocks_per_seq=32)
        pool = on_chip(jax.ShapeDtypeStruct(
            (cfg.n_layer, cfg.n_kv_head, 2560 * 64, cfg.head_dim),
            jnp.bfloat16))
        i32 = lambda *shape: on_chip(
            jax.ShapeDtypeStruct(shape, jnp.int32), jnp.int32)
        if restore:
            latents = on_chip(jax.ShapeDtypeStruct(
                (cfg.n_layer, B, T, cfg.hidden_size), jnp.bfloat16))
            lowered = model._restore.lower(
                params, pool, pool, i32(), latents, i32(B), i32(B, 32),
                i32(B))
        elif step:
            lowered = model.step_program(((B, 1), (1, T))).lower(
                params, pool, pool, i32(_step_lanes(B, T, 32)))
        else:
            traced = model._fwd.trace(params, pool, pool,
                                      i32(B, lanes_width(T, 32)))
            _PAGED_GRID["mistral", B, T] = _paged_grid(traced.jaxpr)
            lowered = traced.lower()
            _LOWERED["mistral", B, T] = lowered.as_text()
        compiled = lowered.compile()
    finally:
        platform._platform = None
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    return compiled, pool, params


V5E_SHAPES = pytest.mark.parametrize("B,T", [(8, 1), (1, 512)],
                                     ids=["decode", "slice"])


@V5E_SHAPES
def test_v5e_program_moves_no_layer_of_the_pool(one_chip, B, T):
    """The optimised v5e program holds no copy or slice of the pool's or
    a layer's extent, and its temporaries stay under one layer of one
    pool."""
    compiled, pool, _ = _v5e_program(one_chip, B, T)
    text = compiled.as_text()
    assert "tpu_custom_call" in text        # the paged kernel is there
    assert pool_sized_copies(text, pool.shape) == []
    layer_bytes = int(np.prod(pool.shape[1:])) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("B,T,restore",
                         [(8, 1, False), (1, 512, False), (1, 512, True),
                          (4, 64, False)],
                         ids=["decode", "slice", "restore", "four-lanes"])
def test_v5e_program_writes_its_rows_at_its_own_granularity(one_chip, B, T,
                                                            restore):
    """A lane that carries one position is one row in a block of its
    own: the decode program keeps the row scatter. A lane that carries
    more is runs of consecutive slots: the slice and restore programs
    write them through ``hds_kv_write``, a block an update, and hold no
    scatter of ``T x KV`` one-row updates into a pool. Either way both
    pools alias input to output, nothing of the pool's or a layer's
    extent is copied, and the temporaries stay under one layer of one
    pool."""
    compiled, pool, _ = _v5e_program(one_chip, B, T, restore)
    text = compiled.as_text()
    scatters = pool_scatters(text, pool.shape)
    if T == 1:
        assert len(scatters) == 2 and "hds_kv_write" not in text
    else:
        assert scatters == [] and "hds_kv_write" in text
    assert pool_sized_copies(text, pool.shape) == []
    # (a restore program keeps only the parameters it reads, so the
    # pools' argument numbers are its own)
    aliases = re.search(r"input_output_alias=\{ \{0\}: \((\d+), \{\}, "
                        r"may-alias\), \{1\}: \((\d+), \{\}, may-alias\) \}",
                        text.split("\n", 1)[0])
    assert aliases and int(aliases[2]) == int(aliases[1]) + 1
    mem = compiled.memory_analysis()
    layer_bytes = int(np.prod(pool.shape[1:])) * 2
    assert mem.alias_size_in_bytes >= 2 * pool.shape[0] * layer_bytes
    assert mem.temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("B,T,restore",
                         [(8, 1, False), (1, 512, False), (1, 512, True)],
                         ids=["decode", "slice", "restore"])
def test_v5e_program_reads_each_layers_weights_in_place(one_chip, B, T,
                                                        restore):
    """No operation of the optimised v5e program has one layer of a
    stacked weight leaf for its result: every matmul of the layer loop
    takes its layer of the leaf inside its own fusion, so a layer's
    weights cross HBM once a program. (With the split into heads folded
    into the q, k and v dots the compiler made them convolutions that
    wanted each kernel sliced out of the leaf and copied into another
    layout: two more passes, 15% of the serve cell's device time.)"""
    compiled, _, params = _v5e_program(one_chip, B, T, restore)
    kernels = [leaf.shape for leaf in jax.tree.leaves(params["layers"])
               if leaf.ndim == 3]
    assert len(kernels) == 7 and all(np.prod(shape[1:]) >= 1 << 20
                                     for shape in kernels)
    assert stacked_layer_copies(compiled.as_text(), kernels) == []


@V5E_SHAPES
@pytest.mark.parametrize("metric", ["paged_attn_roofline",
                                    "paged_kernel_roofline"])
def test_v5e_program_names_the_paged_kernel_to_its_finders(one_chip, B, T,
                                                           metric):
    """A device trace names an operation by its instruction's text, and
    the benchmark's two roofline metrics of the paged kernel find it
    there, one line at a time: by one layer's blocks after the custom
    call's target (what the kernel's operand was before it took the
    whole pool), and by the kernel's name."""
    import json
    import os
    import re
    import benchmarks
    with open(os.path.join(os.path.dirname(benchmarks.__file__), "metrics",
                           f"{metric}.json")) as f:
        spec = json.load(f)
    compiled, pool, _ = _v5e_program(one_chip, B, T)
    _, KV, P, D = pool.shape
    rx = re.compile(spec["pattern"].format_map(
        {"kv_blocks": f"{KV},{P // 64},64,{D}"}))
    # one instruction's text at a time, as a trace holds it (an
    # attribute's value may break the line: "." stops there)
    instructions = re.split(r"\n(?=\s*(?:ROOT )?%)", compiled.as_text())
    found = [text.split(" = ")[0].strip() for text in instructions
             if rx.search(text)]
    assert found and all(name.startswith("%hds_paged_attention")
                         for name in found), found


# ------------------------------------------------------------------ #
# the hybrid trunk's program (gated-delta-rule layers beside full
# attention): here, beside the serving programs' other compiled cases
# ------------------------------------------------------------------ #
@functools.lru_cache(maxsize=None)
def _v5e_hybrid_program(one_chip, B, T, step=False):
    """Olmo-Hybrid-7B widths, two periods (6 linear layers, 2 full), the
    cell's pools (1536 blocks of 64; 64 state slots and the spare),
    compiled for the described chip: ``(compiled, pools, params)``.
    ``step``: as :func:`_v5e_program`'s."""
    from jax.experimental.compilation_cache import compilation_cache
    from hcache_deepspeed_tpu import platform
    from hcache_deepspeed_tpu.inference.model_hybrid import (
        PagedHybridModel, serving_layout)
    from hcache_deepspeed_tpu.models.olmo_hybrid import (
        FULL, LINEAR, OlmoHybridConfig, OlmoHybridForCausalLM)

    class ShapesOnly(PagedHybridModel):
        def load_params(self, params):
            self.params = params

    platform.set_platform("tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = OlmoHybridConfig(
            vocab_size=100352, hidden_size=3840, intermediate_size=11008,
            n_layer=8, n_head=30, n_kv_head=30, max_positions=8192,
            layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2,
            dtype="bfloat16")
        tree = jax.eval_shape(lambda: OlmoHybridForCausalLM(cfg).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 8), np.int32)}))["params"]

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        params = jax.tree_util.tree_map_with_path(
            lambda path, x: on_chip(
                x.shape, jnp.float32 if PagedHybridModel._keep_fp32(path)
                else jnp.bfloat16),
            jax.eval_shape(lambda p: serving_layout(cfg, p), tree))
        model = ShapesOnly(cfg, params, block_size=64,
                           max_blocks_per_seq=128)
        pools = {
            "kv": on_chip((2, 30, 1536 * 64, 128), jnp.bfloat16),
            "state": on_chip((6, 65, 30, 96, 192), jnp.float32),
            "conv": on_chip((6, 65, 3 * cfg.conv_channels), jnp.bfloat16)}
        i32 = lambda *shape: on_chip(shape, jnp.int32)
        if step:
            lowered = model.step_program(((B, 1), (1, T))).lower(
                params, pools["kv"], pools["kv"], pools["state"],
                pools["conv"], i32(_step_lanes(B, T, 128, slot=True)))
        else:
            lowered = model._fwd.lower(
                params, pools["kv"], pools["kv"], pools["state"],
                pools["conv"], i32(B, lanes_width(T, 128, slot=True)))
            _LOWERED["hybrid", B, T] = lowered.as_text()
        compiled = lowered.compile()
    finally:
        platform._platform = None
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    return compiled, pools, params


@pytest.mark.parametrize("B,T,kernel",
                         [(8, 1, "gated_delta_step"),
                          (1, 512, "gated_delta_chunk")],
                         ids=["decode", "slice"])
def test_v5e_hybrid_program_holds_pools_and_weights_in_place(one_chip, B,
                                                             T, kernel):
    """The optimised v5e program of the hybrid trunk runs the
    gated-delta kernel of its shape and the paged kernel, copies or
    slices nothing of the extent of the KV pool or the state pool (or of
    a layer of either), lays the convolution tails' pool out only once,
    and reads every layer's weights inside the matmul that uses them (a
    period's layers handed over as the scan's ``xs`` were sliced out of
    their stack and copied: 1.3 GB of temporaries)."""
    compiled, pools, params = _v5e_hybrid_program(one_chip, B, T)
    text = compiled.as_text()
    assert f"hds_{kernel}" in text and "hds_paged_attention" in text
    # the slice writes its K and V by block runs, the decode lanes by rows
    scatters = pool_scatters(text, pools["kv"].shape)
    assert ("hds_kv_write" in text, len(scatters)) == \
        ((True, 0) if T > 1 else (False, 2)), scatters
    for name in ("kv", "state"):
        assert pool_sized_copies(text, pools[name].shape) == [], name
    assert [c for c in pool_sized_copies(text, pools["conv"].shape)
            if c.startswith("copy")] == []
    kernels = [leaf.shape for stack in ("lin_layers", "full_layers")
               for leaf in jax.tree.leaves(params[stack])
               if leaf.ndim == 3 and np.prod(leaf.shape[1:]) >= 1 << 20]
    assert len(kernels) == 8 + 7
    assert stacked_layer_copies(text, kernels) == []
    # temporaries stay under one state slot's worth of a few lanes, far
    # under a layer of any pool
    layer_bytes = int(np.prod(pools["state"].shape[1:])) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


# ------------------------------------------------------------------ #
# generation by diffusion over blocks (SDAR-MoE): the block program, and
# the causal programs it shares its trunk, kernels and lanes with
# ------------------------------------------------------------------ #
#: sha256 (first 16 digits) of the text the causal serving programs
#: lower to for the described v5e, the Pallas kernels' serialized bodies
#: left out (they carry the line numbers of ``ops/*.py``): recorded on
#: the parent of PR 42 (PR 38's tree) by these same functions. A
#: configuration without ``diffusion_block_length`` must go on lowering
#: to exactly these: the block mask, the per-head norm, the layers'
#: counts and the whole-stack expert products are statics that fold
#: away. A later PR that changes the trunk on purpose records its own.
#: The paged kernel's own jaxpr at ``mask_block`` 1 is PR 43's, which
#: gave the kernel its loop over a lane's own blocks (the programs'
#: text around it did not move: the pools were whole operands before).
#:
#: The sparse cell's two programs (the block program over 32 lanes of 4
#: and its prompt slice) were recorded on the parent of PR 53 (58af4b9),
#: before anything else of that PR was written: it gave the trunk a
#: second lane group for a causal step's decode lanes and prompt slice,
#: and a model that generates by diffusion over blocks has to run the
#: programs it ran before, digest for digest. The four causal ones came
#: through that PR unchanged. The latent trunk's two did not (the
#: parent's: da0f3e89de7fd0ed, f6ffa78c32fbedd0) and are PR 53's own: a
#: lane's last row, for the head and for the routers' probe, is taken at
#: ``t_len - 1`` by ``Lanes.last_rows`` where the probe counted the
#: lane's valid slots, the same row of any lane that has its blocks
#: (``test_latent_family.py
#: test_routing_the_reference_from_the_served_routers_input``).
_PARENT_LOWERED = {
    ("mistral", 8, 1): "39bac33fc0ec6e21",
    ("mistral", 1, 512): "636262a941e36f1f",
    ("hybrid", 8, 1): "b98f521a7036b852",
    ("hybrid", 1, 512): "dc2e86140bffcaae",
    ("sdar-block", 32, 4): "5ad7297a255dd8b2",
    ("sdar-slice", 1, 512): "2833aa14efcc9752",
    ("latent", 16, 1): "5a169b0acd803f24",
    ("latent", 1, 512): "12338aa0eb5c380e"}
_PARENT_PAGED_KERNEL = {(8, 1): "d1b28c3e6e780a26",
                        (1, 512): "b79c96fc96d9ca8f"}


def _digest(text):
    import hashlib
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("trunk,B,T", sorted(_PARENT_LOWERED))
def test_causal_programs_lower_as_on_the_parent(one_chip, trunk, B, T):
    if trunk.startswith("sdar"):
        _v5e_sdar_program(one_chip, B, T, block=trunk == "sdar-block")
    else:
        build = {"mistral": _v5e_program, "hybrid": _v5e_hybrid_program,
                 "latent": _v5e_latent_program}[trunk]
        build(one_chip, B, T)
    text = re.sub(r"[A-Za-z0-9+/=]{200,}", "<payload>",
                  _LOWERED[trunk, B, T])
    assert _digest(text) == _PARENT_LOWERED[trunk, B, T]


@pytest.mark.parametrize("B,T", sorted(_PARENT_PAGED_KERNEL))
def test_the_paged_kernel_at_block_one_is_the_causal_kernel(B, T):
    from hcache_deepspeed_tpu.ops.paged_attention import \
        pallas_paged_attention

    def call(q, k, v, tables, start, kv_len):
        return pallas_paged_attention(q, k, v, jnp.int32(1), tables, start,
                                      kv_len, 64, interpret=True)

    pool = jnp.zeros((2, 8, 64 * 64, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(call)(
        jnp.zeros((B, T, 32, 128), jnp.bfloat16), pool, pool,
        jnp.zeros((B, 32), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.int32))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    assert _digest(str(call.params["jaxpr"])) == _PARENT_PAGED_KERNEL[B, T]

@functools.lru_cache(maxsize=None)
def _v5e_sdar_program(one_chip, B, T, block=True):
    """SDAR-30B-A3B widths (128 experts of 768, top-8, heads of 128 on a
    hidden of 2048), 2 layers, the cell's pool (4096 blocks of 64),
    compiled for the described chip: the block program over ``B`` lanes
    of ``T`` positions, or (``block`` false) the prompt-slice program.
    Returns ``(compiled, pool, params)``."""
    from jax.experimental.compilation_cache import compilation_cache
    from hcache_deepspeed_tpu import platform
    from hcache_deepspeed_tpu.inference.model import stack_layer_params
    from hcache_deepspeed_tpu.inference.model_moe import PagedMoEModel
    from hcache_deepspeed_tpu.models.sdar_moe import (SdarMoeConfig,
                                                      SdarMoeForCausalLM)

    class ShapesOnly(PagedMoEModel):
        def load_params(self, params):
            self.params = params

    platform.set_platform("tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = SdarMoeConfig(
            vocab_size=151936, hidden_size=2048, intermediate_size=768,
            n_layer=2, n_head=32, n_kv_head=4, head_width=128,
            max_positions=2304, diffusion_block_length=4,
            dtype="bfloat16")
        tree = jax.eval_shape(lambda: SdarMoeForCausalLM(cfg).init(
            jax.random.PRNGKey(0),
            {"input_ids": np.zeros((1, 8), np.int32)},
            train=False))["params"]
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jax.ShapeDtypeStruct(
                x.shape, jnp.float32 if PagedMoEModel._keep_fp32(path)
                else jnp.bfloat16, sharding=one_chip),
            {"embed": tree["embed_tokens"]["embedding"],
             "norm": tree["norm"]["weight"],
             "layers": jax.eval_shape(
                 lambda p: stack_layer_params(p, cfg.n_layer), tree),
             "lm_head": tree["lm_head"]["kernel"]})
        model = ShapesOnly(cfg, params, block_size=64,
                           max_blocks_per_seq=36)
        pool = jax.ShapeDtypeStruct((2, 4, 4096 * 64, 128), jnp.bfloat16,
                                    sharding=one_chip)
        program = model._fwd_block if block else model._fwd
        lanes = jax.ShapeDtypeStruct(
            (B, lanes_width(T, 36, slot=block)), jnp.int32,
            sharding=one_chip)
        traced = program.trace(params, pool, pool, lanes)
        _PAGED_GRID["sdar", B, T] = _paged_grid(traced.jaxpr)
        lowered = traced.lower()
        _LOWERED["sdar-block" if block else "sdar-slice", B, T] = \
            lowered.as_text()
        compiled = lowered.compile()
    finally:
        platform._platform = None
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    return compiled, pool, params


@pytest.mark.parametrize("B,T,block", [(128, 4, True), (1, 512, False)],
                         ids=["block", "slice"])
def test_v5e_sdar_program_aliases_pools_and_copies_no_weight(one_chip, B, T,
                                                             block):
    """The block program (128 lanes of four positions, the choice made
    on the device) and the prompt slice under the block mask: both pools
    aliased input to output, nothing pool-sized copied, no stacked
    weight leaf (the experts' three stacks among them) sliced out or
    laid out again, the paged kernel in place."""
    compiled, pool, params = _v5e_sdar_program(one_chip, B, T, block)
    text = compiled.as_text()
    assert "hds_paged_attention" in text
    header = text.split("\n", 1)[0]
    n_leaves = len(jax.tree.leaves(params))
    for out, arg in ((0, n_leaves), (1, n_leaves + 1)):
        assert f"{{{out}}}: ({arg}, {{}}" in header, header
    assert pool_sized_copies(text, pool.shape) == []
    kernels = [leaf.shape for leaf in jax.tree.leaves(params["layers"])
               if leaf.ndim >= 3 and np.prod(leaf.shape[1:]) >= 1 << 20]
    assert len(kernels) == 4 + 3            # q, k, v, o and w1, w2, w3
    assert stacked_layer_copies(text, kernels) == []
    # nor one layer's 128 experts without the leading 1, which is how
    # ``lax.ragged_dot``'s custom call had them sliced out (403 MB a
    # product): the grouped matmul reads the stack by a layer index
    assert re.findall(r"= bf16\[128,(?:2048,768|768,2048)\]\S* "
                      r"(?!bitcast|parameter)[\w\-]+\(", text) == []
    assert text.count("hds_kernel=\"expert_gemm\"") >= 3
    layer_bytes = int(np.prod(pool.shape[1:])) * 2
    # the largest temporaries are the logits the choice is made from
    # (128 x 4 x 151,936 float32, 311 MB: the head's result, and two
    # passes of the softmax beside it), not a layer of the pool
    assert compiled.memory_analysis().temp_size_in_bytes < \
        (1e9 if block else layer_bytes)


@pytest.mark.parametrize("trunk,B,T,slots", [("sdar", 64, 4, 36),
                                             ("mistral", 8, 1, 32)],
                         ids=["sdar-block", "mistral-decode"])
def test_v5e_paged_kernel_steps_over_no_table_slot(one_chip, trunk, B, T,
                                                   slots):
    """The block program at the sparse cell's shapes (64 lanes of 4, 4
    KV heads of 128, a table of 36 slots, blocks of 64) and the Mistral
    decode program compile for the described chip with the paged kernel
    under both its names (``hds_kernel`` and the layer's block view),
    and the kernel's grid is a step a lane: the table's width is no
    factor of it, the walk over a lane's own blocks is the kernel's
    loop."""
    compiled, pool, _ = (_v5e_sdar_program if trunk == "sdar"
                         else _v5e_program)(one_chip, B, T)
    _, KV, P, D = pool.shape
    calls = [text for text in re.split(r"\n(?=\s*(?:ROOT )?%)",
                                       compiled.as_text())
             if re.search(r"hds_kernel\W+paged_attention", text)]
    assert calls and all(
        f'hds_kv_layer_view="bf16[{KV},{P // 64},64,{D}]"' in text
        for text in calls)
    grid = _PAGED_GRID[trunk, B, T]
    assert grid == (B, 1, 1), grid          # every head in one tile
    assert all(steps % slots for steps in grid if steps > 1)


# ------------------------------------------------------------------ #
# latent attention (GLM-4-MoE-Lite): a pool of compressed KV rows, a
# dense layer before the scan over the sparse stack
# ------------------------------------------------------------------ #
@functools.lru_cache(maxsize=None)
def _v5e_latent_program(one_chip, B, T, restore=False, step=False):
    """GLM-4.7-Flash widths (20 heads over rows of 512 + 64, 64 experts
    of 1536, top-4, a dense layer of 10240 first), 1 + 2 layers, the
    cell's pools (8192 blocks of 64) and table (512 slots: 32k
    contexts), compiled for the described chip: the forward over ``B``
    lanes of ``T`` positions, or (``restore``) the program that writes
    all three layers' saved cache rows back. Returns ``(compiled, (c
    pool, r pool), params)``."""
    from jax.experimental.compilation_cache import compilation_cache
    from hcache_deepspeed_tpu import platform
    from hcache_deepspeed_tpu.inference.model import stack_layer_params
    from hcache_deepspeed_tpu.inference.model_latent import PagedLatentModel
    from hcache_deepspeed_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig, param_shapes)

    class ShapesOnly(PagedLatentModel):
        def load_params(self, params):
            self.params = params

    platform.set_platform("tpu")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = Glm4MoeLiteConfig(
            vocab_size=154880, hidden_size=2048, intermediate_size=1536,
            dense_intermediate_size=10240, n_layer=3, n_head=20,
            max_positions=32768, num_experts=64, top_k=4, dtype="bfloat16")
        tree = param_shapes(cfg)
        model = ShapesOnly(cfg, None, block_size=64, max_blocks_per_seq=512)
        stacks = {
            "lead_layers": jax.eval_shape(
                lambda p: stack_layer_params(p, 1), tree),
            "layers": jax.eval_shape(lambda p: stack_layer_params(
                {f"layers_{i - 1}": p[f"layers_{i}"] for i in (1, 2)}, 2),
                tree)}
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jax.ShapeDtypeStruct(
                x.shape, jnp.float32 if PagedLatentModel._keep_fp32(path)
                else jnp.bfloat16, sharding=one_chip),
            {"embed": tree["embed_tokens"]["embedding"],
             "norm": tree["norm"]["weight"],
             "lm_head": tree["lm_head"]["kernel"],
             **{k: jax.eval_shape(model._absorbed, v)
                for k, v in stacks.items()}})
        model.params = params
        pools = tuple(jax.ShapeDtypeStruct(
            (3, 1, 8192 * 64, width), jnp.bfloat16, sharding=one_chip)
            for width in cfg.cache_row_widths)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=one_chip)
        if restore:
            rows = jax.ShapeDtypeStruct((3, B, T, 512 + 64), jnp.bfloat16,
                                        sharding=one_chip)
            lowered = model._restore.lower(
                params, *pools, i32(), rows, i32(B), i32(B, 512), i32(B))
        elif step:
            lowered = model.step_program(((B, 1), (1, T))).lower(
                params, *pools, i32(_step_lanes(B, T, 512)))
        else:
            lowered = model._fwd.lower(
                params, *pools, i32(B, lanes_width(T, 512)))
            _LOWERED["latent", B, T] = lowered.as_text()
        compiled = lowered.compile()
    finally:
        platform._platform = None
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    return compiled, pools, params


@pytest.mark.parametrize("B,T,restore",
                         [(16, 1, False), (1, 512, False), (1, 512, True)],
                         ids=["decode", "slice", "restore"])
def test_v5e_latent_program_holds_its_pools_in_place(one_chip, B, T,
                                                     restore):
    """The latent family's programs at the cell's real shapes: both
    pools (the ``c`` rows and the ``r`` rows) aliased input to output,
    nothing of either pool's or a layer's extent copied or sliced, no
    stacked weight leaf (the absorbed halves of ``W_kvb`` and the
    experts' stacks among them) sliced out or laid out again, the latent
    kernel and the grouped products in place, and **no constant of
    ``max_position_embeddings`` rows**: the rotary step takes its angles
    from the lanes' positions."""
    compiled, pools, params = _v5e_latent_program(one_chip, B, T, restore)
    text = compiled.as_text()
    header = text.split("\n", 1)[0]
    # the restore reads no parameter: a write of what was shipped
    first = 0 if restore else len(jax.tree.leaves(params))
    for out, arg in ((0, first), (1, first + 1)):
        assert f"{{{out}}}: ({arg}, {{}}" in header, header
    for pool in pools:
        assert pool_sized_copies(text, pool.shape) == []
    assert not re.search(r"\[32768[,\]]", text)
    layer_bytes = int(np.prod(pools[0].shape[1:])) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    if restore:
        # a ship and a write: no product, no kernel but the block-run
        # write
        assert "hds_kv_write" in text and "hds_latent_attention" not in text
        assert not re.search(r"= \w+\[[\d,]*\]\S* (dot|convolution)\(",
                             text)
        return
    assert "hds_latent_attention" in text
    assert text.count("hds_kernel=\"expert_gemm\"") >= 3
    assert ("hds_kv_write" in text) == (T > 1)
    kernels = [leaf.shape for leaf in jax.tree.leaves(params["layers"])
               if leaf.ndim >= 3 and np.prod(leaf.shape[1:]) >= 1 << 20]
    # q_a, q_b, kv_a, w_uk, w_uv, o; w1, w2, w3; the shared expert's 3
    assert len(kernels) == 6 + 3 + 3
    assert stacked_layer_copies(text, kernels) == []
    assert re.findall(r"= bf16\[64,(?:2048,1536|1536,2048)\]\S* "
                      r"(?!bitcast|parameter)[\w\-]+\(", text) == []


def _v5e_step_program(one_chip, trunk):
    """The program of a step of 8 decode lanes and a 512-token slice at
    the serve cell's sizes of ``trunk``: ``(compiled, pools, params)``."""
    if trunk == "mistral":
        compiled, pool, params = _v5e_program(one_chip, 8, 512, step=True)
        return compiled, {"kv": pool}, params
    if trunk == "hybrid":
        return _v5e_hybrid_program(one_chip, 8, 512, step=True)
    compiled, pools, params = _v5e_latent_program(one_chip, 8, 512,
                                                  step=True)
    return compiled, dict(zip(("c", "r"), pools)), params


def _instructions(text, kernel):
    """The instructions of a compiled program that the benchmark's
    finders take for ``kernel``: one instruction's text at a time, as a
    trace holds it, searched for ``hds_kernel`` and the name."""
    return [ins for ins in re.split(r"\n(?=\s*(?:ROOT )?%)", text)
            if re.search(rf"hds_kernel\W+{kernel}", ins)
            and "custom_call_target" in ins]


@pytest.mark.parametrize("trunk", ["mistral", "hybrid", "latent"])
def test_v5e_step_program_holds_pools_and_names_both_shapes(one_chip, trunk):
    """The program of a step's 8 decode lanes and 512-token slice at the
    serve cells' sizes: every pool in one buffer (nothing of a pool's or
    a layer's extent copied; the only scatters into a KV pool are the
    decode lanes' rows, the slice goes by block runs), no layer of a
    stacked weight copied, no kernel given up for its reference, and
    each per-lane kernel called at both its shapes under the name the
    benchmark's finders look for: the paged kernel twice a full layer,
    the gated-delta step and chunk kernels once a linear layer each,
    the latent kernel twice a layer. The matrix products run once over
    all 520 rows."""
    from hcache_deepspeed_tpu import ops
    ops.reset_fallback_report()
    compiled, pools, params = _v5e_step_program(one_chip, trunk)
    assert ops.fallback_report() == {}
    text = compiled.as_text()
    for name, pool in pools.items():
        copies = pool_sized_copies(text, pool.shape)
        if name == "conv":      # laid out once, as the two programs do
            copies = [c for c in copies if c.startswith("copy")]
        assert copies == [], name
    kv = [pool for name, pool in pools.items() if name in ("kv", "c", "r")]
    for pool in kv:
        scatters = pool_scatters(text, pool.shape)
        assert len(scatters) == 2, scatters     # the decode lanes' rows
    assert "hds_kv_write" in text
    stacks = [k for k in params if k.endswith("layers")]
    kernels = [leaf.shape for stack in stacks
               for leaf in jax.tree.leaves(params[stack])
               if leaf.ndim >= 3 and np.prod(leaf.shape[1:]) >= 1 << 20]
    assert stacked_layer_copies(text, kernels) == []
    # the layer loop's body holds one layer (the hybrid's: one period,
    # three linear layers and a full one; the latent trunk's dense lead
    # layer stands unrolled before it)
    want = {"mistral": {"paged_attention": [(8, 1), (1, 512)]},
            "hybrid": {"paged_attention": [(8, 1), (1, 512)],
                       "gated_delta_step": [(8, 1)] * 3,
                       "gated_delta_chunk": [(1, 512)] * 3},
            "latent": {"latent_attention": [(8, 1), (1, 512)] * 2}}[trunk]
    for kernel, shapes in want.items():
        calls = _instructions(text, kernel)
        assert len(calls) == len(shapes), (kernel, len(calls))
        found = sorted(
            (8, 1) if re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[8,", ins)
            else (1, 512) for ins in calls)
        assert found == sorted(shapes), (kernel, found)
    layer_bytes = min(int(np.prod(pool.shape[1:])) * pool.dtype.itemsize
                      for pool in pools.values() if pool.ndim >= 4)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    # one pass over the weights: the MLP's products take all rows
    rows = {"mistral": "520,14336", "hybrid": "520,11008",
            "latent": "520,10240"}[trunk]
    assert f"bf16[{rows}]" in text
