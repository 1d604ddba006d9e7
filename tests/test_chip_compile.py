"""The page pool stays in place: the TPU compiler's own verdict, at no chip
time.

The paged decode step, the one-chip adopt and the split runtime's staged
adopt are compiled HERE for a described (not attached) TPU v5e at the
benchmark cells' real shapes, and the compiled modules are held to what
PERF.md §6 "PR 29" found: the pool is a donated buffer that row scatters
and page gathers address in place. A pool that is copied, relaid or stacked
back layer by layer — 113 of 181 ms of the step before that PR — shows here
as a ``copy``, ``dynamic-update-slice`` or ``reshape`` of pool size and as
gigabytes of temporaries. Nothing runs, so nothing here is a time.

Each step is compiled on BOTH decode reads (``paged_kv.decode_read_path``):
the page walk a TPU takes (PERF.md §6 "PR 33": one kernel fetches each slot's
live pages, and no span-sized copy of K or V exists anywhere in the module),
and the page gather every other backend keeps as the oracle. The test
process's backend is the CPU, so the ``walk`` fixture answers the one
question the choice asks of the backend as a TPU would.

ONE file, the topology described inside a fixture (``on-chip-measurement``
§2): only the worker that is handed this file loads the TPU's library.
"""
import dataclasses
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from edgellm_tpu.models import grouped_matmul, hybrid, moe, paged_kv
from edgellm_tpu.models.configs import DEEPSEEK_V3_2_EXP, \
    DOTS3_NOTE_PREV, \
    KEYE_VL_2_0_30B_A3B, LFM2_8B_A1B, \
    LONGCAT_FLASH_CHAT, ModelConfig, tiny_afmoe_config, tiny_hybrid_config, \
    tiny_lfm2_moe_config, tiny_longcat_flash_config, tiny_mellum_config, \
    tiny_mistral4_config
from edgellm_tpu.models.transformer import init_params
from edgellm_tpu.serve import batching

# benchmark/configs/qwen2-0.5b.json and qwen2-1.5b-split4.json: the widths
# and the serving geometry of the cells (the test must not read benchmark/)
QWEN05 = ModelConfig(
    family="qwen2", vocab_size=151936, hidden_size=896, num_layers=24,
    num_heads=14, num_kv_heads=2, intermediate_size=4864,
    max_position_embeddings=131072, norm_eps=1e-6, rope_theta=1e6,
    tie_word_embeddings=True)
PAGES, PAGE, SLOTS, PAGES_PER_SLOT = 24577, 16, 192, 128
SPLIT_STAGES, SPLIT_STAGE_SIZE, SPLIT_KV, SPLIT_HD = 4, 7, 2, 128
PROMPT = 1000      # 62 whole pages and 8 rows: both scatters of an adopt


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it undescribed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it off around these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(params=["walk", "gather"])
def read(request, monkeypatch):
    """The decode read a step is compiled on. ``walk``: what the program
    picks on a TPU for an fp pool of whole tiles; ``gather``: what it picks
    here, the XLA oracle."""
    steps = (batching._batched_step_jit, batching._batched_window_step_jit,
             batching._batched_hybrid_step_jit)

    def forget():       # a jit keeps its trace by arguments, not by backend
        for step in steps:
            step.clear_cache()

    forget()
    if request.param == "walk":
        monkeypatch.setattr(paged_kv, "_on_tpu", lambda: True)
    yield request.param
    forget()


def _walks(hlo: str) -> int:
    """Page-walk kernels in a compiled module."""
    return sum(op == "custom-call" and "paged_decode_walk" in line
               for op, _, _, line in _instructions(hlo))


def _walk_operands(hlo: str) -> list:
    """What each page-walk kernel of a compiled module is handed: the result
    types of its operands, in order (page ids, lengths, query, then what it
    reads out of HBM)."""
    return [re.findall(r"([a-z]+[0-9]+\[[\d,]*\])", re.search(
                r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=", line)[1])
            for op, _, _, line in _instructions(hlo)
            if op == "custom-call" and "paged_decode_walk" in line]


def _row_writes(hlo: str) -> int:
    """Fusions that scatter under ``paged_kv.write``: a step's new rows, a
    layer. (Not a ``.remat`` one: where memory is tight the compiler may run
    a layer's 96-row scatter a second time, in place again, rather than keep
    its result alive across the layers between: the mellum step's first full
    layer.)"""
    writers, computation = set(), None
    for line in hlo.splitlines():
        header = re.match(r"^%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            computation = header[1]
        elif " scatter(" in line and "paged_kv.write" in line:
            writers.add(computation)
    return sum(op == "fusion" and "remat" not in name
               and re.search(r"calls=%?([\w.\-]+)", line)[1] in writers
               for op, name, _, line in _instructions(hlo))


def _span_sized(hlo: str, shapes) -> list:
    """Results of any instruction whose type names one of ``shapes``: a
    slot's whole span of K or V, gathered, reshaped or fused."""
    return [shape for _, _, shape, _ in _instructions(hlo)
            if any(s in shape for s in shapes)]


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _elements(shape_text: str) -> int:
    """The largest array an HLO result type names, in elements."""
    return max((int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
                for dims in re.findall(r"[a-z]+[0-9]+\[([\d,]*)\]",
                                       shape_text)), default=0)


def _instructions(hlo: str):
    """(opcode, name, result type, line) of every instruction of every
    computation of a compiled module, fused computations included."""
    for line in hlo.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\(",
                     line)
        if m:
            name, shape, op = m.groups()
            yield op, name, shape, line


def _moved(hlo: str, at_least: int, ops=("copy", "dynamic-update-slice",
                                         "reshape", "transpose")):
    """Instructions that materialize an array of ``at_least`` elements or
    more by moving it. (A ``bitcast`` moves nothing; the page gather's own
    fused computation holds the ``reshape``/``transpose`` of its OUTPUT,
    which is the gather, so it is exempt by its shape below.)"""
    return [(op, name, shape.split("{")[0]) for op, name, shape, _ in
            _instructions(hlo) if op in ops and _elements(shape) >= at_least]


def test_decode_step_updates_the_pool_where_it_lies(topo, read):
    one = SingleDeviceSharding(topo.devices[0])
    params = _shapes(jax.eval_shape(
        lambda: init_params(QWEN05, jax.random.key(0), dtype=jnp.bfloat16)),
        one)
    pool = _shapes(jax.eval_shape(
        lambda: paged_kv.init_pool(QWEN05, PAGES, PAGE, jnp.bfloat16)), one)
    # a row: 128 K lanes (2 KV heads of 64), then 128 V lanes, in ONE leaf
    width = 2 * QWEN05.num_kv_heads * QWEN05.head_dim
    (leaf,) = pool
    assert leaf.shape == (24, PAGES, PAGE, width) and width == 256
    assert paged_kv.decode_read_path(pool) == {
        "walk": paged_kv.PAGE_WALK, "gather": paged_kv.PAGE_GATHER}[read]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((SLOTS,), jnp.int32)
    step = batching._batched_step_jit.lower(
        QWEN05, params, pool, arr((SLOTS, PAGES_PER_SLOT), jnp.int32), ints,
        ints, arr((SLOTS, 2), jnp.uint32), ints, arr((SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    layer_pool = PAGES * PAGE * width           # one layer's K and V
    gathered = SLOTS * PAGES_PER_SLOT * PAGE * width   # a layer's span read
    # the gather custom fusion names its own output's reshape/transpose
    # (`bf16[24576,16,256]`, `bf16[192,128,16,256]`): the gather itself
    own = {f"bf16[{SLOTS * PAGES_PER_SLOT},{PAGE},{width}]",
           f"bf16[{SLOTS},{PAGES_PER_SLOT},{PAGE},{width}]"}
    moved = [m for m in _moved(hlo, gathered)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    # no copy / dynamic-update-slice / reshape produces a pool-sized array,
    # and no K/V-sized relayout sits between the page gather and the dots
    assert not moved, moved
    # both reads take whole pages at (layer, page) of the pool viewed
    # (L*P, ps, width) ...
    flat_pages = f"bf16[{24 * PAGES},{PAGE},{width}]"
    assert flat_pages in hlo, "the pool is not read as (L*P, ps, width)"
    # ... and the row writes scatter into the pool viewed as rows
    assert f"bf16[{24 * PAGES * PAGE},{width}]" in hlo
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 24 * layer_pool * 2    # donated
    # a step's new rows: ONE scatter a layer (the scan's body), K and V lanes
    assert _row_writes(hlo) == 1, _row_writes(hlo)
    if read == "gather":
        # the attend's dots read the gather's output as it lies
        assert "bhD,bcD->bhc" in hlo and "bhc,bcD->bhD" in hlo
        assert not _walks(hlo)
        # a gathered copy of one layer's K and V at a time
        assert 100e6 < mem.temp_size_in_bytes < 0.5e9
        return
    # the walk: one kernel a layer (the scan's body), the ONE leaf handed to
    # it whole as its one operand in HBM, and NOTHING span-sized anywhere in
    # the module: no result of a slot's 2048 rows a slot, gathered, reshaped
    # or fused
    assert _walks(hlo) == 1, _walks(hlo)
    # (8 KB pages go eight to a run: behind the lengths, in the one array,
    # a count a block of 32 entries of how many runs lead it)
    (operands,) = _walk_operands(hlo)
    assert operands[1:] == [
        f"s32[{SLOTS * (1 + PAGES_PER_SLOT // 32)}]",
        f"bf16[{SLOTS},{QWEN05.num_heads},{width // 2}]", flat_pages], operands
    # that table is the page table's, not a layer's: made ONCE a step, ahead
    # of the scan over the layers (in the scan's body the compiler makes it
    # again every layer: 6 us a layer on the chip, PERF.md section 6 "PR 46")
    made = [line for _, _, shape, line in _instructions(hlo)
            if shape.startswith(f"s32[{SLOTS},{PAGES_PER_SLOT // 32}]")]
    assert made and not [line for line in made if "/while/" in line], made
    spans = _span_sized(hlo, own | {
        f"bf16[{SLOTS},{PAGES_PER_SLOT * PAGE},{width}]"})
    assert not spans, spans[:3]
    # the gathered copy was the step's temporaries: 126.5 MB on the gather,
    # 0.7 MB here
    assert mem.temp_size_in_bytes < 5e6, mem.temp_size_in_bytes


def test_adopt_scatters_in_place(topo):
    one = SingleDeviceSharding(topo.devices[0])
    pool = _shapes(jax.eval_shape(
        lambda: paged_kv.init_pool(QWEN05, PAGES, PAGE, jnp.bfloat16)), one)
    rows = jax.ShapeDtypeStruct(
        (24, PROMPT, QWEN05.num_kv_heads, QWEN05.head_dim), jnp.bfloat16,
        sharding=one)
    dest = jax.ShapeDtypeStruct((PROMPT,), jnp.int32, sharding=one)
    # head=0: a prefill's adopt, whole pages a scatter slice (the cells' path)
    adopt = paged_kv._adopt_impl.lower(pool, rows, rows, dest,
                                       head=0).compile()
    layer_pool = PAGES * PAGE * pool.kv.shape[-1]
    assert not _moved(adopt.as_text(), layer_pool), \
        _moved(adopt.as_text(), layer_pool)
    assert adopt.memory_analysis().temp_size_in_bytes < 16e6


def test_staged_adopt_scatters_in_place_over_four_chips(topo):
    from edgellm_tpu.parallel import split

    mesh = Mesh(np.asarray(topo.devices[:SPLIT_STAGES]), ("stage",))
    staged = NamedSharding(mesh, P("stage"))
    width = 2 * SPLIT_KV * SPLIT_HD         # a row: K lanes, then V lanes
    leaf = jax.ShapeDtypeStruct(
        (SPLIT_STAGES, SPLIT_STAGE_SIZE, PAGES, PAGE, width), jnp.bfloat16,
        sharding=staged)
    rows = jax.ShapeDtypeStruct(
        (SPLIT_STAGES, SPLIT_STAGE_SIZE, PROMPT, SPLIT_KV, SPLIT_HD),
        jnp.float32, sharding=staged)
    dest = jax.ShapeDtypeStruct((PROMPT,), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    adopt = split._adopt_paged_impl.lower(
        paged_kv.PagePool(leaf), rows, rows, dest, head=0).compile()
    hlo = adopt.as_text()
    layer_pool = PAGES * PAGE * width
    assert not _moved(hlo, layer_pool), _moved(hlo, layer_pool)
    # stage-elementwise: the stage axis stayed sliced, nothing crosses chips
    assert not re.search(r"all-gather|all-reduce|collective-permute|"
                         r"all-to-all", hlo)
    assert adopt.memory_analysis().temp_size_in_bytes < 16e6


# benchmark/configs/mellum2-12b-a2.5b-pp4.json: the widths and the serving
# geometry of the cell (two periods of three sliding layers and a full one)
MELLUM = ModelConfig(
    family="mellum", vocab_size=98304, hidden_size=2304, num_layers=8,
    num_heads=32, num_kv_heads=4, intermediate_size=7168,
    max_position_embeddings=131072, norm_eps=1e-6, rope_theta=500000.0,
    rope_scaling=("yarn", 16.0, 8192, 32.0, 1.0, 1.2772588722239782),
    layer_types=(("sliding_attention",) * 3 + ("attention",)) * 2,
    explicit_head_dim=128, sliding_window=1024, num_experts=64,
    experts_per_tok=8, expert_width=896)
M_SLOTS, M_PAGES_PER_SLOT = 96, 384


def test_window_layers_walk_their_rings_and_both_pools_stay_in_place(topo,
                                                                     read):
    """The step of a stack with sliding layers. On the page walk every
    attention layer is ONE kernel that reads out of its pool where the pages
    lie: a window layer's over each slot's RING (65 entries, masked by the
    position a row holds; PERF.md §6 "PR 40"), a full layer's over its live
    pages, and no ring- or span-sized copy of K or V exists anywhere in the
    module. On the gather (the oracle) a window layer's gather takes the
    ring, never the span (384), and a full layer's the span. Either way
    neither pool is copied, relaid or stacked, and what is gathered is the
    step's temporaries (0.78 GB beside 11.2 GB of weights and pools; 0.15 GB
    on the walk, the sampler's four (96, 98304) arrays: 156 -> 152 MB with
    PR 40, a ring's gather having been fused with its attend before it)."""
    one = SingleDeviceSharding(topo.devices[0])
    params = _shapes(jax.eval_shape(
        lambda: init_params(MELLUM, jax.random.key(0), dtype=jnp.bfloat16)),
        one)
    ring = MELLUM.window_pages(PAGE)
    assert ring == 65
    full = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        MELLUM, M_SLOTS * M_PAGES_PER_SLOT + 1, PAGE, jnp.bfloat16)), one)
    window = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        MELLUM, M_SLOTS * ring + 1, PAGE, jnp.bfloat16,
        layers=MELLUM.window_layers)), one)
    # a row of either pool: 512 K lanes (4 KV heads of 128), then 512 V lanes
    width = 2 * MELLUM.num_kv_heads * MELLUM.head_dim
    assert [a.shape for a in full] == [(2, 36865, PAGE, width)]
    assert [a.shape for a in window] == [(6, 6241, PAGE, width)]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((M_SLOTS,), jnp.int32)
    step = batching._batched_window_step_jit.lower(
        MELLUM, params, full, window, arr((8, 64), jnp.int32),
        arr((M_SLOTS, M_PAGES_PER_SLOT), jnp.int32),
        arr((M_SLOTS, ring), jnp.int32), ints, ints,
        arr((M_SLOTS, 2), jnp.uint32), ints, arr((M_SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    gathered = M_SLOTS * ring * PAGE * width        # a window layer's read
    gathers = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
               if op == "gather" and _elements(shape) >= gathered]
    span = f"bf16[{M_SLOTS},{M_PAGES_PER_SLOT},{PAGE},{width}]"
    rings = f"bf16[{M_SLOTS},{ring},{PAGE},{width}]"
    # the rows (K and V lanes in one) of the 6 window layers (static walk)
    # and of the 2 full layers where they are gathered; on the page walk
    # neither a span nor a ring is ever materialized: a kernel a layer, 2
    # full and 6 window, each handed its pool's ONE leaf as pages
    assert sorted(gathers) == ([span] * 2 + [rings] * 6
                               if read == "gather" else []), gathers
    assert _walks(hlo) == (8 if read == "walk" else 0)
    # a step's new rows: one scatter a layer
    assert _row_writes(hlo) == 8, _row_writes(hlo)
    if read == "walk":
        assert not _span_sized(hlo, (rings, span))
        query = f"bf16[{M_SLOTS},{MELLUM.num_heads},{width // 2}]"
        assert sorted(tuple(ops[2:]) for ops in _walk_operands(hlo)) == sorted(
            [(query, f"bf16[{2 * 36865},{PAGE},{width}]")] * 2
            + [(query, f"bf16[{6 * 6241},{PAGE},{width}]")] * 6)
    own = {span, rings,
           f"bf16[{M_SLOTS * M_PAGES_PER_SLOT},{PAGE},{width}]",
           f"bf16[{M_SLOTS * ring},{PAGE},{width}]"}
    moved = [m for m in _moved(hlo, gathered)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    assert not moved, moved
    # each pool is addressed flat: pages at (layer, page), rows at (l, p, r)
    assert f"bf16[{2 * 36865},{PAGE},{width}]" in hlo
    assert f"bf16[{6 * 6241},{PAGE},{width}]" in hlo
    assert f"bf16[{6 * 6241 * PAGE},{width}]" in hlo
    mem = step.memory_analysis()
    # 1.32 GB with the full layers' gathered spans and the rings' (the
    # oracle splits each gathered copy on lanes into K and V: 778 MB as two
    # leaves); on the walk 152 MB, the logits and the sampler's bits over
    # the vocabulary
    assert mem.temp_size_in_bytes < (1.5e9 if read == "gather" else 0.2e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < (
        12.6e9 if read == "gather" else 12.5e9)


# benchmark/configs/qwen2-1.5b-split4.json: the widths of the four-chip cell
QWEN15 = ModelConfig(
    family="qwen2", vocab_size=151936, hidden_size=1536, num_layers=28,
    num_heads=12, num_kv_heads=2, intermediate_size=8960,
    max_position_embeddings=131072, norm_eps=1e-6, rope_theta=1e6,
    tie_word_embeddings=True)


def test_staged_decode_step_updates_the_pool_where_it_lies_over_four_chips(
        topo, read):
    """``step_paged_fn`` as the split cell runs it (four stages of 7 layers,
    hops int8 / int4 / int8): each stage's pool is a scan carry that the row
    scatters and page gathers address in place through all four unroll
    iterations. On the parent's tree the same module held 8 ``select``, 8
    ``dynamic-update-slice`` and 8 ``broadcast`` of a stage's whole
    ``bf16[7,24577,16,256]`` leaf, 8 ``select`` of a layer's and 5.45 GB of
    temporaries (PERF.md §6 "PR 31"): 155 of a 215 ms step."""
    from edgellm_tpu.parallel import SplitConfig, SplitRuntime, \
        make_stage_mesh

    mesh = make_stage_mesh(SPLIT_STAGES, devices=topo.devices)
    staged, everywhere = (NamedSharding(mesh, P("stage")),
                          NamedSharding(mesh, P()))
    rt = SplitRuntime(QWEN15, SplitConfig(
        cuts=(6, 13, 20), hop_codecs=("int8_per_token", "int4_per_token",
                                      "int8_per_token")), mesh)
    sz = rt.stage_size
    assert sz == SPLIT_STAGE_SIZE
    params = jax.eval_shape(
        lambda: init_params(QWEN15, jax.random.key(0), dtype=jnp.bfloat16))
    placed = _shapes({k: v for k, v in params.items() if k != "layers"},
                     everywhere)
    placed["layers"] = {
        k: jax.ShapeDtypeStruct((SPLIT_STAGES, sz) + v.shape[1:], v.dtype,
                                sharding=staged)
        for k, v in params["layers"].items()}
    placed["layers_valid"] = jax.ShapeDtypeStruct(
        (SPLIT_STAGES, sz), jnp.bool_, sharding=staged)
    width = 2 * SPLIT_KV * SPLIT_HD         # a row: K lanes, then V lanes
    leaf = jax.ShapeDtypeStruct((SPLIT_STAGES, sz, PAGES, PAGE, width),
                                jnp.bfloat16, sharding=staged)

    def arr(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=everywhere)

    step = rt._paged_decode_fns(PAGES, PAGE).lower(
        placed, paged_kv.PagePool(leaf), arr((SLOTS, PAGES_PER_SLOT)),
        arr((SLOTS,)), arr((SLOTS,))).compile()
    hlo = step.as_text()
    layer_pool = PAGES * PAGE * width
    gathered = SLOTS * PAGES_PER_SLOT * PAGE * width
    own = {f"bf16[{SLOTS * PAGES_PER_SLOT},{PAGE},{width}]",
           f"bf16[{SLOTS},{PAGES_PER_SLOT},{PAGE},{width}]"}
    moving = ("copy", "select", "dynamic-update-slice", "reshape",
              "transpose", "broadcast", "dynamic-slice")
    moved = [m for m in _moved(hlo, gathered, moving)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    # nothing of a layer's pool or larger is copied, selected, sliced out,
    # put back or relaid, and nothing K/V-sized but the gather's own output
    assert not moved, moved
    # the stage's pool is read as pages at (layer, page), written as rows
    assert f"bf16[{sz * PAGES},{PAGE},{width}]" in hlo, \
        "the stage's pool is not read as (sz*P, ps, width)"
    assert f"bf16[{sz * PAGES * PAGE},{width}]" in hlo
    if read == "walk":
        # inside shard_map, in every unroll iteration's scan body, dead ones
        # included: the kernel, and no span-sized K or V anywhere
        assert _walks(hlo) >= 1, _walks(hlo)
        assert not _span_sized(hlo, own), _span_sized(hlo, own)[:3]
        # every one of them reads ONE operand out of HBM: the stage's leaf
        # (16 KB pages go four to a run: behind the lengths a count a block
        # of 32 entries of how many runs lead it; then the stage's query)
        for ops in _walk_operands(hlo):
            assert ops[1] == f"s32[{SLOTS * (1 + PAGES_PER_SLOT // 32)}]", ops
            assert ops[3:] == [f"bf16[{sz * PAGES},{PAGE},{width}]"], ops
        # the table of leading runs: once a step, outside the layers' scans
        made = [line for _, _, shape, line in _instructions(hlo)
                if shape.startswith(f"s32[{SLOTS},{PAGES_PER_SLOT // 32}]")]
        assert made and not [line for line in made if "/while/" in line], made
    else:
        assert not _walks(hlo)
    # across chips: the three hops, each to the next stage, and the one
    # all-reduce that hands the last stage's hidden state to every chip
    hops = {pairs for op, _, _, line in _instructions(hlo)
            if op == "collective-permute-start"
            for pairs in re.findall(r"source_target_pairs=\{([^}]*\})\}",
                                    line)}
    assert hops == {"{0,1}", "{1,2}", "{2,3}"}, hops
    crossing = [(op, shape.split("{")[0]) for op, _, shape, _ in
                _instructions(hlo)
                if op in ("all-reduce", "all-reduce-start", "all-gather",
                          "all-gather-start", "all-to-all",
                          "reduce-scatter")]
    assert crossing == [("all-reduce", f"f32[{SLOTS},1,1536]")], crossing
    mem = step.memory_analysis()
    # a chip's share: the gathered K and V of one layer at a time (211.9 MB)
    # and little else, where the stacked and selected pools before PR 31
    # were 5.45 GB; the walk leaves 2.3 MB
    assert mem.temp_size_in_bytes < (0.5e9 if read == "gather" else 10e6)
    assert mem.alias_size_in_bytes >= sz * layer_pool * 2       # donated


# benchmark/configs/mistral-small-4-119b-ep4.json: the widths, the chip's
# share (32 of 128 experts, a quarter of the vocabulary, 4 layers) and the
# serving geometry of the cell
MISTRAL4 = ModelConfig(
    family="mistral4", vocab_size=32768, hidden_size=4096, num_layers=4,
    num_heads=32, num_kv_heads=32, intermediate_size=12288,
    max_position_embeddings=1048576, norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling=("yarn", 128.0, 8192, 32.0, 1.0, 1.0),
    layer_types=("latent_attention",) * 4, explicit_head_dim=128,
    num_experts=128, experts_per_tok=4, expert_width=2048, shared_width=2048,
    experts_held=32, q_lora_rank=1024, kv_lora_rank=256, qk_rope_head_dim=64,
    v_head_dim=128, softmax_mscale=1.4852030263919618, query_scale_beta=0.1)
L_SLOTS, L_PAGES_PER_SLOT = 96, 768


def _latent_step(one):
    params = _shapes(jax.eval_shape(
        lambda: init_params(MISTRAL4, jax.random.key(0), dtype=jnp.bfloat16)),
        one)
    pool = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        MISTRAL4, L_SLOTS * L_PAGES_PER_SLOT + 1, PAGE, jnp.bfloat16)), one)
    assert pool.rows.shape == (4, 73729, PAGE, 384)     # 320 lanes, padded

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((L_SLOTS,), jnp.int32)
    return batching._batched_hybrid_step_jit.lower(
        MISTRAL4, params, pool.rows, None,
        arr((4, 32), jnp.int32), arr((L_SLOTS, L_PAGES_PER_SLOT), jnp.int32),
        ints, ints, arr((L_SLOTS, 2), jnp.uint32), ints,
        arr((L_SLOTS,), jnp.float32), None).compile()


def test_latent_step_is_absorbed_and_its_one_leaf_pool_stays_in_place(topo,
                                                                      read):
    """The step of a stack of latent layers at the cell's shapes. On the page
    walk (PERF.md §6 "PR 35"): one kernel a layer reads each slot's live
    pages out of the one-leaf pool, and NOTHING span-sized exists in the
    module — no gather of a slot's 768 pages, no (slots, heads, span) scores:
    the step's temporaries are 17.7 MB where the gather's are 1.08 GB. On the
    page gather: ONE gather a layer of each slot's span of 384-lane rows,
    the gathers' own outputs the only span-sized temporaries (0.9 GB a
    layer, one alive at a time, beside 11.0 GB of weights and pool). Either
    way no copy, relayout or stacking touches the pool, which is donated, and
    no tensor with a (96, 12288, 32, ...) shape exists (keys and values are
    never rebuilt per head: the absorption is real)."""
    one = SingleDeviceSharding(topo.devices[0])
    assert paged_kv.decode_read_path(paged_kv.LatentPool(
        jax.ShapeDtypeStruct((4, 73729, PAGE, 384), jnp.bfloat16))) == {
            "walk": paged_kv.PAGE_WALK, "gather": paged_kv.PAGE_GATHER}[read]
    step = _latent_step(one)
    hlo = step.as_text()
    span = L_PAGES_PER_SLOT * PAGE
    gathered = L_SLOTS * span * 384
    gathers = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
               if op == "gather" and _elements(shape) >= gathered]
    rows = f"bf16[{L_SLOTS},{L_PAGES_PER_SLOT},{PAGE},384]"
    own = {rows, f"bf16[{L_SLOTS * L_PAGES_PER_SLOT},{PAGE},384]",
           f"bf16[{L_SLOTS},{span},384]"}
    moved = [m for m in _moved(hlo, gathered)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    assert not moved, moved
    # nothing per head over the span
    per_head = re.findall(rf"\[{L_SLOTS},{span},32,\d+\]"
                          rf"|\[{L_SLOTS},32,{span},\d+\]", hlo)
    assert not per_head, per_head[:3]
    pool_views = {f"bf16[4,73729,{PAGE},384]",
                  f"bf16[{4 * 73729 * PAGE},384]",
                  f"bf16[{4 * 73729},{PAGE},384]"}
    big = {shape.split("{")[0] for _, _, shape, _ in _instructions(hlo)
           if _elements(shape) >= gathered and not shape.startswith("(")}
    # the pool is addressed flat: pages at (layer, page), rows at (l, p, r)
    assert f"bf16[{4 * 73729},{PAGE},384]" in hlo
    assert f"bf16[{4 * 73729 * PAGE},384]" in hlo
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * 73729 * PAGE * 384 * 2   # donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.5e9
    if read == "gather":
        assert gathers == [rows] * 4, gathers
        assert not _walks(hlo)
        # beside the gathered rows the widest span-sized tensors are the
        # (slots, heads, span) scores
        assert big <= own | pool_views, big
        assert 0.9e9 < mem.temp_size_in_bytes < 1.3e9
        return
    # the walk: the static walk over four layers, a kernel each, handed the
    # whole leaf; of span size there is the pool itself and nothing else
    assert _walks(hlo) == 4, _walks(hlo)
    assert not gathers, gathers
    assert big <= pool_views, big
    scores = _span_sized(hlo, own | {f"[{L_SLOTS},32,{span}]"})
    assert not scores, scores[:3]
    assert mem.temp_size_in_bytes < 50e6, mem.temp_size_in_bytes


# a toy afmoe (hybrid.py's fourth family): one leading dense layer and one
# period S S S F of expert layers, rows of one whole lane tile (2 KV heads of
# 64), pages of 16 rows, a window of 40 keys = a ring of 4 pages
AFMOE = tiny_afmoe_config(hidden_size=128, head_dim=64, num_kv_heads=2,
                          sliding_window=40)
A_SLOTS, A_PAGES_PER_SLOT = 8, 6
#: what moves bytes or multiplies in a step: each must stand under a scope
HEAVY = ("convolution", "dot", "gather", "scatter", "custom-call", "sort")


def _scopes_of_the_heavy(hlo: str):
    """(op paths of the module's matmuls, gathers, scatters, sorts and kernel
    calls that stand under NO registered scope, the registered scopes the
    others stand under). (At a cell's size the compiler prefetches operands
    into VMEM in slices and joins them with a "ConcatBitcast" call of its
    own: a bitcast, no path.)"""
    from edgellm_tpu.obs.names import SCOPE_NAMES

    paths = ["".join(re.findall(r'op_name="([^"]*)"', line))
             for op, _, _, line in _instructions(hlo)
             if op in HEAVY and "ConcatBitcast" not in line]
    unscoped = {path for path in paths
                if not any(seg in SCOPE_NAMES for seg in path.split("/"))}
    under = {seg for path in paths for seg in path.split("/")
             if seg in SCOPE_NAMES}
    return unscoped, under


def _afmoe_step(one, cfg):
    """The afmoe window step lowered for ``one`` described chip."""
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    ring = cfg.window_pages(PAGE)
    full = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, A_SLOTS * A_PAGES_PER_SLOT + 1, PAGE, jnp.bfloat16)), one)
    window = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, A_SLOTS * ring + 1, PAGE, jnp.bfloat16,
        layers=cfg.window_layers)), one)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((A_SLOTS,), jnp.int32)
    return params, full, window, batching._batched_window_step_jit.lower(
        cfg, params, full, window, arr((4, 8), jnp.int32),
        arr((A_SLOTS, A_PAGES_PER_SLOT), jnp.int32),
        arr((A_SLOTS, ring), jnp.int32), ints, ints,
        arr((A_SLOTS, 2), jnp.uint32), ints, arr((A_SLOTS,), jnp.float32),
        None)


def test_afmoe_step_keeps_both_pools_in_place_and_scopes_what_is_heavy(topo,
                                                                       read):
    """The afmoe step at a toy size: the full layer's pool and the four ring
    layers' are donated and addressed in place (a ring and the full layer's
    span a gather each or, on the walk, a kernel each and no ring-sized copy
    anywhere), nothing pool-sized is copied, relaid or stacked,
    ``expert_tokens`` has a row an EXPERT layer, and every matmul, gather,
    scatter, sort and kernel call of the module carries a registered scope:
    the gate, the norms and the dense layer brought no unscoped work."""
    one = SingleDeviceSharding(topo.devices[0])
    params, full, window, lowered = _afmoe_step(one, AFMOE)
    assert "router" not in params["moe"][0] and "wg" in params["window"]
    ring = AFMOE.window_pages(PAGE)
    assert ring == 4 and AFMOE.expert_layers == 4
    assert [a.shape for a in full] == [(1, 49, PAGE, 256)]   # K | V lanes
    assert [a.shape for a in window] == [(4, 33, PAGE, 256)]
    step = lowered.compile()
    hlo = step.as_text()
    gathered = A_SLOTS * ring * PAGE * 256          # a window layer's read
    gathers = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
               if op == "gather" and _elements(shape) >= gathered]
    span = f"bf16[{A_SLOTS},{A_PAGES_PER_SLOT},{PAGE},256]"
    rings = f"bf16[{A_SLOTS},{ring},{PAGE},256]"
    assert sorted(gathers) == (sorted([span] + [rings] * 4)
                               if read == "gather" else []), gathers
    assert _walks(hlo) == (5 if read == "walk" else 0)
    if read == "walk":
        assert not _span_sized(hlo, (rings, span))
    own = {span, rings, f"bf16[{A_SLOTS * A_PAGES_PER_SLOT},{PAGE},256]",
           f"bf16[{A_SLOTS * ring},{PAGE},256]"}
    moved = [m for m in _moved(hlo, gathered)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    assert not moved, moved
    assert f"bf16[{4 * 33},{PAGE},256]" in hlo        # pages at (layer, page)
    assert f"bf16[{4 * 33 * PAGE},256]" in hlo        # rows at (l, p, r)
    # all but two row gathers every walked family makes ahead of its first
    # layer: the embedding's (jnp.take) and each slot's row of the rope table
    unscoped, under = _scopes_of_the_heavy(hlo)
    assert unscoped == {
        "jit(_batched_window_step_jit)/jit(_take)/gather",
        "jit(_batched_window_step_jit)/gather"}, unscoped
    assert under >= {"attn.window", "attn.decode", "paged_kv.write", "mlp",
                     "moe.route", "moe.experts", "moe.shared",
                     "unembed_sample"}, under
    # donated: both pools of both groups
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (49 + 4 * 33) * PAGE * 128 * 2


# benchmark/configs/longcat-flash-chat-ep32.json: the widths, the chip's
# share (16 of 512 routed experts, an eighth of the vocabulary, 4 layers = 8
# sublayers) and the serving geometry of the cell
LONGCAT = dataclasses.replace(
    LONGCAT_FLASH_CHAT, vocab_size=16384, num_layers=4,
    layer_types=("latent_attention",) * 8, experts_held=16)
C_SLOTS, C_PAGES_PER_SLOT = 96, 192


def test_longcat_step_walks_five_tile_rows_and_scopes_what_is_heavy(topo,
                                                                    read):
    """The step of the ``longcat_flash`` cell at its shapes: 8 latent
    sublayers of 640-lane rows at 64 heads through the kernels the mistral4
    cell's 384-lane rows take (a kernel a SUBLAYER on the walk: its two
    1024-row buffers and the (64, 640) query fit VMEM, or this would not
    compile; a gather a sublayer otherwise), the one-leaf pool donated and in
    place, the counter a row a PUBLISHED layer with the identity column, and
    every matmul, gather, scatter, sort and kernel call under a registered
    scope: the shortcut, the identity part and the dense SwiGLUs brought no
    unscoped work."""
    one = SingleDeviceSharding(topo.devices[0])
    cfg = LONGCAT
    assert (cfg.kv_row_lanes, cfg.kv_layers, cfg.expert_layers,
            cfg.counted_experts) == (640, 8, 4, 17)
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    assert [sorted(m) for m in params["moe"][:2]] == [
        ["ln2_scale", "shortcut", "w_down", "w_gate", "w_up"],
        ["ln2_scale", "w_down", "w_gate", "w_up"]]
    pages = C_SLOTS * C_PAGES_PER_SLOT + 1
    pool = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, pages, PAGE, jnp.bfloat16)), one)
    assert pool.rows.shape == (8, 18433, PAGE, 640)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((C_SLOTS,), jnp.int32)
    step = batching._batched_hybrid_step_jit.lower(
        cfg, params, pool.rows, None, arr((4, 17), jnp.int32),
        arr((C_SLOTS, C_PAGES_PER_SLOT), jnp.int32), ints, ints,
        arr((C_SLOTS, 2), jnp.uint32), ints, arr((C_SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    span = C_PAGES_PER_SLOT * PAGE
    gathered = C_SLOTS * span * 640
    gathers = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
               if op == "gather" and _elements(shape) >= gathered]
    rows = f"bf16[{C_SLOTS},{C_PAGES_PER_SLOT},{PAGE},640]"
    assert _walks(hlo) == (8 if read == "walk" else 0)
    # (a gather a sublayer; the compiler may split one in two)
    assert set(gathers) == (set() if read == "walk" else {rows}), gathers
    assert len(gathers) >= (0 if read == "walk" else 8)
    per_head = re.findall(rf"\[{C_SLOTS},{span},64,\d+\]"
                          rf"|\[{C_SLOTS},64,{span},\d+\]", hlo)
    assert not per_head, per_head[:3]
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 8 * pages * PAGE * 640 * 2   # donated
    # weights 10.35 GB + pool 3.02 GB + the step's temporaries
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.6e9
    if read == "walk":
        assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    unscoped, under = _scopes_of_the_heavy(hlo)
    assert unscoped == {
        "jit(_batched_hybrid_step_jit)/jit(_take)/gather",
        "jit(_batched_hybrid_step_jit)/gather"}, unscoped
    assert under >= {"attn.latent", "paged_kv.write", "mlp", "moe.route",
                     "moe.experts", "unembed_sample"}, under
    assert "moe.shared" not in under


# benchmark/configs/lfm2-8b-a1b-pp2.json: the widths, stage 0's depth (layers
# 0-11 as published: 9 short convolutions, 3 attention layers, both dense
# layers, 10 routed ones with all 32 experts) and the serving geometry
LFM2 = dataclasses.replace(LFM2_8B_A1B, num_layers=12,
                           layer_types=LFM2_8B_A1B.layer_types[:12])
F_SLOTS, F_PAGES_PER_SLOT = 96, 288


def test_lfm2_step_keeps_its_windows_and_its_pool_in_place(topo, read):
    """The step of the ``lfm2_moe`` cell at its shapes: three rotated
    attention layers on the page walk (a kernel a layer; a gather of K and
    one of V a layer otherwise), the K/V pool AND the state store's one
    leaf, the nine conv layers' windows, donated and addressed in place (no
    ``copy`` of the windows' shape, nothing pool-sized copied, relaid or
    stacked), the counter a row an EXPERT layer, and every matmul, gather,
    scatter, sort and kernel call under a registered scope: the short
    convolution brought no unscoped work."""
    one = SingleDeviceSharding(topo.devices[0])
    cfg = LFM2
    assert (cfg.conv_layers, cfg.kv_layers, cfg.expert_layers,
            cfg.kv_row_lanes) == (9, 3, 10, 512)
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    assert sorted(params["conv"]) == ["conv_w", "ln1_scale", "w_in", "w_out"]
    assert ["router" in m for m in params["moe"]] == [False] * 2 + [True] * 10
    pages = F_SLOTS * F_PAGES_PER_SLOT + 1
    pool = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, pages, PAGE, jnp.bfloat16)), one)
    assert [a.shape for a in pool] == [(3, 27649, PAGE, 1024)]  # K | V
    state = _shapes(jax.eval_shape(
        lambda: paged_kv.init_slot_state(cfg, F_SLOTS)), one)
    assert {leaf: a.shape for leaf, a in state.items()} == {
        "conv": (9, F_SLOTS, 2, 2048)}

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((F_SLOTS,), jnp.int32)
    step = batching._batched_hybrid_step_jit.lower(
        cfg, params, pool.kv, state, arr((10, 32), jnp.int32),
        arr((F_SLOTS, F_PAGES_PER_SLOT), jnp.int32), ints, ints,
        arr((F_SLOTS, 2), jnp.uint32), ints, arr((F_SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    layer_pool = pages * PAGE * 1024
    gathered = F_SLOTS * F_PAGES_PER_SLOT * PAGE * 1024
    span = f"bf16[{F_SLOTS},{F_PAGES_PER_SLOT},{PAGE},1024]"
    own = {span, f"bf16[{F_SLOTS * F_PAGES_PER_SLOT},{PAGE},1024]"}
    moved = [m for m in _moved(hlo, gathered)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    assert not moved, moved
    assert _walks(hlo) == (3 if read == "walk" else 0)
    gathers = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
               if op == "gather" and _elements(shape) >= gathered]
    assert set(gathers) == (set() if read == "walk" else {span}), gathers
    if read == "walk":
        assert not _span_sized(hlo, own)
    # the windows: written where they lie, a layer's rows at a time, and
    # never copied whole
    windows = "f32[9,96,2,2048]"
    assert windows in hlo
    copies = [(op, name) for op, name, shape, _ in _instructions(hlo)
              if op == "copy" and windows in shape]
    assert not copies, copies
    mem = step.memory_analysis()
    window_bytes = 9 * F_SLOTS * 2 * 2048 * 4
    assert mem.alias_size_in_bytes >= 3 * layer_pool * 2 + window_bytes
    # weights 7.86 GB + pool 2.72 GB + the step's temporaries (0.93 GB on
    # the gather, whose copy is split on lanes into K and V)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11.6e9
    if read == "walk":
        assert mem.temp_size_in_bytes < 100e6, mem.temp_size_in_bytes
    unscoped, under = _scopes_of_the_heavy(hlo)
    assert unscoped == {
        "jit(_batched_hybrid_step_jit)/jit(_take)/gather",
        "jit(_batched_hybrid_step_jit)/gather"}, unscoped
    assert under >= {"shortconv.proj", "attn.decode", "paged_kv.write",
                     "mlp", "moe.route", "moe.experts",
                     "unembed_sample"}, under
    assert not under & {"moe.shared", "ssm.step", "ssm.proj"}


# benchmark/configs/keye-vl-2.0-30b-a3b-ep4.json: the widths, the share (32
# of 128 experts, 37,984 rows of the table) and the serving geometry, ONE of
# the cell's six layers (the compile of six takes 100 s)
KEYE = dataclasses.replace(
    KEYE_VL_2_0_30B_A3B, num_layers=1, layer_types=("sparse_attention",),
    experts_held=32, vocab_size=37984)
K_SLOTS, K_PAGES_PER_SLOT = 32, 1280


def test_keye_step_selects_under_scopes_and_keeps_both_leaves_in_place(
        topo, read):
    """The step of the ``keye_vl2`` cell at its shapes, one layer: the pool's
    TWO leaves (K/V rows of 1024 lanes, index keys of 128) donated and
    written where they lie, a row scatter each. On a TPU's choice both are
    read where they lie: the index keys scored by the index walk (one kernel
    under ``attn.sparse.index``; no gathered copy of a slot's span of keys is
    left, and the step's temporaries fall by its 168 MB), then the page walk
    with the selection as a mask (one kernel, no span-sized copy of K or V);
    on the other the index keys by one page gather of the 128-lane leaf and
    the row gather of 32 x 2048 chosen rows. Every matmul, gather, scatter,
    sort and kernel call under a registered scope."""
    from edgellm_tpu.models import sparse_attn

    one = SingleDeviceSharding(topo.devices[0])
    cfg = KEYE
    assert (cfg.sparse_layers, cfg.kv_row_lanes, cfg.index_row_lanes) == (
        1, 512, 128)
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    assert "w_index" in params["sparse"] and "attn" not in params
    pages = K_SLOTS * K_PAGES_PER_SLOT + 1
    pool = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, pages, PAGE, jnp.bfloat16)), one)
    assert [a.shape for a in pool] == [(1, pages, PAGE, 1024),
                                       (1, pages, PAGE, 128)]
    span = K_PAGES_PER_SLOT * PAGE
    assert sparse_attn.sparse_read_path(cfg, span, pool) == (
        sparse_attn.MASKED_WALK if read == "walk" else sparse_attn.ROW_GATHER)
    assert paged_kv.index_read_path(pool) == (
        paged_kv.INDEX_WALK if read == "walk" else paged_kv.PAGE_GATHER)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((K_SLOTS,), jnp.int32)
    step = batching._batched_hybrid_step_jit.lower(
        cfg, params, pool, None, arr((1, 32), jnp.int32),
        arr((K_SLOTS, K_PAGES_PER_SLOT), jnp.int32), ints, ints,
        arr((K_SLOTS, 2), jnp.uint32), ints, arr((K_SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    kv_leaf, ik_leaf = pages * PAGE * 1024, pages * PAGE * 128
    # nothing the size of either leaf is copied, relaid or stacked
    keys = f"bf16[{K_SLOTS},{K_PAGES_PER_SLOT},{PAGE},128]"
    own = {keys, f"bf16[{K_SLOTS * K_PAGES_PER_SLOT},{PAGE},128]",
           f"bf16[{K_SLOTS},{span},128]"}
    moved = [m for m in _moved(hlo, ik_leaf)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    assert not moved, moved
    assert _walks(hlo) == (1 if read == "walk" else 0)
    index_walks = [line for op, _, _, line in _instructions(hlo)
                   if op == "custom-call" and "paged_index_walk" in line]
    assert len(index_walks) == (1 if read == "walk" else 0)
    assert all("attn.sparse.index" in line for line in index_walks)
    big = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
           if op == "gather" and _elements(shape) >= K_SLOTS * 2048 * 1024]
    # (the index keys: every slot's span of them, by the gather alone; the
    # chosen rows: 32 x 2048 of them, gathered a K or V half at a time)
    assert set(big) == (set() if read == "walk" else
                        {keys, f"bf16[{K_SLOTS},2048,1024]"}), big
    assert read != "walk" or not _span_sized(hlo, own)
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (kv_leaf + ik_leaf)
    # on the walks the scores and the mask; on the gathers the index keys'
    # copy, 168 MB, and the chosen rows, 268 MB more
    assert mem.temp_size_in_bytes < (100e6 if read == "walk" else 700e6), \
        mem.temp_size_in_bytes
    unscoped, under = _scopes_of_the_heavy(hlo)
    # ("": the compiler's own "AllocateBuffer" calls for the carried k-th
    # value of the selection's counting loop, 32 words each: no operation)
    assert unscoped <= {"jit(_batched_hybrid_step_jit)/jit(_take)/gather",
                        "jit(_batched_hybrid_step_jit)/gather", ""}, unscoped
    # (the walk's selection is counting passes, none of them heavy; the
    # gather's is a top-k and the gather of its page ids)
    assert under >= {"attn.sparse", "attn.sparse.index", "paged_kv.write",
                     "moe.route", "moe.experts", "unembed_sample"} | (
        set() if read == "walk" else {"attn.sparse.select"}), under
    assert not under & {"attn.decode", "moe.shared", "mlp"}


@pytest.fixture(params=["kernel", "xla-blocks"])
def attend(request, monkeypatch):
    """The attend a sparse prefill's blocks are compiled on
    (``sparse_attn.sparse_prefill_path``). ``kernel``: what the program
    picks on a TPU, ``flash_attention.masked_attention``; ``xla-blocks``:
    what this process's backend, the CPU, picks, the kernel's oracle."""
    from edgellm_tpu.models import sparse_attn

    if request.param == "kernel":
        monkeypatch.setattr(sparse_attn, "_on_tpu", lambda: True)
    jax.clear_caches()      # the prefill's jit keeps its trace by arguments
    yield request.param
    jax.clear_caches()


def _scores_of_a_block(hlo: str, s: int, rows: int) -> list:
    """float32 tensors of ``rows`` (a block's heads x query rows) by S keys
    or more: a block's attention scores or probabilities where they pass
    through HBM (the index dots' (index heads x query rows, S) are fewer
    rows and stay)."""
    found = []
    for _, _, shape, _ in _instructions(hlo):
        dims = [int(d) for d in re.findall(
            r"\d+", shape.split("{")[0].split("[")[-1])]
        if shape.startswith("f32") and dims and dims[-1] >= s \
                and int(np.prod(dims[:-1])) >= rows:
            found.append(shape)
    return found


def test_keye_prefill_of_a_whole_prompt_builds_no_square_tensor(topo,
                                                                attend):
    """The 16384-token prefill of the cell, one layer: 32 blocks of 512
    query rows, each with its index dot, its selection (no sort: a k-th
    value by counting passes) and its masked softmax. No (S, S) tensor
    exists, and what the compiler holds at once stays under 2.5 GB beside
    the 11.4 GB the cell keeps; on the kernel no (32 heads, 512, S) float32
    scores exist either, and it holds under 1.5 GB."""
    from edgellm_tpu.serve import decode

    one = SingleDeviceSharding(topo.devices[0])
    cfg, s = KEYE, 16384
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    prefill = decode._prefill_jit.lower(
        cfg, params, jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one),
        K_PAGES_PER_SLOT * PAGE, None).compile()
    hlo = prefill.as_text()
    square = [shape for _, _, shape, _ in _instructions(hlo)
              if sum(int(d) >= s for d in re.findall(
                  r"\d+", shape.split("{")[0].split("[")[-1])) >= 2]
    assert not square, square[:3]
    assert not [name for op, name, _, line in _instructions(hlo)
                if op == "sort" and "attn.sparse" in line]
    mem = prefill.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes
    assert "attn.sparse.prefill" in hlo and "attn.sparse.select" in hlo
    scores = _scores_of_a_block(hlo, s, cfg.num_heads * 512)
    if attend == "kernel":
        assert not scores, scores[:3]
        assert "masked_attention" in hlo
        assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    else:
        assert scores and "masked_attention" not in hlo


# the deepseek cell (benchmark/configs/deepseek-v3.2-exp-ep16.json): one
# leading dense layer and one expert layer at the published widths, 16 of the
# 256 routed experts held, an eighth of the vocabulary; 16 slots of 1280
# pages of 16 rows in a pool of two leaves (latent rows 640 lanes, index keys
# 128)
DSV32 = dataclasses.replace(
    DEEPSEEK_V3_2_EXP, num_layers=2, num_dense_layers=1,
    layer_types=("sparse_latent_attention",) * 2, experts_held=16,
    vocab_size=16160)
D_SLOTS, D_PAGES_PER_SLOT = 16, 1280


def test_deepseek_step_walks_once_a_layer_and_keeps_both_leaves_in_place(
        topo, read):
    """The step of the ``deepseek_v32`` cell at its shapes, a dense layer and
    an expert layer: the pool's TWO leaves (latent rows of 640 lanes, index
    keys of 128) donated and written where they lie. On a TPU's choice each
    layer holds ONE index walk (64 heads x 128 lanes, under
    ``attn.sparse.index``) and ONE read of the chosen rows, the page walk of
    the latent leaf at 128 heads x 640 lanes with the selection as a mask;
    no gather of a slot's whole span of either leaf exists, and nothing per
    head over the span (the absorption is real). On the other read the index
    keys come by one page gather a layer and the chosen rows by a row gather
    of 16 x 2048 rows. Every matmul, gather, scatter, sort and kernel call
    under a registered scope, the new kind's under its own."""
    from edgellm_tpu.models import sparse_attn

    one = SingleDeviceSharding(topo.devices[0])
    cfg = DSV32
    assert (cfg.sparse_layers, cfg.latent_layers, cfg.kv_row_lanes,
            cfg.index_row_lanes) == (2, 2, 640, 128)
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    assert "w_index" in params["sparse_latent"] and "latent" not in params
    assert params["sparse_latent"]["wq_index"].shape == (2, 1536, 64 * 128)
    assert "router" not in params["moe"][0]
    assert params["moe"][1]["w_gate"].shape == (16, 7168, 2048)
    pages = D_SLOTS * D_PAGES_PER_SLOT + 1
    pool = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, pages, PAGE, jnp.bfloat16)), one)
    assert type(pool) is paged_kv.IndexedLatentPool
    assert [a.shape for a in pool] == [(2, pages, PAGE, 640),
                                       (2, pages, PAGE, 128)]
    span = D_PAGES_PER_SLOT * PAGE
    assert sparse_attn.sparse_read_path(cfg, span, pool) == (
        sparse_attn.MASKED_WALK if read == "walk" else sparse_attn.ROW_GATHER)
    assert paged_kv.index_read_path(pool) == (
        paged_kv.INDEX_WALK if read == "walk" else paged_kv.PAGE_GATHER)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((D_SLOTS,), jnp.int32)
    step = batching._batched_hybrid_step_jit.lower(
        cfg, params, pool, None, arr((1, 16), jnp.int32),
        arr((D_SLOTS, D_PAGES_PER_SLOT), jnp.int32), ints, ints,
        arr((D_SLOTS, 2), jnp.uint32), ints, arr((D_SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    rows_leaf, ik_leaf = 2 * pages * PAGE * 640, 2 * pages * PAGE * 128
    keys = f"bf16[{D_SLOTS},{D_PAGES_PER_SLOT},{PAGE},128]"
    own = {keys, f"bf16[{D_SLOTS * D_PAGES_PER_SLOT},{PAGE},128]",
           f"bf16[{D_SLOTS},{span},128]"}
    moved = [m for m in _moved(hlo, ik_leaf // 2)
             if not (m[0] in ("reshape", "transpose") and m[2] in own)]
    assert not moved, moved
    # one read of the chosen rows and one index walk a layer
    assert _walks(hlo) == (2 if read == "walk" else 0)
    walked = _walk_operands(hlo)
    assert all(f"bf16[{D_SLOTS},128,640]" in ops
               and f"bf16[{2 * pages},{PAGE},640]" in ops for ops in walked)
    index_walks = [line for op, _, _, line in _instructions(hlo)
                   if op == "custom-call" and "paged_index_walk" in line]
    assert len(index_walks) == (2 if read == "walk" else 0)
    assert all("attn.sparse.index" in line for line in index_walks)
    big = [shape.split("{")[0] for op, _, shape, _ in _instructions(hlo)
           if op == "gather" and _elements(shape) >= D_SLOTS * 2048 * 640]
    # (the gather's: every slot's span of index keys, a layer, and the
    # chosen latent rows, 16 x 2048 of them a layer; no gather anywhere of a
    # slot's whole span of LATENT rows)
    assert sorted(set(big)) == ([] if read == "walk" else sorted(
        {keys, f"bf16[{D_SLOTS},2048,640]"})), big
    assert not _span_sized(hlo, {f"bf16[{D_SLOTS},{span},640]",
                                 f"bf16[{D_SLOTS},{D_PAGES_PER_SLOT},"
                                 f"{PAGE},640]"})
    assert read != "walk" or not _span_sized(hlo, own)
    # nothing per head over the span: keys and values are never rebuilt
    assert not re.findall(rf"\[{D_SLOTS},{span},128,\d+\]", hlo)
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (rows_leaf + ik_leaf)
    assert mem.temp_size_in_bytes < (300e6 if read == "walk" else 700e6), \
        mem.temp_size_in_bytes
    unscoped, under = _scopes_of_the_heavy(hlo)
    # ("gather": the compiler's own split of the chosen rows' gather, which
    # keeps no path; the gather read alone)
    assert unscoped <= {"jit(_batched_hybrid_step_jit)/jit(_take)/gather",
                        "jit(_batched_hybrid_step_jit)/gather", ""} | (
        set() if read == "walk" else {"gather"}), unscoped
    assert under >= {"attn.sparse_latent", "attn.sparse.index",
                     "paged_kv.write", "mlp", "moe.route", "moe.experts",
                     "moe.shared", "unembed_sample"} | (
        set() if read == "walk" else {"attn.sparse.select"}), under
    assert not under & {"attn.decode", "attn.sparse", "attn.latent"}


DOTS3 = dataclasses.replace(
    DOTS3_NOTE_PREV, num_layers=2, num_dense_layers=1,
    layer_types=("sparse_latent_attention", "sliding_latent_attention"),
    experts_held=32, vocab_size=19008)
N_SLOTS, N_PAGES_PER_SLOT = 32, 1280


def test_dots3_step_walks_a_ring_of_latent_rows_beside_the_selected_ones(
        topo, read):
    """The step of the ``dots3_note`` cell at its shapes, a full (dense)
    layer and a window (expert) layer: THREE leaves in two page groups (the
    full layers' latent rows of 640 lanes and index keys of 128 under one
    table, the window layers' ring of latent rows of 1152 lanes under
    another) donated and written where they lie. On a TPU's choice the full
    layer holds one index walk and one masked walk of its latent leaf (128
    heads x 640 lanes), and the window layer ONE ring walk at 64 heads x 1152
    lanes over its 33 entries, a row key and value both; no gather of a
    slot's span or of its ring exists. On the other read the ring comes by
    one page gather. Every heavy operation under a registered scope, the
    window kind's under ``attn.window_latent`` and its ring write under
    ``attn.window_latent.write``, never ``paged_kv.write``."""
    from edgellm_tpu.models import sparse_attn

    one = SingleDeviceSharding(topo.devices[0])
    cfg = DOTS3
    ring = cfg.window_pages(PAGE)
    assert (cfg.sparse_layers, cfg.latent_layers, cfg.window_latent_layers,
            cfg.kv_row_lanes, cfg.window_row_lanes, ring) == (
        1, 1, 1, 640, 1152, 33)
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    assert params["sparse_latent"]["wg"].shape == (1, 5120, 128)
    assert params["window_latent"]["wg"].shape == (1, 5120, 64)
    assert params["window_latent"]["wkv_b"].shape == (1, 1024, 64 * 320)
    assert "wq_index" not in params["window_latent"]
    assert params["moe"][1]["w_gate"].shape == (32, 5120, 1536)
    pages = N_SLOTS * N_PAGES_PER_SLOT + 1
    pool = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, pages, PAGE, jnp.bfloat16)), one)
    rings = _shapes(jax.eval_shape(lambda: paged_kv.init_pool(
        cfg, N_SLOTS * ring + 1, PAGE, jnp.bfloat16,
        layers=cfg.window_layers, lanes=cfg.window_row_lanes)), one)
    assert type(pool) is paged_kv.IndexedLatentPool
    assert type(rings) is paged_kv.LatentPool
    assert rings.rows.shape == (1, N_SLOTS * ring + 1, PAGE, 1152)
    assert paged_kv.decode_read_path(rings) == (
        paged_kv.PAGE_WALK if read == "walk" else paged_kv.PAGE_GATHER)
    assert sparse_attn.sparse_read_path(cfg, N_PAGES_PER_SLOT * PAGE, pool) \
        == (sparse_attn.MASKED_WALK if read == "walk"
            else sparse_attn.ROW_GATHER)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ints = arr((N_SLOTS,), jnp.int32)
    step = batching._batched_window_step_jit.lower(
        cfg, params, pool, rings, arr((1, 32), jnp.int32),
        arr((N_SLOTS, N_PAGES_PER_SLOT), jnp.int32),
        arr((N_SLOTS, ring), jnp.int32), ints, ints,
        arr((N_SLOTS, 2), jnp.uint32), ints, arr((N_SLOTS,), jnp.float32),
        None).compile()
    hlo = step.as_text()
    # the full layer's masked walk and the window layer's ring walk
    assert _walks(hlo) == (2 if read == "walk" else 0)
    walked = _walk_operands(hlo)
    if read == "walk":
        (ringed,) = [ops for ops in walked
                     if f"bf16[{N_SLOTS},64,1152]" in ops]
        assert f"bf16[{N_SLOTS * ring + 1},{PAGE},1152]" in ringed
        assert f"s32[{N_SLOTS},{ring}]" in ringed
        (chosen,) = [ops for ops in walked
                     if f"bf16[{N_SLOTS},128,640]" in ops]
        assert f"bf16[{pages},{PAGE},640]" in chosen
    index_walks = [line for op, _, _, line in _instructions(hlo)
                   if op == "custom-call" and "paged_index_walk" in line]
    assert len(index_walks) == (1 if read == "walk" else 0)
    ring_shapes = {f"bf16[{N_SLOTS},{ring},{PAGE},1152]",
                   f"bf16[{N_SLOTS},{ring * PAGE},1152]"}
    span = N_PAGES_PER_SLOT * PAGE
    assert not _span_sized(hlo, {f"bf16[{N_SLOTS},{span},640]",
                                 f"bf16[{N_SLOTS},{N_PAGES_PER_SLOT},"
                                 f"{PAGE},640]"})
    assert bool(_span_sized(hlo, ring_shapes)) == (read != "walk")
    # nothing per head over the ring: the window kind's absorption is real
    assert not re.findall(rf"\[{N_SLOTS},{ring * PAGE},64,\d+\]", hlo)
    mem = step.memory_analysis()
    leaves = sum(int(np.prod(a.shape)) * 2 for a in (*pool, *rings))
    assert mem.alias_size_in_bytes >= leaves
    unscoped, under = _scopes_of_the_heavy(hlo)
    assert unscoped <= {"jit(_batched_window_step_jit)/jit(_take)/gather",
                        "jit(_batched_window_step_jit)/gather", ""} | (
        set() if read == "walk" else {"gather"}), unscoped
    assert under >= {"attn.sparse_latent", "attn.sparse.index",
                     "attn.window_latent", "attn.window_latent.write",
                     "paged_kv.write", "mlp", "moe.route", "moe.experts",
                     "moe.shared", "unembed_sample"}, under
    assert not under & {"attn.decode", "attn.window", "attn.latent"}
    assert "attn.window_latent/paged_kv.write" not in hlo


def _per_head(hlo: str, s: int) -> list:
    """(heads, shape) of every bfloat16 tensor of (S, heads, lanes) or
    (heads, S, lanes), dimensions of 1 aside, of ten lanes or more and fewer
    than a thousand: keys or values of every position, a head apart (float32
    are a block's scores and index dots, keys first; the rows of the stack's
    two layers, (2, S, lanes), are no heads)."""
    found = []
    for _, _, shape, _ in _instructions(hlo):
        dims = [int(d) for d in re.findall(
            r"\d+", shape.split("{")[0].split("[")[-1]) if int(d) != 1]
        if "bf16[" not in shape[:6] or len(dims) != 3 \
                or s not in dims[:2] or not 10 <= dims[2] < 1000:
            continue
        heads = dims[1] if dims[0] == s else dims[0]
        if heads > 2:
            found.append((heads, shape))
    return found


def test_deepseek_prefill_of_a_whole_prompt_rebuilds_no_key_per_head(topo,
                                                                     attend):
    """The 16384-token prefill of the cell, a dense layer and an expert
    layer: blocks of 64 query rows, each with its own index dot and its
    selection (no sort: a k-th value by counting passes). No (S, S) tensor
    exists, no query, key or value of (S, 128 heads, lanes) either, the
    feed-forwards go 2048 tokens at a time, and what the compiler holds at
    once stays under 3 GB beside the 11.8 GB the cell keeps. On the XLA
    blocks a block attends ABSORBED, its queries made from the q latent: no
    key or value a head exists at all, and a block's (128 heads, 64, S)
    float32 scores do. On the kernel a body attends EXPANDED and no such
    scores exist: the keys and values of every position are rebuilt
    ``sparse_mla.EXPANDED_HEADS`` heads at a time and never for more."""
    from edgellm_tpu.models import sparse_mla
    from edgellm_tpu.serve import decode

    one = SingleDeviceSharding(topo.devices[0])
    cfg, s = DSV32, 16384
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    prefill = decode._prefill_jit.lower(
        cfg, params, jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one),
        D_PAGES_PER_SLOT * PAGE, None).compile()
    hlo = prefill.as_text()
    square = [shape for _, _, shape, _ in _instructions(hlo)
              if sum(int(d) >= s for d in re.findall(
                  r"\d+", shape.split("{")[0].split("[")[-1])) >= 2]
    assert not square, square[:3]
    per_head = _per_head(hlo, s)
    assert not [name for op, name, _, line in _instructions(hlo)
                if op == "sort" and "attn.sparse" in line]
    # the gathered token rows of a routed layer: 2048 tokens x 8 at a time
    assert f"bf16[{2048 * 8},7168]" in hlo
    assert f"bf16[{s * 8},7168]" not in hlo
    mem = prefill.memory_analysis()
    assert mem.temp_size_in_bytes < 3.0e9, mem.temp_size_in_bytes
    assert "attn.sparse_latent.prefill" in hlo
    assert "attn.sparse.select" in hlo and "attn.latent.expand" not in hlo
    scores = _scores_of_a_block(hlo, s, cfg.num_heads * 64)
    if attend == "kernel":
        assert not scores, scores[:3]
        assert "masked_attention" in hlo
        assert per_head and max(h for h, _ in per_head) == \
            sparse_mla.EXPANDED_HEADS < cfg.num_heads, per_head[:5]
    else:
        assert scores and "masked_attention" not in hlo
        assert not per_head, per_head[:3]


# the six families hybrid.py walks, at toy sizes whose expert layers are
# whole lane tiles (D = F = 128: what the grouped-matmul kernel asks for),
# half the routed experts held
WALKED = {name: dataclasses.replace(
    make(hidden_size=128, experts_held=4, expert_offset=2), expert_width=128)
    for name, make in (("granitemoehybrid", tiny_hybrid_config),
                       ("mellum", tiny_mellum_config),
                       ("mistral4", tiny_mistral4_config),
                       ("afmoe", tiny_afmoe_config),
                       ("longcat_flash", tiny_longcat_flash_config),
                       ("lfm2_moe", tiny_lfm2_moe_config))}
PREFILL = moe.DENSE_MAX_TOKENS + 8


@pytest.fixture(params=["kernel", "ragged-dot"])
def products(request, monkeypatch):
    """The path a prefill's grouped products are compiled on
    (``grouped_matmul.grouped_product_path``). ``kernel``: what the program
    picks on a TPU at whole lane tiles; ``ragged-dot``: what it picks here."""
    grouped_matmul._grouped.clear_cache()  # a jit keeps no trace by backend
    if request.param == "kernel":
        monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    yield request.param
    grouped_matmul._grouped.clear_cache()


@pytest.mark.parametrize("family", sorted(WALKED))
def test_prefill_holds_its_grouped_products_to_the_scoped_kernel(
        topo, family, products):
    """A toy prefill past ``moe.DENSE_MAX_TOKENS`` of each walked family:
    on the kernel path two ``grouped_matmul`` kernel calls an expert layer
    (gate + up, down) under ``moe.experts.grouped`` and no ragged dot
    anywhere in the module; on the oracle's path no kernel and the ragged
    dots the kernel replaces."""
    cfg = WALKED[family]
    one = SingleDeviceSharding(topo.devices[0])
    params = _shapes(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0), dtype=jnp.bfloat16)), one)
    ids = jax.ShapeDtypeStruct((1, PREFILL), jnp.int32, sharding=one)
    hlo = jax.jit(lambda p, x: hybrid.prefill_hybrid(
        cfg, p, x, PREFILL, last_only=True)).lower(params, ids).compile(
        ).as_text()
    kernels = [line for op, _, _, line in _instructions(hlo)
               if op == "custom-call" and "grouped_matmul" in line]
    ragged = [line for op, _, _, line in _instructions(hlo)
              if "ragged" in op or (op == "custom-call" and "agged" in line)]
    if products == "ragged-dot":
        assert not kernels and ragged
        return
    assert len(kernels) == 2 * cfg.expert_layers and not ragged, ragged
    assert all("moe.experts/moe.experts.grouped" in line for line in kernels)
    assert f"bf16[{PREFILL * cfg.experts_per_tok},128]" in hlo


def test_decode_step_lowers_to_one_text_whichever_path_the_prefill_takes(
        topo, monkeypatch):
    """The step (8 tokens: the dense expert path) never reaches the grouped
    products: its lowered text is the same with the kernel chosen and
    without, at widths where the choice differs."""
    one = SingleDeviceSharding(topo.devices[0])
    cfg = dataclasses.replace(AFMOE, expert_width=128)
    texts = []
    for on_tpu in (False, True):
        monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: on_tpu)
        assert (moe.grouped_product(cfg) == grouped_matmul.PALLAS_GROUPED) \
            == on_tpu
        batching._batched_window_step_jit.clear_cache()
        texts.append(_afmoe_step(one, cfg)[-1].as_text())
    batching._batched_window_step_jit.clear_cache()
    assert texts[0] == texts[1] and "ragged" not in texts[0]
