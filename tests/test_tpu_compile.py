"""The decode kernel compiled for a described TPU v5e at the widths that run
on the chip. Interpret mode checks a kernel's arithmetic; only Mosaic says
whether its slices meet the tiling and its buffers fit VMEM, and it is
installed here and compiles for a chip that is described, not attached.
Nothing runs: no result, no time.

The topology is described inside a fixture (never at import: one process
at a time may load the TPU's library, and every xdist worker imports every
test file), and all such tests live in this one file."""

import functools
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from polyrl_tpu.ops import paged_attention as pa


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent
    # cache but cannot be read back without a chip (it warns and compiles
    # again): keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2[0])


# (rows, hq, hkv, d, dtype, page_size, table width): the benchmark's cell,
# chip_smoke's model, the 8:1 preset, a tp shard left with one KV head
@pytest.mark.parametrize("s,hq,hkv,d,dtype,page,width", [
    (65, 28, 4, 128, jnp.bfloat16, 64, 192),
    (65, 16, 8, 128, jnp.bfloat16, 64, 32),
    (65, 32, 4, 128, jnp.bfloat16, 64, 64),
    (65, 7, 1, 128, jnp.bfloat16, 64, 192),
    (65, 28, 4, 128, jnp.float32, 64, 192),
    (129, 8, 2, 128, jnp.bfloat16, 64, 192),     # zaya1-8b's CCA layers
    # phi-4-mini-flash-reasoning's paired heads: the shared pool, a ring
    (129, 40, 10, 128, jnp.bfloat16, 64, 320),
    (129, 40, 10, 128, jnp.bfloat16, 64, 8),
    # minicpm-sala's sparse layers: a (request, K/V head) a row of ONE head
    # under 16 query rows, 64 chosen pages of 16 KB in a table of 128:
    # blocks of 32 pages, 64 descriptors each
    (194, 16, 1, 128, jnp.bfloat16, 64, 128),
])
def test_decode_kernel_compiles_for_v5e(one_chip, s, hq, hkv, d, dtype, page,
                                        width):
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = arg((hkv, 1 + 4 * width, page, d), dtype)
    compiled = jax.jit(
        lambda *a: pa.paged_attention_pallas(*a)).lower(
            arg((s, hq, d), dtype), pool, pool,
            arg((s, width), jnp.int32), arg((s,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (rows, hq, hkv, table width, pages a block): minicpm-sala's sparse layers
# and zaya1-8b's CCA layers
@pytest.mark.parametrize("s,hq,hkv,width,b", [(194, 16, 1, 128, 32),
                                              (129, 8, 2, 192, 16)],
                         ids=["sparse", "zaya"])
def test_decode_kernel_unrolls_two_blocks_of_starts_and_waits_by_bytes(
        s, hq, hkv, width, b):
    """A descriptor that is unrolled code is a start in the kernel's jaxpr,
    and what a decode program pays at set-up when it lowers. Two a page of
    the block that a whole block's iteration starts and of the one a last
    block's iteration starts, and the two loops of the call's cold start;
    a wait a pool for a whole block, and for a last block the block's bytes
    or those of each power of two of pages below it: never a wait a page.
    No chip and no compiler: the count is the trace's."""
    arg = jax.ShapeDtypeStruct
    pool = arg((hkv, 1 + 4 * width, 64, 128), jnp.bfloat16)
    assert pa._block_plan(hkv, 64, 128, 2, width) == (b, 2, 3)
    text = str(jax.make_jaxpr(pa.paged_attention_pallas)(
        arg((s, hq, 128), jnp.bfloat16), pool, pool,
        arg((s, width), jnp.int32), arg((s,), jnp.int32)))
    assert text.count("dma_start") == 2 * (2 * b + 2)
    assert text.count("dma_wait") == 2 * (1 + 1 + int(math.log2(b)))


def _colours(lowered) -> tuple[dict[int, int], list[int]]:
    """What a lowered kernel's custom call tells XLA of where its arrays
    live: ({operand: colour}, [a result's colour]); 0 is HBM, and an
    array it says nothing of is memory-space assignment's to place."""
    config = lowered.as_text().replace("\\22", '"')
    ins = re.search(r'"input_memory_space_colors": (\[[^\]]*\])', config)
    outs = re.search(r'"output_memory_colors": (\[[^\]]*\])', config)
    return ({c["operand_index"]: c["color"]
             for c in json.loads(ins.group(1))} if ins else {},
            json.loads(outs.group(1)) if outs else [])


@pytest.mark.parametrize("kernel", ["paged_attention", "paged_kv_write"])
def test_gqa_kernels_pin_their_pools_to_hbm(one_chip, kernel):
    """The write and the attention kernel, each lowered alone at Ouro's
    shapes (a pool of 102 MB, which fits VMEM): the custom call colours
    both pools HBM, as operands and, where it writes them, as results
    (the operands count the scalar-prefetch arguments). A block spec's
    memory space does not reach XLA; these colours do."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    s, h, d, page, width = 7, 16, 128, 64, 72
    pool = arg((h, 4 * LOOPED_PAGES, page, d), jnp.bfloat16)
    if kernel == "paged_attention":
        lowered = jax.jit(lambda *a: pa.paged_attention_pallas(*a)).lower(
            arg((s, h, d), jnp.bfloat16), pool, pool,
            arg((s, width), jnp.int32), arg((s,), jnp.int32))
        assert _colours(lowered) == ({4: 0, 5: 0}, [])
    else:
        lowered = jax.jit(lambda *a: pa.paged_kv_write_pallas(*a)).lower(
            pool, pool, arg((s,), jnp.int32), arg((s,), jnp.int32),
            arg((s, h, d), jnp.bfloat16), arg((s, h, d), jnp.bfloat16))
        assert _colours(lowered) == ({3: 0, 4: 0}, [0, 0])


# -- the head that samples (ops/fused_sample.py) ---------------------------

# (rows, hidden, vocabulary, tied): the two cells' heads (152064 is whole
# tiles, 151936 = 128 x 1187 leaves a ragged last one), and qwen3-1.7b's
# tied embedding read as [V, d]
@pytest.mark.parametrize("s,d,v,tied", [
    (65, 3584, 152064, False),
    (65, 2048, 151936, False),
    (65, 2048, 151936, True),
    (129, 2048, 262272, True),                   # zaya1-8b's tied head
])
def test_head_sample_kernel_compiles_for_v5e(one_chip, s, d, v, tied):
    """No ``chip_precision`` here: the kernel pins DEFAULT on its dot, so
    it compiles under the ``highest`` that tests/conftest.py sets."""
    from polyrl_tpu.ops import fused_sample as fs

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    head = arg((v, d) if tied else (d, v), jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        fs.head_sample_pallas, tied=tied)).lower(
            arg((s, d), jnp.bfloat16), head, arg((2,), jnp.uint32),
            arg((s,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "head_sample" in text
    # no [rows, vocabulary] array, whatever its dtype, beside the kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**20


# -- the MoE block at qwen3-30b-a3b's published widths ---------------------

MOE_ROWS = [64, 512]     # the cell's decode rows; one prefill chunk


@pytest.fixture
def chip_precision():
    """The matmul precision a chip run has (none set), not the float32
    ``highest`` that tests/conftest.py sets for exact CPU parity: XLA's
    grouped-matmul kernel refuses bf16 operands at float32 precision."""
    with jax.default_matmul_precision("default"):
        yield


def _moe_cfg():
    from polyrl_tpu.models import decoder

    return decoder.get_config("qwen3-30b-a3b", num_layers=1)


def _layer_shapes(cfg, one_chip):
    """One layer's weights, as shapes on the described chip."""
    from polyrl_tpu.models import decoder

    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))["layers"]
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype,
                                       sharding=one_chip), tree)


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatchers ask ``jax.default_backend()``, which is the CPU
    here: steer them onto their TPU kernels for the described chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# (hidden, expert width, experts a layer, layers of the stack, choices a
# token, rows, rows a tile): qwen3-30b-a3b's decode step and prefill
# chunk; the decode steps of dots.vlm1 (an expert's gate and up are 58.7
# MB: slabs along K) and of ZAYA1-8B (16.8 MB; its down is 8 MiB whole)
GROUPED_SHAPES = {
    "qwen3-30b-a3b decode": (2048, 768, 128, 7, 8, 64, 16),
    "qwen3-30b-a3b prefill": (2048, 768, 128, 7, 8, 512, 64),
    "dots.vlm1 decode": (7168, 2048, 16, 4, 8, 65, 128),
    "zaya1-8b decode": (2048, 2048, 16, 12, 1, 129, 32),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("shape", GROUPED_SHAPES)
def test_grouped_expert_matmul_compiles_for_v5e(one_chip, chip_precision,
                                                shape, dtype):
    """The owned grouped matmul over the rows' choices in whole tiles an
    expert, one layer's experts of a whole stack, in bf16 and with int8
    experts: a step's slabs, the float32 sums and the blocks fit VMEM."""
    from polyrl_tpu.ops import grouped_matmul as gm

    d, f, e, stack, k, rows, tile = GROUPED_SHAPES[shape]
    assert tile == gm.row_tile(rows * k, e)
    n_tiles = rows * k // tile + e

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    for n_w, shape in [(2, (d, f)), (1, (f, d))]:    # SwiGLU, then down
        scales = ((arg((stack * e, shape[1]), jnp.float32),) * n_w
                  if dtype == jnp.int8 else None)
        compiled = jax.jit(functools.partial(
            gm.grouped_matmul_pallas, tile=tile)).lower(
                arg((n_tiles * tile, shape[0]), jnp.bfloat16),
                (arg((stack * e, *shape), dtype),) * n_w,
                arg((n_tiles,), jnp.int32), arg((1,), jnp.int32),
                scales).compile()
        assert "tpu_custom_call" in compiled.as_text()


# (hidden, expert width, experts held, layers of the stack, choices a
# token, rows): the five MoE cells' decode steps
ROWS_SHAPES = {
    "qwen3-30b-a3b": (2048, 768, 128, 7, 8, 65),
    "ling-3.0-flash": (2560, 768, 128, 6, 8, 129),
    "dots.vlm1": (7168, 2048, 16, 4, 8, 65),
    "zaya1-8b": (2048, 2048, 16, 12, 1, 129),
    "laguna-xs.2": (2048, 512, 32, 9, 8, 65),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("cell", ROWS_SHAPES)
def test_expert_rows_kernels_compile_for_v5e(one_chip, chip_precision, cell,
                                             dtype):
    """The two kernels of a decode step's experts (``ops/grouped_matmul``:
    gate and up over rows taken from the tokens by table, down with each
    token's weighted sum) at the five cells' shapes, one layer's experts
    of a whole stack, in bf16 and with int8 experts: the dynamic row
    copies lower, and the tokens, their float32 result, a step's slabs and
    a tile's rows and sums fit VMEM."""
    from polyrl_tpu.ops import grouped_matmul as gm

    d, f, e, stack, k, n = ROWS_SHAPES[cell]
    m = n * k
    assert gm.rows_by_table(n, d, 2, m, e)
    tile = gm.row_tile(m, e)
    n_tiles = m // tile + e

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def scales(n_w, width):
        return ((arg((stack * e, width), jnp.float32),) * n_w
                if dtype == jnp.int8 else None)

    i32 = jnp.int32
    tab = gm.RowTables(arg((n_tiles,), i32), arg((1,), i32),
                       arg((n_tiles,), i32), arg((n_tiles,), i32),
                       arg((stack * e,), i32), arg((m,), i32))
    gate_up = jax.jit(functools.partial(
        gm.gather_matmul_pallas, tile=tile)).lower(
            arg((n, d), jnp.bfloat16), (arg((stack * e, d, f), dtype),) * 2,
            tab, scales(2, f)).compile()
    down = jax.jit(functools.partial(
        gm.matmul_scatter_pallas, n_tokens=n, tile=tile)).lower(
            arg((n_tiles * tile, f), jnp.bfloat16),
            (arg((stack * e, f, d), dtype),), tab, arg((m,), jnp.float32),
            scales(1, d)).compile()
    assert "tpu_custom_call" in gate_up.as_text()
    assert "tpu_custom_call" in down.as_text()


def _assert_no_tiled_rows(text: str, n: int, k: int, e: int, d: int, f: int):
    """A decode step compiled for the chip keeps no tiled copy of its
    experts' rows: no ``[T*tile, d]`` array (the rows gathered in, the
    products to gather back), no table a tiled row (``[T*tile]``: the
    one-hot fusions that made them), and of the arrays of a megabyte or
    more in VMEM none with a row a tiled row or a choice but ``hidden``,
    which the two kernels hand each other as they did."""
    from polyrl_tpu.ops import grouped_matmul as gm

    m = n * k
    tiled = (m // gm.row_tile(m, e) + e) * gm.row_tile(m, e)
    assert f"[{tiled},{d}]" not in text and f"[{tiled}]" not in text
    by_row = {a for a in _large_in_vmem(text)
              if re.match(rf"\w+\[({tiled}|{m}),", a)}
    assert by_row <= {f"bf16[{tiled},{f}]"}


@pytest.mark.parametrize("rows", MOE_ROWS)
def test_moe_block_compiles_for_v5e(one_chip, chip_precision, on_tpu, rows):
    """One layer's whole routed MLP (route, sort, the gather into tiles,
    the grouped SwiGLU and down projection, weighted sum) at the cell's decode rows and at
    a prefill chunk's, the experts a layer of a whole stack."""
    from polyrl_tpu.models import decoder

    cfg = _moe_cfg()
    lp = _layer_shapes(cfg, one_chip)
    for key in decoder.EXPERT_KEYS:
        lp[key] = jax.ShapeDtypeStruct((7, *lp[key].shape), lp[key].dtype,
                                       sharding=one_chip)
    x = jax.ShapeDtypeStruct((rows, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    valid = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(
        lambda x, lp, v: decoder._moe_mlp(cfg, x, lp, v, layer=3)
    ).lower(x, lp, valid).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "ragged" not in text
    # no copy of a layer's experts, nothing of size experts x rows
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_moe_block_under_ep_compiles_for_four_v5e(v5e_2x2, chip_precision,
                                                  on_tpu):
    """The block with the experts of a stack of 7 sharded over ``ep`` 4,
    traced with the mesh set as the engine and the trainer set it
    (``parallel.mesh.under``): each chip runs the two kernels over its own
    32 experts of the whole stacks, the results are summed, and no chip
    gathers another's experts."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyrl_tpu.models import decoder
    from polyrl_tpu.parallel import mesh as meshlib

    cfg = _moe_cfg()
    mesh = meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=1, ep=4),
                             list(v5e_2x2))
    specs = decoder.param_specs(cfg)["layers"]
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))["layers"]
    lp = {}
    for key, a in tree.items():
        stacked = key in decoder.EXPERT_KEYS
        shape = (7, *a.shape[1:]) if stacked else a.shape[1:]
        spec = specs[key] if stacked else P(*specs[key][1:])
        lp[key] = jax.ShapeDtypeStruct(shape, a.dtype,
                                       sharding=NamedSharding(mesh, spec))
    rows = 64
    everywhere = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((rows, cfg.hidden_size), jnp.bfloat16,
                             sharding=everywhere)
    valid = jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=everywhere)
    block = jax.jit(
        lambda x, lp, v: decoder._moe_mlp(cfg, x, lp, v, layer=3))
    compiled = meshlib.under(mesh, lambda *a: block.lower(*a).compile())(
        x, lp, valid)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "all-reduce" in text
    assert "all-gather" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_moe_decode_layer_compiles_for_v5e(one_chip, chip_precision, on_tpu):
    """One MoE decode layer at the cell's shapes: 64 rows (and the sink
    row), 32/4 heads of 128, the owned paged-attention and KV-write
    kernels, 128 experts top-8, head left out."""
    from polyrl_tpu.models import decoder

    cfg = _moe_cfg()
    s, page, width, n_pages = 65, 64, 96, 4701

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lp = _layer_shapes(cfg, one_chip)
    pool = arg((cfg.num_kv_heads, n_pages, page, cfg.head_dim_), jnp.bfloat16)

    def layer(x, lp, k_pool, v_pool, table, lens, active):
        cos, sin = decoder.rope_cos_sin(cfg, lens[:, None])
        q, k, v = decoder._attn_qkv(cfg, x, lp, cos, sin, (s, 1))
        k_pool, v_pool = pa.paged_kv_write_pallas(
            k_pool, v_pool, table[:, 0], lens % page, k[:, 0], v[:, 0])
        attn = pa.paged_attention_pallas(q[:, 0], k_pool, v_pool, table,
                                         lens + 1)
        x, load = decoder._attn_out_mlp(cfg, x, attn.reshape(s, -1), lp,
                                        active)
        return x, load, k_pool, v_pool

    compiled = jax.jit(layer, donate_argnums=(2, 3)).lower(
        arg((s, cfg.hidden_size), jnp.bfloat16), lp, pool, pool,
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 4   # attention, write, 2 matmuls


def test_moe_decode_layer_under_ep_compiles_for_four_v5e(v5e_2x2,
                                                         chip_precision,
                                                         on_tpu):
    """The decode layer as an engine on an ``ep`` 4 mesh runs it: no
    Mosaic kernel lowers in a program of several chips outside a shard_map
    over every axis, so the attention and the KV write go through the
    engine's wrappers without any ``tp``, and the block goes manual
    itself."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from polyrl_tpu.models import decoder
    from polyrl_tpu.parallel import mesh as meshlib

    cfg = _moe_cfg()
    mesh = meshlib.make_mesh(meshlib.MeshConfig(dp=1, fsdp=1, ep=4),
                             list(v5e_2x2))
    s, page, width, n_pages = 65, 64, 96, 1201
    specs = decoder.param_specs(cfg)["layers"]
    tree = jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg))["layers"]
    lp = {key: jax.ShapeDtypeStruct(
        a.shape[1:], a.dtype,
        sharding=NamedSharding(mesh, P(*specs[key][1:])))
        for key, a in tree.items()}

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, P()))

    pool = arg((cfg.num_kv_heads, n_pages, page, cfg.head_dim_), jnp.bfloat16)
    attn_fn = pa.make_tp_paged_attention(mesh)
    write_fn = pa.make_tp_paged_kv_write(mesh)

    def layer(x, lp, k_pool, v_pool, table, lens, active):
        cos, sin = decoder.rope_cos_sin(cfg, lens[:, None])
        q, k, v = decoder._attn_qkv(cfg, x, lp, cos, sin, (s, 1))
        k_pool, v_pool = write_fn(
            k_pool, v_pool, table[:, 0], lens % page, k[:, 0], v[:, 0])
        attn = attn_fn(q[:, 0], k_pool, v_pool, table, lens + 1)
        x, load = decoder._attn_out_mlp(cfg, x, attn.reshape(s, -1), lp,
                                        active)
        return x, load, k_pool, v_pool

    jitted = jax.jit(layer, donate_argnums=(2, 3))
    compiled = meshlib.under(mesh, lambda *a: jitted.lower(*a).compile())(
        arg((s, cfg.hidden_size), jnp.bfloat16), lp, pool, pool,
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.bool_))
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_qwen3_30b_a3b_preset_equals_the_benchmark_file():
    """The program's preset with the file's overrides is the file, on
    every size: the four MoE keys reach the program through the preset
    alone (``harness.MODEL_FIELDS`` does not carry them)."""
    from benchmark.lib import harness
    from polyrl_tpu.models import decoder

    config = harness.load_config(os.path.join(
        harness.BENCH_DIR, "configs", "qwen3-30b-a3b.json"))
    raw, fields = config["config"], harness.MODEL_FIELDS
    cfg = decoder.get_config(config["preset"],
                             **harness.model_overrides(config))
    moe = {"num_experts": "num_experts",
           "num_experts_per_tok": "num_experts_per_tok",
           "moe_intermediate_size": "moe_intermediate_size",
           "norm_topk_prob": "norm_topk_prob"}
    for key, field in {**fields, **moe}.items():
        assert getattr(cfg, field) == raw[key], key
    # what the preset alone says, beside the depth the file cuts
    preset = decoder.get_config(config["preset"])
    for key, field in moe.items():
        assert getattr(preset, field) == raw[key], key
    assert preset.rms_norm_eps == raw["rms_norm_eps"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert preset.num_layers == 48 and cfg.num_layers == 7
    assert raw["decoder_sparse_step"] == 1 and raw["mlp_only_layers"] == []


# -- the Ling-3.0 hybrid at its published widths ------------------------------


# (rows, heads, pages in the pool, table width): Ling's cell (32 heads, the
# DMAs bound the kernel: a MiB of pages in one piece, three buffers) and
# dots.vlm1's (128 heads, on the ridge: 2048 keys in two sub-blocks)
@pytest.mark.parametrize("s,h,n_pages,width", [
    (129, 32, 15617, 192),
    (65, 128, 10241, 320),
])
def test_latent_attention_kernel_compiles_for_v5e(one_chip, s, h, n_pages,
                                                  width):
    """The absorbed MLA decode kernel at the two cells' shapes: latent
    rows of 576 values in 640 lanes, pages of 64. (A pool 576 wide is
    refused: a DMA slices a pool at whole 128-lane tiles.) It writes
    [rows, heads, 512] in bf16 and nothing else: no float32 copy of the
    output over all 640 lanes (21 MB at 65 x 128) is left among the
    temporaries; and Mosaic, which refuses a kernel over its scoped VMEM,
    takes the rule's buffers at under half of v5e's 16 MiB."""
    from polyrl_tpu.ops import mla_attention as mla

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = functools.partial(mla.latent_paged_attention_pallas, rank=512,
                           scale=192 ** -0.5)
    compiled = jax.jit(fn).lower(
        arg((s, h, 640), jnp.bfloat16),
        arg((1, n_pages, 64, 640), jnp.bfloat16), arg((s, width), jnp.int32),
        arg((s,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * 2**20
    assert mem.output_size_in_bytes == s * h * 512 * 2
    b, _subs, nbuf, _piece = mla._block_plan(h, 640, 512, 64, 2, width)
    assert nbuf * b * 64 * 640 * 2 < 8 * 2**20


def test_latent_attention_kernel_unrolls_no_more_dma_starts():
    """A DMA descriptor that is unrolled code is a start in the kernel's
    jaxpr, and what made the kernel lower slowly once (every page of every
    path unrolled: 0.4-0.8 s a program that holds it, +4.4 s of the
    cell's ``setup_s``; PR 36). At ``dots.vlm1``'s shape the parent of
    PR 48 has 34: a whole block's 32 pages and one loop each for the
    call's first block and for the block a row's last one starts. No
    chip and no compiler: the count is the trace's."""
    from polyrl_tpu.ops import mla_attention as mla

    fn = functools.partial(mla.latent_paged_attention_pallas, rank=512,
                           scale=192 ** -0.5)
    arg = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(fn)(
        arg((65, 128, 640), jnp.bfloat16),
        arg((1, 10241, 64, 640), jnp.bfloat16), arg((65, 320), jnp.int32),
        arg((65,), jnp.int32)))
    assert 0 < text.count("dma_start") <= 34


# (slots, rows, heads): Ling's cell (a row's 32 heads are one block of
# 2 MiB) and a wider model's (64 heads in two blocks, a stack longer than
# the step)
@pytest.mark.parametrize("slots,s,h", [(129, 129, 32), (40, 33, 64)])
def test_kda_state_kernel_compiles_for_v5e(one_chip, slots, s, h):
    """The one-pass KDA state update at head sizes of 128: Mosaic takes
    its transposes of the ``[heads, 128]`` blocks of ``k``, ``q`` and
    ``exp(g)``, the lane broadcasts of their columns and its four 2 MiB
    buffers inside the VMEM it asks for, and the state stack
    is the call's input and output: aliased whole, nothing of its size
    among the temporaries."""
    from polyrl_tpu.ops import kda_state

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(kda_state.kda_state_pallas, donate_argnums=(0,)).lower(
        arg(slots, h, 128, 128), arg(s, h, 128), arg(s, h, 128),
        arg(s, h, 128), arg(s, h, 128), arg(s, h)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == slots * h * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 2**20 + 5 * s * h * 128 * 4


# (slots, rows): the SambaY cell (129 rows, the last block one row) and a
# stack longer than a step whose rows end inside a block
@pytest.mark.parametrize("slots,s", [(129, 129), (40, 33)])
def test_ssm_state_kernel_compiles_for_v5e(one_chip, slots, s):
    """The one-pass Mamba state update at Phi-4-mini-flash's sizes (a
    state of 16 x 5120 float32 a row): Mosaic takes its sublane and lane
    broadcasts, a last block that reaches past the rows and its four
    2.6 MB buffers inside the VMEM it asks for, and the layer's states are
    the call's input and output: aliased whole, nothing of their size
    among the temporaries."""
    from polyrl_tpu.ops import ssm_state

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    n, inner = 16, 5120
    compiled = jax.jit(ssm_state.ssm_state_pallas, donate_argnums=(0,)).lower(
        arg(slots, n, inner), arg(n, inner), arg(s, inner), arg(s, inner),
        arg(s, n), arg(s, n)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == slots * n * inner * 4
    assert mem.temp_size_in_bytes < 2**20 + 2 * s * n * 128 * 4


# -- the MLA projections of a decode step (ops/mla_proj.py) ------------------

MLA_LAYERS = 5


def _written(text: str):
    """(shape's element count, instruction) of everything the optimised
    program writes: the instructions of every computation that no fusion
    calls (a fusion's inside is registers and VMEM, its result is the
    fusion instruction's own), parameters left out."""
    fused = set(re.findall(r"fusion\([^\n]*calls=%?([\w.\-]+)", text))
    comp = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            comp = head.group(1)
        elif line.startswith("}"):
            comp = None
        elif comp is not None and comp not in fused:
            made = re.match(
                r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                line)
            if made and made.group(2) != "parameter":
                dims = made.group(1)
                yield (math.prod(map(int, dims.split(","))) if dims else 1,
                       line.strip())


def _made(text: str, n: int):
    """What the optimised program writes of ``n`` elements or more, plumbing
    apart (a loop's or a tuple's result, a bitcast, a custom call's own
    result): a fusion's result, a ``copy``, a ``copy-done``."""
    plumbing = re.compile(r"= \(?\w+\[[\d,]*\]\S* (while|tuple|"
                          r"get-tuple-element|bitcast|custom-call)\(")
    return [line[:200] for count, line in _written(text)
            if count >= n and not plumbing.search(line)]


def _large_in_vmem(text: str, least: int = 2**20) -> set[str]:
    """The shapes (``bf16[5120,768]``) of the arrays of ``least`` bytes or
    more that the optimised program's entry computation or its loops hold
    in ``S(1)`` (VMEM): a fusion's own result or a custom call's, an
    operand memory-space assignment moved there."""
    sizes = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1}
    found = set()
    for dt, dims in re.findall(r"(\w+)\[([\d,]+)\]\{[^}]*S\(1\)\}", text):
        if sizes.get(dt, 4) * math.prod(map(int, dims.split(","))) >= least:
            found.add(f"{dt}[{dims}]")
    return found


def _taken_into_vmem(text: str, shape: tuple[int, ...]):
    """The lines of the optimised program in which memory-space assignment
    has an array of ``shape`` in hand: the array, or a run of its leading
    dimension, held in ``S(1)`` (VMEM), cut by ``slice-start``, joined by
    ``ConcatBitcast`` or brought back by ``copy-done``."""
    tail = ",".join(map(str, shape[1:]))
    of_shape = re.compile(rf"\w+\[\d+,{tail}\]")
    held = re.compile(of_shape.pattern + r"\{[^}]*S\(1\)\}")
    moves = re.compile(r" (slice-start|copy-done)\(|\"ConcatBitcast\"")
    return [line.strip()[:200] for line in text.splitlines()
            if held.search(line)
            or (moves.search(line) and of_shape.search(line))]


def _mla_projections(one_chip, preset, rows):
    """The MLA layers of a decode step alone, lowered for the described
    chip: ``MLA_LAYERS`` layers of ``preset``'s widths stacked, each one's
    ``_mla_qkv``, absorb, ``latent_paged_attention`` (the real custom
    call: what XLA does beside it is what counts), unabsorb and
    ``_mla_out`` as ``hybrid.paged_decode`` runs them. Returns (the
    optimised HLO, a whole layer's element count by weight)."""
    from polyrl_tpu.models import cache_spec, decoder
    from polyrl_tpu.models.mixers import mla as mixer
    from polyrl_tpu.ops.mla_attention import latent_paged_attention

    cfg = decoder.get_config(preset)
    width, n_pages, page = 192, 2049, 64

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    mla = jax.eval_shape(lambda: decoder.init_params(
        jax.random.PRNGKey(0), cfg))["layers"]["mla"]
    mla = {k: arg((MLA_LAYERS, *a.shape[1:]), a.dtype)
           for k, a in mla.items()}

    def step(mla, x, positions, pool, table, lens):
        for l in range(MLA_LAYERS):
            lp = {k: a[l] for k, a in mla.items()}
            wkv_b = ((mla["wkv_b"], l) if mixer.in_kernel(cfg, rows)
                     else None)
            q_nope, q_rope, _lat = mixer._mla_qkv(cfg, lp, x[:, None],
                                                  positions[:, None])
            q_lat = mixer.mla_absorb(cfg, lp, q_nope[:, 0], q_rope[:, 0],
                                     wkv_b)
            o_lat = latent_paged_attention(q_lat, pool, table, lens,
                                           cfg.kv_lora_rank,
                                           mixer.mla_scale(cfg))
            x = x + mixer._mla_out(
                cfg, lp, x, mixer.mla_unabsorb(cfg, lp, o_lat, wkv_b))
        return x

    compiled = jax.jit(step).lower(
        mla, arg((rows, cfg.hidden_size), cfg.dtype),
        arg((rows,), jnp.int32),
        arg((1, n_pages, page, cache_spec.latent_row(cfg)), cfg.dtype),
        arg((rows, width), jnp.int32), arg((rows,), jnp.int32)).compile()
    layer = {math.prod(mla[k].shape[1:]): k
             for k in ("wq_b", "wq", "wkv_b") if k in mla}
    return compiled.as_text(), layer


@pytest.mark.parametrize("rows", [65, 129])
@pytest.mark.parametrize("preset", ["dots.vlm1-share16",
                                    "ling-3.0-flash-share4"])
def test_mla_projections_read_the_stacks_in_place_on_v5e(
        one_chip, chip_precision, on_tpu, preset, rows):
    """At ``dots.vlm1``'s widths (128 heads, a query latent) and Ling's (32
    heads, none) Mosaic takes the two ``wkv_b`` kernels with their
    windows of the five-layer stack, and nothing the program writes holds
    a whole layer of ``wq_b`` (``wq``) or ``wkv_b``, by element count
    whatever the shape (``[1,1536,24576]``, ``[128,192,1536]``,
    ``[1,512,32768]``, ``[512,128,256]``): the query product takes the
    stack inside its own fusion and lays out the PRODUCT anew."""
    text, layer = _mla_projections(one_chip, preset, rows)
    assert len(layer) == 2
    # absorb, attention and unabsorb a layer
    for kernel in ("mla_absorb", "latent_paged_attention", "mla_unabsorb"):
        assert len(re.findall(rf"%{kernel}[\w.]* = \S+ custom-call\(",
                              text)) == MLA_LAYERS
    assert not [op for count, op in _written(text) if count in layer]


def test_mla_projections_through_the_einsum_do_write_a_layer_out(
        one_chip, chip_precision, on_tpu, monkeypatch):
    """The same program through the einsum (the oracle, which runs off a
    TPU and for the shapes the kernels refuse): XLA feeds the product
    batched over heads from a copy of the layer's ``wkv_b`` with the
    heads major, so the test above cannot pass by matching nothing."""
    from polyrl_tpu.ops import mla_proj

    monkeypatch.setattr(mla_proj, "in_kernel", lambda cfg, rows: False)
    text, layer = _mla_projections(one_chip, "dots.vlm1-share16", 65)
    assert not re.search(r"%mla_(un)?absorb[\w.]* = ", text)
    hit = {layer[count] for count, _op in _written(text) if count in layer}
    assert hit == {"wkv_b"}


def test_ling_decode_step_compiles_for_v5e_within_memory(one_chip,
                                                         chip_precision,
                                                         on_tpu):
    """The cell's whole decode program: 8 fused steps of the 7-layer cut
    at 129 rows, the state slots and the latent pool donated, the token
    drawn inside the head. Everything it holds at once fits a 16 GB chip
    with a gigabyte to spare, and the states are updated in place (no
    copy of a 270 MB state array among the temporaries: the six KDA
    layers' kernel takes the stack as input and output, and no fusion,
    copy or select over a whole stack is left in the program). The router
    chooses without sorting its scores (``blocks._group_limited_topk``):
    of the five sorts a sparse layer had, the two over the scores are
    gone (the groups' ``[129, 8, 64]`` for a group's two best, the rows'
    ``[129, 512]`` for the 8 choices), and 12 are left in the six layers:
    a layer's ``top_k`` of 4 over its 8 group scores ``[129, 8]`` and
    ``_moe_mlp``'s ONE sort of the 1,032 choices by expert, their weights
    beside them. The experts' rows never lie in tiles outside the kernels
    (``_assert_no_tiled_rows``: the parent held ``bf16[5120,2560]`` twice
    a layer and made ``s32[5120]`` tables for it)."""
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("ling-3.0-flash-share4")
    s, width, n_pages, page = 129, 192, 15617, 64

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s)))

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 15 * 10**9
    assert m.temp_size_in_bytes < 200 * 2**20
    # the latent attention, six grouped gate/up and six down matmuls, the
    # head, six state updates
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 20
    made = [line.split(" = ", 1)[1] for line in text.splitlines()
            if " = " in line]
    assert not [op for op in made if re.match(
        r"f32\[129,32,128,128\]\S* (copy|fusion|select)\(", op)]
    sorts = [op.split(" sort(")[0] for op in made if " sort(" in op]
    assert not [op for op in sorts
                if re.search(r"f32\[129,(8,64|512)\]", op)]
    assert len(sorts) <= 12
    _assert_no_tiled_rows(text, s, 8, 128, 2560, 768)


# -- ZAYA1-8B's cell (benchmark/configs/zaya1-8b.json) ----------------------


def _zaya_shapes(one_chip, s, n_pages, page):
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("zaya1-8b-depth12")

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s + 1)))
    return cfg, params, pools


def test_zaya_decode_step_compiles_for_v5e_within_memory(one_chip,
                                                         chip_precision,
                                                         on_tpu):
    """The cell's whole decode program: 8 fused steps of the 12-layer cut
    at 128 rows, the K/V pools and the tails donated, the token drawn
    inside the tied head. Weights (6.06 GB), pool (6.85 GB) and everything
    the step holds at once fit a 16 GB chip; the GQA write and attention
    kernels, the grouped matmuls and the head are Mosaic's."""
    from polyrl_tpu.models import decoder

    s, width, n_pages, page = 128, 192, 8705, 64
    cfg, params, pools = _zaya_shapes(one_chip, s, n_pages, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 12.5e9 < live < 14.5e9
    # no copy of a 285 MB pool among the temporaries
    assert m.temp_size_in_bytes < 200 * 2**20
    # a write and an attention a layer, a gate/up and a down matmul a
    # layer, the head
    assert compiled.as_text().count("tpu_custom_call") >= 4 * 12 + 1


def test_zaya_prefill_chunk_compiles_for_v5e_within_memory(one_chip,
                                                           chip_precision,
                                                           on_tpu):
    """The longest prompt's last chunk: 512 tokens from the slot's tails
    over 128 pages of prefix, beside the weights and the pool."""
    from polyrl_tpu.models import decoder

    n_pages, page, pb, n_pre = 8705, 64, 512, 128
    cfg, params, pools = _zaya_shapes(one_chip, 128, n_pages, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, paged, state, ids, n, at, pre_pages, pages, slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((pb,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((n_pre,), jnp.int32),
        arg((pb // page,), jnp.int32), arg((), jnp.int32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 15 * 10**9
    assert m.temp_size_in_bytes < 2 * 10**9


# -- Phi-4-mini-flash-reasoning's cell (benchmark/configs/phi-4-mini-flash-reasoning.json)


def _sambay_shapes(one_chip, s, n_pages, page):
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("phi-4-mini-flash-reasoning")

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s + 1)))
    return cfg, params, pools


SAMBAY_PAGES = 10987


def test_sambay_decode_step_compiles_for_v5e_and_copies_no_cache(
        one_chip, chip_precision, on_tpu):
    """The cell's whole decode program: 8 fused steps of all 32 layers at
    128 rows, the shared pool, the rings and the Mamba states donated, the
    token drawn inside the tied head. The write and attention kernels take
    10 K/V heads of 128 under 40 query rows (Mosaic's word on it). Weights
    (7.70 GB), the one shared K/V pool (3.60 GB), 129 slots' rings (2.70
    GB) and states (0.42 GB) and everything the step holds at once fit a
    16 GB chip, and nothing the optimised program writes is as large as
    the shared pool's K or a window layer's ring but the write kernel's
    own in-place result; a Mamba layer's state rows are read once and
    written once a step by the update's kernel (``ops/ssm_state.py``), in
    place, and nothing else of their size is written."""
    from polyrl_tpu.models import decoder

    s, width, page = 128, 320, 64
    cfg, params, pools = _sambay_shapes(one_chip, s, SAMBAY_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 14.0e9 < live < 15.2e9
    text = compiled.as_text()
    # a write and an attention a window layer, a write and 8 attentions
    # over the shared pool, a state update a Mamba layer, the head
    assert text.count("tpu_custom_call") >= 2 * 8 + 1 + 8 + 9 + 1
    state_rows = 129 * 16 * 5120          # a Mamba layer's states, float32
    ring = 10 * (1 + 129 * 8) * 64 * 128  # a window layer's K (or V)
    # nothing of a ring's or the pool's size is written but by the write
    # kernel, in place, and nothing of a Mamba layer's states' size but by
    # the update's kernel, in place (their results are the custom calls'
    # own): no fusion's result, no ``copy``
    assert _made(text, state_rows) == []


def test_sambay_prefill_chunk_compiles_for_v5e_within_memory(one_chip,
                                                             chip_precision,
                                                             on_tpu):
    """The longest prompt's last chunk: 512 tokens from the slot's state
    and rings over 256 pages of the shared pool's prefix, beside the
    weights, the pool, the rings and the states."""
    from polyrl_tpu.models import decoder

    page, pb, n_pre = 64, 512, 256
    cfg, params, pools = _sambay_shapes(one_chip, 128, SAMBAY_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, paged, state, ids, n, at, pre_pages, pages, slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((pb,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((n_pre,), jnp.int32),
        arg((pb // page,), jnp.int32), arg((), jnp.int32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 15.6 * 10**9
    assert m.temp_size_in_bytes < 1.2 * 10**9


# -- Laguna-XS.2's cell (benchmark/configs/laguna-xs.2.json) -------------------


def _mixed_shapes(one_chip, s, n_pages, page):
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("laguna-xs.2-share8")

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s + 1)))
    return cfg, params, pools


MIXED_PAGES = 10241


def test_mixed_decode_step_compiles_for_v5e_and_copies_no_cache(
        one_chip, chip_precision, on_tpu):
    """The cell's whole decode program: 8 fused steps of the 9 layers at
    64 rows, the three full layers' pools and the six rings donated, the
    token drawn inside the untied head. The write and attention kernels
    take 8 K/V heads of 128 under 48 query heads (rows of 6, on pages) and
    under 64 (rows of 8, on rings) in ONE program (Mosaic's word on it).
    Weights (3.22 GB), the pools (8.05 GB), 65 slots' rings (0.82 GB) and
    everything the step holds at once fit a 16 GB chip, and nothing the
    optimised program writes is as large as a ring's K but the write
    kernel's own in-place result. A ring (68 MB) would fit VMEM: the
    kernels pin their pools to HBM (``ops.paged_attention._in_hbm``), so
    memory-space assignment takes none in and moves none back, as it did
    ONE ring's K until PR 52; the weights' prefetches are its to make."""
    from polyrl_tpu.models import decoder

    s, width, page = 64, 320, 64
    cfg, params, pools = _mixed_shapes(one_chip, s, MIXED_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 12.0e9 < live < 13.2e9
    text = compiled.as_text()
    # a write and an attention a layer, two grouped matmuls a sparse
    # layer, the head
    assert text.count("tpu_custom_call") >= 2 * 9 + 2 * 8 + 1
    ring = (8, 1 + 65 * 8, 64, 128)       # a window layer's K (or V)
    # (a ``copy-done`` of that size would be memory-space assignment
    # bringing a ring back from VMEM; a weight stack it prefetches is
    # read-only and is never copied back)
    assert _made(text, math.prod(ring)) == []
    assert _taken_into_vmem(text, ring) == []
    # the 32 held experts' rows: taken by table, never in ``[1536, 2048]``
    # tiles outside the kernels
    _assert_no_tiled_rows(text, s, 8, 32, 2048, 512)


def test_mixed_prefill_chunk_compiles_for_v5e_within_memory(one_chip,
                                                            chip_precision,
                                                            on_tpu):
    """The longest prompt's last chunk: 512 tokens from the slot's rings
    over 256 pages of the three full layers' prefix, beside the weights,
    the pools and the rings; the pools are moved by slabs and never laid
    out anew."""
    from polyrl_tpu.models import decoder

    page, pb, n_pre = 64, 512, 256
    cfg, params, pools = _mixed_shapes(one_chip, 64, MIXED_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, paged, state, ids, n, at, pre_pages, pages, slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((pb,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((n_pre,), jnp.int32),
        arg((pb // page,), jnp.int32), arg((), jnp.int32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 14.5 * 10**9
    assert m.temp_size_in_bytes < 1.5 * 10**9


# -- Ouro-2.6B's cell (benchmark/configs/ouro-2.6b.json) -----------------------


def _looped_shapes(one_chip, s, n_pages, page):
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("ouro-2.6b")

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s + 1)))
    return cfg, params, pools


LOOPED_PAGES = 97


def test_looped_decode_step_holds_the_layers_once_and_compiles_for_v5e(
        one_chip, chip_precision, on_tpu):
    """The cell's whole decode program: 8 fused steps of 4 passes of the
    48 layers at 7 rows (6 slots and the sink), the 96 pools donated, the
    token drawn inside the untied head. The passes are a loop of the
    program: the lowered text holds ONE write and ONE attention kernel a
    layer of the stack (at 16 K/V heads under one query head each:
    Mosaic's word on it), not four. Weights (5.34 GB), the pools (9.76 GB)
    and everything the step holds at once fit a 16 GB chip; nothing the
    optimised program writes is as large as a pool but the write kernel's
    own in-place result, and none of the 96 pools is moved: a pool (102
    MB) would fit VMEM, and until PR 52 memory-space assignment took two
    of them in by quarters and copied them back in every pass; the
    kernels now pin their pools to HBM
    (``ops.paged_attention._in_hbm``)."""
    from polyrl_tpu.models import decoder

    s, width, page = 7, 72, 64
    cfg, params, pools = _looped_shapes(one_chip, s, LOOPED_PAGES, page)
    assert pools[1] == () and len(pools[0]) == 48
    assert pools[0][0][0].shape == (16, 4 * LOOPED_PAGES, page, 128)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    lowered = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32))
    text = lowered.as_text()
    # the fused steps' loop and the passes' loop; the three kernels' bodies
    # once each (a layer calls them); a layer's 4 projections' and MLP's
    # products once a layer of the stack
    assert text.count("stablehlo.while") == 2
    assert text.count("tpu_custom_call") == 3
    products = text.count("stablehlo.dot_general")
    assert 48 * 4 <= products < 2 * 48 * 4
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 15.0e9 < live < 15.3e9
    assert m.temp_size_in_bytes < 64 * 2**20
    text = compiled.as_text()
    # a write and an attention a layer, the head: ONCE, under the loop
    assert text.count("tpu_custom_call") == 2 * 48 + 1
    pool = (16, 4 * LOOPED_PAGES, 64, 128)     # a layer's K (or V)
    assert _made(text, math.prod(pool)) == []
    assert _taken_into_vmem(text, pool) == []


@pytest.mark.parametrize("n_pre", [0, 8])
def test_looped_prefill_chunk_holds_the_layers_once_and_compiles_for_v5e(
        one_chip, chip_precision, on_tpu, n_pre):
    """A 512-token chunk, a prompt's first (the cell's: every prompt is one
    chunk; compiled) and one over 8 pages of prefix (lowered): the passes
    a loop around the 48 layers, each pass gathering its prefix from and
    scattering its chunk to its own run of the pools, beside the weights
    and the pools."""
    from polyrl_tpu.models import decoder

    page, pb = 64, 512
    cfg, params, pools = _looped_shapes(one_chip, 7, LOOPED_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, paged, state, ids, n, at, pre_pages, pages, slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    lowered = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((pb,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((n_pre,), jnp.int32),
        arg((pb // page,), jnp.int32), arg((), jnp.int32))
    products = lowered.as_text().count("stablehlo.dot_general")
    assert 48 * 4 <= products < 2 * 48 * 4 + 48 * 4
    if n_pre:
        return      # a minute of compiling: the cell's own chunk alone
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 15.7 * 10**9
    assert m.temp_size_in_bytes < 0.5 * 10**9


# -- MiniCPM-SALA's cell (benchmark/configs/minicpm-sala.json) ------------------


def _sala_shapes(one_chip, s, n_pages, page):
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("minicpm-sala")

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s + 1)))
    return cfg, params, pools


SALA_PAGES = 22978


def test_sala_decode_step_compiles_for_v5e_and_moves_no_pool(
        one_chip, chip_precision, on_tpu):
    """The cell's whole decode program: 8 fused steps of the 12 layers at
    96 rows, the pages with their pooled stores and the 9 layers' states
    donated, the token drawn inside the untied head. The three sparse
    layers' write and attention kernels take the pools as ONE K/V head of
    ``2 * N`` pages under 16 query rows (Mosaic's word on it), and each
    chooses its blocks in the kernel that walks a row's own pooled pages;
    the nine lightning layers update their states in the one-pass kernel.
    Weights (7.86 GB), pages (4.80 GB) and states (1.83 GB) and everything
    the step holds at once fit a 16 GB chip; nothing the optimised program
    writes is as large as a sparse layer's K pool or pooled store, or a
    lightning layer's states, but the kernels' own in-place results and a
    pooled store's scatter; no K/V pool and no pooled store is taken into
    VMEM, and no row's pooled keys are gathered at the table's width."""
    from polyrl_tpu.models import decoder

    s, width, page = 96, 448, 64
    cfg, params, pools = _sala_shapes(one_chip, s, SALA_PAGES, page)
    assert len(pools[0]) == 3 and len(pools[1]) == 12
    assert pools[0][0][0].shape == (2, SALA_PAGES, page, 128)
    assert pools[0][0][2].shape == (SALA_PAGES, 8, 128)
    # the first layer's table of the pages a step attended, then a state
    assert pools[1][0][0].shape == (97, 2, 129)
    assert pools[1][1][0].shape == (97, 32, 128, 128)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 14.3e9 < live < 15.2e9
    text = compiled.as_text()
    # a write, a choice and an attention a sparse layer, a state update a
    # lightning layer, the head
    assert text.count("tpu_custom_call") == 3 * 3 + 9 + 1
    pool = (2, SALA_PAGES, 64, 128)            # a sparse layer's K (or V)
    assert _made(text, math.prod(pool)) == []
    assert _taken_into_vmem(text, pool) == []
    assert _taken_into_vmem(text, (1, 2 * SALA_PAGES, 64, 128)) == []
    # the pooled store stays where it is around its scatter, and what the
    # jnp form gathers of it (every row's pages at the table's width) is
    # not made
    assert _taken_into_vmem(text, (SALA_PAGES, 8, 128)) == []
    # nor its view as rows (the scatter's, the kernel's): held in VMEM, or
    # made by a copy
    flat = re.compile(rf"f32\[{SALA_PAGES * 8},128\]"
                      r"(\{[^}]*S\(1\)\}|\S* (copy|copy-done)\()")
    assert [line[:200] for line in text.splitlines()
            if flat.search(line)] == []
    assert _made(text, 97 * width * 8 * 128) == []
    assert _made(text, 97 * 32 * 128 * 128) == []        # a layer's states


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_sparse_select_kernel_compiles_for_v5e(one_chip, dtype):
    """The kernel that chooses a sparse layer's blocks, alone, at the
    cell's shapes: 97 rows of 2 K/V heads of 16 queries, a 448-wide table
    over a pooled store of one float32 tile a page left in HBM; bf16
    queries (three passes a product) and float32 ones (six). Its strided
    loads of a tile's sublanes, its transposes and its buffers (two rows'
    pages, 4 MiB) are Mosaic's to accept."""
    from polyrl_tpu.ops import sparse_select

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert sparse_select.accepts((SALA_PAGES, 8, 128), jnp.float32, 128, 16)
    compiled = jax.jit(functools.partial(
        sparse_select.sparse_select_pallas, stride=16, kernel=32, block=64,
        topk=64, init_blocks=1, near_blocks=32, dense_len=8192, width=128,
        n_pages=SALA_PAGES)).lower(
            arg((97, 32, 128), dtype), arg((SALA_PAGES, 8, 128), jnp.float32),
            arg((97, 448), jnp.int32), arg((97,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert _taken_into_vmem(text, (SALA_PAGES, 8, 128)) == []


@pytest.mark.parametrize("n_pre", [0, 256])
def test_sala_prefill_chunk_compiles_for_v5e_within_memory(
        one_chip, chip_precision, on_tpu, n_pre):
    """A 512-token chunk, a prompt's first and one over 256 pages of prefix
    (16k tokens): the sparse layers choose per query token and attend
    through the blocks' mask, the lightning layers run the chunked form
    from the slot's state, beside the weights, the pages and the states."""
    from polyrl_tpu.models import decoder

    page, pb = 64, 512
    cfg, params, pools = _sala_shapes(one_chip, 96, SALA_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, paged, state, ids, n, at, pre_pages, pages, slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((pb,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((n_pre,), jnp.int32),
        arg((pb // page,), jnp.int32), arg((), jnp.int32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 15.7 * 10**9
    assert m.temp_size_in_bytes < 1.2 * 10**9


# -- Nemotron-3-Nano's cell (benchmark/configs/nemotron-3-nano-30b-a3b.json) ---


@pytest.mark.parametrize("slots,s", [(65, 64), (40, 33)])
def test_ssd_state_kernel_compiles_for_v5e(one_chip, slots, s):
    """The one-pass Mamba-2 state update at the published sizes (8 groups
    of 128 x 512 float32, 2 MiB a row): Mosaic takes its transposes of the
    ``[groups, 128]`` blocks of B and C, the lane broadcasts of their
    columns and its four 2 MiB buffers inside the VMEM it asks for, and the
    state stack is the call's input and output: aliased whole, nothing of
    its size among the temporaries."""
    from polyrl_tpu.ops import ssd_state

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    assert ssd_state.accepts((slots, 8, 128, 512), jnp.float32)
    compiled = jax.jit(ssd_state.ssd_state_pallas, donate_argnums=(0,)).lower(
        arg(slots, 8, 128, 512), arg(s, 8, 512), arg(s, 8, 512),
        arg(s, 8, 128), arg(s, 8, 128)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == slots * 8 * 128 * 512 * 4
    assert mem.temp_size_in_bytes < 2**20 + 4 * s * 8 * 512 * 4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("rows", [64, 512])
def test_relu2_expert_kernels_compile_for_v5e(one_chip, chip_precision, rows,
                                              dtype):
    """An expert of two matrices at Nemotron-3-Nano's widths, 16 experts
    held of each of 23 layers: ``[16, 2688, 1856]`` under ``relu2`` (9.98
    MB a matrix passes the one-slab rule: three slabs of 896 rows of 1856
    columns, 14.5 lane tiles) and ``[16, 1856, 2688]`` (1856 cannot be cut:
    one slab whole, two in flight), by table at a decode step's 64 rows
    and tiled at a prefill chunk's 512, in bf16 and with int8 experts."""
    from polyrl_tpu.ops import grouped_matmul as gm

    d, f, e, stack, k = 2688, 1856, 16, 23, 6
    m = rows * k
    tile = gm.row_tile(m, e)
    n_tiles = m // tile + e

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def scales(width):
        return ((arg((stack * e, width), jnp.float32),)
                if dtype == jnp.int8 else None)

    i32 = jnp.int32
    up_w, down_w = arg((stack * e, d, f), dtype), arg((stack * e, f, d), dtype)
    if gm.rows_by_table(rows, d, 2, m, e):
        assert rows == 64 and tile == 64
        tab = gm.RowTables(arg((n_tiles,), i32), arg((1,), i32),
                           arg((n_tiles,), i32), arg((n_tiles,), i32),
                           arg((stack * e,), i32), arg((m,), i32))
        up = jax.jit(functools.partial(
            gm.gather_matmul_pallas, tile=tile, act="relu2")).lower(
                arg((rows, d), jnp.bfloat16), (up_w,), tab,
                scales(f)).compile()
        down = jax.jit(functools.partial(
            gm.matmul_scatter_pallas, n_tokens=rows, tile=tile)).lower(
                arg((n_tiles * tile, f), jnp.bfloat16), (down_w,), tab,
                arg((m,), jnp.float32), scales(d)).compile()
    else:
        assert rows == 512 and tile == 256
        up = jax.jit(functools.partial(
            gm.grouped_matmul_pallas, tile=tile, act="relu2")).lower(
                arg((n_tiles * tile, d), jnp.bfloat16), (up_w,),
                arg((n_tiles,), i32), arg((1,), i32), scales(f)).compile()
        down = jax.jit(functools.partial(
            gm.grouped_matmul_pallas, tile=tile)).lower(
                arg((n_tiles * tile, f), jnp.bfloat16), (down_w,),
                arg((n_tiles,), i32), arg((1,), i32), scales(d)).compile()
    assert "tpu_custom_call" in up.as_text()
    assert "tpu_custom_call" in down.as_text()


def _nemotron_shapes(one_chip, s, n_pages, page):
    from polyrl_tpu.models import decoder

    cfg = decoder.get_config("nemotron-3-nano-30b-a3b-share8")

    def shapes(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = shapes(jax.eval_shape(
        lambda: decoder.init_params(jax.random.PRNGKey(0), cfg)))
    pools = shapes(jax.eval_shape(
        lambda: decoder.make_paged_pools(cfg, n_pages, page, slots=s + 1)))
    return cfg, params, pools


NEMOTRON_PAGES = 3601


def test_nemotron_decode_step_compiles_for_v5e_within_memory(
        one_chip, chip_precision, on_tpu):
    """The cell's whole decode program: 8 fused steps of all 52 layers at
    64 rows, the six attention layers' pages and the 23 Mamba-2 layers'
    states and tails donated, the token drawn inside the untied head. The
    Mamba-2 layers update their states in the one-pass kernel, the expert
    layers take their rows by table. Weights (10.52 GB), states (3.19 GB)
    and pages (1.42 GB) and everything the step holds at once fit a 16 GB
    chip, and nothing the optimised program makes is as large as a layer's
    states."""
    from polyrl_tpu.models import decoder

    s, width, page = 64, 128, 64
    cfg, params, pools = _nemotron_shapes(one_chip, s, NEMOTRON_PAGES, page)
    assert len(pools[0]) == 6 and len(pools[1]) == 23
    assert pools[0][0][0].shape == (2, NEMOTRON_PAGES, page, 128)
    assert pools[1][0][0].shape == (65, 8, 128, 512)
    assert pools[1][0][1].shape == (65, 3, 6144)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(params, paged, state, rng, table, lens, last, active, temps):
        def body(carry, _):
            paged, state, rng, lens, last = carry
            rng, sub = jax.random.split(rng)
            head = functools.partial(decoder.head_and_sample, rng=sub,
                                     temps=temps)
            (tok, logp), (paged, state), load = decoder.forward_paged_decode(
                params, cfg, last, lens, (paged, state), table, lens,
                active=active, head_fn=head)
            return (paged, state, rng, lens + 1, tok), (tok, logp, load)
        return jax.lax.scan(body, (paged, state, rng, lens, last), None,
                            length=8)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((2,), jnp.uint32),
        arg((s, width), jnp.int32), arg((s,), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.bool_),
        arg((s,), jnp.float32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 15.0e9 < live < 16.0e9
    text = compiled.as_text()
    # a state update a Mamba-2 layer, a write and an attention an attention
    # layer, the two expert kernels an expert layer, the head
    assert text.count("tpu_custom_call") == 23 + 2 * 6 + 2 * 23 + 1
    assert _made(text, 65 * 8 * 128 * 512) == []         # a layer's states


def test_nemotron_prefill_chunk_compiles_for_v5e_within_memory(
        one_chip, chip_precision, on_tpu):
    """A 512-token chunk over 64 pages of prefix (a 4k prompt's last):
    the Mamba-2 layers run the chunked SSD form from the slot's state, the
    expert layers the tiled form at 512 rows, beside the weights, the
    states and the pages."""
    from polyrl_tpu.models import decoder

    page, pb, n_pre = 64, 512, 64
    cfg, params, pools = _nemotron_shapes(one_chip, 64, NEMOTRON_PAGES, page)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def chunk(params, paged, state, ids, n, at, pre_pages, pages, slot):
        return decoder.prefill_suffix_into_pages(
            params, cfg, ids, n, at, (paged, state), pre_pages, pages, slot)

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        params, pools[0], pools[1], arg((pb,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32), arg((n_pre,), jnp.int32),
        arg((pb // page,), jnp.int32), arg((), jnp.int32)).compile()
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 16.0 * 10**9
    assert m.temp_size_in_bytes < 0.8 * 10**9


def test_nemotron_reference_walk_compiles_for_v5e_within_memory(one_chip):
    """What decides the cell's ``correct`` runs on the chip beside the
    weights: the float32 reference's walk of the longest scored request
    (5,632 positions once padded) with its taps, the whole model and its
    first layer alone (the controls'), fits the chip (with ``control=
    "fp8_weights"``, the control of the log-probabilities' limits, it was
    compiled the same way once by hand: the rounding fuses into the casts
    and moves no size; 100 s of this file's time a case). (A reduction over a
    layer's ``dt`` outside the scan kept every layer's in-projection alive
    to the program's end, 7.3 GB of temporaries, and failed every run of a
    call: the heads' forgetting is summed in the scan's carry.)"""
    from benchmark.lib import harness
    from polyrl_tpu.models import decoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ref = harness.load_named("references", "nemotron_h")
    config = harness.load_config(os.path.join(
        root, "benchmark", "configs", "nemotron-3-nano-30b-a3b.json"))
    cfg = decoder.get_config(config["preset"])
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: decoder.init_params(jax.random.PRNGKey(0),
                                                   cfg)))

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    with jax.default_matmul_precision("highest"):
        compiled = ref._score.lower(
            params, arg((5632,)), arg(()), arg(()),
            ref._sizes(config["config"]), 384, control="").compile()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.6e9
    assert m.temp_size_in_bytes < 5.0e9
