"""Compiled HERE for a described TPU v5e (no chip, nothing runs, no time
is read): the grouped expert kernel at the two latent cells' real shapes
and the state-space step's at granite's (interpret mode skips Mosaic's
tiling rules and VMEM limits, and aliases nothing), and what the
chip's compiler makes of the XLA paged parts path's pool naming at the
three benchmark cells' per-layer shapes.

The first thing pinned: over a scan of layers whose pools ride as ``xs``
(the step's shape in small), the compiled function holds, outside its
fusions, no value as large as ONE layer's pool — no gathered copy of the
pages, no relayout, no f32 copy, no copy of the scan's slice — so both
contractions read the stacked pool operand where it lies. Under the
table naming the same function holds the gathered pages (the control).
The held-page kernel, which walks the pages rows hold where XLA scores
the whole pool, lowers at the three cells' shapes over the WHOLE stacked
pool, and a session's decode slice that runs it copies no pool leaf. The topology is described inside a fixture (only
one process may load the TPU's library; see the on-chip-measurement
guide), and the tests skip where it cannot be described."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_moe import grouped_expert_ffn
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
    held_page_list,
    pallas_paged_pool_parts,
    pool_page_owners,
    xla_paged_decode_attention_parts,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_kda import kda_step_live
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_ssm import live_rows, ssm_step_live

# per-layer shapes of a cell's session (PERF.md §4): row bucket, query
# heads, kv heads, query width, pool pages, pool lanes, latent value width
CELLS = {
    "phi3-mini": (16, 32, 32, 96, 32, 128, None),
    "mistral-7b": (16, 32, 8, 128, 64, 128, None),
    "longcat-flash-ep32": (32, 64, 1, 576, 128, 640, 512),
}
PAGE, JMAX = 128, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


LAYERS = 4


def _compiled_values(cell, naming, one_chip):
    """(a layer's pool elements, element counts of every value the
    compiled function computes outside its fusions: operands and
    constants left out). The function is the step's shape in small: the
    inverse table built once, then a scan over ``LAYERS`` layers whose
    pools ride as ``xs``."""
    b, hq, hkv, d, n_pool, dp, v_width = CELLS[cell]
    latent = v_width is not None

    def parts(q, k_pools, v_pools, table, lengths):
        owners = (
            pool_page_owners(table, lengths, n_pool, PAGE)
            if naming == "pool"
            else None
        )

        def layer(acc, pools):
            k_pool, v_pool = pools
            got = xla_paged_decode_attention_parts(
                q, k_pool, None if latent else v_pool, table, lengths,
                owners=owners,
                **({"scale": 0.1, "v_width": v_width} if latent else {}),
            )
            return acc + got[0], None

        out = d if v_width is None else v_width
        acc0 = jnp.zeros((b, hkv, hq // hkv, out), jnp.float32)
        return jax.lax.scan(layer, acc0, (k_pools, v_pools))[0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = arg((LAYERS, n_pool, hkv, PAGE, dp), jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        text = (
            jax.jit(parts)
            .lower(
                arg((b, hq, d), jnp.float32), pools, pools,
                arg((b, JMAX), jnp.int32), arg((b,), jnp.int32),
            )
            .compile()
            .as_text()
        )
    sizes, fused = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:  # a fusion's body lives in registers: only its result counts
            fused = head.group(1).startswith("fused_computation")
            continue
        m = re.match(
            r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* (\w[\w\-]*)\(", line
        )
        if fused or not m or m.group(2) in (
            "parameter", "constant", "bitcast", "get-tuple-element",
        ):
            continue
        n = 1
        for dim in m.group(1).split(","):
            n *= int(dim) if dim else 1
        sizes.append(n)
    assert sizes
    return n_pool * hkv * PAGE * dp, sizes


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_pool_named_parts_compile_without_a_copy_of_the_pool(cell, one_chip):
    pool_elems, sizes = _compiled_values(cell, "pool", one_chip)
    assert max(sizes) < pool_elems


def test_table_named_parts_compile_with_the_gathered_pages(one_chip):
    """The control: phi3's table names 64 pages of a pool of 32, and the
    compiled function holds them."""
    pool_elems, sizes = _compiled_values("phi3-mini", "table", one_chip)
    assert max(sizes) >= 2 * pool_elems


@pytest.mark.parametrize("cell,layers,rows,kv_heads,width,pages", [
    ("phi3-mini", 32, 16, 32, 96, 32), ("mistral-7b", 32, 16, 8, 128, 64), ("granite-4h-small-ep8", 4, 32, 8, 128, 128),
])
def test_the_pool_kernel_lowers_at_the_cells_shapes_and_reads_the_stacked_pool_where_it_lies(
    cell, layers, rows, kv_heads, width, pages, one_chip,
):
    """A scan over the layers whose every step hands the held-page kernel
    the WHOLE stacked pool and the layer's entry (scalar-prefetched with
    the held-page list), the step's pool part in small: the compiled
    function holds no temporary as large as ONE layer's pool, so no slice
    of the pool is copied for the call (a custom call's operand slice is
    materialised: 67 MB a layer in phi3's cell)."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, k_pool, v_pool, table, lengths):
        owners = held_page_list(pool_page_owners(table, lengths, pages, PAGE))

        def layer(acc, at):
            got = pallas_paged_pool_parts(q, k_pool, v_pool, at, lengths, owners, interpret=False)
            return acc + got[0], None

        acc0 = jnp.zeros((rows, kv_heads, 32 // kv_heads, width), jnp.float32)
        return jax.lax.scan(layer, acc0, jnp.arange(layers))[0]

    pool = arg((layers, pages, kv_heads, PAGE, 128), jnp.bfloat16)
    compiled = jax.jit(step).lower(
        arg((rows, 32, width), jnp.bfloat16), pool, pool, arg((rows, JMAX), jnp.int32), arg((rows,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "pallas_paged_pool" in text
    assert compiled.memory_analysis().temp_size_in_bytes < pages * kv_heads * PAGE * 128 * 2


@pytest.mark.parametrize("kind", ["int8", "bf16"])
@pytest.mark.parametrize("experts,d,f,tokens,rows,blocks", [
    (64, 3584, 1024, 32, 8, 80),  # xing4, a decode step: 32 bucket rows x top-4
    (64, 3584, 1024, 256, 16, 128),  # xing4, the 256-token join chunk
    (16, 6144, 2048, 32, 8, 64),  # longcat, a decode step: 32 x top-12 on 16 held experts
    (16, 6144, 2048, 256, 8, 400),  # longcat, the join chunk
    (9, 4096, 768, 32, 8, 49),  # granite, a decode step: 32 x top-10 of 72 on 9 held experts
    (9, 4096, 768, 256, 64, 49),  # granite, the join chunk: 36 pairs an expert expected, blocks of 64
], ids=["xing4-decode", "xing4-chunk", "longcat-decode", "longcat-chunk", "granite-decode", "granite-chunk"])
def test_the_grouped_expert_kernel_lowers_at_the_cells_shapes(kind, experts, d, f, tokens, rows, blocks, one_chip):
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def leaf(n_in, n_out):
        if kind == "bf16":
            return arg((2, experts, n_in, n_out), jnp.bfloat16)
        return {"q": arg((2, experts, n_in, n_out), jnp.int8), "s": arg((2, experts, 1, n_out), jnp.float32)}

    with jax.default_matmul_precision("default"):  # the suite's "highest" is no bfloat16 matmul's
        text = (
            jax.jit(lambda *a: grouped_expert_ffn(*a, interpret=False))
            .lower(
                arg((tokens, d), jnp.bfloat16), leaf(d, f), leaf(d, f), leaf(f, d), arg((), jnp.int32),
                arg((blocks,), jnp.int32), arg((), jnp.int32), arg((blocks * rows,), jnp.int32),
                arg((blocks * rows,), jnp.float32),
            )
            .compile()
            .as_text()
        )
    assert text.count("tpu_custom_call") >= 2  # both calls are Mosaic kernels


@pytest.mark.parametrize("groups", [1, 8])
def test_the_state_step_kernel_lowers_at_granites_shape_and_writes_the_record_where_it_lies(groups, one_chip):
    """A scan over the record's entries, the step's shape in small: the
    compiled function holds the record ONCE (its result is its donated
    argument) and no temporary as large as one row's state of one layer."""
    ls, b, h, p, n = 36, 32, 128, 64, 128

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(s, mask, xs):
        rows, n_live = live_rows(mask, b)

        def layer(s, xs):
            at, ops = xs
            y, s = ssm_step_live(s, at, rows, n_live, *ops, interpret=False)
            return s, y

        return jax.lax.scan(layer, s, (jnp.arange(ls), xs))

    xs = (
        arg((ls, b, h, p)), arg((ls, b, groups, n)), arg((ls, b, groups, n)), arg((ls, b, h)),
        arg((ls, h)), arg((ls, h)),
    )
    compiled = jax.jit(step, donate_argnums=(0,)).lower(arg((ls, b, h, p, n)), arg((b,), jnp.bool_), xs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    record = ls * b * h * p * n * 4
    assert memory.alias_size_in_bytes >= record
    assert memory.temp_size_in_bytes < h * p * n * 4


@pytest.mark.parametrize("tokens,rows,blocks", [(32, 8, 68), (256, 8, 292)], ids=["decode", "chunk"])
def test_the_grouped_expert_kernel_lowers_with_glm53s_clamped_swiglu(tokens, rows, blocks, one_chip):
    """GLM-5.3-Flash's expert layer: 36 held experts 4096 x 2048, int8, the
    gate and up clamped at 10 inside the gate-up kernel."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def leaf(n_in, n_out):
        return {"q": arg((2, 36, n_in, n_out), jnp.int8), "s": arg((2, 36, 1, n_out), jnp.float32)}

    with jax.default_matmul_precision("default"):
        text = (
            jax.jit(lambda *a: grouped_expert_ffn(*a, limit=10.0, interpret=False))
            .lower(
                arg((tokens, 4096), jnp.bfloat16), leaf(4096, 2048), leaf(4096, 2048), leaf(2048, 4096),
                arg((), jnp.int32), arg((blocks,), jnp.int32), arg((), jnp.int32),
                arg((blocks * rows,), jnp.int32), arg((blocks * rows,), jnp.float32),
            )
            .compile()
            .as_text()
        )
    assert text.count("tpu_custom_call") >= 2


def test_the_kda_step_kernel_lowers_at_glm53s_shape_and_writes_the_record_where_it_lies(one_chip):
    """GLM-5.3-Flash's seven KDA layers over a 32-row bucket: the compiled
    scan holds the record ONCE (its result is its donated argument) and no
    temporary as large as one row's state of one layer."""
    ls, b, h, k = 7, 32, 64, 128

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(s, mask, xs):
        rows, n_live = live_rows(mask, b)

        def layer(s, xs):
            at, ops = xs
            o, s = kda_step_live(s, at, rows, n_live, *ops, interpret=False)
            return s, o

        return jax.lax.scan(layer, s, (jnp.arange(ls), xs))

    xs = (arg((ls, b, h, k)), arg((ls, b, h, k)), arg((ls, b, h, k)), arg((ls, b, h, k)), arg((ls, b, h)))
    compiled = jax.jit(step, donate_argnums=(0,)).lower(arg((ls, b, h, k, k)), arg((b,), jnp.bool_), xs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= ls * b * h * k * k * 4
    assert memory.temp_size_in_bytes < h * k * k * 4


@pytest.mark.parametrize("cell,kv_heads,width", [("phi3-mini", 32, 96), ("mistral-7b", 8, 128)])
def test_the_side_kernel_lowers_at_the_dense_cells_shapes_and_writes_the_side_where_it_lies(cell, kv_heads, width, one_chip):
    """A scan over the layers whose carry is the side leaves, the step's
    side part in small: each layer's kernel writes its token into them and
    reads them where they lie, so the compiled function holds the side
    once (its results are its donated arguments) and no temporary as large
    as ONE layer's slice of it, in either order XLA lays the leaf out
    (phi3's 96-wide heads with the 256 columns on lanes, mistral's
    row-major). XLA's scatter of the token in the kernel's place asked for
    the other order and copied the whole stack twice a layer (my chip runs,
    PR 37: phi3's period 251 -> 3,114 ms)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_side import paged_side_parts

    layers, b, cols, hq = 32, 16, 256, 32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(k_side, v_side, q, new, wp, mask):
        rows, n_live = live_rows(mask, b)

        def layer(sides, at):
            *parts, k_s, v_s = paged_side_parts(q, new, new, *sides, at, wp, rows, n_live, interpret=False)
            return (k_s, v_s), sum(jnp.sum(p) for p in parts)

        return jax.lax.scan(layer, (k_side, v_side), jnp.arange(layers))

    side = arg((layers, b, kv_heads, cols, width), jnp.bfloat16)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        side, side, arg((b, hq, width), jnp.bfloat16), arg((b, kv_heads, width), jnp.bfloat16),
        arg((b,), jnp.int32), arg((b,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    stack = layers * b * kv_heads * cols * width * 2
    assert memory.alias_size_in_bytes >= 2 * stack
    assert memory.temp_size_in_bytes < b * kv_heads * cols * width * 2


_SLICES = {}


def _session_slice(model, one_chip):
    """The compiled text of the whole decode slice a stacked paged session
    of ``model`` compiles (16 steps over a 16-row bucket, a small pool,
    int8 weights) for a described v5e, and the shapes of a side leaf and
    of a pool leaf; compiled once for the tests below."""
    if model in _SLICES:
        return _SLICES[model]
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import jax_engine as je
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.paged_kv import pool_widths
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import get_model_config
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import quantize_leaf
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import Transformer, init_params
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops import (
        pallas_attention, pallas_paged_attention, pallas_paged_side, pallas_quant,
    )

    cfg = get_model_config(model)
    rows, pages, table, side_cols = 16, 16, 4, 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with pytest.MonkeyPatch.context() as monkeypatch:
        monkeypatch.setattr(je, "_stepped_donation", lambda: {"donate_argnums": (1,)})
        for module in (je, pallas_attention, pallas_paged_attention, pallas_paged_side, pallas_quant):
            monkeypatch.setattr(module, "on_tpu", lambda: True)
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda k: init_params(
                cfg, k, jnp.bfloat16, post=lambda n, leaf: quantize_leaf(n, leaf, "int8")), jax.random.PRNGKey(0)),
        )
        eng = je.JaxEngine(registry={cfg.name: cfg}, dtype=jnp.bfloat16, quantize="int8", paged_kv=True,
                           decode_attention=pallas_attention.pallas_decode_attention)
        eng._models[cfg.name] = Transformer(cfg, params)
        kw, vw = pool_widths(cfg, True)
        lead = (cfg.cache_layers, rows, cfg.cache_heads)
        side = lead + (side_cols, cfg.d_head)
        pool = (cfg.cache_layers, pages, cfg.cache_heads, 128, kw)
        i32, f32 = jnp.int32, jnp.float32
        carry = {
            "tokens": sds((rows,), i32), "offsets": sds((rows,), i32), "prompt_lens": sds((rows,), i32),
            "remaining": sds((rows,), i32), "temps": sds((rows,), f32), "top_ps": sds((rows,), f32),
            "rps": sds((rows,), f32), "presence": sds((rows, cfg.vocab_size), jnp.bool_),
            "done": sds((rows,), jnp.bool_), "rngs": sds((rows, 2), jnp.uint32), "table": sds((rows, table), i32),
            "side_k": sds(side, jnp.bfloat16), "side_v": sds(side, jnp.bfloat16),
            "pool_k": sds(pool, jnp.bfloat16),
            "pool_v": sds((cfg.cache_layers, pages, cfg.cache_heads, 128, vw), jnp.bfloat16),
        }
        step = eng._paged_batch_decode_step_fn(cfg.name, 16, 0, False, False, True, False, False, carry=carry)
        with jax.default_matmul_precision("default"):
            text = step.lower(params, carry, sds((), i32)).compile().as_text()
    _SLICES[model] = text, side, pool
    return _SLICES[model]


def _copies(text, shape):
    """Instructions of ``text`` that copy a bfloat16 value of ``shape``."""
    shape = ",".join(map(str, shape))
    return [line for line in text.splitlines() if re.search(rf"= bf16\[{shape}\]\S* copy(-start)?\(", line)]


@pytest.mark.parametrize("model", ["phi3:3.8b", "mistral:7b"])
def test_the_sessions_decode_slice_copies_no_side_leaf(model, one_chip):
    """The whole decode slice a stacked paged session compiles (16 steps
    over a 16-row bucket, a small pool, int8 weights), for a described
    v5e: no instruction copies a side leaf, inside the layer loop or at
    its ends (the parent, XLA's side part, copied both leaves at the
    loop's ends; XLA's scatter of the token beside the kernel, twice a
    layer)."""
    text, side, _ = _session_slice(model, one_chip)
    assert "pallas_paged_side" in text
    copies = _copies(text, side)
    assert not copies, copies[:2]


@pytest.mark.parametrize("model", ["phi3:3.8b", "mistral:7b"])
def test_the_sessions_decode_slice_reads_the_held_pool_pages_where_they_lie(model, one_chip):
    """The same slice runs the held-page kernel (``impl: pallas-pool``)
    over the stacked pool as the carry holds it: no instruction copies a
    pool leaf, and none holds one layer's pool (a slice of the stack
    handed to the kernel would be such a copy)."""
    text, _, pool = _session_slice(model, one_chip)
    assert "pallas_paged_pool" in text
    copies = _copies(text, pool) + _copies(text, pool[1:])
    assert not copies, copies[:2]
    layer = ",".join(map(str, pool[1:]))
    held = [
        line for line in text.splitlines()
        if re.search(rf"= bf16\[(1,)?{layer}\]", line) and "parameter(" not in line
    ]
    assert not held, held[:2]
