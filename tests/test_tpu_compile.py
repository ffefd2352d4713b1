"""Compiled HERE for a described TPU v5e (no chip, nothing runs, no time
is read): the grouped expert kernel at the two latent cells' real shapes
and the state-space step's at granite's (interpret mode skips Mosaic's
tiling rules and VMEM limits, and aliases nothing), and what the
chip's compiler makes of the XLA paged parts path's pool naming at the
three benchmark cells' per-layer shapes.

The one thing pinned: over a scan of layers whose pools ride as ``xs``
(the step's shape in small), the compiled function holds, outside its
fusions, no value as large as ONE layer's pool — no gathered copy of the
pages, no relayout, no f32 copy, no copy of the scan's slice — so both
contractions read the stacked pool operand where it lies. Under the
table naming the same function holds the gathered pages (the control). The topology is described inside a fixture (only
one process may load the TPU's library; see the on-chip-measurement
guide), and the tests skip where it cannot be described."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_moe import grouped_expert_ffn
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
    pool_page_owners,
    xla_paged_decode_attention_parts,
)
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
