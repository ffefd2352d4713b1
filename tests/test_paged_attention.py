"""Paged-KV attention: kernel parity, page pool allocator, write paths.

VERDICT round-2 item 7 (BASELINE.json north star: "paged-KV attention"):
a Pallas decode kernel reading K/V through a page table, parity-tested
against the contiguous kernel, plus the block-table machinery that lets a
continuous-batching scheduler admit mixed-length concurrent requests
without max-shape caches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.paged_kv import (
    PagePool,
    PagePoolExhausted,
    write_prefill,
    write_token,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
    pallas_decode_attention,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
    paged_decode_attention_reference,
    pallas_paged_decode_attention,
)


def _scattered_pool(key, b, hkv, t, d, page, n_extra_pages=3):
    """A contiguous cache scattered into a shuffled page pool.

    Returns (contiguous k/v [B,Hkv,T,D], pool k/v [P,Hkv,page,D],
    page_table [B,T/page]).
    """
    kk, kv_, kp = jax.random.split(key, 3)
    k = jax.random.normal(kk, (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(kv_, (b, hkv, t, d), jnp.float32)
    jmax = t // page
    n_pages = b * jmax + n_extra_pages
    perm = jax.random.permutation(kp, n_pages)[: b * jmax]
    page_table = perm.reshape(b, jmax).astype(jnp.int32)
    k_pool = jnp.zeros((n_pages, hkv, page, d), jnp.float32)
    v_pool = jnp.zeros((n_pages, hkv, page, d), jnp.float32)
    for b_i in range(b):
        for j in range(jmax):
            p = int(page_table[b_i, j])
            k_pool = k_pool.at[p].set(k[b_i, :, j * page : (j + 1) * page])
            v_pool = v_pool.at[p].set(v[b_i, :, j * page : (j + 1) * page])
    return k, v, k_pool, v_pool, page_table


@pytest.mark.parametrize("d", [128, 64])  # aligned + lane-padded head dims
def test_paged_kernel_matches_contiguous_kernel(d):
    """The verdict's parity bar: the paged kernel through a scattered
    page table equals the contiguous kernel on the unscattered cache."""
    b, hq, hkv, t, page = 2, 8, 2, 512, 128
    key = jax.random.PRNGKey(0)
    k, v, k_pool, v_pool, table = _scattered_pool(key, b, hkv, t, d, page)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, hq, d), jnp.float32)
    lengths = jnp.asarray([300, 512], jnp.int32)

    got = pallas_paged_decode_attention(
        q, k_pool, v_pool, table, lengths, interpret=True
    )
    want = pallas_decode_attention(q, k, v, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_paged_kernel_matches_jnp_reference():
    b, hq, hkv, t, d, page = 3, 4, 4, 256, 64, 128
    key = jax.random.PRNGKey(2)
    _, _, k_pool, v_pool, table = _scattered_pool(key, b, hkv, t, d, page)
    q = jax.random.normal(jax.random.PRNGKey(3), (b, hq, d), jnp.float32)
    lengths = jnp.asarray([1, 129, 256], jnp.int32)  # page edges + minimum

    got = pallas_paged_decode_attention(
        q, k_pool, v_pool, table, lengths, interpret=True
    )
    want = paged_decode_attention_reference(q, k_pool, v_pool, table, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_page_pool_allocator():
    pool = PagePool.create(
        n_layers=2, n_pages=8, n_kv_heads=2, d_head=16, page_size=128
    )
    assert pool.free_pages == 8
    assert pool.pages_for(1) == 1
    assert pool.pages_for(128) == 1
    assert pool.pages_for(129) == 2
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert len(set(a) | set(b)) == 7 and pool.free_pages == 1
    with pytest.raises(PagePoolExhausted):
        pool.alloc(2)
    pool.free(a)
    assert pool.free_pages == 4
    c = pool.alloc(4)
    assert len(c) == 4


def test_mixed_length_requests_share_the_pool():
    """The capacity win paging exists for: two requests of very different
    lengths hold exactly ceil(len/page) pages each — no padding to the
    widest request — and both attend correctly through the shared pool."""
    hq, hkv, d, page = 4, 2, 64, 128
    pool = PagePool.create(
        n_layers=1, n_pages=6, n_kv_heads=hkv, d_head=d, page_size=page,
        dtype=jnp.float32,
    )
    lengths = [130, 500]  # 2 pages + 4 pages = 6 — fits exactly
    tables, caches = [], []
    key = jax.random.PRNGKey(4)
    for i, n in enumerate(lengths):
        n_pages = pool.pages_for(n)
        pages = pool.alloc(n_pages)
        key, kk, kv_ = jax.random.split(key, 3)
        k_seq = jax.random.normal(kk, (1, hkv, n, d), jnp.float32)
        v_seq = jax.random.normal(kv_, (1, hkv, n, d), jnp.float32)
        row = jnp.asarray(pages, jnp.int32)
        pool.k, pool.v = write_prefill(pool.k, pool.v, row, k_seq, v_seq, n)
        tables.append(pages)
        caches.append((k_seq, v_seq))
    assert pool.free_pages == 0

    jmax = max(len(t) for t in tables)
    table = jnp.asarray(
        [t + [0] * (jmax - len(t)) for t in tables], jnp.int32
    )
    q = jax.random.normal(jax.random.PRNGKey(5), (2, hq, d), jnp.float32)
    got = pallas_paged_decode_attention(
        q, pool.k[0], pool.v[0], table, jnp.asarray(lengths, jnp.int32),
        interpret=True,
    )
    # per-request contiguous reference at each request's OWN length
    for i, (k_seq, v_seq) in enumerate(caches):
        want = pallas_decode_attention(
            q[i : i + 1],
            k_seq[0][None],
            v_seq[0][None],
            jnp.asarray([lengths[i]], jnp.int32),
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got[i : i + 1]), np.asarray(want), rtol=2e-5, atol=2e-5
        )


def test_engine_paged_batch_matches_contiguous_batch():
    """The serving integration: generate_batch over the page pool emits
    the same tokens as the contiguous batch path, row for row, including
    mixed lengths, sampled rows, and per-row budgets."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
    contiguous = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    reqs = [
        GenerationRequest("tiny", "short row", max_new_tokens=6),
        GenerationRequest("tiny", "a much longer prompt for the second row "
                          "of this batch", max_new_tokens=20),
        GenerationRequest(
            "tiny", "sampled row", max_new_tokens=12,
            temperature=0.7, seed=3,
        ),
    ]
    want = contiguous.generate_batch(reqs)
    got = paged.generate_batch(reqs)
    for g, w in zip(got, want):
        assert g.tokens == w.tokens
        assert g.text == w.text


def test_engine_paged_batch_matches_single_requests():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    reqs = [
        GenerationRequest("tiny", "row a", max_new_tokens=8),
        GenerationRequest("tiny", "row b is different", max_new_tokens=10),
    ]
    batch = paged.generate_batch(reqs)
    for r, req in zip(batch, reqs):
        assert r.tokens == paged.generate(req).tokens


def test_paged_kv_guards():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
    with pytest.raises(ValueError, match="page_size"):
        JaxEngine(registry=registry, paged_kv=True, page_size=100)
    # paged_kv × kv_quantize COMPOSES since the int8 page pool landed
    # (tests/test_paged_int8.py pins its parity) — the old guard is gone
    engine = JaxEngine(registry=registry, paged_kv=True, kv_quantize="int8")
    assert engine.paged_kv and engine.kv_quantize == "int8"


def test_paged_batch_on_tensor_parallel_engine():
    """Paged decode composes with TP: the pool's heads shard over the
    mesh (pages/table replicated) and every row matches the single-device
    paged engine token for token."""
    import jax.numpy as jnp

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import (
        TensorParallelEngine,
    )

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
    tp = TensorParallelEngine(
        mesh=build_mesh(MeshSpec.tp_only(2), devices=jax.devices()[:2]),
        registry=dict(registry),
        dtype=jnp.float32,
        paged_kv=True,
    )
    single = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    reqs = [
        GenerationRequest("tiny", "sharded paged row", max_new_tokens=8),
        GenerationRequest("tiny", "another longer sharded paged row here",
                          max_new_tokens=14),
    ]
    got = tp.generate_batch(reqs)
    want = single.generate_batch(reqs)
    for g, w in zip(got, want):
        assert g.tokens == w.tokens


def test_write_token_appends_through_the_table():
    """Decode-step appends land at (page_table[len//page], len%page) and
    the kernel sees them immediately."""
    hkv, d, page = 2, 64, 128
    pool = PagePool.create(
        n_layers=1, n_pages=3, n_kv_heads=hkv, d_head=d, page_size=page,
        dtype=jnp.float32,
    )
    pages = pool.alloc(2)
    row = jnp.asarray(pages, jnp.int32)

    key = jax.random.PRNGKey(6)
    n0 = 127  # appends will cross the page boundary
    key, kk, kv_ = jax.random.split(key, 3)
    k_seq = jax.random.normal(kk, (1, hkv, n0, d), jnp.float32)
    v_seq = jax.random.normal(kv_, (1, hkv, n0, d), jnp.float32)
    pool.k, pool.v = write_prefill(pool.k, pool.v, row, k_seq, v_seq, n0)

    k_all, v_all = [k_seq], [v_seq]
    length = n0
    for step in range(3):  # slots 127, 128 (page 2!), 129
        key, kk, kv_ = jax.random.split(key, 3)
        k_vec = jax.random.normal(kk, (1, hkv, d), jnp.float32)
        v_vec = jax.random.normal(kv_, (1, hkv, d), jnp.float32)
        pool.k, pool.v = write_token(
            pool.k, pool.v, row, jnp.int32(length), k_vec, v_vec
        )
        k_all.append(k_vec[:, :, None])
        v_all.append(v_vec[:, :, None])
        length += 1

    k_cat = jnp.concatenate(k_all, axis=2)  # [1, Hkv, 130, D]
    v_cat = jnp.concatenate(v_all, axis=2)
    q = jax.random.normal(jax.random.PRNGKey(7), (1, 4, d), jnp.float32)
    got = pallas_paged_decode_attention(
        q, pool.k[0], pool.v[0], row[None], jnp.asarray([length], jnp.int32),
        interpret=True,
    )
    want = pallas_decode_attention(
        q,
        jnp.pad(k_cat, ((0, 0), (0, 0), (0, 2 * page - length), (0, 0))),
        jnp.pad(v_cat, ((0, 0), (0, 0), (0, 2 * page - length), (0, 0))),
        jnp.asarray([length], jnp.int32),
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("parts_impl", ["kernel", "xla"])
def test_engine_paged_stacked_pool_matches_contiguous(
    parts_impl, monkeypatch
):
    """The STACKED-HYBRID decode path (read-only prompt pool closed over
    the layer scan + carry-resident side caches for generated tokens +
    parts/side online-softmax merge — the design that removed the
    full-pool-copy-per-step, docs/PERF.md): forcing the kernel on CPU
    (interpret) must produce token-identical output to the contiguous
    engine, including the head-dim pad path (tiny d_head=16 → pool padded
    to 128). BOTH prompt-parts implementations are pinned — the Pallas
    parts kernel and the gather+fused-XLA variant that is the
    single-chip default since round 5 (PAGED_XLA_PARTS_MIN_ROWS)."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    monkeypatch.setattr(
        je,
        "PAGED_XLA_PARTS_MIN_ROWS",
        1 if parts_impl == "xla" else 10**9,
    )

    registry = {
        "tiny": get_model_config("qwen2:1.5b").tiny(),  # GQA
        "tiny-mha": get_model_config("phi3:3.8b").tiny(),  # MHA (d pads)
    }
    contiguous = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    stacked = JaxEngine(
        registry=dict(registry),
        dtype=jnp.float32,
        paged_kv=True,
        decode_attention=pallas_decode_attention,  # forces the kernel path
    )
    # the stacked mode must actually be active (kernel closure present)
    assert stacked._paged_decode_attention() is not None
    reqs = [
        GenerationRequest("tiny", "short row", max_new_tokens=6),
        GenerationRequest(
            "tiny",
            "a much longer prompt for the second row of this batch",
            max_new_tokens=20,
        ),
        GenerationRequest(
            "tiny", "sampled row", max_new_tokens=12,
            temperature=0.7, seed=3,
        ),
    ]
    want = contiguous.generate_batch(reqs)
    got = stacked.generate_batch(reqs)
    for g, w in zip(got, want):
        assert g.tokens == w.tokens
        assert g.text == w.text
    # MHA coverage (a real-chip phi3 smoke showed bf16 near-tie argmax
    # divergence between impls; this pins the f32 math is exact for the
    # MHA + padded-head-dim combination too)
    mha_reqs = [
        GenerationRequest("tiny-mha", "row one", max_new_tokens=8),
        GenerationRequest("tiny-mha", "row two is longer", max_new_tokens=14),
    ]
    want = contiguous.generate_batch(mha_reqs)
    got = stacked.generate_batch(mha_reqs)
    for g, w in zip(got, want):
        assert g.tokens == w.tokens


def test_paged_parts_kernel_matches_per_layer_kernel():
    """The PRODUCTION stacked path (pallas_paged_decode_attention_parts:
    layer-indexed DMA into [L,P,Hkv,page,Dp], unnormalised output): its
    normalised result acc/l must equal the per-layer kernel on each
    layer's slice at the same lengths."""
    import numpy as np

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pallas_paged_decode_attention,
        pallas_paged_decode_attention_parts,
    )

    rng = np.random.default_rng(0)
    L, P, HKV, PAGE, D = 3, 8, 2, 128, 128
    B, HQ, JMAX = 2, 4, 2
    q = jnp.asarray(rng.normal(size=(B, HQ, D)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(L, P, HKV, PAGE, D)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(L, P, HKV, PAGE, D)), jnp.float32)
    table = jnp.asarray([[3, 5], [1, 6]], jnp.int32)
    lengths = jnp.asarray([200, 130], jnp.int32)
    for layer in range(L):
        want = pallas_paged_decode_attention(
            q, k_pool[layer], v_pool[layer], table, lengths, interpret=True
        )
        acc, m, l = pallas_paged_decode_attention_parts(
            q, k_pool, v_pool, table, lengths,
            layer=jnp.int32(layer), interpret=True,
        )
        got = (acc / l[..., None]).reshape(B, HQ, D)
        assert jnp.allclose(got, want, atol=1e-5), layer
        # the per-layer (xs-streamed) mode must agree too
        acc2, m2, l2 = pallas_paged_decode_attention_parts(
            q, k_pool[layer], v_pool[layer], table, lengths, interpret=True
        )
        got2 = (acc2 / l2[..., None]).reshape(B, HQ, D)
        assert jnp.allclose(got2, want, atol=1e-5), layer
    # zero-length rows exit with the sentinel triplet the self-term
    # merge relies on: (0, -inf, 0)
    acc, m, l = pallas_paged_decode_attention_parts(
        q, k_pool, v_pool, table, jnp.zeros((B,), jnp.int32),
        layer=jnp.int32(0), interpret=True,
    )
    assert jnp.all(acc == 0.0) and jnp.all(l == 0.0)
    assert jnp.all(jnp.isneginf(m))


def test_paged_parts_kernel_rejects_unpadded_head_dim():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pallas_paged_decode_attention_parts,
    )

    q = jnp.zeros((1, 2, 96), jnp.float32)
    pool = jnp.zeros((2, 4, 2, 128, 96), jnp.float32)
    table = jnp.zeros((1, 2), jnp.int32)
    lengths = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="pre-padded"):
        pallas_paged_decode_attention_parts(
            q, pool, pool, table, lengths, layer=jnp.int32(0), interpret=True
        )


def test_paged_kernel_gating_follows_auto_policy():
    """"auto" engages the paged kernel only on TPU backends (its gather
    fallback is the right CPU/test path); an explicitly injected kernel
    opts in anywhere. Pinned because the whole stacked-hybrid path hangs
    off this gate."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
    auto_cpu = JaxEngine(registry=dict(registry), paged_kv=True)
    assert auto_cpu._paged_decode_attention() is None  # CPU: fallback
    explicit = JaxEngine(
        registry=dict(registry),
        paged_kv=True,
        decode_attention=pallas_decode_attention,
    )
    assert explicit._paged_decode_attention() is not None
    none_ = JaxEngine(
        registry=dict(registry), paged_kv=True, decode_attention=None
    )
    assert none_._paged_decode_attention() is None  # explicit XLA-fused


def _tiny_paged_engine(**kwargs):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    return JaxEngine(
        registry={"tiny": get_model_config("qwen2:1.5b").tiny()},
        dtype=jnp.float32, paged_kv=True, **kwargs,
    )


def test_paged_batch_with_mixed_groups_and_solo_rows():
    """A paged batch mixing a same-bucket prefill group with a solo
    fallback row: every row's tokens still match its solo generate()."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        _prompt_alloc,
    )

    engine = _tiny_paged_engine()
    reqs = [
        GenerationRequest("tiny", "short row one", max_new_tokens=6),
        GenerationRequest(
            "tiny",
            # long enough for a larger prompt bucket than the short rows
            # (→ prefills solo), short enough for tiny's max_seq_len
            "solo " * 8,  # byte-level tiny tokenizer: 40 tokens → bucket 64
            max_new_tokens=8,
        ),
        GenerationRequest(
            "tiny", "short row two", max_new_tokens=10,
            temperature=0.6, seed=11,
        ),
    ]
    tok = engine._tokenizer_for("tiny")
    allocs = [_prompt_alloc(len(tok.encode(r.prompt))) for r in reqs]
    multi_groups = {
        a for a in set(allocs) if allocs.count(a) > 1
    }
    assert multi_groups and len(set(allocs)) > 1, (
        "test prompts must produce at least one multi-row group AND a "
        f"solo row; got allocs {allocs}"
    )

    batch = engine.generate_batch(reqs)
    for r, req in zip(batch, reqs):
        assert r.tokens == engine.generate(req).tokens


def test_paged_generate_batch_closes_its_session(monkeypatch):
    """A paged generate_batch is a stepped session run to its end: its
    rows are a session's rows, and whether it returns, refuses a request
    at the open or fails between two slices, no session is left holding
    the model's weights against eviction."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.stepped import (
        SteppedDecodeSession,
    )

    engine = _tiny_paged_engine()
    reqs = [
        GenerationRequest("tiny", "row a", max_new_tokens=8),
        GenerationRequest("tiny", "row b is different", max_new_tokens=24),
    ]
    results = engine.generate_batch(reqs)
    assert [r.request for r in results] == reqs
    assert all(r.extras["stepped"] for r in results)
    assert engine.live_sessions("tiny") == 0 and engine._live_sessions == {}

    too_long = GenerationRequest("tiny", "x" * 250, max_new_tokens=16)
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        engine.generate_batch([reqs[0], too_long])
    assert engine._live_sessions == {}

    real_step, closed = SteppedDecodeSession.step, []

    def failing_step(self, max_steps=None):
        if self.rows[0].generated[1:]:  # the second slice
            raise RuntimeError("slice failed")
        return real_step(self, max_steps)

    real_close = SteppedDecodeSession.close
    monkeypatch.setattr(SteppedDecodeSession, "step", failing_step)
    monkeypatch.setattr(
        SteppedDecodeSession, "close",
        lambda self: (closed.append(self), real_close(self))[1],
    )
    with pytest.raises(RuntimeError, match="slice failed"):
        engine.generate_batch(reqs[1:])
    assert len(closed) == 1 and closed[0].closed
    assert closed[0].pool.free_pages == closed[0].pool.n_pages - 1  # parking
    assert engine._live_sessions == {}


@pytest.mark.parametrize("kv_quantize", [None, "int8"], ids=["bf16", "int8"])
def test_paged_batch_rows_retire_in_their_own_slices(kv_quantize):
    """Rows whose budgets end in the first, second and third 16-step
    slice retire there (a row's ``decode_s`` ends with its own slice,
    not the batch's last), and the results still come back in request
    order, each equal to its solo generate(), under one window id."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )

    engine = _tiny_paged_engine(kv_quantize=kv_quantize)
    reqs = [
        GenerationRequest("tiny", "the longest budget first", max_new_tokens=40),
        GenerationRequest("tiny", "two tokens", max_new_tokens=2),
        GenerationRequest(
            "tiny", "into the second slice", max_new_tokens=20,
            temperature=0.8, seed=5, stop_at_eos=False,
        ),
        GenerationRequest("tiny", "nine", max_new_tokens=9),
    ]
    results = engine.generate_batch(reqs)
    assert [r.request for r in results] == reqs
    for res, req in zip(results, reqs):
        assert res.tokens == engine.generate(req).tokens
        assert res.prompt_tokens == len(
            engine._tokenizer_for("tiny").encode(req.prompt)
        )
    assert len({r.extras["decode_window"] for r in results}) == 1
    assert {r.extras["retire_reason"] for r in results} == {"budget"}
    first, last = results[1].decode_s, results[0].decode_s
    assert first == results[3].decode_s  # one slice retired both
    assert first < results[2].decode_s < last


def test_paged_generate_batch_compiles_the_session_family():
    """One compiled family for paged rows: after a paged generate_batch,
    a decode_open on requests of the same shapes (what the continuous
    scheduler would call) compiles nothing, and neither does its slice."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
        compile_count,
    )

    engine = _tiny_paged_engine()
    reqs = [
        GenerationRequest("tiny", "row a", max_new_tokens=8),
        GenerationRequest("tiny", "row b is different", max_new_tokens=10),
        GenerationRequest(
            "tiny", "row c samples", max_new_tokens=12, temperature=0.7, seed=3
        ),
    ]
    want = [r.tokens for r in engine.generate_batch(reqs)]
    compiles = compile_count()
    got = {
        id(r.request): r.tokens
        for r in engine._drain_session(engine.decode_open(reqs))
    }
    assert compile_count() == compiles
    assert [got[id(r)] for r in reqs] == want


def _eqns(jaxpr):
    """Every equation of a closed jaxpr and of the jaxprs nested in it."""
    todo = [jaxpr.jaxpr]
    while todo:
        for eqn in todo.pop().eqns:
            yield eqn
            todo.extend(jax.core.jaxprs_in_params(eqn.params))


def _gathered_count_leaks(jaxpr, count):
    """(primitive names, shapes of f32 values holding ``count`` elements)
    over a closed jaxpr and every jaxpr nested in it."""
    prims, wide = set(), []
    for eqn in _eqns(jaxpr):
        prims.add(eqn.primitive.name)
        for var in eqn.outvars:
            aval = var.aval
            if aval.dtype == jnp.float32 and aval.size == count:
                wide.append(aval.shape)
    return prims, wide


@pytest.mark.parametrize(
    "dtype,hq,hkv,d,jmax,tol",
    [
        (jnp.float32, 8, 2, 64, 2, 2e-5),  # f32 pool, d 64 -> 128 lanes
        (jnp.bfloat16, 4, 4, 96, 4, 2e-5),  # G = 1, d 96 -> 128, 4 wide
        (jnp.bfloat16, 8, 2, 128, 2, 2e-5),  # G = 4, d 128, no padding
    ],
    ids=["f32-g4-d64", "bf16-g1-d96-table4", "bf16-g4-d128"],
)
@pytest.mark.parametrize("naming", ["table", "pool"])
def test_xla_parts_match_kernel_parts(dtype, hq, hkv, d, jmax, tol, naming):
    """The fused-XLA parts variant, its pages named by table entry
    (gathered) or by pool index (the pool read in place), returns the
    same (acc, m, l) contract as the Pallas parts kernel, including
    lane-padded head dims, an empty-prompt row (m=-inf, l=0, acc=0), a
    one-token row and a row that fills its last page."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pallas_paged_decode_attention_parts,
        pool_page_owners,
        xla_paged_decode_attention_parts,
    )

    b, page, n_pool, dp = 4, 128, 8, 128
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hq, d), jnp.float32)
    k_pool = jax.random.normal(kk, (n_pool, hkv, page, dp), jnp.float32)
    v_pool = jax.random.normal(kv, (n_pool, hkv, page, dp), jnp.float32)
    # zero the padding lanes as the engine's pools do
    k_pool = k_pool.at[..., d:].set(0).astype(dtype)
    v_pool = v_pool.at[..., d:].set(0).astype(dtype)
    table = jnp.asarray(
        [[0, 1, 6, 7], [2, 3, 7, 6], [4, 5, 0, 0], [0, 0, 0, 0]], jnp.int32
    )[:, :jmax]
    # a row that ends inside a page, one that fills its last page, a
    # one-token row, an empty row
    lengths = jnp.asarray([130, jmax * page, 1, 0], jnp.int32)

    acc_k, m_k, l_k = pallas_paged_decode_attention_parts(
        q, k_pool, v_pool, table, lengths, interpret=True
    )
    acc_x, m_x, l_x = xla_paged_decode_attention_parts(
        q, k_pool, v_pool, table, lengths,
        owners=(
            pool_page_owners(table, lengths, n_pool, page)
            if naming == "pool"
            else None
        ),
    )
    assert acc_x.shape == (b, hkv, hq // hkv, d)
    assert acc_x.dtype == m_x.dtype == l_x.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(acc_x), np.asarray(acc_k[..., :d]), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(m_x), np.asarray(m_k), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(
        np.asarray(l_x), np.asarray(l_k), rtol=tol, atol=tol
    )
    # empty-prompt row: zero weight in the caller's merge
    assert not np.isfinite(np.asarray(m_x)[3]).any()
    assert (np.asarray(l_x)[3] == 0).all()
    assert (np.asarray(acc_x)[3] == 0).all()


@pytest.mark.parametrize("variant", ["bf16", "int8"])
def test_xla_parts_read_gathered_pages_as_stored(variant):
    """On bf16 pools, and on int8 codes with f32 scales, the traced
    function holds no ``transpose`` and no f32 value the size of the
    gathered pages: the pages are consumed in the layout and dtype the
    gather produced (before: a relayout copy and an f32 copy, 134 MB a
    layer for K and V at phi3's shapes)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        xla_paged_decode_attention_parts,
        xla_paged_decode_attention_parts_int8,
    )

    b, hq, hkv, d, page, dp, n_pool, jmax = 4, 4, 4, 96, 128, 128, 8, 4
    q = jnp.zeros((b, hq, d), jnp.bfloat16)
    table = jnp.zeros((b, jmax), jnp.int32)
    lengths = jnp.zeros((b,), jnp.int32)
    if variant == "int8":
        codes = jnp.zeros((n_pool, hkv, page, dp), jnp.int8)
        scales = jnp.zeros((n_pool, hkv, page), jnp.float32)
        jaxpr = jax.make_jaxpr(xla_paged_decode_attention_parts_int8)(
            q, codes, scales, codes, scales, table, lengths
        )
    else:
        pool = jnp.zeros((n_pool, hkv, page, dp), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(xla_paged_decode_attention_parts)(
            q, pool, pool, table, lengths
        )
    prims, wide = _gathered_count_leaks(jaxpr, b * jmax * hkv * page * dp)
    assert "gather" in prims and "dot_general" in prims
    assert "transpose" not in prims
    assert wide == []


def _session_like_case(
    seed, lengths, dead, hq, hkv, d, jmax, n_pool, dtype, unowned,
    page=128, dp=128,
):
    """A pool laid out as a stepped session lays it out: page 0 is the
    parking page (zeros), each live row holds ``ceil(len / page)`` pages
    drawn from a shuffle of the others, every other table entry (a slot
    past the row's prompt, every slot of a ``dead`` row, whose length is
    stale) names the parking page. Pages nobody holds are filled with
    ``unowned``: ``"garbage"`` (finite, large) or ``"inf"``. Returns
    ``(q, k_pool, v_pool, table, lengths, live rows)``."""
    rng = np.random.default_rng(seed)
    b = len(lengths)
    k_pool = rng.normal(size=(n_pool, hkv, page, dp)).astype(np.float32)
    v_pool = rng.normal(size=(n_pool, hkv, page, dp)).astype(np.float32)
    k_pool[..., d:] = 0  # the engine's pools zero the padding lanes
    v_pool[..., d:] = 0
    free = list(rng.permutation(np.arange(1, n_pool)))
    table = np.zeros((b, jmax), np.int32)
    for r, n in enumerate(lengths):
        if r in dead:
            continue
        for j in range(-(-n // page)):
            table[r, j] = free.pop()
    fill = np.inf if unowned == "inf" else 1e4
    for pg in free:
        k_pool[pg, ..., :d] = fill * rng.choice([-1.0, 1.0])
        v_pool[pg, ..., :d] = fill
    k_pool[0] = v_pool[0] = 0
    q = rng.normal(size=(b, hq, d)).astype(np.float32)
    live = [r for r in range(b) if r not in dead]
    return (
        jnp.asarray(q),
        jnp.asarray(k_pool).astype(dtype),
        jnp.asarray(v_pool).astype(dtype),
        jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32),
        live,
    )


POOL_NAMING_CASES = {
    # B x Jmax = 16 table entries over a pool of 32, MHA, d 96 in 128
    # lanes; rows: ends inside a page, fills its last page, one token,
    # empty
    "mha-d96-pool-larger": dict(
        lengths=[130, 512, 1, 0], dead=(), hq=4, hkv=4, d=96, jmax=4,
        n_pool=32, dtype=jnp.bfloat16,
    ),
    # 32 table entries over a pool of 16 (the phi3 cell's ratio), GQA,
    # three dead rows with stale lengths parked on page 0, an empty live
    # row
    "gqa-d128-table-larger-dead-rows": dict(
        lengths=[256, 129, 0, 300, 77, 128, 1, 200], dead=(3, 4, 7),
        hq=8, hkv=2, d=128, jmax=4, n_pool=16, dtype=jnp.bfloat16,
    ),
    # float32 pool, d 64 in 128 lanes, table as wide as the pool
    "gqa-f32-d64-table-equals-pool": dict(
        lengths=[200, 256, 60, 0], dead=(0,), hq=8, hkv=2, d=64, jmax=2,
        n_pool=8, dtype=jnp.float32,
    ),
}


@pytest.mark.parametrize("unowned", ["garbage", "inf"])
@pytest.mark.parametrize("case", sorted(POOL_NAMING_CASES))
def test_pool_named_parts_match_table_named_and_kernel(case, unowned):
    """The pool naming scores every pool page once, for the row that
    holds it, and a row's parts are those of its own pages: equal to the
    table naming and to the Pallas parts kernel on every live row, with
    the pool larger and smaller than the table, dead rows sharing the
    parking page, an empty row and a full last page. What a page nobody
    holds contains, ``inf`` included, reaches no result; a dead row
    reads as its parking page or as an empty row, never as NaN."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pallas_paged_decode_attention_parts,
        pool_page_owners,
        xla_paged_decode_attention_parts,
    )

    spec = POOL_NAMING_CASES[case]
    q, k_pool, v_pool, table, lengths, live = _session_like_case(
        11, unowned=unowned, **spec
    )
    owners = pool_page_owners(
        table, lengths, k_pool.shape[0], k_pool.shape[2]
    )
    got = xla_paged_decode_attention_parts(
        q, k_pool, v_pool, table, lengths, owners=owners
    )
    by_table = xla_paged_decode_attention_parts(
        q, k_pool, v_pool, table, lengths
    )
    kernel = pallas_paged_decode_attention_parts(
        q, k_pool, v_pool, table, lengths, interpret=True
    )
    d = spec["d"]
    rows = np.asarray(live)
    for name, g, t, k in zip(("acc", "m", "l"), got, by_table, kernel):
        g, t, k = (np.asarray(x)[rows] for x in (g, t, k))
        if name == "acc":
            k = k[..., :d]
        np.testing.assert_allclose(g, t, rtol=2e-5, atol=2e-5, err_msg=name)
        np.testing.assert_allclose(g, k, rtol=2e-5, atol=2e-5, err_msg=name)
    acc, m, l = (np.asarray(x) for x in got)
    assert not np.isnan(acc).any() and not np.isnan(l).any()
    assert not np.isnan(m).any()
    for r, n in enumerate(spec["lengths"]):
        if n == 0:  # an empty row: zero weight in the caller's merge
            assert np.isneginf(m[r]).all()
            assert (l[r] == 0).all() and (acc[r] == 0).all()
    # at most one dead row reads the parking page; the others are empty
    dead_reading = [r for r in spec["dead"] if np.isfinite(m[r]).all()]
    assert len(dead_reading) <= 1
    for r in set(spec["dead"]) - set(dead_reading):
        assert np.isneginf(m[r]).all() and (l[r] == 0).all()


def test_pool_page_owners_is_the_tables_inverse():
    """One owner a page among the table's REAL entries; unreal entries
    and pages nobody holds have none (row 0, slot Jmax: past every
    length); of several stale rows parked on one page one holds it."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pool_page_owners,
    )

    table = jnp.asarray(
        [[5, 2, 0, 0], [0, 0, 0, 0], [7, 0, 0, 0], [0, 0, 0, 0]], jnp.int32
    )
    lengths = jnp.asarray([200, 300, 128, 0], jnp.int32)  # row 1 is stale
    own = pool_page_owners(table, lengths, 8, 128)
    row, slot = np.asarray(own.row), np.asarray(own.slot)
    assert (row[[5, 2, 7]] == [0, 0, 2]).all()
    assert (slot[[5, 2, 7]] == [0, 1, 0]).all()
    assert (slot[[1, 3, 4, 6]] == 4).all() and (row[[1, 3, 4, 6]] == 0).all()
    # the parking page: claimed by row 1's three stale slots, one holds it
    assert row[0] == 1 and slot[0] in (0, 1, 2)
    mine = np.asarray(own.mine)
    assert mine[0].tolist() == [True, True, False, False]
    assert mine[1].sum() == 1 and mine[1, slot[0]]
    assert mine[2].tolist() == [True, False, False, False]
    assert not mine[3].any()


@pytest.mark.parametrize("naming", ["table", "pool"])
def test_latent_parts_match_a_plain_reference(naming):
    """The latent form (``v_pool=None``: one kv head, keys a row's whole
    width, values its first ``v_width`` columns, the caller's ``scale``)
    under both namings against plain gathered attention parts; no
    kernel takes that shape."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pool_page_owners,
        xla_paged_decode_attention_parts,
    )

    width, v_width, heads, page = 24, 16, 8, 128
    spec = dict(
        lengths=[130, 256, 0, 90, 1, 255], dead=(3,), hq=heads, hkv=1,
        d=width, jmax=2, n_pool=16, dtype=jnp.float32,
    )
    q, pool, _, table, lengths, live = _session_like_case(
        5, unowned="inf" if naming == "pool" else "garbage", **spec
    )
    scale = 0.37
    acc, m, l = xla_paged_decode_attention_parts(
        q, pool, None, table, lengths, scale=scale, v_width=v_width,
        owners=(
            pool_page_owners(table, lengths, pool.shape[0], page)
            if naming == "pool"
            else None
        ),
    )
    assert acc.shape == (len(lengths), 1, heads, v_width)
    rows = np.asarray(pool)[np.asarray(table)]  # [B, Jmax, 1, page, Dp]
    rows = rows[:, :, 0].reshape(len(lengths), -1, rows.shape[-1])
    for r in live:
        n = int(lengths[r])
        if n == 0:
            assert np.isneginf(np.asarray(m)[r]).all()
            assert (np.asarray(l)[r] == 0).all()
            continue
        keys = rows[r, :n, :width]
        sc = np.asarray(q)[r] @ keys.T * scale  # [H, n]
        want_m = sc.max(axis=1)
        p = np.exp(sc - want_m[:, None])
        np.testing.assert_allclose(
            np.asarray(m)[r, 0], want_m, rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(l)[r, 0], p.sum(axis=1), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(acc)[r, 0], p @ keys[:, :v_width],
            rtol=2e-4, atol=2e-4,
        )


@pytest.mark.parametrize("variant", ["bf16", "int8", "latent"])
def test_xla_parts_read_the_pool_in_place(variant):
    """Under the pool naming the traced function gathers nothing the
    size of the pool (only the per-page query, the inverse table's rows
    and the rows' per-page results), relayouts nothing and holds no f32
    value the size of the pool: both contractions read the pool
    operand itself."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
        pool_page_owners,
        xla_paged_decode_attention_parts,
        xla_paged_decode_attention_parts_int8,
    )

    b, hq, hkv, d, page, dp, n_pool, jmax = 16, 4, 4, 96, 128, 128, 8, 4
    q = jnp.zeros((b, hq, d), jnp.bfloat16)
    table = jnp.zeros((b, jmax), jnp.int32)
    lengths = jnp.zeros((b,), jnp.int32)
    if variant == "int8":
        codes = jnp.zeros((n_pool, hkv, page, dp), jnp.int8)
        scales = jnp.zeros((n_pool, hkv, page), jnp.float32)

        def parts(q, codes, scales, table, lengths):
            return xla_paged_decode_attention_parts_int8(
                q, codes, scales, codes, scales, table, lengths,
                owners=pool_page_owners(table, lengths, n_pool, page),
            )

        jaxpr = jax.make_jaxpr(parts)(q, codes, scales, table, lengths)
    else:
        latent = variant == "latent"
        pool = jnp.zeros((n_pool, hkv, page, dp), jnp.bfloat16)

        def parts(q, pool, table, lengths):
            return xla_paged_decode_attention_parts(
                q, pool, None if latent else pool, table, lengths,
                owners=pool_page_owners(table, lengths, n_pool, page),
                **({"scale": 0.1, "v_width": 64} if latent else {}),
            )

        jaxpr = jax.make_jaxpr(parts)(q, pool, table, lengths)
    pool_elems = n_pool * hkv * page * dp
    prims, wide = _gathered_count_leaks(jaxpr, pool_elems)
    assert "dot_general" in prims and "transpose" not in prims
    assert wide == []
    gathered = [
        max(v.aval.size for v in eqn.outvars)
        for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "gather"
    ]
    # the largest gather is the rows' per-page value sums: B x Jmax pages
    # of [Hkv, G, Dp] f32, 1/page of the table naming's gathered pages
    assert gathered and max(gathered) <= b * jmax * hkv * (hq // hkv) * dp
    assert max(gathered) * 8 <= pool_elems


def test_paged_parts_policy_is_width_and_jmax_aware(monkeypatch):
    """The stacked parts impl choice is static-shape-driven: XLA parts
    for wide batches with NARROW page tables; the Pallas kernel below
    the row threshold OR when the table is wide (the XLA gather reads
    Jmax pages for every row, so the longest row taxes all —
    docs/PERF.md mixed-length A/B)."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention as ppa
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    monkeypatch.setattr(je, "PAGED_XLA_PARTS_MIN_ROWS", 4)
    monkeypatch.setattr(je, "PAGED_XLA_PARTS_MAX_JMAX", 8)
    monkeypatch.setattr(
        ppa, "xla_paged_decode_attention_parts",
        lambda *a, **k: "xla",
    )
    monkeypatch.setattr(
        ppa, "pallas_paged_decode_attention_parts",
        lambda *a, **k: "kernel",
    )
    engine = JaxEngine(
        registry={"tiny": get_model_config("qwen2:1.5b").tiny()},
        paged_kv=True,
        decode_attention=pallas_decode_attention,  # enables kernels
    )
    da = engine._paged_decode_attention()

    def kc(b, jmax):
        return {
            "pool": jnp.zeros((4, 2, 128, 128)),
            "table": jnp.zeros((b, jmax), jnp.int32),
            "side": jnp.zeros((b, 2, 8, 16)),
        }

    q = jnp.zeros((8, 4, 16))
    lengths = jnp.zeros((8,), jnp.int32)
    assert da(q, kc(8, 2), kc(8, 2), lengths) == "xla"  # wide B, narrow table
    assert da(q, kc(8, 16), kc(8, 16), lengths) == "kernel"  # wide table
    q2 = jnp.zeros((2, 4, 16))
    l2 = jnp.zeros((2,), jnp.int32)
    assert da(q2, kc(2, 2), kc(2, 2), l2) == "kernel"  # below row threshold
