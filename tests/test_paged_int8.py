"""Paged KV pool × int8 KV quantization: the two capacity features
composed (VERDICT round-5 directives #3/#4).

The pool holds int8 pages — codes + per-position scales pooled together
(engine/paged_kv.py quantized mode) — and the stacked-hybrid decode
merges int8 prompt parts (both impls: the Pallas parts kernel and the
gather+fused-XLA variant) with quantized side caches. Token parity is
pinned against the CONTIGUOUS int8 path (solo, batch, TP virtual mesh),
and the fixed-budget admission regression pins the capacity payoff: at
equal BATCH_KV_BUDGET_BYTES on the mixed-length study fleet, paged
admits ≥ contiguous rows per decode window and paged+int8 admits ≥
paged-bf16.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
    JaxEngine,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
    quantize_kv_vector,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
    pallas_decode_attention,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
    pallas_paged_decode_attention_parts,
    pallas_paged_decode_attention_parts_int8,
    pool_page_owners,
    xla_paged_decode_attention_parts_int8,
)


# -- kernel parity ----------------------------------------------------------
def _quantized_pools(seed, l, p, hkv, page, d):
    rng = np.random.default_rng(seed)
    kf = jnp.asarray(rng.normal(size=(l, p, hkv, page, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(l, p, hkv, page, d)), jnp.float32)
    kq, ks = quantize_kv_vector(kf)
    vq, vs = quantize_kv_vector(vf)
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    return (kq, ks, vq, vs), (kd, vd)


def test_int8_parts_kernel_matches_dequantized_bf16_parts():
    """The int8 parts kernel folds scales into the online softmax; its
    (acc, m, l) must equal the bf16 parts kernel on the dequantized pool
    — per-layer (xs-streamed) AND stacked-``layer`` modes, including
    page-edge and zero-length rows."""
    L, P, HKV, PAGE, D = 2, 8, 2, 128, 128
    B, HQ = 3, 4
    (kq, ks, vq, vs), (kd, vd) = _quantized_pools(0, L, P, HKV, PAGE, D)
    q = jnp.asarray(
        np.random.default_rng(1).normal(size=(B, HQ, D)), jnp.float32
    )
    table = jnp.asarray([[3, 5], [1, 6], [0, 2]], jnp.int32)
    lengths = jnp.asarray([200, 129, 0], jnp.int32)

    for layer in range(L):
        want = pallas_paged_decode_attention_parts(
            q, kd[layer], vd[layer], table, lengths, interpret=True
        )
        got = pallas_paged_decode_attention_parts_int8(
            q, kq[layer], ks[layer], vq[layer], vs[layer], table, lengths,
            interpret=True,
        )
        stacked = pallas_paged_decode_attention_parts_int8(
            q, kq, ks, vq, vs, table, lengths,
            layer=jnp.int32(layer), interpret=True,
        )
        for g, s, w in zip(got, stacked, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5
            )
            np.testing.assert_allclose(
                np.asarray(s), np.asarray(w), rtol=2e-5, atol=2e-5
            )
    # the zero-length row exits with the merge's sentinel triplet
    acc, m, l = pallas_paged_decode_attention_parts_int8(
        q, kq[0], ks[0], vq[0], vs[0], table, jnp.zeros((B,), jnp.int32),
        interpret=True,
    )
    assert jnp.all(acc == 0.0) and jnp.all(l == 0.0)
    assert jnp.all(jnp.isneginf(m))


@pytest.mark.parametrize(
    "hq,hkv,d,jmax",
    [(4, 2, 96, 2), (4, 4, 96, 4), (8, 2, 128, 2)],
    ids=["g2-d96", "g1-d96-table4", "g4-d128"],
)
@pytest.mark.parametrize("naming", ["table", "pool"])
def test_xla_int8_parts_match_kernel_and_lane_padded_head_dim(
    hq, hkv, d, jmax, naming
):
    """The XLA variant, its pages gathered through the table or read in
    place by pool index, returns the kernel's exact contract over int8
    pages — including a lane-padded pool head dim (d=96 → Dp=128) whose
    pad lanes carry zero codes, a row that fills its last page, a
    one-token row and an empty row."""
    P, PAGE, DP = 8, 128, 128
    rng = np.random.default_rng(2)
    kf = jnp.asarray(rng.normal(size=(P, hkv, PAGE, DP)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(P, hkv, PAGE, DP)), jnp.float32)
    kf = kf.at[..., d:].set(0)  # engine pools zero the pad lanes
    vf = vf.at[..., d:].set(0)
    kq, ks = quantize_kv_vector(kf)
    vq, vs = quantize_kv_vector(vf)
    q = jnp.asarray(rng.normal(size=(4, hq, d)), jnp.float32)
    table = jnp.asarray(
        [[0, 1, 6, 7], [2, 3, 7, 6], [4, 5, 0, 0], [0, 0, 0, 0]], jnp.int32
    )[:, :jmax]
    lengths = jnp.asarray([130, jmax * PAGE, 1, 0], jnp.int32)

    acc_k, m_k, l_k = pallas_paged_decode_attention_parts_int8(
        q, kq, ks, vq, vs, table, lengths, interpret=True
    )
    acc_x, m_x, l_x = xla_paged_decode_attention_parts_int8(
        q, kq, ks, vq, vs, table, lengths,
        owners=(
            pool_page_owners(table, lengths, P, PAGE)
            if naming == "pool"
            else None
        ),
    )
    assert acc_x.shape == (4, hkv, hq // hkv, d)
    np.testing.assert_allclose(
        np.asarray(acc_x), np.asarray(acc_k), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(m_x), np.asarray(m_k), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(l_x), np.asarray(l_k), rtol=2e-5, atol=2e-5
    )
    assert not np.isfinite(np.asarray(m_x)[3]).any()
    assert (np.asarray(l_x)[3] == 0).all()
    assert (np.asarray(acc_x)[3] == 0).all()


@pytest.mark.parametrize("unowned_scale", [3.0, np.inf])
def test_pool_named_int8_parts_skip_pages_nobody_holds(unowned_scale):
    """The int8 twin under the pool naming passes the pool's own codes
    and per-position scales: equal to the table naming and to the int8
    kernel on the live rows of a session-like pool (more table entries
    than pages, two stale rows parked on page 0, an empty row), and the
    pages nobody holds — scales of ``inf`` too — reach no result."""
    P, PAGE, DP, hq, hkv, d, jmax = 16, 128, 128, 8, 2, 96, 4
    rng = np.random.default_rng(4)
    kf = rng.normal(size=(P, hkv, PAGE, DP)).astype(np.float32)
    vf = rng.normal(size=(P, hkv, PAGE, DP)).astype(np.float32)
    kf[..., d:] = 0
    vf[..., d:] = 0
    kf[0] = vf[0] = 0  # the parking page
    lengths = [256, 129, 0, 300, 128, 1, 200, 77]
    dead = (3, 6)
    free = list(rng.permutation(np.arange(1, P)))
    table = np.zeros((len(lengths), jmax), np.int32)
    for r, n in enumerate(lengths):
        if r not in dead:
            for j in range(-(-n // PAGE)):
                table[r, j] = free.pop()
    kq, ks = quantize_kv_vector(jnp.asarray(kf))
    vq, vs = quantize_kv_vector(jnp.asarray(vf))
    idle = jnp.asarray(free, jnp.int32)
    ks = ks.at[idle].set(unowned_scale)
    vs = vs.at[idle].set(unowned_scale)
    q = jnp.asarray(rng.normal(size=(len(lengths), hq, d)), jnp.float32)
    table, lens = jnp.asarray(table), jnp.asarray(lengths, jnp.int32)

    got = xla_paged_decode_attention_parts_int8(
        q, kq, ks, vq, vs, table, lens,
        owners=pool_page_owners(table, lens, P, PAGE),
    )
    by_table = xla_paged_decode_attention_parts_int8(
        q, kq, ks, vq, vs, table, lens
    )
    kernel = pallas_paged_decode_attention_parts_int8(
        q, kq, ks, vq, vs, table, lens, interpret=True
    )
    live = np.asarray([r for r in range(len(lengths)) if r not in dead])
    for g, t, k in zip(got, by_table, kernel):
        g, t, k = (np.asarray(x)[live] for x in (g, t, k))
        np.testing.assert_allclose(g, t, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g, k, rtol=2e-5, atol=2e-5)
    acc, m, l = (np.asarray(x) for x in got)
    assert not (np.isnan(acc).any() or np.isnan(m).any() or np.isnan(l).any())
    assert np.isneginf(m[2]).all() and (l[2] == 0).all()


# -- pool plumbing ----------------------------------------------------------
def test_quantized_page_pool_round_trip():
    """write_prefill + write_token on a quantized pool hold the same
    values (after dequant) the bf16 pool holds, at the same slots."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.paged_kv import (
        PagePool,
        write_prefill,
        write_token,
    )

    hkv, d, page = 2, 64, 128
    pool = PagePool.create(
        n_layers=1, n_pages=3, n_kv_heads=hkv, d_head=d, page_size=page,
        quantized=True,
    )
    assert pool.quantized and pool.n_pages == 3 and pool.free_pages == 3
    pages = pool.alloc(2)
    row = jnp.asarray(pages, jnp.int32)
    rng = np.random.default_rng(3)
    n0 = 127
    k_seq = jnp.asarray(rng.normal(size=(1, hkv, n0, d)), jnp.float32)
    v_seq = jnp.asarray(rng.normal(size=(1, hkv, n0, d)), jnp.float32)
    pool.k, pool.v = write_prefill(pool.k, pool.v, row, k_seq, v_seq, n0)
    # the boundary-crossing append (slot 127 then page 2 slot 0)
    k_vec = jnp.asarray(rng.normal(size=(1, hkv, d)), jnp.float32)
    v_vec = jnp.asarray(rng.normal(size=(1, hkv, d)), jnp.float32)
    pool.k, pool.v = write_token(
        pool.k, pool.v, row, jnp.int32(n0), k_vec, v_vec
    )
    pool.k, pool.v = write_token(
        pool.k, pool.v, row, jnp.int32(n0 + 1), k_vec * 2, v_vec * 2
    )
    # dequant the first row's pages and compare against direct
    # quantization of the same vectors (single source of scale math)
    want_q, want_s = quantize_kv_vector(k_seq[0, :, 5])  # position 5
    got_q = pool.k["q"][0, pages[0], :, 5]
    got_s = pool.k["s"][0, pages[0], :, 5]
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s))
    # the append landed on page 2, slot 0
    app_q, app_s = quantize_kv_vector(k_vec[0] * 2)
    np.testing.assert_array_equal(
        np.asarray(pool.k["q"][0, pages[1], :, 0]), np.asarray(app_q)
    )
    np.testing.assert_allclose(
        np.asarray(pool.k["s"][0, pages[1], :, 0]), np.asarray(app_s)
    )


# -- engine token parity ----------------------------------------------------
@pytest.fixture(scope="module")
def registry():
    return {"tiny": get_model_config("qwen2:1.5b").tiny()}


@pytest.fixture(scope="module")
def parity_reqs():
    return [
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


@pytest.fixture(scope="module")
def contiguous_int8_tokens(registry, parity_reqs):
    engine = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, kv_quantize="int8"
    )
    return [r.tokens for r in engine.generate_batch(parity_reqs)]


def test_engine_accepts_paged_with_kv_quantize(registry):
    """The round-5 guard is lifted: the composition constructs (the old
    ValueError said 'an int8 pool is future work')."""
    engine = JaxEngine(
        registry=dict(registry), paged_kv=True, kv_quantize="int8"
    )
    assert engine.paged_kv and engine.kv_quantize == "int8"


@pytest.mark.parametrize("parts_impl", ["kernel", "xla"])
def test_paged_int8_stacked_matches_contiguous_int8(
    parts_impl, monkeypatch, registry, parity_reqs, contiguous_int8_tokens
):
    """STACKED-HYBRID paged decode over an int8 pool (both prompt-parts
    impls) emits the contiguous int8 path's tokens, row for row —
    mixed lengths, sampled rows, per-row budgets."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    monkeypatch.setattr(
        je, "PAGED_XLA_PARTS_MIN_ROWS",
        1 if parts_impl == "xla" else 10**9,
    )
    paged8 = JaxEngine(
        registry=dict(registry),
        dtype=jnp.float32,
        paged_kv=True,
        kv_quantize="int8",
        decode_attention=pallas_decode_attention,  # stacked mode on CPU
    )
    assert paged8._paged_decode_attention() is not None
    got = paged8.generate_batch(parity_reqs)
    for g, want in zip(got, contiguous_int8_tokens):
        assert g.tokens == want


def test_paged_int8_legacy_gather_matches_contiguous_int8(
    registry, parity_reqs, contiguous_int8_tokens
):
    """LEGACY mode (no kernel → per-step quantized pool writes + the
    dequantizing gather fallback — the multi-device no-head-shard path)
    matches the contiguous int8 tokens too."""
    paged8 = JaxEngine(
        registry=dict(registry), dtype=jnp.float32,
        paged_kv=True, kv_quantize="int8",
    )
    assert paged8._paged_decode_attention() is None  # gather fallback
    got = paged8.generate_batch(parity_reqs)
    for g, want in zip(got, contiguous_int8_tokens):
        assert g.tokens == want


def test_paged_int8_batch_matches_solo(registry):
    """Each batch row is token-identical to its own solo generate() on
    the same paged+int8 engine (the solo path runs the contiguous int8
    decode — same quantized stream, different layout)."""
    paged8 = JaxEngine(
        registry=dict(registry), dtype=jnp.float32,
        paged_kv=True, kv_quantize="int8",
        decode_attention=pallas_decode_attention,
    )
    reqs = [
        GenerationRequest("tiny", "row a", max_new_tokens=8),
        GenerationRequest("tiny", "row b is different", max_new_tokens=10),
    ]
    batch = paged8.generate_batch(reqs)
    for r, req in zip(batch, reqs):
        assert r.tokens == paged8.generate(req).tokens


def test_paged_int8_on_tensor_parallel_engine(registry):
    """TP × paged × int8: codes/scales shard over the mesh heads
    (pool/pool_scale placements) and the int8 parts kernel runs through
    its shard_map rule, token-identical to the single-device paged+int8
    engine. The dryrun's tp=8 virtual-mesh leg runs the same
    composition at mesh width 8 (__graft_entry__.py)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import (
        TensorParallelEngine,
    )

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    tp = TensorParallelEngine(
        mesh=build_mesh(MeshSpec.tp_only(2), devices=jax.devices()[:2]),
        registry=dict(registry),
        dtype=jnp.float32,
        paged_kv=True,
        kv_quantize="int8",
        decode_attention=pallas_decode_attention,
    )
    assert tp._paged_decode_attention(registry["tiny"]) is not None
    single = JaxEngine(
        registry=dict(registry), dtype=jnp.float32,
        paged_kv=True, kv_quantize="int8",
        decode_attention=pallas_decode_attention,
    )
    reqs = [
        GenerationRequest("tiny", "sharded paged quantized row",
                          max_new_tokens=8),
        GenerationRequest("tiny", "another longer sharded paged quantized "
                          "row here", max_new_tokens=14),
    ]
    got = [r.tokens for r in tp.generate_batch(reqs)]
    want = [r.tokens for r in single.generate_batch(reqs)]
    assert got == want


# -- admission --------------------------------------------------------------
MIXED_FLEET_LENS = (26, 235, 913, 3697)  # the docs/PERF.md study mix


def _admitted_rows(monkeypatch, budget, **engine_kw):
    """Rows per decode window the estimator admits for a 256-row mixed
    fleet at ``budget`` — flagship shapes, pure arithmetic (no weights)."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    monkeypatch.setattr(je, "BATCH_KV_BUDGET_BYTES", budget)
    cfg = get_model_config("qwen2:1.5b")
    rows = 256
    ids = [[1] * MIXED_FLEET_LENS[i % 4] for i in range(rows)]
    reqs = [
        GenerationRequest(cfg.name, "x", max_new_tokens=256)
        for _ in range(rows)
    ]
    engine = JaxEngine(
        registry={cfg.name: cfg}, dtype=jnp.bfloat16,
        decode_attention=pallas_decode_attention, **engine_kw
    )
    return engine._max_batch_rows(cfg, reqs, ids)


@pytest.mark.parametrize("budget", [2_500_000_000, 4_500_000_000])
def test_equal_budget_admission_is_monotone_in_cache_density(
    monkeypatch, budget
):
    """THE capacity regression (VERDICT round-5 directive #4): at equal
    BATCH_KV_BUDGET_BYTES on the mixed-length study fleet, paged admits
    ≥ contiguous rows per decode window and paged+int8 admits ≥
    paged-bf16 — with the composition strictly widest at the default
    budget (the docs/PERF.md admission table's ladder)."""
    contiguous = _admitted_rows(monkeypatch, budget)
    paged = _admitted_rows(monkeypatch, budget, paged_kv=True)
    paged8 = _admitted_rows(
        monkeypatch, budget, paged_kv=True, kv_quantize="int8"
    )
    assert paged >= contiguous
    assert paged8 >= paged
    assert paged8 > contiguous  # the composition must actually pay off


def test_max_admission_rows_tracks_cache_density(registry):
    """The scheduler-facing probe: denser layouts admit wider fleets for
    the same anchor request, without loading any weights."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    cfg = get_model_config("qwen2:1.5b")
    req = GenerationRequest(
        cfg.name, "m" * 1800, max_new_tokens=256
    )  # ~1.8k-token prompt

    def probe(**kw):
        e = JaxEngine(
            registry={cfg.name: cfg}, dtype=jnp.bfloat16,
            decode_attention=pallas_decode_attention, **kw
        )
        assert not e._models  # estimate only — nothing loads
        return e.max_admission_rows(req)

    contiguous = probe()
    paged8 = probe(paged_kv=True, kv_quantize="int8")
    assert paged8 >= contiguous
    assert paged8 >= je.BATCH_MIN_SPLIT_ROWS


def test_scheduler_budget_aware_admission_uses_backend_estimate():
    """BatchScheduler raises a batch's cap to the backend's
    max_admission_rows estimate (and ignores a failing probe)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationResult,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        BatchScheduler,
    )

    class Backend:
        def __init__(self, rows):
            self.rows = rows
            self.batches = []

        def generate(self, request):
            return self._result(request)

        def generate_batch(self, requests):
            self.batches.append(len(requests))
            return [self._result(r) for r in requests]

        @staticmethod
        def _result(request):
            return GenerationResult(
                request=request, tokens=[1], text="x",
                prompt_tokens=1, generated_tokens=1,
                prefill_s=0.0, decode_s=0.0, total_s=0.0,
            )

        def max_admission_rows(self, request):
            if self.rows is None:
                raise RuntimeError("probe down")
            return self.rows

    backend = Backend(rows=64)
    sched = BatchScheduler(backend, max_batch=2, window_s=0.2)
    assert sched.budget_aware
    sched.start()
    try:
        import threading

        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(
                    sched.submit(
                        GenerationRequest("m", "p", max_new_tokens=1)
                    )
                )
            )
            for _ in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sched.stop()
    assert len(results) == 6
    # without the estimate the cap of 2 forces ≥3 batches; the raised
    # cap of 64 admits everything the window catches into fewer calls
    assert backend.batches and max(backend.batches) > 2

    # a failing probe falls back to the static cap, never to an error
    flaky = Backend(rows=None)
    sched2 = BatchScheduler(flaky, max_batch=4, window_s=0.05)
    probe_req = GenerationRequest("m", "p", max_new_tokens=1)
    assert sched2._admission_cap(
        type("T", (), {"request": probe_req})()
    ) == 4

    # explicit opt-out pins the static cap
    sched3 = BatchScheduler(Backend(rows=64), max_batch=4, budget_aware=False)
    assert not sched3.budget_aware
