"""Iteration-level (stepped) decode sessions: token parity with solo
generate() — including rows admitted mid-flight — early row retirement,
and in-flight page recycling (engine/stepped.py; the engine half of the
continuous scheduler).

Parity discipline is the PR-1 batch-parity machinery: for a fixed
request set, every row's token stream under the stepped session must be
identical to its solo ``generate()`` stream, whatever the cache layout
(contiguous / paged × bf16 / int8-KV)."""

import jax.numpy as jnp
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


@pytest.fixture(scope="module")
def registry():
    return {"tiny": get_model_config("qwen2:1.5b").tiny()}


@pytest.fixture(scope="module")
def engine(registry):
    return JaxEngine(registry=dict(registry), dtype=jnp.float32)


def _drain(session, max_steps=8, limit=200):
    """Step the session dry; returns results in retirement order."""
    out = []
    for _ in range(limit):
        if not session.active:
            break
        out.extend(session.step(max_steps))
    assert not session.active, "session did not drain"
    return out


def test_stepped_matches_solo_and_retires_early(engine):
    reqs = [
        GenerationRequest("tiny", "first prompt", max_new_tokens=6),
        GenerationRequest(
            "tiny", "second, longer-running row", max_new_tokens=40,
            stop_at_eos=False,
        ),
        GenerationRequest(
            "tiny", "third", max_new_tokens=12, temperature=0.9, seed=5
        ),
    ]
    solo = [engine.generate(r) for r in reqs]
    sess = engine.decode_open(reqs)
    results = {}
    retired_while_running = False
    while sess.active:
        for res in sess.step(8):
            results[id(res.request)] = res
            if sess.active:
                retired_while_running = True
    # short rows retired mid-flight, not at batch end
    assert retired_while_running
    for r, s in zip(reqs, solo):
        got = results[id(r)]
        assert got.tokens == s.tokens
        assert got.text == s.text
        assert got.prompt_tokens == s.prompt_tokens
        assert got.extras["stepped"] is True
        assert got.extras["retire_reason"] in ("eos", "budget")


def test_stepped_join_mid_flight_is_solo_identical(engine):
    long = GenerationRequest(
        "tiny", "anchor runs long", max_new_tokens=48, stop_at_eos=False
    )
    sess = engine.decode_open([long], reserve_rows=4)
    assert sess.free_slots >= 1
    sess.step(4)  # the anchor is mid-flight now
    joiner = GenerationRequest(
        "tiny", "late arrival", max_new_tokens=10, seed=3
    )
    assert sess.can_join(joiner)
    sess.join(joiner)
    assert sess.active == 2
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(long)].tokens == engine.generate(long).tokens
    assert results[id(joiner)].tokens == engine.generate(joiner).tokens


def test_stepped_join_refuses_incompatible(engine, registry):
    sess = engine.decode_open(
        [GenerationRequest("tiny", "anchor", max_new_tokens=8)],
        reserve_rows=4,
    )
    # wrong top_k
    assert not sess.can_join(
        GenerationRequest("tiny", "x", max_new_tokens=4, top_k=7)
    )
    # budget whose generation bucket cannot fit the session cache
    assert not sess.can_join(
        GenerationRequest("tiny", "x", max_new_tokens=200)
    )
    _drain(sess)
    # a drained session has no live rows and still refuses joins once closed
    sess.close()
    assert not sess.can_join(GenerationRequest("tiny", "x", max_new_tokens=4))


def test_stepped_mixed_sampling_knobs_parity(engine):
    reqs = [
        GenerationRequest(
            "tiny", "nucleus row", max_new_tokens=10, temperature=1.0,
            top_p=0.9, seed=1,
        ),
        GenerationRequest(
            "tiny", "penalised row", max_new_tokens=10,
            repeat_penalty=1.5,
        ),
        GenerationRequest("tiny", "plain row", max_new_tokens=10),
    ]
    sess = engine.decode_open(reqs)
    results = {id(r.request): r for r in _drain(sess, max_steps=4)}
    for r in reqs:
        assert results[id(r)].tokens == engine.generate(r).tokens


def test_stepped_budget_one_row_retires_with_prefill_token(engine):
    req = GenerationRequest("tiny", "one token only", max_new_tokens=1)
    sess = engine.decode_open([req])
    results = _drain(sess)
    want = engine.generate(req)
    assert results[0].tokens == want.tokens


def test_stepped_paged_recycles_pages_mid_flight(registry):
    """The acceptance criterion: a retired row's pages return to the pool
    BEFORE the batch's last row finishes — the free-page count recovers
    mid-flight — and its result was handed back while the long row was
    still decoding."""
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    plain = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    reqs = [
        GenerationRequest("tiny", "short", max_new_tokens=6),
        GenerationRequest(
            "tiny", "the long-running companion row", max_new_tokens=100,
            stop_at_eos=False,
        ),
    ]
    sess = paged.decode_open(reqs, reserve_rows=4)
    free0 = sess.pool.free_pages
    results = {}
    recovered_mid_flight = False
    retired_before_end = False
    while sess.active:
        for res in sess.step(8):
            results[id(res.request)] = res
            if sess.active:
                retired_before_end = True
        if sess.active and sess.pool.free_pages > free0:
            recovered_mid_flight = True
    assert recovered_mid_flight
    assert retired_before_end
    for r in reqs:
        assert results[id(r)].tokens == plain.generate(r).tokens


def test_stepped_paged_join_allocates_freed_pages(registry):
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    plain = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    long = GenerationRequest(
        "tiny", "anchor decodes on", max_new_tokens=60, stop_at_eos=False
    )
    sess = paged.decode_open([long], reserve_rows=4)
    sess.step(8)
    joiner = GenerationRequest("tiny", "joins late", max_new_tokens=12, seed=9)
    assert sess.can_join(joiner)
    free_before = sess.pool.free_pages
    sess.join(joiner)
    assert sess.pool.free_pages < free_before  # pages really allocated
    results = {id(r.request): r for r in _drain(sess, max_steps=16)}
    assert results[id(long)].tokens == plain.generate(long).tokens
    assert results[id(joiner)].tokens == plain.generate(joiner).tokens


@pytest.mark.parametrize("paged", [False, True])
def test_stepped_int8_kv_parity_with_join(registry, paged):
    """Stepped sessions compose with the int8 KV cache on both layouts:
    every row (including a mid-flight joiner) matches the same engine's
    solo stream."""
    e8 = JaxEngine(
        registry=dict(registry),
        dtype=jnp.float32,
        kv_quantize="int8",
        paged_kv=paged,
    )
    reqs = [
        GenerationRequest("tiny", "alpha", max_new_tokens=8, seed=1),
        GenerationRequest(
            "tiny", "beta beta", max_new_tokens=24, temperature=1.1, seed=2,
            stop_at_eos=False,
        ),
    ]
    sess = e8.decode_open(reqs, reserve_rows=4)
    sess.step(4)
    joiner = GenerationRequest("tiny", "gamma joins", max_new_tokens=10, seed=3)
    assert sess.can_join(joiner)
    sess.join(joiner)
    results = {id(r.request): r for r in _drain(sess)}
    for r in reqs + [joiner]:
        assert results[id(r)].tokens == e8.generate(r).tokens


def test_stepped_close_frees_pages(registry):
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    reqs = [
        GenerationRequest("tiny", "row a", max_new_tokens=40),
        GenerationRequest("tiny", "row b", max_new_tokens=40),
    ]
    sess = paged.decode_open(reqs)
    total = sess.pool.n_pages
    held = total - sess.pool.free_pages
    assert held > 1  # rows + the parking page
    sess.close()
    assert sess.pool.free_pages == total - 1  # only parking stays held
    with pytest.raises(RuntimeError, match="closed"):
        sess.step()


def _layout_engine(registry, paged, kv):
    return JaxEngine(
        registry=dict(registry),
        dtype=jnp.float32,
        paged_kv=paged,
        kv_quantize=kv,
    )


@pytest.mark.parametrize(
    "paged,kv",
    [(False, None), (False, "int8"), (True, None), (True, "int8")],
    ids=["contig-bf16", "contig-int8", "paged-bf16", "paged-int8"],
)
def test_chunked_join_parity_all_layouts(registry, paged, kv):
    """The ISSUE-4 tentpole invariant: a joiner whose prefill streams in
    as MULTIPLE token-budgeted chunks — interleaved with decode slices
    the companion keeps generating through — produces a stream
    bit-identical to its solo generate(), and so does the companion that
    decoded across the whole chunked join. All four cache layouts."""
    eng = _layout_engine(registry, paged, kv)
    anchor = GenerationRequest(
        "tiny", "a" * 120, max_new_tokens=40, stop_at_eos=False, seed=1
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(4)  # the anchor is mid-flight
    joiner = GenerationRequest(
        "tiny", "j" * 100, max_new_tokens=12, seed=3
    )
    assert sess.can_join(joiner)
    pj = sess.join_begin(joiner, chunk_tokens=32)
    assert pj.total_chunks >= 3  # 101 prompt ids at 32-token chunks
    assert sess.free_slots == sess.b_bucket - 2  # slot reserved
    done = False
    while not done:
        done = sess.join_step(pj)
        if not done:
            # the companion keeps decoding BETWEEN prefill chunks —
            # exactly the scheduler's interleave
            sess.step(2)
    assert sess.active == 1  # joiner not live until commit
    sess.join_commit(pj)
    assert sess.active == 2
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(anchor)].tokens == eng.generate(anchor).tokens
    assert results[id(joiner)].tokens == eng.generate(joiner).tokens


def test_chunked_join_single_chunk_matches_sync_join(engine):
    """A short-prompt joiner through the chunked protocol is the
    one-shot join (the sync path is implemented over it)."""
    anchor = GenerationRequest(
        "tiny", "anchor stays", max_new_tokens=32, stop_at_eos=False
    )
    sess = engine.decode_open([anchor], reserve_rows=4)
    sess.step(4)
    joiner = GenerationRequest("tiny", "quick", max_new_tokens=8, seed=5)
    pj = sess.join_begin(joiner)
    assert pj.total_chunks == 1
    assert sess.join_step(pj)
    sess.join_commit(pj)
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(joiner)].tokens == engine.generate(joiner).tokens


def test_join_abort_releases_slot_and_pages(registry):
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    anchor = GenerationRequest(
        "tiny", "anchor", max_new_tokens=40, stop_at_eos=False
    )
    sess = paged.decode_open([anchor], reserve_rows=4)
    free0 = sess.pool.free_pages
    slots0 = sess.free_slots
    pj = sess.join_begin(
        GenerationRequest("tiny", "j" * 80, max_new_tokens=8), chunk_tokens=32
    )
    assert sess.pool.free_pages < free0  # pages reserved at begin
    assert sess.free_slots == slots0 - 1
    sess.join_step(pj)
    sess.join_abort(pj)
    assert sess.pool.free_pages == free0
    assert sess.free_slots == slots0
    sess.close()


def test_can_join_rejects_prompt_over_session_bucket(engine):
    """A prompt whose bucketed alloc + generation bucket exceeds the
    session's cache must be refused BEFORE any prefill is paid (it would
    overflow the contiguous row cache)."""
    sess = engine.decode_open(
        [GenerationRequest("tiny", "tiny anchor", max_new_tokens=16)],
        reserve_rows=4,
    )
    # session cache: prompt bucket 32 + gen bucket 16 = 48 slots
    assert sess.cache_len == 48
    long_prompt = GenerationRequest("tiny", "x" * 100, max_new_tokens=8)
    assert not sess.can_join(long_prompt)
    with pytest.raises(RuntimeError, match="cannot join"):
        sess.join_begin(long_prompt)
    _drain(sess)


def test_can_join_rejects_when_pool_drained(registry):
    """Paged admission probe: a joiner whose pages don't fit the pool's
    free list right now is deferred, not failed."""
    paged = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    sess = paged.decode_open(
        [GenerationRequest(
            "tiny", "anchor", max_new_tokens=24, stop_at_eos=False
        )],
        reserve_rows=4,
    )
    joiner = GenerationRequest("tiny", "late", max_new_tokens=8)
    assert sess.can_join(joiner)
    hog = sess.pool.alloc(sess.pool.free_pages)  # drain the free list
    assert not sess.can_join(joiner)
    sess.pool.free(hog)
    assert sess.can_join(joiner)
    sess.close()


def test_stepped_validates_inputs(engine):
    with pytest.raises(ValueError, match="one model"):
        engine.decode_open(
            [
                GenerationRequest("tiny", "x", max_new_tokens=4),
                GenerationRequest("other", "y", max_new_tokens=4),
            ]
        )
    with pytest.raises(ValueError, match="at least one"):
        engine.decode_open([])


def test_stacked_session_reads_the_pool_in_place_through_join_and_retirement(
    registry, monkeypatch
):
    """A stacked paged session without a prefix store (no page has two
    readers) compiles the XLA parts path with its pages named by POOL
    INDEX: the slice builds the table's inverse, ``/debug/state`` says
    ``xla-pool``, and greedy rows — through a retirement whose pages a
    joiner takes over, and dead rows parked on one page — decode the
    tokens of the contiguous ``generate_batch``."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention as ppa
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    built = []
    real_owners = ppa.pool_page_owners

    def counting(table, lengths, n_pages, page):
        built.append((table.shape, n_pages, page))
        return real_owners(table, lengths, n_pages, page)

    monkeypatch.setattr(ppa, "pool_page_owners", counting)
    stacked = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True,
        decode_attention=pallas_decode_attention,  # the parts path on CPU
    )
    plain = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    short = GenerationRequest("tiny", "short row", max_new_tokens=6)
    long = GenerationRequest(
        "tiny", "the long-running companion row", max_new_tokens=60,
        stop_at_eos=False,
    )
    joiner = GenerationRequest(
        "tiny", "joins into the pages the short row left", max_new_tokens=14
    )
    sess = stacked.decode_open([short, long], reserve_rows=4)
    assert sess.stacked and sess.store is None
    assert sess.debug_state()["attention"]["impl"] == "xla-pool"
    results = {}
    while id(short) not in results:
        for res in sess.step(4):
            results[id(res.request)] = res
    assert sess.active == 1  # the short row's pages are free again
    freed = sess.pool.free_pages
    assert sess.can_join(joiner)
    sess.join(joiner)
    assert sess.pool.free_pages < freed
    for res in _drain(sess):
        results[id(res.request)] = res
    sess.close()
    want = plain.generate_batch([short, long, joiner])
    for req, w in zip((short, long, joiner), want):
        assert results[id(req)].tokens == w.tokens
    # one inverse a compiled slice step (built at trace time, outside the
    # layer scan), over the session's table and pool
    assert built and all(
        shape == (len(sess.rows), sess.jmax) and n == sess.pool.n_pages
        for shape, n, _ in built
    )
    keys = [k for k in stacked._decode_cache if k[0] == "paged-step"]
    assert keys and all(k[-1] is False for k in keys)
