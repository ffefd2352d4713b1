"""Shared-prefix paging over the ENGINE-level prefix store (ISSUE 7 →
ISSUE 14): PagePool refcounts, store-backed stepped-session integration,
and the store × preemption interaction.

The contracts under test:

- refcounted pages: a page is recycled only when its LAST reader frees
  it; every pre-existing free site (retire/cancel/abort/close) keeps
  its exact-free-count behavior whether or not pages are shared;
- joiners whose prompt shares a published prefix map the STORE's
  read-only pages (billed ONCE), seed the boundary positions (CoW),
  chunk-prefill only the divergent tail — and stay TOKEN-IDENTICAL to
  their solo ``generate()`` on all four cache layouts;
- publication is PAGE-BACKED and UNCAPPED (ISSUE 14): a joiner's own
  divergent-tail pages are adopted by the store, so a second-generation
  sharer maps them read-only too; the store's holdings survive sharer
  retirement, and the pool free-count accounts for them exactly;
- the store OUTLIVES the session: a joiner in a FRESH session (prior
  session closed — its pool dead) still hits, restoring spilled pages
  into the new pool, and close() leaves the old pool fully free (only
  the parking page held);
- a preemption victim whose row maps store-shared pages releases them
  at preempt and re-shares them from the store at resume; a store that
  moved on (eviction) degrades the resume to recompute.

The radix-tree data structure itself (splitting, budgets, spill and
restore arithmetic) is pinned in tests/test_radix_store.py.
"""

import jax.numpy as jnp
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
    JaxEngine,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.paged_kv import (
    PagePool,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.prefix import (
    PREFIX_COW_COPIES_C,
    PREFIX_HIT_TOKENS_C,
    PREFIX_SHARED_PAGES_G,
    common_prefix_len,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.radix_store import (
    STORE_HITS_C,
    STORE_RESTORES_C,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
    get_model_config,
)

# 140 's' chars -> 141 ids (BOS + bytes): one FULL 128-token page plus a
# 13-token partial — every sharer exercises both the page mapping and
# the copy-on-write boundary.
SHARED = "s" * 140


@pytest.fixture(scope="module")
def registry():
    return {"tiny": get_model_config("qwen2:1.5b").tiny(max_seq_len=512)}


def _engine(registry, paged=True, kv=None, share=True, **kw):
    return JaxEngine(
        registry=dict(registry),
        dtype=jnp.float32,
        paged_kv=paged,
        kv_quantize=kv,
        prefix_share=share,
        **kw,
    )


def _drain(session, max_steps=8, limit=400):
    out = []
    for _ in range(limit):
        if not session.active:
            break
        out.extend(session.step(max_steps))
    assert not session.active, "session did not drain"
    return out


# -- PagePool refcounts --------------------------------------------------------


def _tiny_pool(n_pages=8):
    return PagePool.create(
        n_layers=1, n_pages=n_pages, n_kv_heads=1, d_head=4, page_size=128
    )


def test_pool_share_defers_recycling_to_last_reader():
    pool = _tiny_pool()
    pages = pool.alloc(2)
    free0 = pool.free_pages
    pool.share(pages)  # second reader
    assert pool.refcount(pages[0]) == 2
    assert pool.shared_pages == 2
    pool.free(pages)  # first reader leaves — pages stay allocated
    assert pool.free_pages == free0
    assert pool.shared_pages == 0
    pool.free(pages)  # last reader leaves — NOW they recycle
    assert pool.free_pages == free0 + 2
    assert pool.refcount(pages[0]) == 0


def test_pool_double_free_and_share_free_raise():
    pool = _tiny_pool()
    pages = pool.alloc(1)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free(pages)
    with pytest.raises(ValueError, match="share a free page"):
        pool.share(pages)


def test_common_prefix_len():
    assert common_prefix_len([1, 2], [1, 2, 3]) == 2
    assert common_prefix_len([1, 2, 3], [1, 9]) == 1
    assert common_prefix_len([7], [8]) == 0


# -- session integration: sharing, parity, exact accounting --------------------


@pytest.mark.parametrize("kv", [None, "int8"], ids=["bf16", "int8"])
def test_sharers_map_pages_and_match_solo_exactly(registry, kv):
    """The core invariant on both paged pools: sharers map the anchor's
    read-only prefix page (fewer pages off the free list than a full
    allocation), every stream is bit-identical to solo generate(),
    sharer retirement returns everything except what the STORE adopted
    (page-backed tail publication — accounted exactly), and close()
    restores the pool fully (store nodes spill; only parking held)."""
    eng = _engine(registry, kv=kv)
    plain = _engine(registry, kv=kv, share=False)
    store = eng.prefix_store
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor tail", max_new_tokens=90,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    assert store.debug_state()["nodes"] == 1  # the anchor published
    sess.step(4)
    free_before = sess.pool.free_pages
    held_before = store.hbm_pages_held
    j1 = GenerationRequest("tiny", SHARED + " j-one", max_new_tokens=8, seed=3)
    j2 = GenerationRequest("tiny", SHARED + " j-two!!", max_new_tokens=8, seed=4)
    assert sess.can_join(j1)
    pj = sess.join_begin(j1, chunk_tokens=32)
    assert pj.hit_tokens == 142  # BOS + 140 shared chars + ' '
    assert pj.shared_pages == 1  # one full page mapped read-only
    assert sess.pool.refcount(pj.pages[0]) >= 3  # anchor + store + j1
    while not sess.join_step(pj):
        pass
    sess.join_commit(pj)
    sess.join(j2)  # the one-shot join path shares too
    results = {}
    while len(results) < 2:  # both sharers retire; anchor still live
        for res in sess.step(8):
            results[id(res.request)] = res
    assert sess.active == 1
    # exact accounting under UNCAPPED publication: the store adopts a
    # sharer's full-page-aligned TAIL pages (here the short tails span
    # no full page, so adopted == 0 and restoration is exact like PR 7;
    # test_joiner_tail_pages_published_for_second_generation pins the
    # adopted > 0 shape) — everything else recycled
    adopted = store.hbm_pages_held - held_before
    assert sess.pool.free_pages == free_before - adopted
    for res in _drain(sess):
        results[id(res.request)] = res
    for r in (anchor, j1, j2):
        assert results[id(r)].tokens == plain.generate(r).tokens
    total = sess.pool.n_pages
    sess.close()
    # detach spilled every store node out of this pool: free-count
    # exactly restored, only the parking page stays held
    assert sess.pool.free_pages == total - 1
    assert store.hbm_pages_held == 0


@pytest.mark.parametrize(
    "paged,kv",
    [(False, None), (False, "int8"), (True, None), (True, "int8")],
    ids=["contig-bf16", "contig-int8", "paged-bf16", "paged-int8"],
)
def test_cow_divergence_mid_page_parity_all_layouts(registry, paged, kv):
    """A joiner diverging MID-PAGE (141 shared ids = 1 full page + 13
    partial) seeds the boundary from the store and recomputes only the
    tail — token parity with solo generate() on all four cache layouts
    (paged pools share pages; contiguous sessions get seed-only reuse)."""
    eng = _engine(registry, paged=paged, kv=kv)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=60,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(4)
    joiner = GenerationRequest(
        "tiny", SHARED + " divergent continuation", max_new_tokens=12, seed=9
    )
    hits0 = PREFIX_HIT_TOKENS_C.labels().value
    pj = sess.join_begin(joiner, chunk_tokens=32)
    assert pj.hit_tokens > 0
    assert PREFIX_HIT_TOKENS_C.labels().value - hits0 == pj.hit_tokens
    while not sess.join_step(pj):
        pass
    sess.join_commit(pj)
    results = {id(r.request): r for r in _drain(sess)}
    ref = _engine(registry, paged=paged, kv=kv, share=False)
    assert results[id(anchor)].tokens == ref.generate(anchor).tokens
    assert results[id(joiner)].tokens == ref.generate(joiner).tokens


@pytest.mark.parametrize(
    "paged,kv",
    [(False, None), (False, "int8"), (True, None), (True, "int8")],
    ids=["contig-bf16", "contig-int8", "paged-bf16", "paged-int8"],
)
def test_fresh_session_joiner_hits_cross_session(registry, paged, kv):
    """THE ISSUE-14 acceptance path on all four layouts: the publishing
    session CLOSES (its pool dies), a new session opens, and a joiner
    whose prompt shares the published prefix still hits — paged pools
    restore the spilled pages into the NEW pool and map them read-only
    (restore counter moves), contiguous sessions seed from the host
    slab — token-for-token equal to solo generate()."""
    eng = _engine(registry, paged=paged, kv=kv)
    plain = _engine(registry, paged=paged, kv=kv, share=False)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=24,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    _drain(sess)
    sess.close()
    # fresh session, fresh pool; anchor sized so the joiner fits
    a2 = GenerationRequest(
        "tiny", "x" * 170 + " new session anchor", max_new_tokens=24,
        stop_at_eos=False, seed=2,
    )
    sess2 = eng.decode_open([a2], reserve_rows=4)
    sess2.step(2)
    joiner = GenerationRequest(
        "tiny", SHARED + " cross-session tail", max_new_tokens=10, seed=7
    )
    hits0 = STORE_HITS_C.labels().value
    restores0 = STORE_RESTORES_C.labels().value
    assert sess2.can_join(joiner)
    pj = sess2.join_begin(joiner, chunk_tokens=32)
    assert pj.hit_tokens > 0, "no cross-session hit"
    assert STORE_HITS_C.labels().value == hits0 + 1
    if paged:
        assert pj.shared_pages >= 1, "store pages not mapped in new pool"
        assert STORE_RESTORES_C.labels().value > restores0
    while not sess2.join_step(pj):
        pass
    sess2.join_commit(pj)
    results = {id(r.request): r for r in _drain(sess2)}
    assert results[id(joiner)].tokens == plain.generate(joiner).tokens
    assert results[id(a2)].tokens == plain.generate(a2).tokens
    total = sess2.pool.n_pages if paged else None
    sess2.close()
    if paged:
        assert sess2.pool.free_pages == total - 1


def test_cow_copy_counted_and_shared_pages_gauge(registry):
    eng = _engine(registry)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=60,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    cow0 = PREFIX_COW_COPIES_C.labels().value
    sess.join(GenerationRequest("tiny", SHARED + " q", max_new_tokens=6, seed=2))
    # hit 142 tokens > 1 shared page * 128 -> the partial page was CoW'd
    assert PREFIX_COW_COPIES_C.labels().value == cow0 + 1
    assert PREFIX_SHARED_PAGES_G.labels().value >= 1
    _drain(sess)
    sess.close()
    assert PREFIX_SHARED_PAGES_G.labels().value == 0


def test_cancelled_sharer_restores_shared_refs_exactly(registry):
    """Cancellation (the disconnect/deadline retirement path) drops
    exactly one reference per mapped page — the ISSUE 6 exact page-free
    accounting composes with store sharing. The cancelled sharer never
    commits, so the store adopts nothing from it."""
    eng = _engine(registry)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=90,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(4)
    victim = GenerationRequest(
        "tiny", SHARED + " cancelled", max_new_tokens=60,
        stop_at_eos=False, seed=5,
    )
    ids = sess.tok.encode(victim.prompt)
    shared_page = eng.prefix_store.hbm_run("tiny", ids)[0]
    free0 = sess.pool.free_pages
    held0 = eng.prefix_store.hbm_pages_held
    refs0 = sess.pool.refcount(shared_page)
    sess.join(victim)
    # the one-shot join COMMITTED → its tail pages were adopted by the
    # store (page-backed publication); the mapping added one reference
    adopted = eng.prefix_store.hbm_pages_held - held0
    assert sess.pool.refcount(shared_page) == refs0 + 1
    sess.step(4)
    assert sess.cancel(victim)
    # cancel returns the row's OWN references; the store keeps its
    # adopted tail pages (that is the uncapped-publication point)
    assert sess.pool.free_pages == free0 - adopted
    assert sess.pool.refcount(shared_page) == refs0
    _drain(sess)
    sess.close()


def test_join_abort_restores_shared_refs(registry):
    eng = _engine(registry)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=60,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    free0 = sess.pool.free_pages
    pj = sess.join_begin(
        GenerationRequest("tiny", SHARED + " aborted", max_new_tokens=8, seed=6),
        chunk_tokens=32,
    )
    assert pj.shared_pages == 1 and sess.pool.free_pages < free0
    sess.join_abort(pj)
    assert sess.pool.free_pages == free0
    _drain(sess)
    sess.close()


def test_can_join_bills_shared_pages_once(registry):
    """Admission billing: with the free list squeezed to exactly the
    DIVERGENT-TAIL pages, a sharer still fits (its prefix pages are
    billed once, to the store) while an equal-shape non-sharer is
    deferred."""
    eng = _engine(registry)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=60,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sharer = GenerationRequest(
        "tiny", SHARED + " sq", max_new_tokens=8, seed=7
    )
    stranger = GenerationRequest(
        "tiny", "x" * 140 + " sq", max_new_tokens=8, seed=7
    )
    # same shape, same page need — only the prefix differs
    need = sess._pages_needed(145, 8)
    hog = sess.pool.alloc(sess.pool.free_pages - (need - 1))
    assert sess.can_join(sharer)  # needs need-1 own pages (1 shared)
    assert not sess.can_join(stranger)  # needs all `need` pages
    sess.pool.free(hog)
    _drain(sess)
    sess.close()


def test_joiner_tail_pages_published_for_second_generation():
    """ISSUE 14 retires PR 7's page cap: a joiner's commit publishes
    its own divergent-tail pages, so a SECOND-generation sharer
    matching the longer prompt maps MORE pages than the anchor-only
    match would give — not just more seeded tokens."""
    wide = {"tiny": get_model_config("qwen2:1.5b").tiny(max_seq_len=1024)}
    eng = _engine(wide)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=90,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(2)
    # long enough that j1's divergent tail itself spans a full page
    # (262 ids: full pages [1, 2) are PAST the anchor's shared page)
    long_tail = SHARED + " stage " + "t" * 110
    j1 = GenerationRequest("tiny", long_tail + " one", max_new_tokens=6, seed=2)
    sess.join(j1)
    j2 = GenerationRequest("tiny", long_tail + " two", max_new_tokens=6, seed=3)
    pj = sess.join_begin(j2, chunk_tokens=32)
    assert pj.hit_tokens > 142  # seeded past the anchor's common prefix
    assert pj.shared_pages >= 2  # j1's tail page mapped too (uncapped)
    while not sess.join_step(pj):
        pass
    sess.join_commit(pj)
    results = {id(r.request): r for r in _drain(sess)}
    ref = _engine(wide, share=False)
    for r in (j1, j2):
        assert results[id(r)].tokens == ref.generate(r).tokens
    sess.close()


def test_contiguous_store_survives_close(registry):
    eng = _engine(registry, paged=False)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=24,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    store_state = sess.debug_state()["prefix_store"]
    assert store_state["nodes"] == 1
    assert store_state["hbm_pages"] == 0  # contiguous: seed-only nodes
    _drain(sess)
    sess.close()
    # the ENGINE store outlives the session (the ISSUE 14 point)
    assert eng.prefix_store.debug_state()["nodes"] == 1
    assert eng.prefix_store.debug_state()["host_bytes"] > 0


def test_prefix_share_off_is_default_and_inert(registry):
    eng = JaxEngine(registry=dict(registry), dtype=jnp.float32, paged_kv=True)
    assert eng.prefix_share is False
    assert eng.prefix_store is None
    sess = eng.decode_open(
        [GenerationRequest("tiny", SHARED + " a", max_new_tokens=6, seed=1)]
    )
    assert sess.store is None
    assert "prefix_store" not in sess.debug_state()
    _drain(sess)
    sess.close()


def test_max_admission_rows_bills_shared_prefix_once(registry, monkeypatch):
    """The budget-aware admission estimate admits a LARGER fleet under
    prefix sharing: sharers are billed only their divergent-tail pages,
    so the same KV budget caps more rows."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import (
        jax_engine as je,
    )

    req = GenerationRequest(
        "tiny", "s" * 600, max_new_tokens=8, stop_at_eos=False
    )
    share_eng = _engine(registry)
    plain_eng = _engine(registry, share=False)
    cfg = share_eng.registry["tiny"]
    # 601 prompt ids + 8 generation tokens -> 5 legacy pages per row;
    # 4 of them shared. Budget sized to EXACTLY the shared bill of one
    # 64-row chunk (anchor pays 5, every sharer 1): the full bill
    # (64 x 5 pages) blows it and stays at the 32-row floor.
    need = -(-(601 + 8) // 128)
    g_bucket = je._bucket(8, je.GEN_BUCKETS)
    budget = plain_eng._paged_chunk_bytes(
        cfg, [need] + [1] * 63, 64, g_bucket, False
    )
    monkeypatch.setattr(je, "BATCH_KV_BUDGET_BYTES", int(budget))
    assert plain_eng.max_admission_rows(req) == 32  # full bill: floor
    assert share_eng.max_admission_rows(req) == 64  # shared billed once


def test_engine_validates_prefix_knobs(registry):
    with pytest.raises(ValueError, match="prefix_index_entries"):
        JaxEngine(registry=dict(registry), prefix_index_entries=0)
    with pytest.raises(ValueError, match="prefix_store_hbm_bytes"):
        JaxEngine(registry=dict(registry), prefix_store_hbm_bytes=-1)
    with pytest.raises(ValueError, match="prefix_store_host_bytes"):
        JaxEngine(registry=dict(registry), prefix_store_host_bytes=-1)
    with pytest.raises(ValueError, match="scope"):
        JaxEngine(
            registry=dict(registry),
            prefix_share=True,
            prefix_store_scope="both",
        )


# -- store × preemption interaction (ISSUE 14 satellite) -----------------------


def test_preempted_sharer_releases_and_reshares_store_pages(registry):
    """A victim whose row maps store-shared pages preempts correctly:
    the shared pages are RELEASED (never swapped — the store and other
    readers keep them device-resident), its own pages spill, and the
    resume re-shares the same store pages — the continued stream is
    bit-identical to an uninterrupted run."""
    eng = _engine(registry)
    plain = _engine(registry, share=False)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=90,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(2)
    victim = GenerationRequest(
        "tiny", SHARED + " victim tail", max_new_tokens=24,
        stop_at_eos=False, seed=5,
    )
    sess.join(victim)
    sess.step(4)
    free_mid = sess.pool.free_pages
    shared_page = sess.rows[
        next(r for r, row in enumerate(sess.rows)
             if row is not None and row.request is victim)
    ].pages[0]
    refs_live = sess.pool.refcount(shared_page)
    pr = sess.preempt(victim, policy="swap")
    assert pr is not None
    assert pr.shared_pages == [shared_page]
    # the shared page was released (one ref down), own pages swapped out
    assert sess.pool.refcount(shared_page) == refs_live - 1
    assert pr.blob is not None and pr.n_own_pages >= 1
    sess.step(2)
    assert sess.can_resume(pr)
    pending = sess.resume_begin(pr)
    while not sess.join_step(pending):
        pass
    sess.join_commit(pending)
    assert sess.pool.refcount(shared_page) == refs_live  # re-shared
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(victim)].tokens == plain.generate(victim).tokens
    assert free_mid  # silence lint; the real invariant is parity above
    sess.close()


def test_preempt_resume_degrades_to_recompute_after_store_eviction(registry):
    """Eviction-degrades-to-recompute: while the victim is parked the
    store's tree for its prefix is dropped — the resume plan can no
    longer verify the released shared pages and falls back to a full
    re-prefill, still token-exact."""
    eng = _engine(registry)
    plain = _engine(registry, share=False)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor", max_new_tokens=90,
        stop_at_eos=False, seed=1,
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(2)
    victim = GenerationRequest(
        "tiny", SHARED + " victim tail", max_new_tokens=24,
        stop_at_eos=False, seed=5,
    )
    sess.join(victim)
    sess.step(4)
    pr = sess.preempt(victim, policy="swap")
    assert pr is not None and pr.shared_pages
    # the store moves on: every node evicted (refs released)
    eng.prefix_store.release_all()
    plan = sess._resume_plan(pr)
    assert plan is not None and plan["mode"] == "recompute"
    assert sess.can_resume(pr)
    pending = sess.resume_begin(pr)
    while not sess.join_step(pending):
        pass
    sess.join_commit(pending)
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(victim)].tokens == plain.generate(victim).tokens
    sess.close()
    assert sess.pool.free_pages == sess.pool.n_pages - 1


def test_stacked_session_with_a_store_keeps_the_table_naming(
    registry, monkeypatch
):
    """With a prefix store one pool page sits in several rows' tables,
    so no page has ONE owner: the stacked session's step compiles the
    XLA parts path with its pages gathered through the table (the step
    it compiled before the pool naming existed; ``impl: xla``), builds
    no inverse table, and sharers of a page decode their solo tokens."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention as ppa
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    def never(*a, **k):
        raise AssertionError("a session with a store built page owners")

    monkeypatch.setattr(ppa, "pool_page_owners", never)
    eng = _engine(registry, decode_attention=pallas_decode_attention)
    plain = _engine(registry, share=False)
    anchor = GenerationRequest(
        "tiny", SHARED + " anchor tail", max_new_tokens=40,
        stop_at_eos=False, seed=1,
    )
    sharer = GenerationRequest("tiny", SHARED + " sharer", max_new_tokens=10)
    sess = eng.decode_open([anchor], reserve_rows=4)
    assert sess.stacked and sess.store is not None
    assert sess.debug_state()["attention"]["impl"] == "xla"
    sess.step(4)
    sess.join(sharer)
    shared_page = sess.rows[0].pages[0]
    assert sum(shared_page in r.pages for r in sess.rows if r) == 2
    results = {id(r.request): r for r in _drain(sess)}
    sess.close()
    for req in (anchor, sharer):
        assert results[id(req)].tokens == plain.generate(req).tokens
    keys = [k for k in eng._decode_cache if k[0] == "paged-step"]
    assert keys and all(k[-1] is True for k in keys)
