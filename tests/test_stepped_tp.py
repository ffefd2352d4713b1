"""Sharded stepped decode sessions on the forced-host mesh (ISSUE 8).

The continuous scheduler's engine half (`engine/stepped.py`) carries one
explicit SPMD pytree; these tests pin that the SAME session code is
device-count-agnostic: on a 2- and an 8-device tensor-parallel mesh
(virtual CPU devices — conftest forces 8), every row's token stream is
bit-identical to its solo ``generate()`` on all four cache layouts,
mid-flight joiners and shared-prefix joiners included; cancellation
restores the pool free count EXACTLY (the PR-6 invariant, now on sharded
rows); and the carry's declared shardings survive stepping — KV payload
over heads, row control replicated.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
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

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def _tiny8():
    """A tiny config whose head/ff dims divide tp ∈ {2, 8} (the
    test_parallel.py convention)."""
    return dataclasses.replace(
        get_model_config("mistral:7b").tiny(),
        n_heads=8,
        n_kv_heads=8,
        d_ff=128,
        d_model=64,
        d_head=16,
        max_seq_len=1024,  # room for the ≥1-full-page shared prefix
    )


@pytest.fixture(scope="module")
def registry():
    return {"tiny": _tiny8()}


def _tp_engine(registry, n_devices, **kwargs):
    mesh = build_mesh(
        MeshSpec.tp_only(), devices=jax.devices()[:n_devices]
    )
    return TensorParallelEngine(
        mesh=mesh, registry=dict(registry), dtype=jnp.float32, **kwargs
    )


def _drain(session, max_steps=8, limit=300):
    out = []
    for _ in range(limit):
        if not session.active:
            break
        out.extend(session.step(max_steps))
    assert not session.active, "session did not drain"
    return out


LAYOUTS = [
    pytest.param(False, None, id="contiguous-bf16"),
    pytest.param(False, "int8", id="contiguous-int8kv"),
    pytest.param(True, None, id="paged-bf16"),
    pytest.param(True, "int8", id="paged-int8kv"),
]


@pytest.mark.parametrize("n_devices", [2, 8])
@pytest.mark.parametrize("paged,kv", LAYOUTS)
def test_tp_stepped_parity_with_mid_flight_join(registry, n_devices, paged, kv):
    """The acceptance matrix: 4 cache layouts × {2, 8}-device mesh, a
    mid-flight joiner included — every row token-identical to its own
    solo generate() on the same sharded engine."""
    eng = _tp_engine(registry, n_devices, paged_kv=paged, kv_quantize=kv)
    anchor = GenerationRequest(
        "tiny", "anchor runs long on the mesh", max_new_tokens=24,
        stop_at_eos=False,
    )
    short = GenerationRequest(
        "tiny", "short companion", max_new_tokens=6, seed=2
    )
    joiner = GenerationRequest(
        "tiny", "late arrival joins mid-flight", max_new_tokens=10, seed=3
    )
    solo = {id(r): eng.generate(r) for r in (anchor, short, joiner)}
    sess = eng.decode_open([anchor, short], reserve_rows=4)
    sess.step(4)  # anchor mid-flight
    assert sess.can_join(joiner)
    sess.join(joiner)
    results = {id(r.request): r for r in _drain(sess)}
    for req in (anchor, short, joiner):
        assert results[id(req)].tokens == solo[id(req)].tokens, (
            f"row diverged on tp={n_devices} paged={paged} kv={kv}"
        )
    sess.close()


def test_tp_carry_shardings_declared_and_stable(registry):
    """The tentpole's contract, directly: KV payload leaves shard over
    the heads axis, row-control leaves replicate, and one compiled
    slice step returns the carry with the SAME placements (explicit
    out_shardings — no silent reshard, no host bounce)."""
    from jax.sharding import PartitionSpec as P

    eng = _tp_engine(registry, 8, paged_kv=True)
    sess = eng.decode_open(
        [
            GenerationRequest(
                "tiny", "sharding probe", max_new_tokens=20,
                stop_at_eos=False,
            )
        ],
        reserve_rows=2,
    )

    def specs():
        out = {}
        for key, leaf in sess.carry.items():
            arr = leaf["q"] if isinstance(leaf, dict) else leaf
            out[key] = arr.sharding.spec
        return out

    before = specs()
    assert before["pool_k"] == P(None, None, "tp", None, None)
    assert before["pool_v"] == P(None, None, "tp", None, None)
    for key in ("tokens", "done", "remaining", "table", "presence"):
        assert before[key] == P(), key
    sess.step(4)
    assert specs() == before  # one slice later: placements unchanged
    # per-device accounting reflects the head shard: each of the 8
    # devices holds 1/8 of the pool payload
    state = sess.debug_state()
    assert state["mesh"]["devices"] == 8
    assert state["mesh"]["axes"] == {"tp": 8}
    pool_leaf = sess.carry["pool_k"]
    total = pool_leaf.nbytes + sess.carry["pool_v"].nbytes
    assert state["pool"]["per_device"]["bytes"] == total // 8
    sess.close()


def test_tp_carry_falls_back_to_replicated_kv(registry):
    """Heads that don't divide the mesh replicate the KV payload — the
    documented fallback keeps the session correct (and the debug
    surface honest) instead of crashing the mesh."""
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(_tiny8(), n_heads=6, n_kv_heads=3, d_ff=128)
    eng = _tp_engine({"tiny3": cfg}, 2, paged_kv=True)
    req = GenerationRequest(
        "tiny3", "odd heads", max_new_tokens=16, stop_at_eos=False
    )
    joiner = GenerationRequest(
        "tiny3", "replicated joiner", max_new_tokens=6, seed=4
    )
    solo = eng.generate(req)
    solo_joiner = eng.generate(joiner)
    sess = eng.decode_open([req], reserve_rows=2)
    arr = sess.carry["pool_k"]
    assert arr.sharding.spec == P(None, None, None, None, None)
    sess.step(4)
    # the regression that shipped this assert: a JOIN's eager page
    # scatter let GSPMD re-shard the replicated pool, and the next
    # slice's explicit in_shardings rejected the arg — _recommit_carry
    # re-pins the placement after every host-side mutation batch
    sess.join(joiner)
    assert sess.carry["pool_k"].sharding.spec == P(
        None, None, None, None, None
    )
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(req)].tokens == solo.tokens
    assert results[id(joiner)].tokens == solo_joiner.tokens
    sess.close()


@pytest.mark.parametrize(
    "heads,devices,paged,kv",
    [
        pytest.param(8, 8, True, None, id="paged-sharded-pool"),
        pytest.param(8, 8, True, "int8", id="paged-int8kv"),
        pytest.param(3, 2, True, None, id="paged-replicated-pool"),
        pytest.param(8, 8, False, None, id="contiguous"),
    ],
)
def test_tp_join_leaves_every_leaf_where_it_is_declared(
    registry, monkeypatch, heads, devices, paged, kv
):
    """The row install is one program whose output shardings are the
    carry's declared ones: after a join every leaf sits on its declared
    sharding BEFORE anything re-places it, a ``_recommit_carry`` then
    has nothing to move (it hands back the very same arrays), and the
    join itself re-places nothing that the program wrote."""
    cfg = dataclasses.replace(_tiny8(), n_heads=2 * heads, n_kv_heads=heads)
    eng = _tp_engine({"tiny": cfg}, devices, paged_kv=paged, kv_quantize=kv)
    anchor = GenerationRequest(
        "tiny", "anchor runs long on the mesh", max_new_tokens=24,
        stop_at_eos=False,
    )
    joiner = GenerationRequest(
        "tiny", "late arrival joins mid-flight", max_new_tokens=10, seed=3
    )
    solo = eng.generate(joiner)
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(4)
    pending = sess.join_begin(joiner)
    while not sess.join_step(pending):
        pass
    recommits = []
    recommit = sess._recommit_carry
    monkeypatch.setattr(
        sess, "_recommit_carry", lambda: (recommits.append(1), recommit())
    )
    sess.join_commit(pending)
    # the one re-pin runs BEFORE the program (for what the host wrote
    # eagerly: nothing here); none after it
    assert recommits == [1]
    declared = eng._stepped_carry_shardings(cfg, sess.carry)
    leaves = jax.tree.leaves(sess.carry)
    for leaf, want in zip(leaves, jax.tree.leaves(declared)):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
            leaf.shape, leaf.sharding, want,
        )
    shardings = [leaf.sharding for leaf in leaves]
    recommit()
    after = jax.tree.leaves(sess.carry)
    assert [leaf.sharding for leaf in after] == shardings
    assert all(a is b for a, b in zip(after, leaves))  # nothing moved
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(joiner)].tokens == solo.tokens
    sess.close()


def test_tp_shared_prefix_joiner_parity_and_exact_restoration(registry):
    """Shared-prefix CoW paging composes on the mesh: the joiner maps
    read-only head-sharded prefix pages, chunk-prefills only the
    divergent tail, stays solo-identical — and retirement + close()
    restore the pool free count exactly (refcounted pages, PR 7)."""
    eng = _tp_engine(registry, 8, paged_kv=True, prefix_share=True)
    # ≥1 FULL 128-token page of shared prefix (character tokenizer —
    # the test_prefix.py convention)
    prefix = "s" * 140 + " "
    anchor = GenerationRequest(
        "tiny", prefix + "anchor question", max_new_tokens=24,
        stop_at_eos=False,
    )
    sharer = GenerationRequest(
        "tiny", prefix + "different tail", max_new_tokens=8, seed=5
    )
    solo_sharer = eng.generate(sharer)
    sess = eng.decode_open([anchor], reserve_rows=4)
    sess.step(2)
    assert sess.can_join(sharer)
    free_before_join = sess.pool.free_pages
    pj = sess.join_begin(sharer)
    assert pj.hit_tokens > 0, "joiner did not hit the published prefix"
    assert pj.shared_pages > 0, "no pool pages were mapped read-only"
    # only the divergent tail came off the free list — the shared page
    # is a refcounted read-only mapping, billed once
    assert free_before_join - sess.pool.free_pages < len(pj.pages)
    while not sess.join_step(pj):
        pass
    sess.join_commit(pj)
    assert sess.pool.shared_pages > 0  # live CoW mapping on the mesh
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(sharer)].tokens == solo_sharer.tokens
    # exact restoration: close releases rows, then index refs LAST —
    # every refcount reaches zero and only the parking page stays out
    sess.close()
    assert sess.pool.free_pages == sess.pool.n_pages - 1


@pytest.mark.parametrize("n_devices", [2, 8])
def test_tp_spec_session_parity_and_draft_sharding(registry, n_devices):
    """ISSUE 9 on the mesh: a speculating stepped session — draft KV
    leaves in the SPMD carry, sharded by the DRAFT model's own heads —
    emits the plain greedy stream for every row incl. a mid-flight
    joiner, and the declared carry placements survive stepping."""
    from jax.sharding import PartitionSpec as P

    draft_cfg = dataclasses.replace(_tiny8(), n_layers=1)
    reg = {"tiny": _tiny8(), "tiny-d": draft_cfg}
    eng = _tp_engine(
        reg, n_devices, paged_kv=True,
        speculative={"tiny": ("tiny-d", 3)},
    )
    anchor = GenerationRequest(
        "tiny", "mesh anchor runs long", max_new_tokens=24,
        stop_at_eos=False,
    )
    joiner = GenerationRequest(
        "tiny", "late mesh arrival", max_new_tokens=10, seed=3
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    assert sess.spec is not None
    # draft payload sharded over the DRAFT's heads (8 % tp == 0),
    # per-row spec state replicated
    assert sess.carry["draft_k"].sharding.spec == P(
        None, None, "tp", None, None
    )
    for key in ("draft_offsets", "spec_rounds", "spec_accepted"):
        assert sess.carry[key].sharding.spec == P(), key
    # ISSUE 10: the kernel-less native verify's scratch leaves ride the
    # SPMD carry as KV payload — [L,B,Hkv,k+1,Dh], heads over tp
    assert not sess.stacked  # no kernel on the forced-host mesh
    for key in ("scratch_k", "scratch_v"):
        assert sess.carry[key].sharding.spec == P(
            None, None, "tp", None, None
        ), key
    before = {
        key: leaf.sharding.spec
        for key, leaf in sess.carry.items()
        if not isinstance(leaf, dict)
    }
    sess.step(4)
    assert sess.can_join(joiner)
    sess.join(joiner)
    results = {id(r.request): r for r in _drain(sess)}
    after = {
        key: leaf.sharding.spec
        for key, leaf in sess.carry.items()
        if not isinstance(leaf, dict)
    }
    assert after == before  # placements stable across spec slices + join
    for req in (anchor, joiner):
        assert results[id(req)].tokens == eng._generate_plain(req).tokens, (
            f"spec row diverged on tp={n_devices}"
        )
        assert results[id(req)].extras["spec"]["rounds"] >= 1
    sess.close()
    assert sess.pool.free_pages == sess.pool.n_pages - 1


@pytest.mark.parametrize("n_devices", [2, 8])
def test_tp_spec_stacked_native_verify_on_mesh(registry, n_devices):
    """ISSUE 10 × ISSUE 8: the STACKED native verify on a mesh — the
    multi-query parts kernel runs under shard_map with heads sharded
    and the verify's candidates in the head-sharded side caches; the
    speculating session stays plain-greedy identical and bills
    prompt-only pages. Kernels are enabled by patching the gate (the
    forced-host mesh has no TPU), which leaves the draft's contiguous
    decode kernel-free as production would."""
    draft_cfg = dataclasses.replace(_tiny8(), n_layers=1)
    reg = {"tiny": _tiny8(), "tiny-d": draft_cfg}
    eng = _tp_engine(
        reg, n_devices, paged_kv=True,
        speculative={"tiny": ("tiny-d", 3)},
    )
    eng._specialised_kernels_enabled = lambda: True  # engage the wrapper
    exp = _tp_engine(reg, n_devices, paged_kv=True)
    anchor = GenerationRequest(
        "tiny", "stacked mesh anchor", max_new_tokens=20,
        stop_at_eos=False,
    )
    joiner = GenerationRequest(
        "tiny", "stacked mesh joiner", max_new_tokens=8, seed=3
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    assert sess.spec is not None and sess.stacked
    assert sess._pages_needed(100, 40) == -(-100 // 128)  # prompt-only
    sess.step(2)
    assert sess.can_join(joiner)
    sess.join(joiner)
    results = {id(r.request): r for r in _drain(sess)}
    for req in (anchor, joiner):
        assert results[id(req)].tokens == exp._generate_plain(req).tokens
        assert results[id(req)].extras["spec"]["rounds"] >= 1
    sess.close()
    assert sess.pool.free_pages == sess.pool.n_pages - 1


def test_tp_cancel_restores_free_count_exactly(registry):
    """PR-6's cancellation invariant on SHARDED rows (the ROADMAP
    follow-on): cancel() parks the table row and frees the victim's
    pages mid-flight with exact free-count restoration, and the
    surviving anchor decodes on, unperturbed, to its solo stream."""
    eng = _tp_engine(registry, 8, paged_kv=True)
    anchor = GenerationRequest(
        "tiny", "anchor", max_new_tokens=40, stop_at_eos=False
    )
    victim = GenerationRequest(
        "tiny", "victim row to cancel", max_new_tokens=40,
        stop_at_eos=False, seed=3,
    )
    solo_anchor = eng.generate(anchor)
    sess = eng.decode_open([anchor], reserve_rows=4)
    free_before_join = sess.pool.free_pages
    sess.step(4)
    sess.join(victim)
    victim_pages = next(
        row.pages
        for row in sess.rows
        if row is not None and row.request is victim
    )
    assert sess.pool.free_pages == free_before_join - len(victim_pages)
    sess.step(4)
    assert sess.cancel(victim)
    assert sess.pool.free_pages == free_before_join
    assert sess.active == 1
    results = _drain(sess)
    assert results[0].tokens == solo_anchor.tokens
    sess.close()


# -- tp×dp in-mesh row sharding (ISSUE 19) -------------------------------------


def _dp_engine(registry, dp, tp, **kwargs):
    mesh = build_mesh(
        MeshSpec.dp_tp(dp, tp), devices=jax.devices()[: dp * tp]
    )
    return TensorParallelEngine(
        mesh=mesh, registry=dict(registry), dtype=jnp.float32, **kwargs
    )


@pytest.mark.parametrize("paged,kv", LAYOUTS)
def test_dp_stepped_parity_all_layouts(registry, paged, kv):
    """The ISSUE-19 acceptance matrix: a 2×2 tp×dp mesh (4 virtual
    devices), all four cache layouts — every row, a mid-flight joiner
    included, emits the token stream bit-identical to its solo
    generate() on the SAME dp-sharded engine."""
    eng = _dp_engine(registry, 2, 2, paged_kv=paged, kv_quantize=kv)
    anchor = GenerationRequest(
        "tiny", "dp anchor runs long on the mesh", max_new_tokens=24,
        stop_at_eos=False,
    )
    short = GenerationRequest(
        "tiny", "dp short companion", max_new_tokens=6, seed=2
    )
    joiner = GenerationRequest(
        "tiny", "dp late joiner lands here", max_new_tokens=10,
        seed=3,
    )
    solo = {id(r): eng.generate(r) for r in (anchor, short, joiner)}
    sess = eng.decode_open([anchor, short], reserve_rows=4)
    assert sess.dp_shards == 2, "dp never engaged on the 2x2 mesh"
    sess.step(4)
    assert sess.can_join(joiner)
    sess.join(joiner)
    results = {id(r.request): r for r in _drain(sess)}
    for req in (anchor, short, joiner):
        assert results[id(req)].tokens == solo[id(req)].tokens, (
            f"row diverged on dp=2 tp=2 paged={paged} kv={kv}"
        )
    sess.close()


@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 4), (4, 2)])
def test_dp_mesh_shapes_paged_parity(registry, dp, tp):
    """Mesh-shape sweep on the paged layout: pure-dp (4×1), wide-tp
    (2×4) and the full 8-device 4×2 — the same session code engages
    whatever dp the mesh offers and stays solo-identical."""
    eng = _dp_engine(registry, dp, tp, paged_kv=True)
    reqs = [
        GenerationRequest(
            "tiny", f"dp sweep row {i}", max_new_tokens=12, seed=i + 1,
            stop_at_eos=False,
        )
        for i in range(3)
    ]
    solo = {id(r): eng.generate(r) for r in reqs}
    sess = eng.decode_open(reqs, reserve_rows=4)
    assert sess.dp_shards == dp
    results = {id(r.request): r for r in _drain(sess)}
    for req in reqs:
        assert results[id(req)].tokens == solo[id(req)].tokens, (
            f"row diverged on dp={dp} tp={tp}"
        )
    sess.close()
    assert sess.pool.free_pages == sess.pool.n_pages - sess.dp_shards


def test_dp_carry_shardings_declared_and_stable(registry):
    """The dp contract, directly: payload leaves gain a 'dp' row/page
    axis next to the tp head axis, row-control leaves shard their
    leading row dim over dp instead of replicating, and one compiled
    slice step returns the carry with the SAME placements."""
    from jax.sharding import PartitionSpec as P

    eng = _dp_engine(registry, 2, 2, paged_kv=True)
    sess = eng.decode_open(
        [
            GenerationRequest(
                "tiny", "dp sharding probe", max_new_tokens=20,
                stop_at_eos=False,
            )
        ],
        reserve_rows=4,
    )
    assert sess.dp_shards == 2

    def specs():
        out = {}
        for key, leaf in sess.carry.items():
            arr = leaf["q"] if isinstance(leaf, dict) else leaf
            out[key] = arr.sharding.spec
        return out

    before = specs()
    # pool payload: page dim over dp, heads over tp
    assert before["pool_k"] == P(None, "dp", "tp", None, None)
    assert before["pool_v"] == P(None, "dp", "tp", None, None)
    # row control: leading row dim over dp (no longer replicated)
    for key in ("tokens", "done", "remaining", "table", "presence"):
        assert before[key][0] == "dp", (key, before[key])
    sess.step(4)
    assert specs() == before  # one slice later: placements unchanged
    state = sess.debug_state()
    assert state["mesh"]["devices"] == 4
    assert state["mesh"]["axes"] == {"dp": 2, "tp": 2}
    sess.close()


def test_dp_per_shard_parking_and_page_locality(registry):
    """The host allocator mirrors the GSPMD split: each dp shard keeps
    its OWN parking page, and a row's pages come from the page range
    its shard owns (best-effort locality — spillover is allowed, the
    preference is what's pinned here on an empty pool)."""
    eng = _dp_engine(registry, 2, 2, paged_kv=True)
    reqs = [
        GenerationRequest(
            "tiny", f"locality row {i}", max_new_tokens=8, seed=i + 1
        )
        for i in range(4)
    ]
    sess = eng.decode_open(reqs, reserve_rows=4)
    assert sess.dp_shards == 2
    assert len(sess.parking_pages) == 2
    half = sess.pool.n_pages // 2
    shard_of = lambda p: 0 if p < half else 1  # noqa: E731
    # parking pages live one per shard
    assert sorted(shard_of(p) for p in sess.parking_pages) == [0, 1]
    # every live row's pages sit on the shard that owns the row slot
    for r, row in enumerate(sess.rows):
        if row is None:
            continue
        want = sess._row_shard(r)
        assert all(shard_of(p) == want for p in row.pages), (
            r, want, row.pages,
        )
    # cancellation hands the pages back and keeps the exact-free
    # invariant on the sharded pool
    free_before = sess.pool.free_pages
    victim = next(row for row in sess.rows if row is not None)
    pages = len(victim.pages)
    assert sess.cancel(victim.request)
    assert sess.pool.free_pages == free_before + pages
    sess.close()


def test_dp_mid_flight_join_lands_on_row_shard(registry):
    """A mid-flight joiner on the dp mesh allocates its pages on the
    shard owning its seat — the join path routes through the same
    shard-preferred allocator as open — and still matches solo."""
    eng = _dp_engine(registry, 2, 2, paged_kv=True)
    anchor = GenerationRequest(
        "tiny", "dp join anchor", max_new_tokens=20, stop_at_eos=False
    )
    joiner = GenerationRequest(
        "tiny", "dp joiner lands sharded", max_new_tokens=8, seed=5
    )
    solo_joiner = eng.generate(joiner)
    sess = eng.decode_open([anchor], reserve_rows=4)
    assert sess.dp_shards == 2
    sess.step(2)
    sess.join(joiner)
    half = sess.pool.n_pages // 2
    r, row = next(
        (r, row)
        for r, row in enumerate(sess.rows)
        if row is not None and row.request is joiner
    )
    want = sess._row_shard(r)
    assert all(
        (0 if p < half else 1) == want for p in row.pages
    ), (r, want, row.pages)
    results = {id(r_.request): r_ for r_ in _drain(sess)}
    assert results[id(joiner)].tokens == solo_joiner.tokens
    sess.close()


def test_dp_indivisible_bucket_falls_back_to_tp_only(registry):
    """A bucket width that does not divide dp must NOT engage row
    sharding (the stepped_carry_shardings divisibility fallback) — the
    session still serves, tp-only, instead of crashing the mesh."""
    from jax.sharding import PartitionSpec as P

    eng = _dp_engine(registry, 4, 2, paged_kv=True)
    req = GenerationRequest(
        "tiny", "bucket of two on dp four", max_new_tokens=8
    )
    solo = eng.generate(req)
    # b_bucket=2 (one row + reserve 1 → bucket 2) does not divide dp=4
    sess = eng.decode_open([req], reserve_rows=1)
    assert sess.b_bucket % 4 != 0
    assert sess.dp_shards == 1
    assert sess.carry["tokens"].sharding.spec == P()
    results = _drain(sess)
    assert results[0].tokens == solo.tokens
    sess.close()


def test_dp_continuous_scheduler_serves_sharded_rows(registry):
    """The serve plumbing end-to-end in-process: a tp×dp engine behind
    the continuous scheduler (what ``serve --backend jax-tp --tp N
    --dp M`` builds) admits staggered rows, steps them on the sharded
    session and retires both with solo-identical streams."""
    import threading
    import time

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )

    eng = _dp_engine(registry, 2, 2, paged_kv=True)
    r1 = GenerationRequest(
        "tiny", "sched dp row one", max_new_tokens=10, stop_at_eos=False
    )
    r2 = GenerationRequest(
        "tiny", "sched dp row two", max_new_tokens=8, seed=2
    )
    solo = {id(r): eng.generate(r) for r in (r1, r2)}
    sched = ContinuousScheduler(eng, slice_steps=2)
    sched.start()
    try:
        done = {}

        def run(req):
            done[id(req)] = sched.submit(req)

        threads = [
            threading.Thread(target=run, args=(r,)) for r in (r1, r2)
        ]
        threads[0].start()
        time.sleep(0.05)
        threads[1].start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "dp scheduler row hung"
        for req in (r1, r2):
            assert done[id(req)].tokens == solo[id(req)].tokens
        assert sched.debug_state()["backend_mesh"]["axes"] == {
            "dp": 2,
            "tp": 2,
        }
    finally:
        sched.stop()


def test_tp_deadline_reap_through_continuous_scheduler(registry):
    """Deadline reaping propagates into the sharded session: a
    mid-flight ``deadline_ms`` expiry retires the row through the
    continuous scheduler's reap sweep (session.cancel on the mesh) and
    the caller fails with DeadlineExceeded — not a hang, not a stuck
    slot."""
    import threading

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.stream import (
        DeadlineExceeded,
    )

    eng = _tp_engine(registry, 2, paged_kv=True)
    # warm the compiled shapes so the deadline races decode, not XLA
    warm = GenerationRequest(
        "tiny", "warm", max_new_tokens=200, stop_at_eos=False
    )
    sess = eng.decode_open([warm], reserve_rows=2)
    sess.step(2)
    sess.close()
    sched = ContinuousScheduler(eng, slice_steps=2)
    sched.start()
    try:
        doomed = GenerationRequest(
            "tiny", "doomed long row", max_new_tokens=200,
            stop_at_eos=False, deadline_ms=300.0,
        )
        errs = {}

        def run():
            try:
                sched.submit(doomed)
            except BaseException as exc:  # noqa: BLE001
                errs["exc"] = exc

        t = threading.Thread(target=run)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), "deadline-doomed request hung"
        assert isinstance(errs.get("exc"), DeadlineExceeded), errs
        # the session closed behind the reaped row: the scheduler's
        # debug surface shows no live session holding mesh state
        assert sched.debug_state()["backend_mesh"]["devices"] == 2
    finally:
        sched.stop()
