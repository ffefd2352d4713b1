"""Batched speculative decoding inside stepped decode sessions (ISSUE 9).

The acceptance mechanics under test: per slice, every live row drafts k
tokens then ONE target forward scores its k+1 candidate positions, and
rows advance by their own longest-accepted-prefix length m ∈ [1, k+1] —
so retirement, EOS clipping, budgets, joins and page accounting all move
at per-row variable stride. Parity discipline is the usual one: every
row's stream must be bit-identical to plain greedy decode on the same
engine configuration (float32 pins, per the numerics caveat in
engine/speculative.py), whatever the cache layout.
"""

import dataclasses

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
    tiny = get_model_config("qwen2:1.5b").tiny(max_seq_len=1024)
    return {
        "tiny": tiny,
        # a genuinely different (weaker) draft exercises the rejection
        # path; same vocab by construction
        "tiny-d": dataclasses.replace(tiny, n_layers=1),
        # an alias of the target config: identical seeded weights, so
        # every draft is accepted — the acceptance-friendly arm
        "tiny-same": tiny,
    }


@pytest.fixture(scope="module")
def plain(registry):
    return JaxEngine(registry=dict(registry), dtype=jnp.float32)


def _spec_engine(registry, draft="tiny-d", k=3, **kwargs):
    return JaxEngine(
        registry=dict(registry),
        dtype=jnp.float32,
        speculative={"tiny": (draft, k)},
        **kwargs,
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
    pytest.param(False, None, id="contig-bf16"),
    pytest.param(False, "int8", id="contig-int8"),
    pytest.param(True, None, id="paged-bf16"),
    pytest.param(True, "int8", id="paged-int8"),
]


@pytest.mark.parametrize("paged,kv", LAYOUTS)
def test_spec_session_parity_all_layouts_with_join(registry, paged, kv):
    """The tentpole invariant: a speculating session — mid-flight joiner
    included — emits exactly the plain greedy stream of the same engine
    configuration, on all four cache layouts (int8 target KV composes:
    the former kv_quantize × speculative exclusion is retired)."""
    eng = _spec_engine(registry, paged_kv=paged, kv_quantize=kv)
    exp = JaxEngine(
        registry=dict(registry), dtype=jnp.float32,
        paged_kv=paged, kv_quantize=kv,
    )
    reqs = [
        GenerationRequest("tiny", "alpha prompt", max_new_tokens=12),
        GenerationRequest(
            "tiny", "the longer second row runs on", max_new_tokens=24,
            stop_at_eos=False, seed=2,
        ),
    ]
    sess = eng.decode_open(reqs, reserve_rows=4)
    assert sess.spec is not None, "session did not speculate"
    sess.step(4)
    joiner = GenerationRequest("tiny", "late joiner", max_new_tokens=10, seed=3)
    assert sess.can_join(joiner)
    sess.join(joiner)
    results = {id(r.request): r for r in _drain(sess)}
    for r in reqs + [joiner]:
        assert results[id(r)].tokens == exp._generate_plain(r).tokens, (
            f"diverged: paged={paged} kv={kv} prompt={r.prompt!r}"
        )
        spec = results[id(r)].extras["spec"]
        assert spec["k"] == 3 and spec["draft_model"] == "tiny-d"
        assert spec["rounds"] >= 1
        assert 0 <= spec["accepted"] <= spec["drafted"]


def test_spec_rows_advance_multiple_tokens_per_round(registry):
    """With an identical-weights draft every proposal is accepted: rows
    advance ~k+1 tokens per target forward — the amortization the mode
    exists for — and the stream still equals plain greedy decode."""
    eng = _spec_engine(registry, draft="tiny-same", k=4)
    plain_eng = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    req = GenerationRequest(
        "tiny", "perfect acceptance", max_new_tokens=33, stop_at_eos=False
    )
    sess = eng.decode_open([req])
    res = _drain(sess)[0]
    assert res.tokens == plain_eng._generate_plain(req).tokens
    spec = res.extras["spec"]
    # 32 decode tokens in ≤ ceil(32/5)+1 rounds; acceptance ≈ 1
    assert spec["rounds"] <= 8, spec
    assert spec["accepted"] >= spec["rounds"] * 3, spec


def test_spec_paged_bills_no_slack_and_restores_exactly(registry):
    """ISSUE 10: the 2k+2 slack page bill is GONE — a paged speculative
    row bills exactly the plain-decode page count (the verify keeps
    candidates in the scratch/side leaves, never in out-of-budget pool
    slots), and retire/cancel/close restore the pool free count EXACTLY
    — on bf16 and int8 pools."""
    for kv in (None, "int8"):
        eng = _spec_engine(registry, k=3, paged_kv=True, kv_quantize=kv)
        plain_eng = JaxEngine(
            registry=dict(registry), dtype=jnp.float32,
            paged_kv=True, kv_quantize=kv,
        )
        anchor = GenerationRequest(
            "tiny", "anchor decodes on", max_new_tokens=40, stop_at_eos=False
        )
        sess = eng.decode_open([anchor], reserve_rows=4)
        assert sess.spec is not None
        assert not hasattr(sess, "spec_slack")  # the attribute is retired
        plain_sess = plain_eng.decode_open([anchor], reserve_rows=4)
        # slack-free billing: spec row == plain row == ceil((s+mnt)/page)
        assert (
            sess._pages_needed(100, 40)
            == plain_sess._pages_needed(100, 40)
            == -(-(100 + 40) // 128)
        )
        # the kernel-less native mode carries its candidates in the
        # scratch leaves (head-layout mini cache), visible in debug
        st = sess.debug_state()
        assert st["spec"]["verify_mode"] == "native"
        assert st["spec"]["scratch_bytes"] > 0
        assert "scratch_k" in sess.carry and "scratch_v" in sess.carry
        plain_sess.close()
        free0 = sess.pool.free_pages
        sess.step(4)
        victim = GenerationRequest(
            "tiny", "victim row", max_new_tokens=30, stop_at_eos=False, seed=5
        )
        assert sess.can_join(victim)
        sess.join(victim)
        victim_pages = next(
            row.pages
            for row in sess.rows
            if row is not None and row.request is victim
        )
        assert sess.pool.free_pages == free0 - len(victim_pages)
        sess.step(4)
        # cancel restores the victim's slack-free pages exactly
        assert sess.cancel(victim)
        assert sess.pool.free_pages == free0
        results = _drain(sess)
        assert results[0].tokens == plain_eng._generate_plain(anchor).tokens
        sess.close()
        assert sess.pool.free_pages == sess.pool.n_pages - 1  # parking only


def test_spec_chunked_joiner_prefills_draft_too(registry):
    """A long-prompt joiner into a speculating session: its TARGET
    prefill chunks interleave as usual AND its DRAFT prefill rides the
    same chunk machinery (one chunk forward per join_step call) — the
    committed row then speculates and stays solo-identical."""
    eng = _spec_engine(registry, k=3)
    plain_eng = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    anchor = GenerationRequest(
        "tiny", "a" * 120, max_new_tokens=40, stop_at_eos=False, seed=1
    )
    sess = eng.decode_open([anchor], reserve_rows=4)
    assert sess.spec is not None
    sess.step(4)
    joiner = GenerationRequest("tiny", "j" * 100, max_new_tokens=12, seed=3)
    assert sess.can_join(joiner)
    pj = sess.join_begin(joiner, chunk_tokens=32)
    assert len(pj.chunks) >= 3  # 101 prompt ids at 32-token chunks
    assert len(pj.draft_chunks) >= 3  # the draft prefills the FULL prompt
    steps = 0
    done = False
    while not done:
        done = sess.join_step(pj)
        steps += 1
        if not done:
            sess.step(2)  # the anchor keeps speculating between chunks
    assert steps >= len(pj.chunks) + len(pj.draft_chunks)
    sess.join_commit(pj)
    results = {id(r.request): r for r in _drain(sess)}
    assert results[id(anchor)].tokens == plain_eng._generate_plain(anchor).tokens
    assert results[id(joiner)].tokens == plain_eng._generate_plain(joiner).tokens
    assert results[id(joiner)].extras["spec"]["rounds"] >= 1


STACKED_KV = [
    pytest.param(None, id="stacked-bf16"),
    pytest.param("int8", id="stacked-int8"),
]


def _stacked_spec_engine(registry, kv, **kwargs):
    """A paged spec engine in STACKED-HYBRID mode on CPU: injecting the
    contiguous decode kernel flips _specialised_kernels_enabled, so the
    paged wrapper (and its multi-query twins, interpret mode) engages —
    the test_paged_int8.py convention."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
        pallas_decode_attention,
    )

    return _spec_engine(
        registry, paged_kv=True, kv_quantize=kv,
        decode_attention=pallas_decode_attention, **kwargs,
    )


@pytest.mark.parametrize("kv", STACKED_KV)
def test_spec_stacked_hybrid_paged_parity_with_join_and_cancel(registry, kv):
    """The newly-un-excluded layout (ISSUE 10): a speculating session in
    STACKED-HYBRID paged mode — the multi-query parts kernel streams
    each row's prompt pages once for all k+1 candidate positions,
    candidates land in the side caches — stays bit-identical to plain
    greedy decode on the same engine configuration through mid-flight
    joins and cancellation, with EXACT pool free-count restoration."""
    eng = _stacked_spec_engine(registry, kv)
    exp = JaxEngine(
        registry=dict(registry), dtype=jnp.float32,
        paged_kv=True, kv_quantize=kv,
    )
    anchor = GenerationRequest(
        "tiny", "stacked anchor runs on", max_new_tokens=24,
        stop_at_eos=False,
    )
    short = GenerationRequest(
        "tiny", "short stacked row", max_new_tokens=8, seed=2
    )
    sess = eng.decode_open([anchor, short], reserve_rows=4)
    assert sess.spec is not None and sess.stacked, (
        "session did not take the stacked×spec path"
    )
    # stacked spec rows bill PROMPT-ONLY pages — same as plain stacked
    plain_sess = exp.decode_open([anchor], reserve_rows=2)
    del plain_sess  # plain CPU engine has no kernel: compare by rule
    assert sess._pages_needed(100, 40) == -(-100 // 128)
    assert sess.debug_state()["spec"]["verify_mode"] == "native"
    assert sess.debug_state()["spec"]["scratch_bytes"] > 0
    free0 = sess.pool.free_pages
    sess.step(2)
    joiner = GenerationRequest(
        "tiny", "stacked late joiner", max_new_tokens=10, seed=3
    )
    victim = GenerationRequest(
        "tiny", "stacked victim row", max_new_tokens=30,
        stop_at_eos=False, seed=5,
    )
    assert sess.can_join(joiner)
    sess.join(joiner)
    assert sess.can_join(victim)
    sess.join(victim)
    sess.step(2)
    # cancellation restores the victim's pages exactly, mid-flight
    victim_pages = next(
        row.pages
        for row in sess.rows
        if row is not None and row.request is victim
    )
    assert sess.cancel(victim)
    del victim_pages
    results = {id(r.request): r for r in _drain(sess)}
    for r in (anchor, short, joiner):
        assert results[id(r)].tokens == exp._generate_plain(r).tokens, (
            f"stacked spec diverged: kv={kv} prompt={r.prompt!r}"
        )
        assert results[id(r)].extras["spec"]["rounds"] >= 1
    sess.close()
    assert sess.pool.free_pages == sess.pool.n_pages - 1  # parking only
    del free0


def test_spec_stacked_vs_scratch_modes_agree(registry):
    """The two native verify modes — stacked (multi-query kernel) and
    kernel-less (scratch + table commit) — emit the same stream for the
    same request: the mode is an execution detail, not a numerics
    choice (float32 pins, per the module caveat)."""
    req = GenerationRequest(
        "tiny", "mode agreement probe", max_new_tokens=20,
        stop_at_eos=False,
    )
    stacked_eng = _stacked_spec_engine(registry, None)
    scratch_eng = _spec_engine(registry, paged_kv=True)
    s1 = stacked_eng.decode_open([req])
    assert s1.stacked
    s2 = scratch_eng.decode_open([req])
    assert not s2.stacked
    r1 = _drain(s1)[0]
    r2 = _drain(s2)[0]
    assert r1.tokens == r2.tokens


def test_spec_session_admits_sampled_rows_and_joiners(registry):
    """ISSUE 16 retires the greedy-only gate: sampled anchors SPECULATE
    (rejection resampling), a speculating session's can_join admits a
    sampled joiner, and only hotter-than-spec_temperature_max rows
    still defer to a plain session."""
    eng = _spec_engine(registry)
    sampled = GenerationRequest(
        "tiny", "sampled anchor", max_new_tokens=8, temperature=0.9, seed=5
    )
    sess = eng.decode_open([sampled])
    assert sess.spec is not None
    res = _drain(sess)[0]
    assert res.extras["spec"]["rounds"] >= 1
    assert res.extras["spec"]["source"] == "model"

    hot = GenerationRequest(
        "tiny", "too hot to draft", max_new_tokens=8, temperature=5.0, seed=6
    )
    hot_sess = eng.decode_open([hot])
    assert hot_sess.spec is None  # above the default 2.0 cap: plain
    _drain(hot_sess)

    greedy = GenerationRequest(
        "tiny", "greedy anchor", max_new_tokens=24, stop_at_eos=False
    )
    sess2 = eng.decode_open([greedy], reserve_rows=4)
    assert sess2.spec is not None
    sampled_joiner = GenerationRequest(
        "tiny", "sampled joiner", max_new_tokens=8, temperature=0.7, seed=7
    )
    assert sess2.can_join(sampled_joiner)
    sess2.join(sampled_joiner)
    hot_joiner = GenerationRequest(
        "tiny", "hot joiner", max_new_tokens=8, temperature=5.0
    )
    assert not sess2.can_join(hot_joiner)
    results = {id(r.request): r for r in _drain(sess2)}
    assert results[id(sampled_joiner)].extras["spec"]["rounds"] >= 1


def test_spec_adaptive_fallback_preserves_parity(registry):
    """The adaptive policy: a weak draft under a high floor first
    SHRINKS the draft length (llm_spec_k_adapt_total{direction=down},
    ISSUE 19) and only falls the session back to plain decode from
    k=1 — llm_spec_fallback_total moves, extras mark fallback, and the
    stream is STILL the plain greedy stream at every k along the way
    (both modes emit the target's argmax tokens)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        REGISTRY,
    )

    def snap(name):
        return sum(
            v
            for k, v in REGISTRY.snapshot().get(name, {}).items()
            if "source=model" in k
        )

    eng = _spec_engine(registry, spec_accept_floor=0.95)
    plain_eng = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    req = GenerationRequest(
        "tiny", "long fallback run", max_new_tokens=120, stop_at_eos=False
    )
    before = snap("llm_spec_fallback_total")
    down0 = snap("llm_spec_k_adapt_total")
    sess = eng.decode_open([req])
    assert sess.spec is not None and sess.spec["k"] == 3
    res = _drain(sess, max_steps=4)[0]
    assert sess.spec is None and sess.spec_fallback
    assert res.extras["spec"]["fallback"] is True
    assert res.tokens == plain_eng._generate_plain(req).tokens
    assert snap("llm_spec_fallback_total") == before + 1
    # the shrink stage ran before the fallback: k stepped 3 -> 1
    assert snap("llm_spec_k_adapt_total") >= down0 + 1


def test_spec_session_through_continuous_scheduler(registry):
    """End-to-end through the serving stack: the continuous scheduler
    opens a speculating session, a staggered arrival joins it, results
    carry the spec extras, and every stream is plain-greedy identical.
    The scheduler's decode_open floor override rides along."""
    import threading

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
        ContinuousScheduler,
    )

    eng = _spec_engine(registry, draft="tiny-same", k=3)
    plain_eng = JaxEngine(registry=dict(registry), dtype=jnp.float32)
    anchor = GenerationRequest(
        "tiny", "scheduler anchor", max_new_tokens=48, stop_at_eos=False
    )
    late = GenerationRequest("tiny", "late arrival", max_new_tokens=8, seed=2)
    # warm compiled shapes outside the scheduler
    warm = eng.decode_open([anchor, late], reserve_rows=4)
    _drain(warm)
    sched = ContinuousScheduler(eng, slice_steps=4, spec_accept_floor=0.05)
    sched.start()
    results = {}
    try:
        def submit(req):
            results[id(req)] = sched.submit(req)

        t1 = threading.Thread(target=submit, args=(anchor,))
        t2 = threading.Thread(target=submit, args=(late,))
        t1.start()
        t2.start()
        t1.join(timeout=120)
        t2.join(timeout=120)
    finally:
        sched.stop()
    assert set(results) == {id(anchor), id(late)}
    for req in (anchor, late):
        assert results[id(req)].tokens == plain_eng._generate_plain(req).tokens
        assert results[id(req)].extras["spec"]["rounds"] >= 1


def test_spec_debug_state_reports_session_and_rows(registry):
    eng = _spec_engine(registry, k=3)
    req = GenerationRequest(
        "tiny", "debug probe", max_new_tokens=24, stop_at_eos=False
    )
    sess = eng.decode_open([req])
    sess.step(4)
    state = sess.debug_state()
    assert state["spec"]["active"] is True
    assert state["spec"]["draft_model"] == "tiny-d"
    assert state["spec"]["k"] == 3
    assert state["spec"]["rounds_total"] >= 1
    assert "spec_rounds" in state["rows"][0]
    _drain(sess)


def test_spec_disabled_when_draft_cache_cannot_fit(registry):
    """A budget whose draft cache would exceed the draft's max_seq_len
    serves the session PLAIN (never fails a request plain decode would
    serve) — the solo path's fallback rule, stepped."""
    small = {
        "tiny": get_model_config("qwen2:1.5b").tiny(),  # max_seq_len 256
        "tiny-d": dataclasses.replace(
            get_model_config("qwen2:1.5b").tiny(), n_layers=1
        ),
    }
    eng = JaxEngine(
        registry=small, dtype=jnp.float32, speculative={"tiny": ("tiny-d", 3)}
    )
    req = GenerationRequest(
        "tiny", "big budget", max_new_tokens=128, stop_at_eos=False
    )
    sess = eng.decode_open([req])
    assert sess.spec is None  # margin would blow max_seq_len: plain
    res = _drain(sess)
    # the plain engine's own stream for the same request, wherever the
    # random tiny model happens to meet EOS (the compiled loop stops
    # there whatever stop_at_eos says, solo and stepped alike)
    plain = JaxEngine(registry=small, dtype=jnp.float32)
    assert res[0].tokens == plain.generate(req).tokens
    assert "spec" not in (res[0].extras or {})


def test_solo_spec_emits_obs_and_nested_extras(registry):
    """Satellite: the solo path no longer drops rounds/accepted on the
    floor — extras['spec'] plus the llm_spec_* families move."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.metrics import (
        REGISTRY,
    )

    eng = _spec_engine(registry, draft="tiny-same", k=4)
    before = (
        REGISTRY.snapshot()
        .get("llm_spec_rounds_total", {})
        .get("source=model", 0)
    )
    res = eng.generate(
        GenerationRequest(
            "tiny", "solo obs", max_new_tokens=17, stop_at_eos=False
        )
    )
    spec = res.extras["spec"]
    assert spec["rounds"] == res.extras["spec_rounds"]
    assert spec["accepted"] == res.extras["spec_accepted"]
    assert spec["drafted"] == spec["rounds"] * 4
    after = (
        REGISTRY.snapshot()
        .get("llm_spec_rounds_total", {})
        .get("source=model", 0)
    )
    assert after >= before + spec["rounds"]
