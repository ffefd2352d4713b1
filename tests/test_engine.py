"""Generation backends: fake determinism, JAX engine end-to-end on tiny models."""

import jax.numpy as jnp
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import FakeBackend
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
    GEN_BUCKETS,
    PROMPT_BUCKETS,
    JaxEngine,
    _bucket,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
    get_model_config,
)


def test_fake_backend_is_deterministic():
    be = FakeBackend()
    req = GenerationRequest(model="m", prompt="hello", max_new_tokens=16)
    r1, r2 = be.generate(req), be.generate(req)
    assert r1.tokens == r2.tokens and r1.text == r2.text
    r3 = be.generate(
        GenerationRequest(model="m", prompt="hello", max_new_tokens=16, seed=1)
    )
    assert r3.tokens != r1.tokens
    assert r1.generated_tokens == 16
    assert r1.tokens_per_s > 0


def test_bucket_rounding():
    assert _bucket(1, PROMPT_BUCKETS) == 32
    assert _bucket(33, PROMPT_BUCKETS) == 64
    assert _bucket(2048, GEN_BUCKETS) == 2048
    with pytest.raises(ValueError, match="exceeds"):
        _bucket(99999, GEN_BUCKETS)


@pytest.fixture(scope="module")
def engine():
    registry = {
        "tiny-a": get_model_config("qwen2:1.5b").tiny(),
        "tiny-gemma": get_model_config("gemma:2b").tiny(),
    }
    return JaxEngine(registry=registry, dtype=jnp.float32)


def test_jax_engine_generates(engine):
    req = GenerationRequest(model="tiny-a", prompt="hello tpu", max_new_tokens=12)
    result = engine.generate(req)
    assert result.generated_tokens <= 12
    assert len(result.tokens) == result.generated_tokens
    assert result.prompt_tokens == len("hello tpu".encode()) + 1
    assert result.prefill_s > 0 and result.decode_s > 0
    assert all(0 <= t < engine.registry["tiny-a"].vocab_size for t in result.tokens)


def test_jax_engine_greedy_is_deterministic(engine):
    req = GenerationRequest(model="tiny-a", prompt="abc", max_new_tokens=10)
    assert engine.generate(req).tokens == engine.generate(req).tokens


def test_jax_engine_seed_changes_sampled_output(engine):
    r0 = engine.generate(
        GenerationRequest("tiny-a", "abc", 24, temperature=1.5, seed=0)
    )
    r1 = engine.generate(
        GenerationRequest("tiny-a", "abc", 24, temperature=1.5, seed=1)
    )
    assert r0.tokens != r1.tokens


def test_jax_engine_compile_cache_reused(engine):
    # same buckets → same compiled callables
    engine.generate(GenerationRequest("tiny-a", "xy", 10))
    n_prefill = len(engine._prefill_cache)
    n_decode = len(engine._decode_cache)
    engine.generate(GenerationRequest("tiny-a", "different prompt!", 12))
    assert len(engine._prefill_cache) == n_prefill
    assert len(engine._decode_cache) == n_decode
    # a not-yet-seen generation bucket compiles one more decode fn
    engine.generate(GenerationRequest("tiny-a", "xy", 60))
    assert len(engine._decode_cache) == n_decode + 1


def test_jax_engine_multiple_families(engine):
    r = engine.generate(GenerationRequest("tiny-gemma", "hi", 8))
    assert r.generated_tokens <= 8


def test_jax_engine_generates_exactly_max_new_without_eos(engine):
    """The decode loop must run exactly the requested steps, not the bucket
    (timing/energy would otherwise include unrequested work)."""
    r = engine.generate(
        GenerationRequest("tiny-a", "count", 11, stop_at_eos=False)
    )
    assert r.generated_tokens == 11


def test_jax_engine_rejects_overflowing_cache(engine):
    with pytest.raises(ValueError, match="max_seq_len"):
        # tiny max_seq_len is 256; 32-prompt + 256-gen buckets exceed it
        engine.generate(GenerationRequest("tiny-a", "x", 250))


def test_warmup_compiles_once_and_resets_on_unload():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config as gmc,
    )

    eng = JaxEngine(registry={"t": gmc("qwen2:1.5b").tiny()}, dtype=jnp.float32)
    req = GenerationRequest("t", "warm me", 10)
    eng.warmup(req)
    assert len(eng._warmed) == 1
    eng.warmup(req)  # no-op
    assert len(eng._warmed) == 1
    eng.unload_all()
    assert len(eng._warmed) == 0  # fresh load must re-warm


def test_jax_engine_unload(engine_factory=None):
    registry = {"tiny-a": get_model_config("qwen2:1.5b").tiny()}
    eng = JaxEngine(registry=registry, dtype=jnp.float32)
    eng.generate(GenerationRequest("tiny-a", "x", 8))
    assert eng._models
    eng.unload_all()
    assert not eng._models and not eng._decode_cache


def test_generate_stream_matches_generate_greedy(engine):
    req = GenerationRequest("tiny-a", "stream me", max_new_tokens=20)
    mono = engine.generate(req)
    chunks = list(engine.generate_stream(req, chunk_tokens=4))
    assert chunks[-1].done and chunks[-1].result is not None
    streamed_tokens = [t for c in chunks[:-1] for t in c.tokens]
    assert streamed_tokens == mono.tokens
    assert chunks[-1].result.tokens == mono.tokens
    assert chunks[-1].result.text == mono.text
    # multiple incremental chunks actually happened
    assert len(chunks) >= 2


def test_generate_stream_matches_generate_sampled(engine):
    # rng threads through chunk boundaries → identical sample path
    req = GenerationRequest(
        "tiny-a", "abc", max_new_tokens=16, temperature=1.2, seed=3
    )
    mono = engine.generate(req)
    chunks = list(engine.generate_stream(req, chunk_tokens=5))
    assert [t for c in chunks[:-1] for t in c.tokens] == mono.tokens


def test_generate_with_top_p_and_repeat_penalty(engine):
    req = GenerationRequest(
        "tiny-a",
        "abc",
        max_new_tokens=12,
        temperature=1.0,
        top_p=0.9,
        repeat_penalty=1.3,
        seed=0,
    )
    r1, r2 = engine.generate(req), engine.generate(req)
    assert r1.tokens == r2.tokens  # deterministic under a fixed seed
    assert r1.generated_tokens >= 1
    # the static-flag variants get their own compiled decode entries
    assert any(k[3] or k[4] for k in engine._decode_cache)


def test_repeat_penalty_reduces_repetition(engine):
    base = GenerationRequest("tiny-a", "zzz", max_new_tokens=32)
    plain = engine.generate(base)
    penalised = engine.generate(
        GenerationRequest(
            "tiny-a", "zzz", max_new_tokens=32, repeat_penalty=1.8
        )
    )
    # greedy decode on random weights tends to cycle; the penalty must
    # produce at least as many distinct tokens
    assert len(set(penalised.tokens)) >= len(set(plain.tokens))


def test_warmup_compiles_stream_decode_bucket(engine):
    req = GenerationRequest("tiny-gemma", "warm", max_new_tokens=40)
    engine.warmup(req)
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        DEFAULT_STREAM_CHUNK,
    )

    keys = {k[:2] for k in engine._decode_cache if k[0] == "tiny-gemma"}
    assert ("tiny-gemma", 64) in keys  # monolithic g_bucket
    assert ("tiny-gemma", DEFAULT_STREAM_CHUNK) in keys  # stream chunk bucket


def test_generate_batch_matches_single_greedy(engine):
    reqs = [
        GenerationRequest("tiny-a", "first prompt", max_new_tokens=10),
        GenerationRequest("tiny-a", "a second, rather longer prompt here", max_new_tokens=14),
        GenerationRequest("tiny-a", "3rd", max_new_tokens=6),
    ]
    singles = [engine.generate(r) for r in reqs]
    batch = engine.generate_batch(reqs)
    assert len(batch) == 3
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens
        assert b.text == s.text
        assert b.prompt_tokens == s.prompt_tokens


def test_generate_batch_matches_single_sampled(engine):
    reqs = [
        GenerationRequest(
            "tiny-a", "alpha", max_new_tokens=12, temperature=1.1, seed=5
        ),
        GenerationRequest(
            "tiny-a", "beta beta", max_new_tokens=12, temperature=0.8, seed=9
        ),
    ]
    singles = [engine.generate(r) for r in reqs]
    batch = engine.generate_batch(reqs)
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens


def test_generate_batch_mixed_knobs(engine):
    reqs = [
        GenerationRequest(
            "tiny-a", "x", max_new_tokens=8, temperature=1.0,
            top_p=0.9, seed=1,
        ),
        GenerationRequest(
            "tiny-a", "yy", max_new_tokens=8, temperature=0.0,
            repeat_penalty=1.5,
        ),
    ]
    singles = [engine.generate(r) for r in reqs]
    batch = engine.generate_batch(reqs)
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens


def test_generate_batch_validates_inputs(engine):
    with pytest.raises(ValueError, match="one model"):
        engine.generate_batch(
            [
                GenerationRequest("tiny-a", "x", max_new_tokens=4),
                GenerationRequest("tiny-gemma", "y", max_new_tokens=4),
            ]
        )
    with pytest.raises(ValueError, match="one top_k"):
        engine.generate_batch(
            [
                GenerationRequest("tiny-a", "x", max_new_tokens=4, top_k=3),
                GenerationRequest("tiny-a", "y", max_new_tokens=4, top_k=5),
            ]
        )
    assert engine.generate_batch([]) == []


def test_generate_batch_chunks_oversized_fleets(engine, monkeypatch):
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    # Force the memory-bounded width down to the floor so the seam logic
    # is exercised without compiling a 256-row loop on CPU.
    monkeypatch.setattr(je, "BATCH_KV_BUDGET_BYTES", 1)
    seam = je.BATCH_MIN_SPLIT_ROWS
    n = seam + 3
    reqs = [
        GenerationRequest("tiny-a", f"p{i}", max_new_tokens=4, seed=i)
        for i in range(n)
    ]
    batch = engine.generate_batch(reqs)
    assert len(batch) == n
    # spot-check parity at the chunk seam
    for i in (0, seam - 1, seam, n - 1):
        assert batch[i].tokens == engine.generate(reqs[i]).tokens
    # the two chunks decoded in separate, explicitly-tagged windows
    assert len({r.extras["decode_window"] for r in batch}) == 2


def test_generate_batch_width_is_memory_bounded(engine):
    """The sub-batch width tracks the estimated KV-cache footprint: tiny
    rows fit hundreds wide; max-context rows fall back to the known-safe
    floor (the round-3-era hard cap)."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    engine.load_model("tiny-a")
    cfg = engine._models["tiny-a"].cfg
    short = [GenerationRequest("tiny-a", "p", max_new_tokens=4)] * 64
    ids = [[1, 2, 3]] * 64
    assert engine._max_batch_rows(cfg, short, ids) == je.BATCH_BUCKETS[-1]

    # a synthetic huge config: one row's cache alone exceeds the budget →
    # the floor wins (never refuse, never split below the known-safe cap)
    import dataclasses

    big = dataclasses.replace(
        cfg, n_layers=4000, d_head=4096, max_seq_len=100000
    )
    long_req = [GenerationRequest("tiny-a", "p", max_new_tokens=2048)]
    assert (
        engine._max_batch_rows(big, long_req, [[1] * 900])
        == je.BATCH_MIN_SPLIT_ROWS
    )


def test_max_batch_rows_paged_estimates_are_mode_aware(monkeypatch):
    """The paged estimate differs by mode and must not over-bill: the
    first dual-engine bench used one conservative factor for both paged
    modes, billed stacked rows ~3× their real bytes, and silently split
    a '128-row' fleet at 64 — re-creating the decode-window artifact in
    a fresh measurement (docs/PERF.md)."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    registry = {"tiny": get_model_config("qwen2:1.5b").tiny()}
    paged = je.JaxEngine(
        registry=dict(registry), dtype=jnp.float32, paged_kv=True
    )
    paged.load_model("tiny")
    cfg = paged._models["tiny"].cfg
    reqs = [GenerationRequest("tiny", "p", max_new_tokens=16)] * 8
    ids = [[1, 2, 3]] * 8

    legacy = paged._max_batch_rows(cfg, reqs, ids)  # CPU: no kernels
    monkeypatch.setattr(
        je.JaxEngine,
        "_paged_decode_attention",
        lambda self, c=None: (lambda *a, **k: None),
    )
    stacked = paged._max_batch_rows(cfg, reqs, ids)
    # tiny shapes: everything fits the widest bucket in every mode
    assert legacy == stacked == je.BATCH_BUCKETS[-1]

    # The estimate now bills each mode its ACTUAL allocation
    # (per-row pages, chunk-level pow2 pool rounding — PR 1): a budget
    # set exactly between a mode's own 64- and 128-row chunk needs must
    # admit exactly 64 in that mode. Checked for BOTH modes — stacked
    # bills prompt-only pages (at the lane-padded head dim) + side
    # columns, legacy bills prompt + budget pages at the raw head dim.
    wide = [
        GenerationRequest("tiny", "p", max_new_tokens=128)
    ] * 128
    wide_ids = [[1, 2, 3]] * 128
    g_bucket = je._bucket(128, je.GEN_BUCKETS)
    for is_stacked in (True, False):
        pages_per_row = 1 if is_stacked else -(-(3 + 128) // 128)
        rows_pages = [pages_per_row] * 128
        need64 = paged._paged_chunk_bytes(
            cfg, rows_pages[:64], 64, g_bucket, is_stacked
        )
        need128 = paged._paged_chunk_bytes(
            cfg, rows_pages, 128, g_bucket, is_stacked
        )
        assert need64 < need128
        monkeypatch.setattr(
            je, "BATCH_KV_BUDGET_BYTES", (need64 + need128) // 2
        )
        monkeypatch.setattr(
            je.JaxEngine,
            "_paged_decode_attention",
            (lambda self, c=None: (lambda *a, **k: None))
            if is_stacked
            else (lambda self, c=None: None),
        )
        assert paged._max_batch_rows(cfg, wide, wide_ids) == 64, is_stacked


def test_generate_batch_mixed_top_p_rows_stay_bit_identical(engine):
    # a sampled row with top_p disabled next to a top_p row: the disabled
    # row's draw must not be perturbed by the batch-wide nucleus filter
    reqs = [
        GenerationRequest(
            "tiny-a", "nucleus", max_new_tokens=10, temperature=1.0,
            top_p=0.8, seed=2,
        ),
        GenerationRequest(
            "tiny-a", "free", max_new_tokens=10, temperature=1.3, seed=7,
        ),  # top_p = 1.0 (disabled)
    ]
    singles = [engine.generate(r) for r in reqs]
    batch = engine.generate_batch(reqs)
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens


def test_chunked_prefill_matches_single_chunk(monkeypatch):
    """Force tiny prefill chunks: output must be identical to the
    single-chunk path (the flash/jnp prefill handles offset > 0)."""
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    registry = {"tiny-c": get_model_config("qwen2:1.5b").tiny(max_seq_len=512)}
    prompt = "a moderately long prompt " * 8  # ~200 byte-tokens
    req = GenerationRequest("tiny-c", prompt, max_new_tokens=12)

    plain = JaxEngine(registry=registry, dtype=jnp.float32).generate(req)
    monkeypatch.setattr(je, "PREFILL_CHUNK", 64)
    chunked_engine = JaxEngine(registry=registry, dtype=jnp.float32)
    chunked = chunked_engine.generate(req)
    assert chunked.tokens == plain.tokens
    assert chunked.text == plain.text
    # several prefill chunk compilations actually happened
    assert len(chunked_engine._prefill_cache) >= 2


def test_long_prompt_beyond_largest_bucket(monkeypatch):
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine as je

    monkeypatch.setattr(je, "PREFILL_CHUNK", 64)
    registry = {"tiny-c": get_model_config("qwen2:1.5b").tiny(max_seq_len=512)}
    engine = JaxEngine(registry=registry, dtype=jnp.float32)
    prompt = "x" * 300  # > PREFILL_CHUNK once chunking is forced
    r = engine.generate(
        GenerationRequest("tiny-c", prompt, max_new_tokens=8)
    )
    assert r.prompt_tokens == 301  # bos + 300 bytes
    assert r.generated_tokens >= 1


def test_prefix_cache_exact_and_partial_hits():
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    cold = JaxEngine(registry=registry, dtype=jnp.float32)
    warm = JaxEngine(registry=registry, dtype=jnp.float32, prefix_cache_size=4)

    sys_prompt = "You are a helpful assistant. "
    r_a = GenerationRequest("tiny-p", sys_prompt + "Question A?", max_new_tokens=10)
    r_b = GenerationRequest("tiny-p", sys_prompt + "Question A? And B too?", max_new_tokens=10)

    # identical outputs with and without the cache, for exact re-ask and
    # prefix-extension
    assert warm.generate(r_a).tokens == cold.generate(r_a).tokens
    assert warm.generate(r_a).tokens == cold.generate(r_a).tokens  # exact hit
    assert warm.generate(r_b).tokens == cold.generate(r_b).tokens  # partial hit
    assert len(warm._prefix_cache["tiny-p"]) >= 2


def test_prefix_cache_lru_eviction():
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    engine = JaxEngine(registry=registry, dtype=jnp.float32, prefix_cache_size=2)
    for i in range(4):
        engine.generate(
            GenerationRequest("tiny-p", f"prompt number {i}", max_new_tokens=4)
        )
    assert len(engine._prefix_cache["tiny-p"]) == 2


def test_prefix_cache_byte_cap_evicts_lru(monkeypatch):
    """The prefix cache is capped by BYTES across all models (VERDICT
    round-2 item 6): cached KV is device memory and an entry count says
    nothing about its size."""
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    engine = JaxEngine(
        registry=registry, dtype=jnp.float32, prefix_cache_size=8
    )
    # measure with a prompt of the same length as the test prompts below
    # (entry bytes scale with prompt tokens)
    engine.generate(
        GenerationRequest("tiny-p", "prompt number 9", max_new_tokens=4)
    )
    one_entry = engine._prefix_bytes()
    assert one_entry > 0
    # cap at ~2.5 entries: storing 4 must keep only 2
    engine2 = JaxEngine(
        registry=registry,
        dtype=jnp.float32,
        prefix_cache_size=8,
        prefix_cache_bytes=int(2.5 * one_entry),
    )
    for i in range(4):
        engine2.generate(
            GenerationRequest("tiny-p", f"prompt number {i}", max_new_tokens=4)
        )
    assert engine2._prefix_bytes() <= int(2.5 * one_entry)
    kept = list(engine2._prefix_cache["tiny-p"])
    assert len(kept) == 2
    # the survivors are the most recently used (LRU went first)
    tok = engine2._tokenizer_for("tiny-p")
    assert kept == [
        tuple(tok.encode("prompt number 2")),
        tuple(tok.encode("prompt number 3")),
    ]


def test_prefix_kv_evicted_before_model_load_exceeds_budget(monkeypatch):
    """Allocation accounting sees cached prompt KV: a model load that
    would exceed the budget evicts prefix entries FIRST (pure recompute),
    and only then resident weights (VERDICT round-2 item 6)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils import memory as mem
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_weight_bytes,
    )

    registry = {
        "a": get_model_config("qwen2:1.5b").tiny(),
        "b": get_model_config("gemma:2b").tiny(),
    }
    one = estimate_weight_bytes(registry["a"], None, 4)
    monkeypatch.setattr(mem, "LOAD_TRANSIENT_HEADROOM_BYTES", 0)
    eng = JaxEngine(
        registry=registry, dtype=jnp.float32, prefix_cache_size=8
    )
    # a long prompt → a large cached-prefix KV entry (121 ids → bucket 128;
    # within tiny()'s max_seq_len alongside the 16-token generation bucket)
    eng.generate(
        GenerationRequest("a", "x" * 120, max_new_tokens=4)
    )
    prefix_bytes = eng._prefix_bytes()
    assert prefix_bytes > 0
    # budget: both models' weights fit ONLY if the prefix KV goes
    both = one + estimate_weight_bytes(registry["b"], None, 4)
    monkeypatch.setenv(
        "TPU_ALLOC_BUDGET_BYTES", str(both + prefix_bytes // 2)
    )
    eng.load_model("b")
    # prefix evicted, BOTH models still resident (weights were spared)
    assert eng._prefix_bytes() < prefix_bytes
    assert "a" in eng._models and "b" in eng._models


def test_prefix_cache_byte_cap_alone_enables_cache():
    """A byte cap without an entry cap must still enable the cache (not
    be silently inert)."""
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    engine = JaxEngine(
        registry=registry,
        dtype=jnp.float32,
        prefix_cache_bytes=64 * 1024 * 1024,
    )
    engine.generate(GenerationRequest("tiny-p", "hello", max_new_tokens=4))
    assert engine._prefix_bytes() > 0


def test_prefix_cache_disabled_by_default():
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    engine = JaxEngine(registry=registry, dtype=jnp.float32)
    engine.generate(GenerationRequest("tiny-p", "hello", max_new_tokens=4))
    assert engine._prefix_cache == {}


def test_prefix_cache_partial_hit_near_cache_boundary():
    """Review repro: a cached 60-token prompt extended by 2 tokens would
    re-chunk past cache_len (tail bucket rounding) and the clamped write
    would corrupt the prefix — the hit must shrink instead."""
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    cold = JaxEngine(registry=registry, dtype=jnp.float32)
    warm = JaxEngine(registry=registry, dtype=jnp.float32, prefix_cache_size=4)
    p60 = "x" * 59  # +BOS = 60 tokens
    p62 = "x" * 61  # +BOS = 62 tokens, shares the 60-token prefix
    r60 = GenerationRequest("tiny-p", p60, max_new_tokens=16)
    r62 = GenerationRequest("tiny-p", p62, max_new_tokens=16)
    warm.generate(r60)  # seeds the cache with the 60-token prefix
    assert warm.generate(r62).tokens == cold.generate(r62).tokens


def test_prefix_cache_rejects_negative_size():
    with pytest.raises(ValueError, match="prefix_cache_size"):
        JaxEngine(prefix_cache_size=-1)


def test_prefix_cache_entries_store_only_prompt_region():
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    engine = JaxEngine(registry=registry, dtype=jnp.float32, prefix_cache_size=2)
    engine.generate(GenerationRequest("tiny-p", "abcde", max_new_tokens=64))
    (k, v, _, _stamp), = engine._prefix_cache["tiny-p"].values()
    assert k.shape[3] == 6  # bos + 5 bytes, not prompt_bucket + gen_bucket


def test_stop_strings_truncate_output(engine):
    # find a sampled generation with enough text to cut (random weights can
    # emit ids that decode to nothing)
    base = full = None
    for seed in range(8):
        cand = GenerationRequest(
            "tiny-a", "halt here", max_new_tokens=24, temperature=0.8,
            seed=seed,
        )
        r = engine.generate(cand)
        if len(r.text) >= 4:
            base, full = cand, r
            break
    assert full is not None, "no seed produced 4+ chars of text"
    stop_str = full.text[2:4]
    import dataclasses as _dc

    stopped = engine.generate(_dc.replace(base, stop=(stop_str,)))
    assert stop_str not in stopped.text
    assert stopped.text == full.text[: full.text.find(stop_str)]
    assert stopped.generated_tokens == len(stopped.tokens)
    # streamed output agrees with the non-streamed stop cut
    chunks = list(
        engine.generate_stream(_dc.replace(base, stop=(stop_str,)), chunk_tokens=4)
    )
    streamed = "".join(c.text for c in chunks[:-1])
    assert streamed == stopped.text
    assert chunks[-1].result.text == stopped.text


def test_stop_strings_no_match_is_identity(engine):
    req = GenerationRequest(
        "tiny-a", "no stops", max_new_tokens=12, stop=(" NEVER ",)
    )
    plain = engine.generate(
        GenerationRequest("tiny-a", "no stops", max_new_tokens=12)
    )
    assert engine.generate(req).tokens == plain.tokens


def test_stop_string_spanning_chunks_does_not_leak_prefix(engine):
    """A stop string split across chunk boundaries must not leak its first
    characters into the stream (prefix holdback)."""
    import dataclasses as _dc

    base = None
    for seed in range(10):
        cand = GenerationRequest(
            "tiny-a", "span", max_new_tokens=24, temperature=0.9, seed=seed
        )
        r = engine.generate(cand)
        if len(r.text) >= 8:
            base, full = cand, r
            break
    assert base is not None
    stop_str = full.text[4:7]  # 3 chars, will straddle chunk_tokens=2 decode
    stopped = engine.generate(_dc.replace(base, stop=(stop_str,)))
    chunks = list(
        engine.generate_stream(_dc.replace(base, stop=(stop_str,)), chunk_tokens=2)
    )
    streamed = "".join(c.text for c in chunks[:-1])
    assert streamed == stopped.text == chunks[-1].result.text
    assert stop_str not in streamed


def test_stop_request_does_not_burn_full_budget(engine):
    """generate() with a stop hit must not decode the whole token budget
    (it would measure energy for discarded work)."""
    import dataclasses as _dc

    base = None
    for seed in range(10):
        cand = GenerationRequest(
            "tiny-a", "budget", max_new_tokens=128, temperature=0.9, seed=seed
        )
        r = engine.generate(cand)
        if len(r.text) >= 6:
            base, full = cand, r
            break
    assert base is not None
    stop_str = full.text[2:4]
    stopped = engine.generate(_dc.replace(base, stop=(stop_str,)))
    # streaming chunk granularity: the decode stops within ~2 chunks of
    # the hit, nowhere near the 128-token budget
    assert stopped.generated_tokens < 64


def test_empty_stop_string_rejected():
    with pytest.raises(ValueError, match="stop"):
        GenerationRequest("m", "x", max_new_tokens=4, stop=("",))


def test_empty_prompt_encoding_rejected(engine):
    """A tokenizer that yields zero prompt ids (HF checkpoint with no BOS +
    empty prompt) must fail cleanly, not sample from an all-pad prefill."""

    class NoBosTokenizer:
        pad_id = 0
        eos_id = 2
        vocab_size = 16

        def encode(self, text, add_bos=True):
            return []  # no BOS, empty prompt

        def decode(self, ids):
            return ""

    engine.load_model("tiny-a")
    engine._tokenizers["tiny-a"] = NoBosTokenizer()
    try:
        with pytest.raises(ValueError, match="zero tokens"):
            engine.generate(GenerationRequest("tiny-a", "", max_new_tokens=4))
    finally:
        del engine._tokenizers["tiny-a"]


def test_protocol_num_predict_cap_matches_engine_buckets():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve import protocol

    assert protocol.MAX_NUM_PREDICT == GEN_BUCKETS[-1]


def test_apply_stop_binary_search_matches_linear_scan():
    """The binary-searched token cut must equal the original linear scan's
    (smallest prefix whose decode covers the kept text) for a prefix-stable
    tokenizer, across cut positions."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        _apply_stop,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.tokenizer import (
        ByteTokenizer,
    )

    tok = ByteTokenizer()
    text = "the quick brown fox jumps over the lazy dog"
    tokens = tok.encode(text, add_bos=False)
    assert tok.decode(tokens) == text
    for stop_str in ("quick", " fox", "dog", "t", "o"):
        got_tokens, got_text = _apply_stop(list(tokens), text, tok, (stop_str,))
        kept = text[: text.find(stop_str)]
        assert got_text == kept
        # linear-scan reference
        k, acc = 0, ""
        while k < len(tokens) and len(acc) < len(kept):
            k += 1
            acc = tok.decode(tokens[:k])
        assert got_tokens == tokens[:k]


def test_apply_stop_fixup_repairs_non_monotone_decode():
    """Cleanup/merging tokenizers make decode length only approximately
    monotone in prefix length — the bisect can land positions off. The
    bounded fix-up must restore the smallest covering prefix (ADVICE
    round-2: wire-visible token counts were drifting)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        _apply_stop,
    )

    class WeirdTok:
        # decode length by prefix length: dips at 2 and 4 steer the bisect
        # to land at 5; the true smallest covering prefix (len >= 2) is 3.
        lens = [0, 1, 1, 4, 1, 4]

        def decode(self, ids):
            return "abZd"[: self.lens[len(ids)]]

    tokens = [10, 11, 12, 13, 14]
    text = "abZd"  # full decode; stop at index 2 → kept = "ab"
    got_tokens, got_text = _apply_stop(tokens, text, WeirdTok(), ("Z",))
    assert got_text == "ab"
    assert got_tokens == tokens[:3]


def test_per_model_quantize_dict():
    """One engine can serve different models at different quant modes
    (small = int8 for speed, large = int4 for capacity)."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        is_quantized,
    )

    registry = {
        "tiny-a": get_model_config("qwen2:1.5b").tiny(),
        "tiny-gemma": get_model_config("gemma:2b").tiny(),
    }
    eng = JaxEngine(
        registry=registry,
        dtype=jnp.float32,
        quantize={"tiny-a": "int8", "default": None},
    )
    assert eng._quant_mode("tiny-a") == "int8"
    assert eng._quant_mode("tiny-gemma") is None
    eng.load_model("tiny-a")
    eng.load_model("tiny-gemma")
    assert is_quantized(eng._models["tiny-a"].params["wq"])
    assert not is_quantized(eng._models["tiny-gemma"].params["wq"])
    r = eng.generate(GenerationRequest("tiny-a", "hi", max_new_tokens=6))
    assert r.generated_tokens <= 6
    with pytest.raises(ValueError, match="unsupported quantize"):
        JaxEngine(registry=registry, quantize={"tiny-a": "int3"})


def test_install_model_reinstall_evicts_stale_state():
    """Re-installing a model name must drop compiled fns, prefix KV and
    warm markers derived from the old weights/config."""
    import jax

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
        init_params,
    )

    cfg_old = get_model_config("qwen2:1.5b").tiny()
    cfg_new = get_model_config("gemma:2b").tiny()  # different architecture
    eng = JaxEngine(registry={}, dtype=jnp.float32, prefix_cache_size=2)
    eng.install_model(
        "m", cfg_old, init_params(cfg_old, jax.random.PRNGKey(0), jnp.float32)
    )
    r_old = eng.generate(GenerationRequest("m", "same prompt", 8))
    assert eng._prefill_cache and eng._prefix_cache.get("m")
    eng.install_model(
        "m", cfg_new, init_params(cfg_new, jax.random.PRNGKey(1), jnp.float32)
    )
    assert not eng._prefix_cache.get("m")
    assert not [k for k in eng._prefill_cache if "m" in k]
    assert not [k for k in eng._decode_cache if "m" in k]
    r_new = eng.generate(GenerationRequest("m", "same prompt", 8))
    # different config + weights → decode runs the NEW architecture
    assert eng._models["m"].cfg == cfg_new
    assert r_new.tokens != r_old.tokens


def test_lru_weight_eviction_under_allocation_budget(monkeypatch):
    """When total resident weights would overflow the allocation budget,
    the least-recently-used model's weights are evicted; compiled state
    survives, so a reload serves the same compiled fns."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils import memory as mem
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_weight_bytes,
    )

    registry = {
        "a": get_model_config("qwen2:1.5b").tiny(),
        "b": get_model_config("gemma:2b").tiny(),
    }
    one = estimate_weight_bytes(registry["a"], None, 4)
    # headroom dwarfs tiny models; shrink it so the budget math is exact
    monkeypatch.setattr(mem, "LOAD_TRANSIENT_HEADROOM_BYTES", 0)
    monkeypatch.setenv("TPU_ALLOC_BUDGET_BYTES", str(int(1.7 * one)))
    eng = JaxEngine(registry=registry, dtype=jnp.float32)
    eng.generate(GenerationRequest("a", "warm a", 6))
    n_decode = len(eng._decode_cache)
    eng.load_model("b")  # must evict a's weights to fit
    assert "a" not in eng._models and "b" in eng._models
    assert len(eng._decode_cache) == n_decode  # compiled state kept
    # transparent reload: generating on the evicted model works and reuses
    # the compiled decode fn (no new cache entries)
    r = eng.generate(GenerationRequest("a", "warm a", 6))
    assert r.generated_tokens == 6
    assert len(eng._decode_cache) == n_decode
    assert "b" not in eng._models  # b became the LRU victim in turn


def test_lru_recency_updated_on_use(monkeypatch):
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils import memory as mem
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.memory import (
        estimate_weight_bytes,
    )

    registry = {
        "a": get_model_config("qwen2:1.5b").tiny(),
        "b": get_model_config("gemma:2b").tiny(),
        "c": get_model_config("phi3:3.8b").tiny(),
    }
    one = estimate_weight_bytes(registry["a"], None, 4)
    monkeypatch.setattr(mem, "LOAD_TRANSIENT_HEADROOM_BYTES", 0)
    monkeypatch.setenv("TPU_ALLOC_BUDGET_BYTES", str(int(2.9 * one)))
    eng = JaxEngine(registry=registry, dtype=jnp.float32)
    eng.load_model("a")
    eng.load_model("b")
    eng.load_model("a")  # touch a → b becomes LRU
    eng.load_model("c")  # must evict b, not a
    assert "a" in eng._models and "c" in eng._models
    assert "b" not in eng._models


def test_auto_policy_engages_specialised_kernels_on_tpu(monkeypatch):
    """The "auto" attention policy's TPU side (unreachable on the CPU
    suite without a mock): specialised kernels engage for the int8-KV
    and paged cache representations while the plain path stays on XLA's
    fused attention (decode_attention None) — the measured round-4
    policy, docs/PERF.md."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import (
        jax_engine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        get_model_config,
    )

    monkeypatch.setattr(jax_engine, "on_tpu", lambda: True)
    plain = JaxEngine(registry={"t": get_model_config("qwen2:1.5b").tiny()})
    assert plain._auto_attention
    assert plain.decode_attention is None  # plain cache: XLA fused
    assert plain._specialised_kernels_enabled()
    assert plain._paged_decode_attention() is not None

    kv = JaxEngine(kv_quantize="int8")
    assert (
        kv._decode_attention_for_cache(get_model_config("qwen2:1.5b"))
        is not None  # d_head 128: int8 kernel
    )
    assert (
        kv._decode_attention_for_cache(get_model_config("phi3:3.8b"))
        is not None  # d_head 96 engages too since the round-5 scales
        # BlockSpec fix (the round-4 trace abort was never the head dim)
    )


def _spy_prefill_calls(monkeypatch, engine):
    """Count invocations of compiled prefill fns (one per chunk/group)."""
    calls = []
    orig = engine._prefill_fn

    def spy(model, bucket, cache_len):
        fn = orig(model, bucket, cache_len)

        def wrapped(*a, **k):
            calls.append((bucket, cache_len))
            return fn(*a, **k)

        return wrapped

    monkeypatch.setattr(engine, "_prefill_fn", spy)
    return calls


def test_generate_batch_groups_same_bucket_prefills(monkeypatch, engine):
    """VERDICT round-4 missing #3: same-bucket prompts prefill as ONE
    padded [G, S] forward, not G sequential dispatches — while every
    row's tokens stay bit-identical to its solo generate()."""
    reqs = [
        GenerationRequest(
            "tiny-a", f"prompt number {i}", max_new_tokens=8,
            temperature=0.9, seed=100 + i,
        )
        for i in range(4)
    ]
    singles = [engine.generate(r) for r in reqs]
    calls = _spy_prefill_calls(monkeypatch, engine)
    batch = engine.generate_batch(reqs)
    assert len(calls) == 1  # one grouped prefill for all four rows
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens
    # grouped rows share the group's prefill window (the decode_s
    # convention applied to prefill)
    assert len({b.prefill_s for b in batch}) == 1


def test_generate_batch_mixed_buckets_one_prefill_per_group(
    monkeypatch, engine
):
    """Prompts spanning two buckets become two grouped prefills (not
    four solo ones), each row still solo-identical."""
    short = "tok " * 4
    long = "tok " * 12  # beyond the 32-token bucket, inside 64
    reqs = [
        GenerationRequest("tiny-a", short + "a", max_new_tokens=6),
        GenerationRequest("tiny-a", long + "b", max_new_tokens=6),
        GenerationRequest("tiny-a", short + "c", max_new_tokens=6),
        GenerationRequest("tiny-a", long + "d", max_new_tokens=6),
    ]
    singles = [engine.generate(r) for r in reqs]
    calls = _spy_prefill_calls(monkeypatch, engine)
    batch = engine.generate_batch(reqs)
    assert len(calls) == 2  # one per prompt bucket
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens


def test_generate_batch_grouped_prefill_with_prefix_cache():
    """Prefix-cache engines still produce solo-identical batches: hit
    rows take the solo path (device-copy prefill), misses group — and a
    grouped prefill does not populate the prefix cache (documented
    trade-off in _batch_states)."""
    registry = {"tiny-p": get_model_config("qwen2:1.5b").tiny()}
    warm = JaxEngine(registry=registry, dtype=jnp.float32, prefix_cache_size=4)
    cold = JaxEngine(registry=registry, dtype=jnp.float32)

    seed_req = GenerationRequest("tiny-p", "shared system prompt", max_new_tokens=4)
    warm.generate(seed_req)  # stores the prefix solo
    n_entries = len(warm._prefix_cache["tiny-p"])

    reqs = [
        GenerationRequest("tiny-p", "shared system prompt", max_new_tokens=6),
        GenerationRequest("tiny-p", "a fresh question", max_new_tokens=6),
        GenerationRequest("tiny-p", "another new ask", max_new_tokens=6),
    ]
    singles = [cold.generate(r) for r in reqs]
    batch = warm.generate_batch(reqs)
    for s, b in zip(singles, batch):
        assert b.tokens == s.tokens
    # grouped (miss) rows did not store prefixes; the solo hit row re-stored
    assert len(warm._prefix_cache["tiny-p"]) <= n_entries + 1


def test_batch_results_carry_explicit_decode_window_ids(engine):
    """Every generate_batch result carries extras["decode_window"] — the
    contract bench.py's distinct-window accounting relies on (float
    equality of decode_s silently miscounts windows; docs/PERF.md)."""
    reqs = [
        GenerationRequest("tiny-a", f"w{i}", max_new_tokens=4, seed=i)
        for i in range(3)
    ]
    batch = engine.generate_batch(reqs)
    wids = {r.extras["decode_window"] for r in batch}
    assert len(wids) == 1  # one chunk → one shared window id
    again = engine.generate_batch(reqs)
    assert {r.extras["decode_window"] for r in again} != wids  # fresh id


def test_assemble_rows_matches_naive_assembly_randomized():
    """Property test for the fused row assembly: for random mixtures of
    grouped and solo states, group sizes, member orderings and padding,
    _assemble_rows' gather+permutation output must equal the naive
    per-row construction (the pre-round-5 slice-and-concat semantics).
    The identity-skip fast paths make this worth fuzzing: they engage
    only for full in-order groups, and a wrong skip would scramble rows
    silently."""
    import numpy as np

    registry = {"tiny-a": get_model_config("qwen2:1.5b").tiny()}
    eng = JaxEngine(registry=registry, dtype=jnp.float32)
    rng = np.random.default_rng(7)

    for trial in range(12):
        n_groups = int(rng.integers(0, 3))
        groups = []
        for g in range(n_groups):
            gb = int(rng.choice([2, 4]))
            shared = {
                "first": jnp.asarray(
                    rng.integers(0, 99, gb), jnp.int32
                ),
                "presence": jnp.asarray(rng.random((gb, 5)) < 0.5),
                "rng": jnp.asarray(
                    rng.integers(0, 2**31, (gb, 2)), jnp.uint32
                ),
            }
            members = list(rng.permutation(gb))[: int(rng.integers(1, gb + 1))]
            groups.append((shared, members))
        n_solo = int(rng.integers(0 if n_groups else 1, 3))
        solo_vals = []
        for s in range(n_solo):
            solo_vals.append(
                {
                    "first": jnp.asarray(
                        rng.integers(0, 99, 1), jnp.int32
                    ),
                    "presence": jnp.asarray(rng.random((1, 5)) < 0.5),
                    "rng": jnp.asarray(
                        rng.integers(0, 2**31, 2), jnp.uint32
                    ),
                }
            )
        # interleave grouped and solo rows in a random global order
        entries = []
        for gi_, (shared, members) in enumerate(groups):
            for m in members:
                entries.append(("g", gi_, m))
        for si in range(n_solo):
            entries.append(("s", si, None))
        order = rng.permutation(len(entries))
        states = []
        for idx in order:
            kind, a, b_ = entries[idx]
            if kind == "g":
                states.append({"group": groups[a][0], "gi": int(b_)})
            else:
                states.append(dict(solo_vals[a]))
        n = len(states)
        b_bucket = _bucket(n, (1, 2, 4, 8, 16))
        asm = eng._assemble_rows(
            states, b_bucket, eng._row_field_specs(states)
        )
        # naive reference: per-row values + row-0 padding
        def naive(field, solo_key):
            rows = []
            for st in states:
                if "group" in st:
                    rows.append(np.asarray(st["group"][field])[st["gi"]])
                else:
                    v = np.asarray(st[solo_key])
                    rows.append(v[0] if field != "rng" else v)
            rows += [rows[0]] * (b_bucket - n)
            return np.stack(rows)

        np.testing.assert_array_equal(
            np.asarray(asm["first"]), naive("first", "first")
        )
        np.testing.assert_array_equal(
            np.asarray(asm["presence"]), naive("presence", "presence")
        )
        np.testing.assert_array_equal(
            np.asarray(asm["rng"]), naive("rng", "rng")
        )


def test_assemble_rows_identity_fast_paths():
    """Deterministic pin for _assemble_rows' two zero-copy skips, which
    the randomized trials rarely generate: ONE full group whose members
    appear in gi-order and fill the batch bucket exactly engages both
    the identity gather (members == range(gb)) and the identity take
    (perm == arange, no padding). A wrong skip scrambles rows silently —
    so the output is checked value-for-value, not just for shape."""
    import numpy as np

    registry = {"tiny-a": get_model_config("qwen2:1.5b").tiny()}
    eng = JaxEngine(registry=registry, dtype=jnp.float32)
    gb = 4
    shared = {
        "first": jnp.asarray([10, 11, 12, 13], jnp.int32),
        "presence": jnp.asarray(np.arange(gb * 5).reshape(gb, 5) % 3 == 0),
        "rng": jnp.asarray(
            np.arange(gb * 2).reshape(gb, 2), jnp.uint32
        ),
    }
    states = [{"group": shared, "gi": i} for i in range(gb)]
    asm = eng._assemble_rows(states, gb, eng._row_field_specs(states))
    np.testing.assert_array_equal(
        np.asarray(asm["first"]), np.asarray(shared["first"])
    )
    np.testing.assert_array_equal(
        np.asarray(asm["presence"]), np.asarray(shared["presence"])
    )
    np.testing.assert_array_equal(
        np.asarray(asm["rng"]), np.asarray(shared["rng"])
    )

    # and the NEAR-miss: same group with members reversed must NOT take
    # the identity path — rows come back in the reversed request order
    rev = [{"group": shared, "gi": gb - 1 - i} for i in range(gb)]
    asm_rev = eng._assemble_rows(rev, gb, eng._row_field_specs(rev))
    np.testing.assert_array_equal(
        np.asarray(asm_rev["first"]),
        np.asarray(shared["first"])[::-1],
    )
