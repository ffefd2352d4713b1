"""A joiner's row installs in ONE device program (engine/stepped.py
``_row_program``, engine/paged_kv.py ``install_pages``).

After a chunked join the carry must equal, leaf for leaf and bit for bit,
what a plain install gives: the reference below is written with numpy
indexing from the carry as it was before the commit and the joiner's
private prefill cache. Real positions hold the prefill's values (through
``quantize_kv_vector`` for an int8 pool), the tail page's padding and the
pool's padding lanes are zero, pages before the shared boundary and every
other row are untouched. One executable serves every prompt length of a
bucket, and the ``session.join.install`` span says so."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import (
    FAKE_PREFIX_PAGE,
    FakeBackend,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
    JaxEngine,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
    ModelConfig,
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
    quantize_kv_vector,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs import metrics as obs
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import (
    pallas_decode_attention,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
    compile_count,
)

# tests/test_latent_experts.py's tiny model: one latent cache row a token
# and attention block, no V leaf; two blocks a layer; an expert layer
LATENT = ModelConfig(
    name="latent-experts-tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=1,
    d_head=24, d_ff=128, rope_theta=1e7, norm_eps=1e-5, max_seq_len=1024,
    attention="latent", q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True, blocks_per_layer=2, d_ff_expert=32,
    n_experts=8, router_width=12, n_zero_experts=4, top_k_experts=3, routed_scaling_factor=6.0,
    renormalize_topk=False, router_bias=True,
)
DENSE = get_model_config("qwen2:1.5b").tiny(max_seq_len=512)
SHARED = "s" * 140  # one full page of 128 and a copy-on-write partial one


def _engine(pool: str, shared: int, stacked: bool) -> JaxEngine:
    if pool == "latent":
        return JaxEngine(
            registry={LATENT.name: LATENT}, dtype=jnp.float32, paged_kv=True,
            quantize="int8", seed=1,
        )
    return JaxEngine(
        registry={"tiny": DENSE},
        dtype=jnp.bfloat16 if pool == "bf16" else jnp.float32,
        paged_kv=True,
        kv_quantize="int8" if pool == "int8" else None,
        prefix_share=bool(shared),
        decode_attention=pallas_decode_attention if stacked else "auto",
    )


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _reference_install(before, pending, sess, request, first, rng, presence):
    """The carry a plain install leaves, from the carry ``before`` the
    commit: numpy indexing, one page and one leaf at a time."""
    want = jax.tree.map(np.array, before)
    r, pages, page = pending.slot, pending.pages, sess.page_size
    s_real = len(pending.ids)
    n_prompt_pages = -(-s_real // page)
    for pool_key, cache in (("pool_k", pending.k_cache), ("pool_v", pending.v_cache)):
        leaf = want[pool_key]
        codes = leaf["q"] if isinstance(leaf, dict) else leaf
        seq = np.asarray(cache)[:, 0]  # [L, Hkv, alloc, D]
        for j in range(min(pending.shared_pages, n_prompt_pages), n_prompt_pages):
            rows = np.zeros(codes.shape[:1] + codes.shape[2:], dtype=seq.dtype)
            n = min(page, s_real - j * page)
            rows[:, :, :n, : seq.shape[-1]] = seq[:, :, j * page : j * page + n]
            if isinstance(leaf, dict):
                q, s = quantize_kv_vector(jnp.asarray(rows))
                leaf["q"][:, pages[j]], leaf["s"][:, pages[j]] = np.asarray(q), np.asarray(s)
            else:
                leaf[:, pages[j]] = rows
    table_row = np.full((sess.jmax,), sess._parking_for(r), dtype=np.int32)
    table_row[: len(pages)] = pages
    want["table"][r] = table_row
    if sess.stacked:
        for side in jax.tree.leaves((want["side_k"], want["side_v"])):
            side[:, r] = 0
    for key, value in (
        ("tokens", first), ("rngs", rng), ("presence", presence), ("offsets", s_real),
        ("prompt_lens", s_real), ("remaining", request.max_new_tokens - 1),
        ("temps", np.float32(request.temperature)), ("top_ps", np.float32(request.top_p)),
        ("rps", np.float32(request.repeat_penalty)), ("done", False),
    ):
        want[key][r] = value
    return want


CASES = [
    pytest.param(pool, shared, stacked, id=f"{pool}-shared{shared}-{'stacked' if stacked else 'legacy'}")
    for pool in ("bf16", "int8")
    for shared in (0, 1)
    for stacked in (False, True)
] + [pytest.param("latent", 0, True, id="latent-shared0-stacked")]


@pytest.mark.parametrize("pool,shared,stacked", CASES)
def test_a_chunked_join_leaves_the_carry_a_plain_install_would(pool, shared, stacked):
    eng = _engine(pool, shared, stacked)
    model = LATENT.name if pool == "latent" else "tiny"
    anchor = GenerationRequest(model, SHARED + " anchor", max_new_tokens=40, stop_at_eos=False)
    joiner = GenerationRequest(
        model, SHARED + " a joiner's own tail, past the page", max_new_tokens=9,
        temperature=0.8, top_p=0.9, repeat_penalty=1.1, seed=11,
    )
    sess = eng.decode_open([anchor], reserve_rows=4, slice_steps=4)
    try:
        assert sess.stacked == stacked
        sess.step(4)
        pending = sess.join_begin(joiner, chunk_tokens=64)
        assert pending.shared_pages == shared
        while not sess.join_step(pending):
            pass
        before = _host(sess.carry)
        r = sess.join_commit(pending)
        got = _host(sess.carry)
        # the solo path's first token: same key, same sampler call
        first = sess.rows[r].generated[0]
        rng = np.asarray(jax.random.split(jax.random.PRNGKey(joiner.seed))[0])
        presence = np.zeros((sess.cfg.vocab_size,), dtype=bool)
        presence[pending.ids + [first]] = True
        want = _reference_install(before, pending, sess, joiner, first, rng, presence)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for path, leaf in jax.tree_util.tree_leaves_with_path(got):
            ref = want
            for key in path:
                ref = ref[key.key]
            assert leaf.dtype == ref.dtype and leaf.shape == ref.shape, path
            np.testing.assert_array_equal(leaf, ref, err_msg=str(path))
        # the tail page past the prompt is zero, and it was written
        last = pending.pages[-(-len(pending.ids) // sess.page_size) - 1]
        tail = len(pending.ids) % sess.page_size
        codes = got["pool_k"]["q"] if pool == "int8" else got["pool_k"]
        assert tail and not codes[:, last, :, tail:].any() and codes[:, last, :, :tail].any()
    finally:
        sess.close()


def test_three_prompt_lengths_of_one_bucket_share_one_install_executable():
    eng = _engine("bf16", 0, False)
    anchor = GenerationRequest("tiny", "a" * 150, max_new_tokens=60, stop_at_eos=False)
    sess = eng.decode_open([anchor], reserve_rows=4, slice_steps=4)
    try:
        install = eng._row_install_fn("tiny", sess.carry)
        # the open ran it, on a slot outside the bucket: compiled, and nothing is seated
        assert sess.row_programs == 1 and sess.active == 1
        executables = install._cache_size()  # the process's, whichever wrapper holds them
        reqs = [GenerationRequest("tiny", "b" * n, max_new_tokens=5, seed=n) for n in (131, 190, 255)]
        solo = [eng.generate(req).tokens for req in reqs]
        results = {}
        for i, req in enumerate(reqs):
            pending = sess.join_begin(req)
            assert pending.cache_len == 256
            while not sess.join_step(pending):
                pass
            compiles = compile_count()
            sess.join_commit(pending)
            # the first commit may meet its sampler for the first time; none compiles an install
            assert i == 0 or compile_count() == compiles, f"prompt {len(pending.ids)} compiled"
            assert install._cache_size() == executables and sess.row_programs == 2 + i
            while id(req) not in results:  # its pages go back before the next joins
                results.update((id(res.request), res.tokens) for res in sess.step(4))
        assert eng._row_install_fn("tiny", sess.carry) is install
        assert [results[id(req)] for req in reqs] == solo
    finally:
        sess.close()


@pytest.fixture
def obs_on():
    was = obs.enabled()
    obs.enable()
    yield
    (obs.enable if was else obs.disable)()


def test_the_install_span_counts_its_programs_and_pages(obs_on):
    eng = _engine("bf16", 1, True)
    anchor = GenerationRequest("tiny", SHARED + " anchor", max_new_tokens=40, stop_at_eos=False)
    sess = eng.decode_open([anchor], reserve_rows=4, slice_steps=4)
    try:
        for prompt, pages in ((SHARED + " shares the first page", 1), ("x" * 300, 3)):
            mark = TRACER.seq()
            sess.join(GenerationRequest("tiny", prompt, max_new_tokens=4))
            (span,) = [s for s in TRACER.spans(since=mark) if s.name == "session.join.install"]
            assert span.attrs == {"programs": 1, "pages": pages}
    finally:
        sess.close()


def test_the_fake_twin_reports_the_same_attributes(obs_on):
    sess = FakeBackend().decode_open([GenerationRequest("m", "anchor", max_new_tokens=30)])
    try:
        mark = TRACER.seq()
        pending = sess.join_begin(GenerationRequest("m", "j" * (2 * FAKE_PREFIX_PAGE), max_new_tokens=4))
        while not sess.join_step(pending):
            pass
        sess.join_commit(pending)
        spans = {s.name: s for s in TRACER.spans(since=mark)}
        install = spans["session.join.install"]
        # 2 pages of bytes and the BOS's; no recurrent state to install
        assert install.attrs == {"programs": 1, "pages": 3, "state_bytes": 0}
        assert install.parent_id == spans["session.join.commit"].span_id
    finally:
        sess.close()
