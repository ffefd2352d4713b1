"""The pool part of paged decode as one kernel over the pages rows hold
(ops/pallas_paged_attention.py ``pallas_paged_pool_parts``, interpret mode
on the CPU) against the XLA path it replaces (``_page_parts`` with
``owners``: every pool page scored, the pages nobody holds masked): the
held-page list the kernel's grid walks, the rule that chooses the kernel
(``paged_parts_impl``, ``/debug/state`` -> ``attention.impl``), a tiny
dense paged session served on it, and ``sched.slice``'s
``pool_pages_read``."""

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import jax_engine as je
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import GenerationRequest
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.fake import FakeBackend
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import ModelConfig, get_model_config
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.obs.trace import TRACER
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops import pallas_paged_attention as ppa
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_attention import pallas_decode_attention
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_paged_attention import (
    _page_parts,
    held_page_list,
    paged_pool_supported,
    pallas_paged_pool_parts,
    pool_page_owners,
)

PAGE = 16

# layers, pool pages, kv heads, group, head width (the pool's lanes are
# 128), the table [B, Jmax] and the rows' cached lengths. Parked rows sit
# on one page as a session parks them; unheld pages hold NaN / inf in
# every case.
CASES = {
    # phi3's form in small: MHA, 96-wide heads in 128 lanes
    "phi3-form": (2, 8, 4, 1, 96, [[3, 5, 0], [6, 2, 4], [7, 1, 1]], [20, 40, 5]),
    # mistral's form: GQA, group 4, heads of a whole lane tile
    "mistral-form": (2, 8, 2, 4, 128, [[3, 5, 0], [6, 2, 4], [7, 1, 1]], [32, 33, 16]),
    # granite's four attention layers of one pool
    "granite-four-layers": (4, 12, 2, 4, 128, [[9, 4, 0], [2, 11, 0], [0, 0, 0], [5, 0, 0]], [30, 17, 7, 2]),
    # dead rows parked on page 0 with stale lengths: one claims it
    "parked-stale": (2, 8, 2, 4, 128, [[0, 0, 0], [3, 4, 0], [0, 0, 0], [0, 0, 0]], [20, 25, 9, 40]),
    # a row with no prompt token among rows that have one
    "empty-row": (2, 8, 2, 4, 128, [[1, 2, 0], [5, 0, 0], [6, 7, 0]], [17, 0, 32]),
    # nobody holds a page: the grid's one step visits nobody
    "none-held": (2, 8, 2, 4, 128, [[1, 1, 1], [1, 1, 1]], [0, 0]),
    "one-held": (2, 8, 2, 4, 128, [[6, 1, 1], [1, 1, 1]], [3, 0]),
    # every pool page held
    "all-held": (2, 6, 2, 4, 128, [[4, 0, 2], [1, 5, 3]], [48, 36]),
    # last pages partly filled, by one token and by all but one
    "partial-last-page": (2, 8, 4, 1, 96, [[2, 3, 0], [5, 7, 6]], [17, 47]),
}


def _case(name, seed=0):
    layers, n_pool, hkv, group, d, table, lens = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = [
        jax.random.normal(k, (layers, n_pool, hkv, PAGE, 128)).at[..., d:].set(0.0)
        for k in ks[:2]
    ]
    q = jax.random.normal(ks[2], (len(table), hkv * group, d))
    return q, pool, jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)


def _held(owners):
    return np.asarray(owners.order)[: int(owners.n_held)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_held_page_kernel_gives_the_xla_pool_parts_without_reading_an_unheld_page(case):
    q, (kp, vp), table, lens = _case(case)
    owners = held_page_list(pool_page_owners(table, lens, kp.shape[1], PAGE))
    held = np.zeros(kp.shape[1], bool)
    held[_held(owners)] = True
    expect = {"none-held": 0, "one-held": 1, "all-held": kp.shape[1]}
    if case in expect:
        assert int(owners.n_held) == expect[case]
    unheld = jnp.asarray(~held)[None, :, None, None, None]
    k_bad, v_bad = jnp.where(unheld, jnp.nan, kp), jnp.where(unheld, jnp.inf, vp)
    for at in range(kp.shape[0]):
        acc, m, l = pallas_paged_pool_parts(q, k_bad, v_bad, jnp.int32(at), lens, owners)
        want_acc, want_m, want_l = _page_parts(q, kp[at], vp[at], lens, owners)
        assert acc.shape == want_acc.shape and m.shape == want_m.shape
        empty = ~np.isfinite(np.asarray(want_m))
        # a row that reads nothing: (0, -inf, 0), as the XLA path has it
        np.testing.assert_array_equal(~np.isfinite(np.asarray(m)), empty)
        np.testing.assert_array_equal(np.asarray(l)[empty], 0.0)
        np.testing.assert_array_equal(np.asarray(acc)[empty], 0.0)
        np.testing.assert_allclose(np.where(empty, 0.0, m), np.where(empty, 0.0, want_m), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(l, want_l, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(acc, want_acc, rtol=1e-5, atol=1e-5)


def test_the_held_page_kernel_rounds_a_bfloat16_pool_as_xlas_default_precision_does():
    """bfloat16 pool, as the cells store it: the query and the
    probabilities rounded to it, float32 scores and sums."""
    q, (kp, vp), table, lens = _case("mistral-form", seed=3)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    owners = held_page_list(pool_page_owners(table, lens, kp.shape[1], PAGE))
    acc, m, l = pallas_paged_pool_parts(q, kp, vp, jnp.int32(1), lens, owners)
    want = _page_parts(q.astype(jnp.bfloat16).astype(jnp.float32), kp[1], vp[1], lens, owners)
    for got, ref in zip((acc / l[..., None], m), (want[0] / want[2][..., None], want[1])):
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("seed", range(4))
def test_the_held_list_names_each_held_page_once_by_row_then_slot(seed):
    """``order[:n_held]`` is exactly the pages a real table entry holds,
    in the table's row-major order; the parked rows' page once."""
    rng = np.random.default_rng(seed)
    b, jmax, n_pool = 8, 4, 32
    pages = rng.permutation(n_pool)
    table = np.full((b, jmax), pages[0], np.int32)  # the parking page
    lens = rng.integers(1, 3 * PAGE, b).astype(np.int32)
    live = rng.random(b) < 0.6
    used = 1
    for r in np.flatnonzero(live):
        n = -(-lens[r] // PAGE)
        table[r, :n] = pages[used: used + n]
        used += n
    if seed == 0:
        lens[~live] = 0  # parked rows of length 0 claim nothing
    owners = held_page_list(pool_page_owners(jnp.asarray(table), jnp.asarray(lens), n_pool, PAGE))
    got = _held(owners)
    row, slot = np.asarray(owners.row)[got], np.asarray(owners.slot)[got]
    want = {
        (int(table[r, j]), r, j) for r in np.flatnonzero(live) for j in range(jmax) if j * PAGE < lens[r]
    }
    parked = ~live & (lens > 0)
    assert set(zip(got.tolist(), row.tolist(), slot.tolist())) - want <= {
        (int(pages[0]), r, j) for r in np.flatnonzero(parked) for j in range(jmax) if j * PAGE < lens[r]
    }
    assert len(got) == len(want) + bool(parked.any())  # the parking page once, whoever claims it
    assert np.all(np.diff(row * jmax + slot) > 0)  # by row, then by slot


def test_the_pool_kernel_rule_takes_a_bfloat16_stacked_pool_and_nothing_else(monkeypatch):
    pool = jnp.zeros((2, 4, 2, 128, 128), jnp.bfloat16)
    assert paged_pool_supported(pool)
    assert not paged_pool_supported(pool.astype(jnp.float32))
    assert not paged_pool_supported({"q": pool.astype(jnp.int8), "s": pool[..., 0]})
    assert not paged_pool_supported(pool[..., :96])
    assert je.paged_parts_impl(16, 4, pool) == "xla"  # no TPU here
    monkeypatch.setattr(je, "on_tpu", lambda: True)
    assert je.paged_parts_impl(16, 4, pool) == "pallas-pool"
    assert je.paged_parts_impl(16, 4) == "xla"  # pages with several readers, a latent pool
    assert je.paged_parts_impl(16, 16, pool) == "pallas"  # a wide table keeps the table kernel
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import unpartitioned_kernels_disabled

    with unpartitioned_kernels_disabled():  # a sharded engine's trace
        assert je.paged_parts_impl(16, 4, pool) == "xla"


# -- the session ------------------------------------------------------------------------

# a latent model's pool: one compressed row a token, in the stacked pool's form
LATENT = ModelConfig(
    name="latent-tiny", vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=1,
    d_head=24, d_ff=128, rope_theta=1e7, norm_eps=1e-5, max_seq_len=512,
    attention="latent", q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, mla_scale_q_lora=True, mla_scale_kv_lora=True,
)
TINY = dataclasses.replace(get_model_config("qwen2:1.5b").tiny(), max_seq_len=512)
REGISTRY = {
    "tiny": TINY,
    "tiny-two-blocks": dataclasses.replace(
        get_model_config("mistral:7b").tiny(), name="tiny-two-blocks", blocks_per_layer=2, max_seq_len=512,
    ),
    LATENT.name: LATENT,
}


def _engine(dtype=jnp.float32, **kw):
    return JaxEngine(
        registry=dict(REGISTRY), dtype=dtype, paged_kv=True, decode_attention=pallas_decode_attention, **kw,
    )


def _on_the_chip(monkeypatch, float32=False):
    """The rule as a TPU sees it (the kernel still runs interpreted), and
    with ``float32`` a float32 pool taken too, so that the session's
    tokens can be held to the solo ones exactly."""
    monkeypatch.setattr(je, "on_tpu", lambda: True)
    if float32:
        monkeypatch.setattr(
            ppa, "paged_pool_supported",
            lambda pool: getattr(pool, "ndim", 0) == 5 and pool.dtype == jnp.float32,
        )


@pytest.mark.parametrize("model", ["tiny", "tiny-two-blocks"])
def test_a_session_serves_the_solo_tokens_on_the_held_page_kernel_and_counts_the_pages_it_read(model, monkeypatch):
    """Two rows open, one retires early, a third joins into the pages it
    left: the tokens of the contiguous ``generate_batch``; every slice's
    ``pool_pages_read`` is the held pages the device's list names times
    the steps the slice ran. With two attention blocks a layer each block
    reads its own entry of the stacked pool."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine import stepped

    reqs = [
        GenerationRequest(model, "short row", max_new_tokens=6),
        GenerationRequest(model, "the long-running companion row", max_new_tokens=60, stop_at_eos=False),
        GenerationRequest(model, "joins into the pages the short row left", max_new_tokens=40, stop_at_eos=False),
    ]
    alone = [r.tokens for r in JaxEngine(registry=dict(REGISTRY), dtype=jnp.float32).generate_batch(reqs)]
    _on_the_chip(monkeypatch, float32=True)
    seen = []
    real = stepped.SteppedDecodeSession._pool_pages_read

    def checked(self, live):
        n = real(self, live)
        carry = self.carry
        owners = held_page_list(pool_page_owners(carry["table"], carry["prompt_lens"], self.pool.n_pages, self.page_size))
        seen.append((n, int(owners.n_held)))
        return n

    monkeypatch.setattr(stepped.SteppedDecodeSession, "_pool_pages_read", checked)
    sess = _engine().decode_open(reqs[:2], reserve_rows=4, slice_steps=8)
    assert sess.debug_state()["attention"]["impl"] == "pallas-pool"
    got = {}
    while reqs[0].prompt not in got:
        for res in sess.step():
            got[res.request.prompt] = res.tokens
    sess.join(reqs[2])
    while sess.active:
        for res in sess.step():
            got[res.request.prompt] = res.tokens
        reads = sess.last_slice_side["pool_pages_read"]
        assert reads % seen[-1][0] == 0 and 0 < reads <= seen[-1][0] * 8
    sess.close()
    assert [got[r.prompt] for r in reqs] == alone
    assert len(seen) >= 6 and all(n == held for n, held in seen)


def test_debug_state_names_the_pool_kernel_where_the_rule_takes_it_and_xla_elsewhere(monkeypatch):
    """bfloat16 pool on a TPU: ``pallas-pool``; an int8-KV session and a
    latent model's one-row-a-token pool keep ``xla-pool``, and a session
    with a prefix store (pages with several readers) the table's gather,
    ``xla``; off the TPU ``xla-pool``."""

    def impl(model="tiny", **kw):
        req = GenerationRequest(model, "a prompt", max_new_tokens=4)
        sess = _engine(**kw).decode_open([req], reserve_rows=2)
        try:
            return sess.debug_state()["attention"]["impl"]
        finally:
            sess.close()

    assert impl(dtype=jnp.bfloat16) == "xla-pool"
    _on_the_chip(monkeypatch)
    assert impl(dtype=jnp.bfloat16) == "pallas-pool"
    assert impl(dtype=jnp.bfloat16, kv_quantize="int8") == "xla-pool"
    assert impl(dtype=jnp.bfloat16, prefix_share=True) == "xla"
    assert impl(model=LATENT.name, dtype=jnp.bfloat16) == "xla-pool"


def test_the_scheduler_puts_pool_pages_read_on_every_slice_span_real_and_fake():
    """Off the TPU the session reads the pool in place, ``xla-pool``: every
    page of the pool at each step; the fake twin reads none."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import ContinuousScheduler

    for backend, model in ((_engine(), "tiny"), (FakeBackend(), "m")):
        mark = TRACER.seq()
        sched = ContinuousScheduler(backend, max_batch=4, slice_steps=8)
        sched.start()
        reqs = [
            GenerationRequest(model, f"prompt {i}", max_new_tokens=20 + 9 * i, stop_at_eos=False)
            for i in range(3)
        ]
        try:
            with ThreadPoolExecutor(3) as pool:
                list(pool.map(sched.submit, reqs))
        finally:
            sched.stop()
        spans = [s for s in TRACER.spans(since=mark) if s.name == "sched.slice"]
        assert spans
        for span in spans:
            n = span.attrs["pool_pages_read"]
            pages = span.attrs["pool_pages"]
            assert (n == 0) if model == "m" else (n % pages == 0 and 0 < n <= pages * 8)
