"""Token sampling: greedy, temperature, top-k, top-p, repeat penalty.

All jit/scan-safe and static-shape friendly: every path returns an int32
token id and runtime knobs (temperature, top_p, repeat_penalty) are traced
scalars selected with ``lax.select``/``where``, so one compiled decode loop
serves all sampling settings. The knobs mirror the Ollama ``options`` the
reference's experiment could set on its requests
(experiment/RunnerConfig.py:128-131 builds ``{model, prompt, stream}``;
Ollama's API additionally accepts ``temperature``, ``top_k``, ``top_p``,
``repeat_penalty`` — this is the server-side implementation of those).

``top_k`` is a *static* int (it changes the computation's lattice);
``top_p``/``repeat_penalty`` are ``None`` to statically disable (keeping the
vocab sort / penalty scatter out of the compiled loop entirely) or traced
scalars to apply.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def apply_repeat_penalty(
    logits: jnp.ndarray,
    presence: jnp.ndarray,
    penalty: "jnp.ndarray | float",
) -> jnp.ndarray:
    """Discount tokens already emitted (llama.cpp/Ollama semantics).

    ``presence`` is a bool mask [..., vocab] of token ids seen so far
    (prompt + generated). Positive logits divide by ``penalty``, negative
    multiply — so penalty > 1 always moves penalised logits down.
    """
    penalty = jnp.asarray(penalty, dtype=jnp.float32)
    penalised = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(presence, penalised, logits)


def top_p_filter(
    logits: jnp.ndarray, top_p: "jnp.ndarray | float"
) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest prefix of probability-sorted
    tokens whose cumulative mass reaches ``top_p``; mask the rest to -inf.

    Applied to *unscaled* (pre-temperature) logits, matching llama.cpp's
    sampler order. Always keeps at least the argmax (the exclusive-cumsum
    of the top token is 0 < top_p for any top_p > 0).
    """
    top_p = jnp.asarray(top_p, dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    sorted_probs = jnp.sort(probs, axis=-1)[..., ::-1]
    cum_excl = jnp.cumsum(sorted_probs, axis=-1) - sorted_probs
    kept = cum_excl < top_p
    # Smallest kept probability = the inclusion threshold, mapped back to
    # the unsorted lattice by value comparison (ties keep extra tokens —
    # harmless: they had identical probability).
    threshold = jnp.min(
        jnp.where(kept, sorted_probs, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(probs >= threshold, logits, -jnp.inf)


def modified_probs(
    logits: jnp.ndarray,
    temperature: "jnp.ndarray | float",
    top_k: int = 0,
    top_p: "Optional[jnp.ndarray | float]" = None,
) -> jnp.ndarray:
    """The *modified* distribution :func:`sample_token` draws from, as
    explicit probabilities [..., vocab].

    Replicates the sampler chain exactly — top-k mask, then nucleus
    filter on the unscaled logits, then temperature scaling — and
    softmaxes the result. Speculative rejection resampling (ISSUE 16)
    needs both the target's and the draft's modified distributions in
    closed form: the accept test is ``u < min(1, p(x)/q(x))`` and the
    residual is ``max(p − q, 0)``, both over THESE probabilities, which
    is what makes the speculative stream's marginals provably identical
    to plain ancestral sampling from the same chain (Leviathan et al.
    2023, app. A).

    ``temperature``/``top_p`` may be traced arrays but must already be
    shaped to broadcast against ``logits[..., :1]`` (callers with
    per-row knobs and [B, S, V] logits pass ``t[:, None, None]``).
    Temperature is clamped at 1e-6 like the sampler; greedy rows are
    expected to take the argmax lane instead of reading this tensor.
    """
    logits = logits.astype(jnp.float32)
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        logits = top_p_filter(logits, top_p)
    safe_t = jnp.maximum(
        jnp.asarray(temperature, dtype=jnp.float32), 1e-6
    )
    return jax.nn.softmax(logits / safe_t, axis=-1)


@jax.named_scope("sample")
def sample_token(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: "jnp.ndarray | float",
    top_k: int = 0,
    top_p: "Optional[jnp.ndarray | float]" = None,
    presence: Optional[jnp.ndarray] = None,
    repeat_penalty: "Optional[jnp.ndarray | float]" = None,
) -> jnp.ndarray:
    """Sample the next token id from ``logits`` [..., vocab].

    ``temperature`` may be a traced scalar; 0 (or <1e-6) means greedy.
    ``top_k`` is a *static* int (0 disables). ``top_p`` statically disables
    when ``None``, else is a traced scalar in (0, 1]. ``repeat_penalty``
    (with its ``presence`` mask) statically disables when ``None``.
    Order matches llama.cpp's sampler chain: penalties → top-k → top-p →
    temperature — the nucleus is computed on the *unscaled* distribution,
    then temperature reshapes what survived.
    """
    logits = logits.astype(jnp.float32)
    if repeat_penalty is not None and presence is not None:
        logits = apply_repeat_penalty(logits, presence, repeat_penalty)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        logits = top_p_filter(logits, top_p)
    temperature = jnp.asarray(temperature, dtype=jnp.float32)
    safe_t = jnp.maximum(temperature, 1e-6)
    scaled = logits / safe_t
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jax.lax.select(temperature < 1e-6, greedy, sampled)


def sample_token_per_row(
    logits: jnp.ndarray,  # [B, vocab]
    keys: jax.Array,  # [B] rng keys — one independent stream per row
    temperature: jnp.ndarray,  # [B]
    top_k: int = 0,
    top_p: Optional[jnp.ndarray] = None,  # [B]
    presence: Optional[jnp.ndarray] = None,  # [B, vocab]
    repeat_penalty: Optional[jnp.ndarray] = None,  # [B]
) -> jnp.ndarray:
    """Row-independent :func:`sample_token`: each batch row has its own rng
    key and its own sampling knobs, so a row's draw is bit-identical to a
    single-request ``sample_token`` call with that row's key — the property
    that makes batched generation reproduce per-request results exactly.
    ``top_k`` stays static and shared (it shapes the computation)."""
    if presence is None:

        def one(lg, key, t, p):
            return sample_token(
                lg, key, t, top_k, p if top_p is not None else None
            )

        return jax.vmap(one)(
            logits,
            keys,
            temperature,
            top_p if top_p is not None else temperature,
        )

    def one_rp(lg, key, t, p, pres, rp):
        return sample_token(
            lg,
            key,
            t,
            top_k,
            p if top_p is not None else None,
            pres,
            rp,
        )

    return jax.vmap(one_rp)(
        logits,
        keys,
        temperature,
        top_p if top_p is not None else temperature,
        presence,
        repeat_penalty,
    )
