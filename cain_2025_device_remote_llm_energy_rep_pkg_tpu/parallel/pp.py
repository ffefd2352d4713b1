"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` axis.

The reference has no parallelism at all (SURVEY.md §2 "Parallelism &
communication"); pipeline parallelism is part of the first-class scaling
mandate (task brief: the driver dry-runs tp/pp/dp/sp/ep shardings). The
TPU-native formulation leans on the stacked-layer parameter layout
(models/transformer.py): every layer leaf already carries a leading ``[L, …]``
axis, so sharding that axis over the ``pp`` mesh axis *is* the stage
assignment — stage ``i`` holds layers ``[i·L/S, (i+1)·L/S)`` with no
repacking.

Schedule: classic GPipe fill-drain expressed as a single ``lax.scan`` over
``M + S - 1`` ticks inside ``shard_map``. Each tick every stage
1. receives its predecessor's activation via a non-cyclic
   ``lax.ppermute`` shift (neighbour-to-neighbour ICI traffic),
2. runs its local layer slice (an inner ``lax.scan``),
3. the last stage folds the finished microbatch into the loss.

Because the whole schedule is one traced scan, XLA overlaps the ppermute
with the stage compute, and ``jax.value_and_grad`` *through* the schedule
gives exact pipeline-parallel backprop (the transpose of ppermute is the
reverse shift, so cotangents flow stage-by-stage in reverse — a fill-drain
backward pass for free). Gradients of replicated leaves (embeddings, final
norm) are partial per stage and are ``psum``-reduced over ``pp``.

Training attention is cache-free causal self-attention (the numerically
trusted ``ops.attention.prefill_attention``), so the pipelined loss matches
``parallel.train.next_token_loss`` up to f32 reduction order — the parity
test in tests/test_pp.py checks loss *and* grads against the single-device
step.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig, UnsupportedMechanism
from ..models.transformer import NON_LAYER_LEAVES, logits_for, run_blocks
from ..ops.norms import rms_norm
from ..ops.rope import rope_angles

Params = Dict[str, Any]

# Leaves with no leading [L, …] layer axis — replicated across stages.
REPLICATED_LEAVES = NON_LAYER_LEAVES


def pp_param_specs(cfg: ModelConfig, axis: str = "pp") -> Dict[str, P]:
    """PartitionSpec per leaf: the stacked-layer axis over ``axis``."""
    specs: Dict[str, P] = {
        "embed": P(),
        "final_norm": P(),
        "attn_norm": P(axis, None),
        "mlp_norm": P(axis, None),
        "wq": P(axis, None, None),
        "wk": P(axis, None, None),
        "wv": P(axis, None, None),
        "wo": P(axis, None, None),
    }
    # MoE MLP leaves carry an extra expert axis; the stage (layer) axis is
    # still the leading one either way.
    mlp_nd = 4 if cfg.n_experts else 3
    for k in ("w_gate", "w_up", "w_down"):
        specs[k] = P(axis, *([None] * (mlp_nd - 1)))
    if cfg.n_experts:
        specs["router"] = P(axis, None, None)
    if cfg.qkv_bias:
        specs.update(bq=P(axis, None), bk=P(axis, None), bv=P(axis, None))
    if not cfg.tie_embeddings:
        specs["lm_head"] = P()
    return specs


def _pp_local_loss_body(cfg: ModelConfig, n_microbatches: int,
                        n_stages: int, axis: str, reduce: bool = True):
    """Per-device pipeline loss body (runs inside shard_map).

    With ``reduce`` the scalar is ``psum``'d over ``axis`` so every stage
    sees the same value. The grad path differentiates the *unreduced* body
    (loss lives only on the last stage; cotangents reach earlier stages
    through the ppermute transposes exactly once) because the transpose of
    an in-body psum under ``check_vma=False`` over-counts by the axis size.
    """

    def local_loss(local: Params, tokens: jnp.ndarray) -> jnp.ndarray:
        stage = jax.lax.axis_index(axis)
        m = n_microbatches
        b, s = tokens.shape
        if b % m != 0:
            raise ValueError(f"batch {b} not divisible by {m} microbatches")
        mb = tokens.reshape(m, b // m, s)
        inputs, targets = mb[:, :, :-1], mb[:, :, 1:]
        b_mb, s_in = b // m, s - 1

        positions = jnp.broadcast_to(
            jnp.arange(s_in, dtype=jnp.int32)[None, :], (b_mb, s_in)
        )
        cos, sin = rope_angles(positions, cfg.d_head, cfg.rope_theta)
        stacked = {k: v for k, v in local.items() if k not in REPLICATED_LEAVES}
        embed_scale = (
            jnp.asarray(cfg.d_model, local["embed"].dtype) ** 0.5
            if cfg.gemma_norm
            else None
        )

        n_local = cfg.n_layers // n_stages

        def tick(carry, t):
            recv = jax.lax.ppermute(
                carry, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            fed = local["embed"][inputs[jnp.clip(t, 0, m - 1)]]
            if embed_scale is not None:
                fed = fed * embed_scale
            x_in = jnp.where(stage == 0, fed, recv)
            # Same layer math as every other execution mode: zero caches of
            # exactly S_in slots make the cache path pure causal attention.
            cache = jnp.zeros(
                (n_local, b_mb, cfg.n_kv_heads, s_in, cfg.d_head), dtype=x_in.dtype
            )
            x_out, _, _ = run_blocks(
                stacked, cfg, x_in, jnp.int32(0), cache, cache, cos, sin, None
            )
            return x_out, x_out

        x0 = jnp.zeros((b_mb, s_in, cfg.d_model), dtype=local["embed"].dtype)
        _, ys = jax.lax.scan(
            tick, x0, jnp.arange(m + n_stages - 1, dtype=jnp.int32)
        )
        # On the last stage, tick S-1+j finishes microbatch j. Project to the
        # vocab once, over all M finished microbatches — not per tick (the
        # fill/drain ticks' projections would be masked-out dead work).
        finished = ys[n_stages - 1 :]  # [M, b_mb, s_in, D]
        h = rms_norm(
            finished, local["final_norm"], cfg.norm_eps, gemma_style=cfg.gemma_norm
        )
        logits = logits_for(local, cfg, h)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        total = jnp.where(stage == n_stages - 1, -jnp.mean(ll), 0.0)
        return jax.lax.psum(total, axis) if reduce else total

    return local_loss


def _check_stages(cfg: ModelConfig, mesh: Mesh, axis: str) -> int:
    n_stages = mesh.shape[axis]
    if cfg.residual_streams > 1 or len(cfg.layer_runs) > 1:
        raise UnsupportedMechanism(
            "mesh", cfg.name,
            "stages slice ONE run of one-stream layers; several residual "
            "streams or a dense prefix before expert layers are not staged",
        )
    if cfg.n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pp={n_stages}"
        )
    return n_stages


def make_pp_loss(cfg: ModelConfig, mesh: Mesh, n_microbatches: int,
                 axis: str = "pp"):
    """Pipelined next-token loss: (params, tokens [B,S]) → scalar loss.

    Forward evaluation only — do NOT ``jax.grad`` through this (the in-body
    psum's transpose over-counts by the pp axis size under check_vma=False);
    use :func:`make_pp_grad` / :func:`make_pp_train_step` for gradients.
    """
    n_stages = _check_stages(cfg, mesh, axis)
    body = _pp_local_loss_body(cfg, n_microbatches, n_stages, axis)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(pp_param_specs(cfg, axis), P(None, None)),
        out_specs=P(),
        check_vma=False,
    )


def make_pp_grad(cfg: ModelConfig, mesh: Mesh, n_microbatches: int,
                 axis: str = "pp"):
    """(params, tokens) → (loss, grads) through the pipeline schedule.

    Layer-leaf grads are stage-local by construction; replicated-leaf grads
    (embed / final_norm / lm_head) are partial per stage and psum-reduced.
    """
    n_stages = _check_stages(cfg, mesh, axis)
    specs = pp_param_specs(cfg, axis)
    body = _pp_local_loss_body(cfg, n_microbatches, n_stages, axis, reduce=False)

    def vag(local: Params, tokens: jnp.ndarray):
        raw_loss, grads = jax.value_and_grad(body)(local, tokens)
        loss = jax.lax.psum(raw_loss, axis)  # value only; grads seeded unreduced
        grads = {
            k: (jax.lax.psum(g, axis) if k in REPLICATED_LEAVES else g)
            for k, g in grads.items()
        }
        return loss, grads

    return shard_map(
        vag,
        mesh=mesh,
        in_specs=(specs, P(None, None)),
        out_specs=(P(), specs),
        check_vma=False,
    )


def make_pp_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    n_microbatches: int,
    learning_rate: float = 1e-4,
    axis: str = "pp",
):
    """(init_fn, step_fn) for pipeline-parallel training over ``mesh``.

    Mirrors ``parallel.train.make_train_step``'s contract: ``init_fn(params)
    → (placed_params, opt_state)``; ``step(params, opt_state, tokens [B,S])
    → (params, opt_state, loss)`` with B divisible by n_microbatches.
    """
    import optax  # deferred: inference-only deployments never need it

    optimizer = optax.adam(learning_rate)
    specs = pp_param_specs(cfg, axis)
    shardings = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    grad_fn = make_pp_grad(cfg, mesh, n_microbatches, axis)

    def init_fn(params: Params) -> Tuple[Params, Any]:
        params = {
            k: jax.device_put(v, shardings[k]) for k, v in params.items()
        }
        opt_state = jax.jit(optimizer.init)(params)
        return params, opt_state

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step_fn(params: Params, opt_state, tokens: jnp.ndarray):
        loss, grads = grad_fn(params, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        params = jax.lax.with_sharding_constraint(params, shardings)
        return params, opt_state, loss

    return init_fn, step_fn
