"""Tensor-parallel generation engine over a device mesh.

BASELINE.json's "remote" treatment: where the reference POSTs to an Ollama
server on a second machine (experiment/RunnerConfig.py:122-131), here the
request is served by a TPU slice running Megatron-style TP decode. The model
code is unchanged — params/caches carry NamedShardings (rules in
``sharding.py``) and jit's SPMD partitioner inserts the ICI collectives.

On the single-chip (or CPU) dev environment the same class runs with a 1- or
8-virtual-device mesh, so the treatment is exercised everywhere.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..engine.jax_engine import JaxEngine
from ..models.config import ModelConfig
from ..models.quantize import unpartitioned_kernels_disabled
from .mesh import MeshSpec, build_mesh
from .sharding import (
    cache_shardings,
    quant_cache_shardings,
    replicated,
    shard_model,
    stepped_carry_shardings,
)


class TensorParallelEngine(JaxEngine):
    """JaxEngine with params and KV caches sharded over the mesh's ``tp`` axis.

    All generate paths run with the weight-side Pallas kernels disabled
    (the int4 matmul, the grouped expert FFN): they have no GSPMD
    partitioning rule, so under a mesh the int4 kernel would force the
    partitioner to all-gather the packed weights every step and a real
    chip refuses to partition a Mosaic kernel; the XLA paths partition
    like any other matmul.
    """

    def __init__(self, mesh: Optional[Mesh] = None, **kwargs) -> None:
        super().__init__(**kwargs)
        self.mesh = mesh if mesh is not None else build_mesh(MeshSpec.tp_only())

    def generate(self, request):
        with unpartitioned_kernels_disabled():
            return super().generate(request)

    def generate_batch(self, requests):
        with unpartitioned_kernels_disabled():
            return super().generate_batch(requests)

    def generate_speculative(self, request, draft_model, k=4, prompt_ids=None):
        with unpartitioned_kernels_disabled():
            return super().generate_speculative(
                request, draft_model, k, prompt_ids
            )

    def generate_stream(self, request, chunk_tokens=None):
        kwargs = {} if chunk_tokens is None else {"chunk_tokens": chunk_tokens}
        with unpartitioned_kernels_disabled():
            yield from super().generate_stream(request, **kwargs)

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def load_model(self, model: str) -> None:
        already = model in self._models
        super().load_model(model)
        if not already:
            tf = self._models[model]
            tf.params = shard_model(tf.params, tf.cfg, self.mesh)
            jax.block_until_ready(tf.params)

    def _place_cache(
        self, k_cache: jnp.ndarray, v_cache: jnp.ndarray, cfg: ModelConfig
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        sharding = cache_shardings(cfg, self.mesh)
        return (
            jax.device_put(k_cache, sharding),
            jax.device_put(v_cache, sharding),
        )

    def _place_quant_cache(self, cfg: ModelConfig, cache):
        """Explicit mesh placement of a ``{"q","s"}`` cache leaf (codes
        keep the bf16 cache's head sharding; scales drop the reduced head
        dim) so decode partitions the int8 stream instead of inheriting
        whatever GSPMD inferred for the eager quantization ops."""
        shardings = quant_cache_shardings(cfg, self.mesh)
        return {
            key: jax.device_put(cache[key], shardings[key])
            for key in ("q", "s")
        }

    def _maybe_quantize_cache(self, st):
        st = super()._maybe_quantize_cache(st)
        if self.kv_quantize:
            cfg = st["tf"].cfg
            st["k_cache"] = self._place_quant_cache(cfg, st["k_cache"])
            st["v_cache"] = self._place_quant_cache(cfg, st["v_cache"])
        return st

    def _quantize_batch_cache(self, model, k_cache, v_cache):
        kq, vq = super()._quantize_batch_cache(model, k_cache, v_cache)
        cfg = self._models[model].cfg
        return (
            self._place_quant_cache(cfg, kq),
            self._place_quant_cache(cfg, vq),
        )

    # -- stepped-decode sessions on the mesh (ISSUE 8) -----------------------
    # The continuous scheduler's per-iteration carry (engine/stepped.py)
    # is one pytree; these four hooks make it SPMD-clean end to end —
    # explicit placement at open, explicit in/out shardings + donation
    # on the jitted slice step, and the same int4-kernel guard the
    # generate paths apply — so `serve --backend jax-tp --scheduler
    # continuous` runs iteration-level batching on the mesh with the
    # scheduler loop unchanged.
    def _stepped_carry_shardings(self, cfg: ModelConfig, carry, draft_cfg=None):
        """KV payload over heads when they divide ``tp`` (the pool
        reuses the ``pool_scale`` placement for int8 scales), row
        control + page table replicated, a speculative session's draft
        cache by the DRAFT model's own heads — sharding.py holds the
        one rule; this hook just binds the session's carry to it."""
        return stepped_carry_shardings(
            cfg, self.mesh, carry, draft_cfg=draft_cfg
        )

    def _place_carry(self, cfg: ModelConfig, carry, draft_cfg=None):
        shardings = self._stepped_carry_shardings(
            cfg, carry, draft_cfg=draft_cfg
        )
        return jax.tree_util.tree_map(jax.device_put, carry, shardings)

    def _stepped_jit(self, cfg: ModelConfig, carry, fn, draft_cfg=None):
        """The slice step as a pure SPMD program: explicit in/out
        shardings (so a mis-placed leaf is a visible reshard at the jit
        boundary, never a silent per-step host bounce) and, on
        accelerator backends, a donated carry — output KV buffers alias
        the inputs', exactly the monolithic loop's memory profile (CPU
        skips the donation: see jax_engine._stepped_donation). The
        params slot takes default placement either way — for the
        speculative step fn it is the (target, draft) params PAIR, and
        the carry stays argument 1 so the donation covers it."""
        from ..engine.jax_engine import _stepped_donation

        shardings = self._stepped_carry_shardings(
            cfg, carry, draft_cfg=draft_cfg
        )
        repl = replicated(self.mesh)
        return jax.jit(
            fn,
            in_shardings=(None, shardings, None),
            out_shardings=(repl, repl, shardings),
            **_stepped_donation(),
        )

    def _row_install_jit(self, cfg: ModelConfig, carry, fn, draft_cfg=None):
        """A session's row install with the carry's shardings declared
        on its way in and out: every leaf leaves the program where the
        slice step expects it, so nothing has to be re-placed after a
        join. The row's own arrays (its private cache, placed by
        ``_place_cache``; a few host-built control values) take default
        placement."""
        from ..engine.jax_engine import _stepped_donation

        shardings = self._stepped_carry_shardings(
            cfg, carry, draft_cfg=draft_cfg
        )
        return jax.jit(
            fn,
            in_shardings=(None, shardings),
            out_shardings=shardings,
            **_stepped_donation(),
        )

    def _stepped_compute_ctx(self):
        return unpartitioned_kernels_disabled()

    def _dp_shards(self) -> int:
        """The mesh's ``dp`` extent (ISSUE 19): stepped sessions use it
        to pre-partition their page pool into per-shard ranges aligned
        with the carry's row split."""
        return int(self.mesh.shape.get("dp", 1))

    def mesh_info(self) -> Optional[Dict]:
        dev = self.mesh.devices.flat[0]
        return {
            "devices": int(self.mesh.devices.size),
            "axes": {k: int(v) for k, v in self.mesh.shape.items()},
            "platform": getattr(dev, "platform", "unknown"),
        }

    def _paged_decode_attention(self, cfg: Optional[ModelConfig] = None):
        """TP × stacked-paged composition (VERDICT round-4 weak #3): the
        paged parts kernel has no GSPMD partition rule, but paged decode
        attention is HEAD-independent — so when the model's KV heads
        divide the ``tp`` axis, wrap the kernel in ``shard_map`` with
        heads sharded and everything else (pages, table, lengths)
        replicated-or-local: each device runs the unmodified kernel on
        its head shard, zero collectives inside, and the parts re-enter
        GSPMD head-sharded exactly like the surrounding attention math.
        Heads that don't divide (and unknown ``cfg``) keep the jnp
        gather-through-the-table fallback — the measured-worst path
        (docs/PERF.md), but the only correct one without a head shard."""
        if self.n_devices == 1:
            return super()._paged_decode_attention(cfg)
        if not self._specialised_kernels_enabled():
            return None
        from .sharding import cache_spec

        # Engagement derives from the ONE head-axis divisibility rule
        # (sharding.py cache_spec, which also placed the pool): the
        # shard_map specs below must claim exactly the sharding the pool
        # actually has, or every step pays a hidden reshard.
        if cfg is None or tuple(cache_spec(cfg, self.mesh))[2] != "tp":
            return None  # gather fallback: heads can't shard
        if self._dp_shards() > 1:
            # dp row sharding splits the pool's PAGE dim across the dp
            # axis; the shard_map specs below claim a pure-tp pool, so
            # under dp the kernel would force a per-step all-gather of
            # the pool. The jnp gather fallback partitions under GSPMD
            # (pages resolve shard-locally when the allocator's
            # per-shard ranges hold) — use it.
            return None
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..ops.pallas_paged_attention import (
            pallas_paged_decode_attention_mq_parts,
            pallas_paged_decode_attention_mq_parts_int8,
            pallas_paged_decode_attention_parts,
            pallas_paged_decode_attention_parts_int8,
        )

        mesh = self.mesh
        q_spec = P(None, "tp", None)  # [B, Hq, D]
        pool_spec = P(None, "tp", None, None)  # [P, Hkv, page, D]
        scale_spec = P(None, "tp", None)  # [P, Hkv, page]
        acc_spec = P(None, "tp", None, None)  # [B, Hkv, G, D]
        ml_spec = P(None, "tp", None)  # [B, Hkv, G]
        # multi-query verify block (ISSUE 10): query positions ride a
        # second batch-like dim, heads still the only sharded axis —
        # the kernel stays head-independent, so the same shard_map
        # recipe applies with one more replicated leading dim
        mq_q_spec = P(None, None, "tp", None)  # [B, Q, Hq, D]
        mq_acc_spec = P(None, None, "tp", None, None)  # [B, Q, Hkv, G, D]
        mq_ml_spec = P(None, None, "tp", None)  # [B, Q, Hkv, G]

        def decode_attention(q, kc, vc, lengths):
            if "side" not in kc or kc.get("layer") is not None:
                # only the per-layer stacked parts path is wired through
                # the engine (the whole-stacked-pool "layer" variant has
                # no construction site outside direct kernel tests)
                raise NotImplementedError(
                    "TP paged rule covers the per-layer stacked parts "
                    "path only"
                )
            if q.ndim == 4:
                offsets = kc["write_pos"] + kc["prompt_lens"]
                if isinstance(kc["pool"], dict):
                    def inner_mq_int8(q_, kq_, ks_, vq_, vs_, t_, l_, o_):
                        return pallas_paged_decode_attention_mq_parts_int8(
                            q_, kq_, ks_, vq_, vs_, t_, l_, o_
                        )

                    return shard_map(
                        inner_mq_int8,
                        mesh=mesh,
                        in_specs=(
                            mq_q_spec, pool_spec, scale_spec,
                            pool_spec, scale_spec, P(), P(), P(),
                        ),
                        out_specs=(mq_acc_spec, mq_ml_spec, mq_ml_spec),
                        check_vma=False,
                    )(
                        q,
                        kc["pool"]["q"], kc["pool"]["s"],
                        vc["pool"]["q"], vc["pool"]["s"],
                        kc["table"], lengths, offsets,
                    )

                def inner_mq(q_, k_, v_, t_, l_, o_):
                    return pallas_paged_decode_attention_mq_parts(
                        q_, k_, v_, t_, l_, o_
                    )

                return shard_map(
                    inner_mq,
                    mesh=mesh,
                    in_specs=(
                        mq_q_spec, pool_spec, pool_spec, P(), P(), P(),
                    ),
                    out_specs=(mq_acc_spec, mq_ml_spec, mq_ml_spec),
                    check_vma=False,
                )(q, kc["pool"], vc["pool"], kc["table"], lengths, offsets)
            if isinstance(kc["pool"], dict):
                # int8 pool: codes shard like the pool, the per-position
                # scales like the head-reduced pool_scale placement —
                # the kernel's head-independence is unchanged (each
                # device folds its own head shard's scales)
                def inner_int8(q_, kq_, ks_, vq_, vs_, t_, l_):
                    return pallas_paged_decode_attention_parts_int8(
                        q_, kq_, ks_, vq_, vs_, t_, l_
                    )

                return shard_map(
                    inner_int8,
                    mesh=mesh,
                    in_specs=(
                        q_spec, pool_spec, scale_spec,
                        pool_spec, scale_spec, P(), P(),
                    ),
                    out_specs=(acc_spec, ml_spec, ml_spec),
                    check_vma=False,
                )(
                    q,
                    kc["pool"]["q"], kc["pool"]["s"],
                    vc["pool"]["q"], vc["pool"]["s"],
                    kc["table"], lengths,
                )

            def inner_fn(q_, k_, v_, t_, l_):
                return pallas_paged_decode_attention_parts(
                    q_, k_, v_, t_, l_
                )

            return shard_map(
                inner_fn,
                mesh=mesh,
                in_specs=(q_spec, pool_spec, pool_spec, P(), P()),
                out_specs=(acc_spec, ml_spec, ml_spec),
                check_vma=False,
            )(q, kc["pool"], vc["pool"], kc["table"], lengths)

        return decode_attention

    def _paged_decode_impl(
        self,
        cfg: ModelConfig,
        rows: int,
        table_width: int,
        shared_pages: bool,
    ) -> str:
        """On a mesh the rule above has two outcomes whatever the
        shapes: the Pallas parts kernel under ``shard_map``, or the jnp
        gather path."""
        if self.n_devices == 1:
            return super()._paged_decode_impl(
                cfg, rows, table_width, shared_pages
            )
        if self._paged_decode_attention(cfg) is None:
            return "gather"
        return "pallas"

    def _prefill_attention_for(self, cfg: ModelConfig):
        """The flash-prefill Pallas kernel under a multi-device mesh:
        Mosaic kernels have no GSPMD partition rule (on real chips the
        compile refuses: "Mosaic kernels cannot be automatically
        partitioned" — interpret mode on CPU never notices), but
        prefill attention is HEAD-independent like the paged parts
        kernel above. When the KV heads divide ``tp`` — the same
        ``cache_spec`` rule that placed the cache — run the unmodified
        kernel per head shard inside ``shard_map``; otherwise use the
        jnp path, which GSPMD partitions."""
        kernel = super()._prefill_attention_for(cfg)
        if kernel is None or self.n_devices == 1:
            return kernel
        from .sharding import cache_spec

        if tuple(cache_spec(cfg, self.mesh))[2] != "tp":
            return None
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        q_spec = P(None, None, "tp", None)  # [B, S, Hq, D]
        kv_spec = P(None, "tp", None, None)  # [B, Hkv, T, D]

        def prefill_attention(q, k_cache, v_cache, offset):
            return shard_map(
                kernel,
                mesh=self.mesh,
                in_specs=(q_spec, kv_spec, kv_spec, P()),
                out_specs=q_spec,
                check_vma=False,
            )(q, k_cache, v_cache, offset)

        return prefill_attention

    def _decode_attention_for_cache(self, cfg=None):
        """The int8 flash-decode Pallas kernel has no GSPMD partitioning
        rule (like the int4 matmul kernel) — under a real multi-device
        mesh the jnp fallback path partitions fine, so use it there."""
        if self.kv_quantize and self.n_devices > 1:
            return None
        return super()._decode_attention_for_cache(cfg)
