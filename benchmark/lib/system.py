"""The system under test: the one place the benchmark touches the
program. Builds the engine the way ``serve --backend jax --paged-kv
--scheduler continuous`` does (``runner/cli.py``), wraps it in a
``ContinuousScheduler`` with the scheduler's defaults, and hands out
``submit_stream`` channels. No HTTP front, no child process: the process
that holds the chip drives the scheduler directly.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

from . import family


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig``, from the fields the configuration's
    family gives for the file's sizes."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
        ModelConfig,
    )

    return ModelConfig(**family.load(c).program_config(c))


class System:
    """Engine + scheduler, started. ``slices`` fills with
    ``(t_end, gap_s, rows)`` for every gap between two decode slices, from
    the scheduler's own ``slice_gap_sink`` probe."""

    def __init__(self, cfg: Dict[str, Any], seed: int) -> None:
        import jax.numpy as jnp

        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
            JaxEngine,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.serve.scheduler import (
            ContinuousScheduler,
        )
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.utils.compile_cache import (
            compile_count,
        )

        eng = cfg["engine"]
        if eng.get("scheduler") != "continuous":
            raise ValueError("only the continuous scheduler is driven here")
        mc = model_config(cfg)
        self.model = mc.name
        self.engine = JaxEngine(
            registry={mc.name: mc},
            dtype=jnp.dtype(eng.get("dtype", "bfloat16")),
            decode_attention=eng.get("decode_attention", "auto"),
            quantize=eng.get("quantize"),
            kv_quantize=eng.get("kv_quantize"),
            paged_kv=bool(eng.get("paged_kv", False)),
            page_size=int(eng.get("page_size", 128)),
            prefix_share=bool(eng.get("prefix_share", False)),
            seed=seed,
        )
        self.compile_count = compile_count
        self.compile_count()  # registers the listener
        self.engine.load_model(mc.name)
        self.scheduler = ContinuousScheduler(self.engine)
        self.slice_steps = int(self.scheduler.slice_steps)
        self.slices: List[Tuple[float, float, int]] = []
        self.scheduler.slice_gap_sink = self._on_gap
        self.scheduler.start()

    def _on_gap(self, gap_s: float, rows: int) -> None:
        self.slices.append((time.monotonic(), float(gap_s), int(rows)))

    def submit(self, prompt: str, output_tokens: int):
        """One greedy request that runs to its token budget."""
        from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
            GenerationRequest,
        )

        return self.scheduler.submit_stream(
            GenerationRequest(
                model=self.model,
                prompt=prompt,
                max_new_tokens=output_tokens,
                temperature=0.0,
                stop_at_eos=False,
            )
        )

    def session_shape(self) -> Dict[str, Any]:
        """Static shapes of the live session, as the scheduler's own
        debug state reports them (empty when idle)."""
        state = self.scheduler.debug_state().get("session") or {}
        keep = ("b_bucket", "active", "free_slots", "pool", "attention")
        return {k: state[k] for k in keep if k in state}

    def close(self) -> None:
        """Stop the scheduler and drop every reference to device state."""
        self.scheduler.stop()
        self.scheduler.slice_gap_sink = None
        self.engine = None
        self.scheduler = None
        gc.collect()
