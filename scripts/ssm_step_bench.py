#!/usr/bin/env python3
"""The state-space decode step's kernel (ops/pallas_ssm.py) on the chip, at
the granite cell's shapes: 36 layers of a 32-row bucket with 12 rows live,
as a scan over the record (the step's shape in small). Prints, per tile
size, ms a step and the share of 819 GB/s that the live rows' state, read
and written once, comes to; beside it XLA's update of the whole bucket
(``models/ssm.py::_step`` written back where it lies), and how far the two
agree. Chip only.

    python3 scripts/ssm_step_bench.py [live rows ...]
"""

import sys
import time
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.ssm import _step  # noqa: E402
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops import pallas_ssm  # noqa: E402

LS, B, H, P, N, G = 36, 32, 128, 64, 128, 1
CFG = types.SimpleNamespace(ssm_n_heads=H, ssm_n_groups=G)


def operands(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(ks[0], (LS, B, H, P)),
        jax.random.normal(ks[1], (LS, B, G, N)),
        jax.random.normal(ks[2], (LS, B, G, N)),
        jax.nn.softplus(jax.random.normal(ks[3], (LS, B, H)) - 2.0),
        -jnp.exp(jax.random.uniform(ks[4], (LS, H), minval=0.0, maxval=2.77)),
        jnp.ones((LS, H)),
    )


def kernel_step(s, mask, ops):
    rows, n = pallas_ssm.live_rows(mask, B)

    def layer(s, xs):
        at, (x, bm, cm, dt, a_neg, d) = xs
        y, s = pallas_ssm.ssm_step_live(s, at, rows, n, x, bm, cm, dt, a_neg, d)
        return s, y

    return jax.lax.scan(layer, s, (jnp.arange(LS), ops))


def bucket_step(s, mask, ops):
    def layer(s, xs):
        at, (x, bm, cm, dt, a_neg, d) = xs
        s0 = jax.lax.dynamic_index_in_dim(s, at, 0, keepdims=False)
        y, s1 = _step(CFG, s0, x, bm, cm, jnp.where(mask[:, None], dt, 0.0), a_neg, d)
        return jax.lax.dynamic_update_index_in_dim(s, s1, at, 0), y

    return jax.lax.scan(layer, s, (jnp.arange(LS), ops))


def timed(fn, s, mask, ops, reps=10):
    fn = jax.jit(fn, donate_argnums=(0,))
    s, y = fn(s, mask, ops)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(reps):
        s, y = fn(s, mask, ops)
    jax.block_until_ready(s)
    return (time.perf_counter() - t0) / reps * 1e3, s, y


def main(argv):
    assert jax.default_backend() == "tpu", "chip only"
    print(jax.devices()[0].device_kind, flush=True)
    ops = operands(1)
    for live in [int(a) for a in argv] or [12]:
        mask = jnp.zeros((B,), bool).at[jnp.arange(live) * (B // max(live, 1)) % B].set(live > 0)
        live = int(mask.sum())
        least_ms = 2 * live * LS * H * P * N * 4 / 819e9 * 1e3
        s0 = jax.random.normal(jax.random.PRNGKey(0), (LS, B, H, P, N))
        ms, s_ref, y_ref = timed(bucket_step, s0, mask, ops)
        print(f"live {live}: xla-bucket {ms:.3f} ms a step ({least_ms / ms:.1%} of 819 GB/s for the live rows)", flush=True)
        s_ref, y_ref = np.asarray(s_ref[:, :, :2]), np.asarray(y_ref)
        for mb in (0.5, 1, 2, 4):
            pallas_ssm.STEP_STATE_BYTES = int(mb * (1 << 20))
            s0 = jax.random.normal(jax.random.PRNGKey(0), (LS, B, H, P, N))
            ms, s, y = timed(kernel_step, s0, mask, ops)
            live_y = np.asarray(mask)[None, :, None, None]
            print(
                f"live {live}: pallas-live tile {mb} MB {ms:.3f} ms a step ({least_ms / ms:.1%}); "
                f"max |s - s_ref| {np.abs(np.asarray(s[:, :, :2]) - s_ref).max():.3g}, "
                f"max |y - y_ref| {np.abs(np.asarray(y) - np.where(live_y, y_ref, 0)).max():.3g} "
                f"of {np.abs(y_ref).max():.3g}",
                flush=True,
            )
            del s, y


if __name__ == "__main__":
    main(sys.argv[1:])
