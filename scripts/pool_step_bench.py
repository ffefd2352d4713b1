#!/usr/bin/env python3
"""The pool part of paged decode (ops/pallas_paged_attention.py) on the
chip, at the cells' shapes: a scan over the layers of a session's stacked
pool, the step's pool part in small, with the live rows holding two pages
each (prompts of 129-256 tokens) and the bucket's other rows parked on one
page with a stale length, as a session holds them. Prints ms a step of
XLA's read of the whole pool in place (``xla_paged_decode_attention_parts``
with ``owners``: every page scored, the pages nobody holds masked) and of
the held-page kernel (``pallas_paged_pool_parts``), the share of 819 GB/s
that the held pages' bytes come to, and how far the two agree. Chip only.

    python3 scripts/pool_step_bench.py [cell ...]
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops import pallas_paged_attention as ppa  # noqa: E402

# layers, row bucket, query heads, kv heads, head width, pool pages, live rows
CELLS = {
    "phi3": (32, 16, 32, 32, 96, 32, 7),
    "mistral": (32, 16, 32, 8, 128, 64, 8),
    "granite": (4, 32, 32, 8, 128, 128, 14),
}
PAGE, DP, JMAX, REPS = 128, 128, 4, 50


def xla_pool(q, kp, vp, table, lens):
    owners = ppa.pool_page_owners(table, lens, kp.shape[1], PAGE)

    def layer(acc, pools):
        a, m, l = ppa.xla_paged_decode_attention_parts(q, *pools, table, lens, owners=owners)
        return acc + a / jnp.where(l > 0, l, 1.0)[..., None], None

    b, hq, d = q.shape
    hkv = kp.shape[2]
    return jax.lax.scan(layer, jnp.zeros((b, hkv, hq // hkv, d)), (kp, vp))[0]


def pallas_pool(q, kp, vp, table, lens):
    owners = ppa.held_page_list(ppa.pool_page_owners(table, lens, kp.shape[1], PAGE))

    def layer(acc, at):
        a, m, l = ppa.pallas_paged_pool_parts(q, kp, vp, at, lens, owners)
        return acc + a / jnp.where(l > 0, l, 1.0)[..., None], None

    b, hq, d = q.shape
    hkv = kp.shape[2]
    return jax.lax.scan(layer, jnp.zeros((b, hkv, hq // hkv, d)), jnp.arange(kp.shape[0]))[0]


def timed(fn, *args):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS * 1e3, np.asarray(out)


def main(argv):
    assert jax.default_backend() == "tpu", "chip only"
    print(jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(0)
    for cell in argv or list(CELLS):
        layers, b, hq, hkv, d, n_pool, live = CELLS[cell]
        kp, vp = (
            jax.random.normal(jax.random.PRNGKey(i), (layers, n_pool, hkv, PAGE, DP), jnp.bfloat16)
            for i in (1, 2)
        )
        q = jax.random.normal(jax.random.PRNGKey(3), (b, hq, d), jnp.bfloat16)
        pages = rng.permutation(n_pool)
        parking = int(pages[0])
        table = np.full((b, JMAX), parking, np.int32)
        lens = rng.integers(129, 257, b).astype(np.int32)  # the parked rows' stale lengths too
        rows = rng.choice(b, live, replace=False)
        for i, r in enumerate(rows):
            table[r, :2] = pages[1 + 2 * i: 3 + 2 * i]
        held = 2 * live + (live < b)
        page_bytes = layers * hkv * PAGE * DP * 2 * 2  # K and V of one page, every layer
        least_ms = held * page_bytes / 819e9 * 1e3
        args = (q, kp, vp, jnp.asarray(table), jnp.asarray(lens))
        ms, ref = timed(xla_pool, *args)
        print(
            f"{cell}: xla-pool {ms:.3f} ms a step (the whole pool, {n_pool} pages, at 819 GB/s: "
            f"{n_pool * page_bytes / 819e9 * 1e3:.3f} ms)", flush=True,
        )
        ms, got = timed(pallas_pool, *args)
        err = np.abs(got - ref)[rows].max()
        print(
            f"{cell}: pallas-pool {ms:.3f} ms a step ({least_ms / ms:.1%} of 819 GB/s for the {held} held pages, "
            f"{least_ms:.3f} ms); max |out - xla-pool| on live rows {err:.3g} of {np.abs(ref[rows]).max():.3g}",
            flush=True,
        )


if __name__ == "__main__":
    main(sys.argv[1:])
