"""Int8 weight-only quantization: accuracy, size, engine + TP integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
    GenerationRequest,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import JaxEngine
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.config import (
    get_model_config,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
    DEFAULT_QUANT_KEYS,
    is_quantized,
    maybe_dequant,
    params_nbytes,
    quantize_params,
    quantize_tensor,
)
from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.transformer import (
    Transformer,
    forward,
    logits_for,
)


def test_quantize_tensor_round_trip_accuracy():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128)) * 0.05
    q = quantize_tensor(w)
    assert q["q"].dtype == jnp.int8
    deq = maybe_dequant(q, jnp.float32)
    # symmetric int8: relative error bounded by ~1/127 of the channel max
    err = np.abs(np.asarray(deq) - np.asarray(w))
    per_channel_max = np.abs(np.asarray(w)).max(axis=0)
    assert (err <= per_channel_max / 127.0 * 1.01 + 1e-8).all()


def test_maybe_dequant_passthrough():
    w = jnp.ones((4, 4))
    assert maybe_dequant(w) is w


def test_quantize_params_halves_size():
    cfg = get_model_config("qwen2:1.5b").tiny()
    tf = Transformer.initialise(cfg, seed=0, dtype=jnp.bfloat16)
    qparams = quantize_params(tf.params)
    for key in DEFAULT_QUANT_KEYS:
        assert is_quantized(qparams[key])
    # embeddings quantize too (int8 in every mode): the logits matmul
    # streams them every decode step
    assert is_quantized(qparams["embed"])
    assert params_nbytes(qparams) < 0.6 * params_nbytes(tf.params)


def test_quantized_forward_close_to_full_precision():
    cfg = get_model_config("mistral:7b").tiny()
    tf = Transformer.initialise(cfg, seed=1, dtype=jnp.float32)
    toks = jnp.array([[3, 7, 11, 2]], dtype=jnp.int32)
    k0, v0 = tf.init_cache(1, 8, dtype=jnp.float32)
    hidden_fp, _, _ = forward(tf.params, cfg, toks, jnp.int32(0), k0, v0)
    logits_fp = logits_for(tf.params, cfg, hidden_fp[:, -1])
    qparams = quantize_params(tf.params)
    hidden_q, _, _ = forward(qparams, cfg, toks, jnp.int32(0), k0, v0)
    logits_q = logits_for(qparams, cfg, hidden_q[:, -1])
    # int8 weight noise shifts logits slightly; ranking of the top token is
    # a weak ask for random weights, so compare the distributions
    corr = np.corrcoef(
        np.asarray(logits_fp).ravel(), np.asarray(logits_q).ravel()
    )[0, 1]
    assert corr > 0.99


def test_engine_int8_generates_and_shrinks():
    registry = {"t": get_model_config("qwen2:1.5b").tiny()}
    fp = JaxEngine(registry=registry, dtype=jnp.float32)
    q8 = JaxEngine(registry=registry, dtype=jnp.float32, quantize="int8")
    r = q8.generate(GenerationRequest("t", "quantized", 10))
    assert r.generated_tokens <= 10
    fp.load_model("t")
    assert params_nbytes(q8._models["t"].params) < params_nbytes(
        fp._models["t"].params
    )


def test_engine_rejects_unknown_quantize():
    with pytest.raises(ValueError, match="unsupported quantize"):
        JaxEngine(quantize="fp4")


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_tp_engine_with_int8():
    import dataclasses

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import (
        TensorParallelEngine,
    )

    cfg = dataclasses.replace(
        get_model_config("mistral:7b").tiny(),
        n_heads=8,
        n_kv_heads=8,
        d_ff=128,
        d_model=64,
        d_head=16,
    )
    registry = {"t8": cfg}
    single = JaxEngine(registry=registry, dtype=jnp.float32, quantize="int8")
    tp = TensorParallelEngine(
        mesh=build_mesh(MeshSpec.tp_only()),
        registry=registry,
        dtype=jnp.float32,
        quantize="int8",
    )
    req = GenerationRequest("t8", "int8 tensor parallel", max_new_tokens=10)
    assert single.generate(req).tokens == tp.generate(req).tokens

def test_int4_pack_roundtrip():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        maybe_dequant,
        quantize_tensor_int4,
    )

    w = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 8), jnp.float32)
    leaf = quantize_tensor_int4(w)
    assert leaf["q4"].shape == (2, 8, 8)  # packed along the input axis
    assert leaf["q4"].dtype == jnp.int8
    back = maybe_dequant(leaf, jnp.float32)
    assert back.shape == w.shape
    # 4-bit symmetric in [-7,7]: worst-case error is scale/2
    err = jnp.max(jnp.abs(back - w))
    assert float(err) <= float(jnp.max(leaf["s"])) / 2 + 1e-6
    # odd input dim rejected
    with pytest.raises(ValueError, match="even"):
        quantize_tensor_int4(jnp.ones((3, 8)))


def test_int4_forward_close_to_full_precision():
    cfg = get_model_config("mistral:7b").tiny()
    tf = Transformer.initialise(cfg, seed=1, dtype=jnp.float32)
    toks = jnp.array([[3, 7, 11, 2]], dtype=jnp.int32)
    shape = (cfg.n_layers, 1, cfg.n_kv_heads, 8, cfg.d_head)
    z = jnp.zeros(shape, jnp.float32)
    hidden, _, _ = forward(tf.params, cfg, toks, jnp.int32(0), z, z, None)
    full = logits_for(tf.params, cfg, hidden)
    qp = quantize_params(tf.params, mode="int4")
    hidden_q, _, _ = forward(qp, cfg, toks, jnp.int32(0), z, z, None)
    quant = logits_for(qp, cfg, hidden_q)
    # int4 is coarse; the ranking should broadly survive on tiny models
    assert full.shape == quant.shape
    corr = jnp.corrcoef(full.ravel(), quant.ravel())[0, 1]
    assert float(corr) > 0.95


def test_engine_int4_generates_and_shrinks():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.backend import (
        GenerationRequest,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.engine.jax_engine import (
        JaxEngine,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        params_nbytes,
    )

    cfg = get_model_config("qwen2:1.5b").tiny()
    full = JaxEngine(registry={"m": cfg}, dtype=jnp.float32)
    full.load_model("m")
    q4 = JaxEngine(registry={"m": cfg}, dtype=jnp.float32, quantize="int4")
    q4.load_model("m")
    assert params_nbytes(q4._models["m"].params) < 0.45 * params_nbytes(
        full._models["m"].params
    )
    r = q4.generate(GenerationRequest("m", "hello int4", max_new_tokens=8))
    assert r.generated_tokens >= 1


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_tp_engine_with_int4():
    import dataclasses

    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.parallel.tp import (
        TensorParallelEngine,
    )

    cfg = dataclasses.replace(
        get_model_config("mistral:7b").tiny(),
        n_heads=8,
        n_kv_heads=8,
        d_ff=128,
        d_model=64,
        d_head=16,
    )
    registry = {"t4": cfg}
    single = JaxEngine(registry=registry, dtype=jnp.float32, quantize="int4")
    tp = TensorParallelEngine(
        mesh=build_mesh(MeshSpec.tp_only()),
        registry=registry,
        dtype=jnp.float32,
        quantize="int4",
    )
    req = GenerationRequest("t4", "int4 tensor parallel", max_new_tokens=10)
    assert single.generate(req).tokens == tp.generate(req).tokens

    # the i32-lane nibble layout shards the same way ({"q32","s"} leaves)
    single_i = JaxEngine(
        registry=dict(registry), dtype=jnp.float32, quantize="int4-i32"
    )
    tp_i = TensorParallelEngine(
        mesh=build_mesh(MeshSpec.tp_only()),
        registry=dict(registry),
        dtype=jnp.float32,
        quantize="int4-i32",
    )
    assert single_i.generate(req).tokens == tp_i.generate(req).tokens


def test_int4_pallas_matmul_matches_dequant():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        quantize_tensor_int4,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_quant import (
        int4_matmul,
        int4_matmul_supported,
    )

    w = jax.random.normal(jax.random.PRNGKey(0), (512, 256), jnp.float32) * 0.1
    leaf = quantize_tensor_int4(w)
    assert int4_matmul_supported(1, 256, 256)
    # The kernel contracts in bf16 (MXU-native; 4-bit weights are exact in
    # bf16, activations are bf16 in the real decode path) — the reference
    # therefore truncates the activations the same way.
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 512), jnp.float32)
    got = int4_matmul(x, leaf["q4"], leaf["s"])
    x16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    want = x16 @ maybe_dequant(leaf, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )
    # multi-row (speculative verify window) and non-square blocks
    x5 = jax.random.normal(jax.random.PRNGKey(2), (5, 512), jnp.float32)
    got5 = int4_matmul(x5, leaf["q4"], leaf["s"])
    want5 = x5.astype(jnp.bfloat16).astype(jnp.float32) @ maybe_dequant(
        leaf, jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(got5), np.asarray(want5), rtol=1e-4, atol=1e-4
    )


def test_int4_i32_pack_roundtrip_and_kernel_parity():
    """The i32-lane nibble layout (VERDICT round-2 item 8 experiment):
    pack/dequant round-trips exactly against the halves layout, and the
    i32 kernel matches the dequantized reference."""
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        quantize_tensor_int4,
        quantize_tensor_int4_i32,
    )
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_quant import (
        int4_matmul_i32,
    )

    w = jax.random.normal(jax.random.PRNGKey(0), (2048, 256), jnp.float32) * 0.1
    leaf_h = quantize_tensor_int4(w)
    leaf_i = quantize_tensor_int4_i32(w)
    assert leaf_i["q32"].shape == (256, 256)
    assert leaf_i["q32"].dtype == jnp.int32
    # identical quantized values, independent of packing layout
    np.testing.assert_array_equal(
        np.asarray(maybe_dequant(leaf_i, jnp.float32)),
        np.asarray(maybe_dequant(leaf_h, jnp.float32)),
    )

    for rows in (1, 5):
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 2048), jnp.float32)
        got = int4_matmul_i32(x, leaf_i["q32"], leaf_i["s"])
        want = x.astype(jnp.bfloat16).astype(jnp.float32) @ maybe_dequant(
            leaf_i, jnp.float32
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )


def test_int4_dense_dot_routes_and_matches():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        dense_dot,
        quantize_tensor_int4,
    )

    w = jax.random.normal(jax.random.PRNGKey(3), (512, 128), jnp.float32) * 0.1
    leaf = quantize_tensor_int4(w)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 512), jnp.float32)
    kernel_out = dense_dot(x, leaf)  # decode shape → kernel path
    x16 = x.astype(jnp.bfloat16).astype(jnp.float32)
    xla_out = jnp.einsum("bsd,dh->bsh", x16, maybe_dequant(leaf, x.dtype))
    # bf16-contracting kernel vs f32 einsum on bf16-truncated activations
    np.testing.assert_allclose(
        np.asarray(kernel_out), np.asarray(xla_out), rtol=1e-4, atol=1e-4
    )
    # prefill shape falls back to the einsum path, same numbers
    xp = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 512), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dense_dot(xp, leaf)),
        np.asarray(jnp.einsum("bsd,dh->bsh", xp, maybe_dequant(leaf, xp.dtype))),
        rtol=2e-5,
        atol=2e-5,
    )


def test_embed_rowwise_scales_resist_outlier_rows():
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        embed_lookup,
        quantize_tensor_rowwise,
    )

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.02
    w = w.at[7].set(w[7] * 500.0)  # one outlier vocab row
    leaf = quantize_tensor_rowwise(w)
    assert leaf["s"].shape == (64, 1)  # one scale per vocab row
    deq = maybe_dequant(leaf, jnp.float32)
    # non-outlier rows keep their own resolution
    err = jnp.abs(deq[:7] - w[:7])
    assert float(jnp.max(err)) <= float(jnp.max(jnp.abs(w[:7]))) / 127 * 1.01
    # gather path dequantizes row-local
    rows = embed_lookup(leaf, jnp.asarray([[1, 7]]), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rows[0, 0]), np.asarray(deq[1]), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(rows[0, 1]), np.asarray(deq[7]), atol=1e-6
    )


def test_int4_kernel_disabled_context_uses_einsum(monkeypatch):
    import cain_2025_device_remote_llm_energy_rep_pkg_tpu.ops.pallas_quant as pq
    from cain_2025_device_remote_llm_energy_rep_pkg_tpu.models.quantize import (
        dense_dot,
        unpartitioned_kernels_disabled,
        quantize_tensor_int4,
    )

    w = jax.random.normal(jax.random.PRNGKey(3), (512, 128)) * 0.1
    leaf = quantize_tensor_int4(w)
    x = jnp.ones((1, 1, 512), jnp.float32)

    def boom(*a, **k):
        raise AssertionError("kernel must not run under the disabled context")

    monkeypatch.setattr(pq, "int4_matmul", boom)
    with unpartitioned_kernels_disabled():
        out = dense_dot(x, leaf)  # einsum path despite decode shape
    assert out.shape == (1, 1, 128)
