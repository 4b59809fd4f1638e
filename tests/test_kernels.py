"""Pallas kernel validation: interpret-mode allclose vs pure-jnp oracles,
swept over shapes and dtypes (the per-kernel contract from the assignment).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,Hq,Hkv,D",
    [
        (1, 128, 128, 2, 2, 64),   # MHA
        (2, 256, 256, 4, 2, 64),   # GQA 2:1
        (1, 128, 256, 8, 1, 32),   # MQA, uneven seq
        (2, 128, 128, 4, 4, 128),  # mxu-width head
    ],
)
def test_flash_attention_sweep(B, Sq, Skv, Hq, Hkv, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    expect = ref.flash_attention_ref(qf, kf, vf, causal=True)
    expect = expect.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - expect.astype(jnp.float32))))
    assert err < tol, f"max err {err}"


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    B, S, H, D = 1, 256, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_kv=64, interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    expect = ref.flash_attention_ref(qf, kf, vf, causal=True, window=window)
    expect = expect.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    assert jnp.allclose(out, expect, atol=2e-5)


@pytest.mark.parametrize("C,N", [(3, 1000), (10, 4096), (7, 12345)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_reduce_sweep(C, N, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (C, N), dtype)
    w = jax.random.uniform(jax.random.PRNGKey(1), (C,)) + 0.05
    got = ops.fedavg_reduce({"x": x}, w, interpret=True)["x"]
    expect = ref.fedavg_reduce_ref(x, w / w.sum()).astype(dtype)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    assert jnp.allclose(
        got.astype(jnp.float32), expect.astype(jnp.float32), atol=tol
    )


def test_fedavg_reduce_weight_normalization():
    """Scaling all weights by a constant must not change the result."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 512))
    w = jnp.array([1.0, 2.0, 3.0, 4.0])
    a = ops.fedavg_reduce({"x": x}, w, interpret=True)["x"]
    b = ops.fedavg_reduce({"x": x}, w * 100, interpret=True)["x"]
    assert jnp.allclose(a, b, atol=1e-6)


def test_fedavg_reduce_vs_tree_weighted_mean_oracle():
    """Kernel == tree_weighted_mean on a realistic multi-leaf delta tree:
    non-tile-multiple leaf sizes (padding path), bf16 leaves, and raw
    (unnormalized) example-count weights."""
    from repro.utils import tree_unstack, tree_weighted_mean

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    C = 5
    stacked = {
        "conv": {
            "w": jax.random.normal(ks[0], (C, 3, 3, 1, 16)),  # 144 < tile
            "b": jax.random.normal(ks[1], (C, 16)),
        },
        "fc": jax.random.normal(ks[2], (C, 123, 37)),  # 4551 % 2048 != 0
        "half": jax.random.normal(ks[3], (C, 2049), jnp.bfloat16),
    }
    weights = jnp.array([320.0, 64.0, 128.0, 7.0, 1.0])  # unnormalized counts
    got = ops.fedavg_reduce(stacked, weights, interpret=True)
    expect = tree_weighted_mean(tree_unstack(stacked), np.array(weights))
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(expect)):
        tol = 3e-2 if g.dtype == jnp.bfloat16 else 1e-5
        assert g.dtype == e.dtype
        assert jnp.allclose(
            g.astype(jnp.float32), e.astype(jnp.float32), atol=tol
        ), float(jnp.max(jnp.abs(g.astype(jnp.float32) - e.astype(jnp.float32))))


def test_fedavg_reduce_single_client_identity():
    """C=1: the weighted mean of one delta is the delta itself (any weight)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3000))
    out = ops.fedavg_reduce({"x": x}, jnp.array([17.0]), interpret=True)["x"]
    assert jnp.allclose(out, x[0], atol=1e-6)


@pytest.mark.parametrize("N", [1, 100, 2048, 2049, 12345])
def test_fedavg_reduce_padding_sweep(N):
    """Non-tile-multiple flattened sizes exercise the kernel's pad path."""
    x = jax.random.normal(jax.random.PRNGKey(2), (3, N))
    w = jnp.array([1.0, 2.0, 5.0])
    got = ops.fedavg_reduce({"x": x}, w, interpret=True)["x"]
    expect = ref.fedavg_reduce_ref(x, w / w.sum())
    assert jnp.allclose(got, expect, atol=1e-5)


@pytest.mark.parametrize("C", [40, 300])
def test_fedavg_reduce_wide_cohort(C):
    """Cohorts wide enough to narrow the tile below its default."""
    x = jax.random.normal(jax.random.PRNGKey(4), (C, 5000))
    w = jax.random.uniform(jax.random.PRNGKey(5), (C,)) + 0.05
    got = ops.fedavg_reduce({"x": x}, w, interpret=True)["x"]
    expect = ref.fedavg_reduce_ref(x, w / w.sum())
    assert jnp.allclose(got, expect, atol=1e-5)


# Row counts on both sides of the 32-row block (one block, several, a
# partial last one) and leaf widths on both sides of the tile.
ROW_LAYOUTS = [(2, 16), (10, 4608), (32, 2048), (33, 5000), (70, 144)]


@pytest.mark.parametrize("R,N", ROW_LAYOUTS)
def test_quantize_rows_multirow_bitwise(R, N):
    x = jax.random.normal(jax.random.PRNGKey(R), (R, N)) * 0.1
    scales = jnp.max(jnp.abs(x), axis=-1) * (1.0 / 127.0)
    got = ops.quantize_rows(x, scales, interpret=True)
    assert got.shape == (R, N) and got.dtype == jnp.int8
    assert jnp.array_equal(got, ref.quantize_rows_ref(x, scales))


@pytest.mark.parametrize("R,N", ROW_LAYOUTS)
def test_downcast_bf16_rows_multirow_bitwise(R, N):
    x = jax.random.normal(jax.random.PRNGKey(R), (R, N))
    got = ops.downcast_bf16_rows(x, interpret=True)
    assert got.shape == (R, N) and got.dtype == jnp.bfloat16
    assert jnp.array_equal(got, ref.downcast_bf16_rows_ref(x))


def test_row_kernels_reject_unaligned_tile():
    x = jnp.zeros((2, 300))
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.downcast_bf16_rows(x, tile=100, interpret=True)


@pytest.mark.parametrize("n", [100, 4096, 9999])
def test_quantize_sweep(n):
    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n,)) * 3.0}
    payload = ops.quantize_tree(tree, jax.random.PRNGKey(1), interpret=True)
    deq = ops.dequantize_tree(payload, tree)
    # error bounded by one quantum
    assert float(jnp.max(jnp.abs(deq["a"] - tree["a"]))) <= float(payload["scale"]) * 1.01
    # matches the oracle given the same uniform bits
    from repro.utils import flatten_to_vector
    vec, _ = flatten_to_vector(tree)
    uniform = jax.random.uniform(jax.random.PRNGKey(1), vec.shape, jnp.float32)
    expect = ref.quantize_stochastic_ref(vec, uniform, payload["scale"])
    assert jnp.array_equal(payload["q"], expect)


def test_quantize_stochastic_unbiased():
    """Stochastic rounding is unbiased: E[q*scale] ~= x."""
    x = jnp.full((20000,), 0.3)
    tree = {"x": x}
    accum = jnp.zeros_like(x)
    for s in range(5):
        payload = ops.quantize_tree(tree, jax.random.PRNGKey(s), interpret=True)
        accum = accum + ops.dequantize_tree(payload, tree)["x"]
    mean = float(jnp.mean(accum / 5))
    assert abs(mean - 0.3) < 2e-3


@pytest.mark.parametrize("M,d,F", [(64, 32, 128), (128, 64, 256), (256, 128, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swiglu_sweep(M, d, F, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (M, d), dtype)
    wg = (jax.random.normal(ks[1], (d, F)) * 0.1).astype(dtype)
    wu = (jax.random.normal(ks[2], (d, F)) * 0.1).astype(dtype)
    wd = (jax.random.normal(ks[3], (F, d)) * 0.1).astype(dtype)
    got = ops.swiglu(x, wg, wu, wd, block_m=64, block_f=64, interpret=True)
    expect = ref.swiglu_ref(x, wg, wu, wd)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    assert jnp.allclose(
        got.astype(jnp.float32), expect.astype(jnp.float32), atol=tol
    ), float(jnp.max(jnp.abs(got.astype(jnp.float32) - expect.astype(jnp.float32))))


def test_swiglu_matches_model_mlp():
    """Kernel == the model's mlp_forward (the layer it would replace)."""
    from repro.configs import get_reduced
    from repro.models.base import Ctx
    from repro.models.mlp import mlp_forward, mlp_params

    cfg = get_reduced("qwen3-8b").replace(dtype="float32", param_dtype="float32")
    p = mlp_params(Ctx("init", jax.random.PRNGKey(0), jnp.float32), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    expect = mlp_forward(cfg, p, x)
    got = ops.swiglu(x, p["w_gate"], p["w_up"], p["w_down"], block_m=16, block_f=64, interpret=True)
    assert jnp.allclose(got, expect, atol=1e-4)
