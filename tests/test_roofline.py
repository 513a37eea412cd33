"""Validation of the loop-aware HLO analyzer against XLA's own
cost_analysis (loop-free modules) and against analytic expectations
(loop trip counts, collectives)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.hlo_parse import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matmul_flops_match_xla():
    M, K, N = 128, 256, 64
    A = jax.ShapeDtypeStruct((M, K), jnp.float32)
    B = jax.ShapeDtypeStruct((K, N), jnp.float32)
    comp = _compile(lambda a, b: a @ b, A, B)
    cost = analyze_hlo(comp.as_text())
    xla_flops = comp.cost_analysis()["flops"]
    assert abs(cost.flops - 2 * M * K * N) / (2 * M * K * N) < 0.01
    assert abs(cost.flops - xla_flops) / xla_flops < 0.05


def test_scan_flops_scale_with_trip_count():
    M, L = 64, 12

    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    X = jax.ShapeDtypeStruct((M, M), jnp.float32)
    W = jax.ShapeDtypeStruct((L, M, M), jnp.float32)
    comp = _compile(f, X, W)
    cost = analyze_hlo(comp.as_text())
    expect = L * 2 * M * M * M
    # XLA's own count misses the trip count:
    assert comp.cost_analysis()["flops"] < 0.2 * expect
    assert abs(cost.flops - expect) / expect < 0.05


def test_nested_scan_multiplies():
    M, L1, L2 = 32, 4, 6

    def f(x, ws):
        def outer(x, wrow):
            def inner(x, w):
                return x @ w, None
            x, _ = jax.lax.scan(inner, x, wrow)
            return x, None
        x, _ = jax.lax.scan(outer, x, ws)
        return x

    X = jax.ShapeDtypeStruct((M, M), jnp.float32)
    W = jax.ShapeDtypeStruct((L1, L2, M, M), jnp.float32)
    cost = analyze_hlo(_compile(f, X, W).as_text())
    expect = L1 * L2 * 2 * M ** 3
    assert abs(cost.flops - expect) / expect < 0.05


def test_bytes_reasonable_for_elementwise():
    N = 1 << 16
    X = jax.ShapeDtypeStruct((N,), jnp.float32)
    comp = _compile(lambda x: jnp.tanh(x) * 2 + 1, X)
    cost = analyze_hlo(comp.as_text())
    # one read + one write of the buffer, within 3x slack for copies
    assert 2 * 4 * N * 0.5 <= cost.bytes <= 2 * 4 * N * 3


def test_collective_bytes_counted():
    import os
    from jax.sharding import NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 2:
        import pytest
        pytest.skip("needs >1 device")


def test_dot_general_batched():
    B, M, K, N = 8, 32, 64, 16
    A = jax.ShapeDtypeStruct((B, M, K), jnp.float32)
    Bm = jax.ShapeDtypeStruct((B, K, N), jnp.float32)
    comp = _compile(lambda a, b: jnp.einsum("bmk,bkn->bmn", a, b), A, Bm)
    cost = analyze_hlo(comp.as_text())
    expect = B * 2 * M * K * N
    assert abs(cost.flops - expect) / expect < 0.05


def test_peaks_keyed_by_device_kind():
    """Peaks come from one table keyed by device_kind; an unknown kind
    (the CPU included) is an error, never a default."""
    import pytest
    from repro.roofline.analysis import analyze, peaks

    v5e = peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    comp = _compile(lambda x: x * 2 + 1,
                    jax.ShapeDtypeStruct((1024,), jnp.float32))
    terms = analyze(comp, chips=1, model_flops=0.0, device_kind="TPU v5 lite")
    assert terms.memory_s == terms.bytes_per_device / 819e9 > 0
    assert terms.to_dict()["device_kind"] == "TPU v5 lite"
    with pytest.raises(ValueError, match="no published peaks"):
        analyze(comp, chips=1, model_flops=0.0,
                device_kind=jax.devices()[0].device_kind)
