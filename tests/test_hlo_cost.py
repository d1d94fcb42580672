"""Calibration tests for the trip-count-aware HLO cost analyzer."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze, parse_hlo


D, T = 256, 7


def _scanned(x, W):
    def body(c, _):
        return jnp.tanh(c @ W), None
    c, _ = jax.lax.scan(body, x, None, length=T)
    return c


def _unrolled(x, W):
    for _ in range(T):
        x = jnp.tanh(x @ W)
    return x


def test_scan_flops_match_unrolled():
    x, W = jnp.zeros((8, D)), jnp.zeros((D, D))
    fs = analyze(jax.jit(_scanned).lower(x, W).compile().as_text())["flops"]
    fu = analyze(jax.jit(_unrolled).lower(x, W).compile().as_text())["flops"]
    expect = 2 * 8 * D * D * T
    assert fs == pytest.approx(expect, rel=0.01)
    assert fu == pytest.approx(expect, rel=0.01)


def test_xla_cost_analysis_undercounts_scans():
    """Documents WHY the analyzer exists: XLA counts while bodies once."""
    x, W = jnp.zeros((8, D)), jnp.zeros((D, D))
    c = jax.jit(_scanned).lower(x, W).compile()
    cost = c.cost_analysis()
    xla_flops = cost.get("flops", 0.0)
    expect = 2 * 8 * D * D * T
    assert xla_flops < expect * 0.5  # undercount
    assert analyze(c.as_text())["flops"] == pytest.approx(expect, rel=0.01)


def test_grad_flops_about_3x_forward():
    x, W = jnp.zeros((8, D)), jnp.zeros((D, D))
    g = jax.grad(lambda w, x_: jnp.sum(_scanned(x_, w)))
    f = analyze(jax.jit(g).lower(W, x).compile().as_text())["flops"]
    fwd = 2 * 8 * D * D * T
    assert f == pytest.approx(3 * fwd, rel=0.05)


def test_nested_scan_trip_counts_compose():
    def nested(x, W):
        def outer(c, _):
            def inner(h, _):
                return h @ W, None
            h, _ = jax.lax.scan(inner, c, None, length=3)
            return h, None
        c, _ = jax.lax.scan(outer, x, None, length=5)
        return c

    x, W = jnp.zeros((8, D)), jnp.zeros((D, D))
    f = analyze(jax.jit(nested).lower(x, W).compile().as_text())["flops"]
    assert f == pytest.approx(2 * 8 * D * D * 15, rel=0.01)


def test_parse_computations():
    x, W = jnp.zeros((8, D)), jnp.zeros((D, D))
    comps = parse_hlo(jax.jit(_scanned).lower(x, W).compile().as_text())
    assert "__entry__" in comps
    assert any(i.opcode == "while" for i in comps["__entry__"].instrs)


def test_top_contributors():
    x, W = jnp.zeros((8, D)), jnp.zeros((D, D))
    r = analyze(jax.jit(_scanned).lower(x, W).compile().as_text(), top_n=3)
    assert len(r["top_bytes"]) == 3
    assert r["top_flops"][0][0] > 0
