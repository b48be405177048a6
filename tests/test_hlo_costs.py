"""Trip-weighted HLO cost analyzer unit tests (synthetic HLO + real jits)."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_costs import (normalize_cost_analysis, parse_costs,
                                    trip_weighted_costs)

SAMPLE = """
HloModule m

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %w = f32[8,8]{1,0} parameter(0)
  %d = f32[8,8]{1,0} dot(%w, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
}

%cond (p2: (s32[], f32[8,8])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %d0 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %w0 = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8] get-tuple-element(%w0), index=1
}
"""


def test_dot_flops_from_shapes():
    comps, entry = parse_costs(SAMPLE)
    assert entry == "main"
    # each dot: 2 * 8*8 (out) * 8 (contract) = 1024 flops
    assert comps["main"].flops == pytest.approx(1024)
    assert comps["body"].flops == pytest.approx(1024)


def test_trip_weighting():
    t1 = trip_weighted_costs(SAMPLE, trip_hints=())
    t5 = trip_weighted_costs(SAMPLE, trip_hints=(5,))
    # +1 flop: the while-cond compare counts as one elementwise op
    assert t1["flops"] == pytest.approx(1024 * 2 + 1)    # body once
    assert t5["flops"] == pytest.approx(1024 * 6 + 1)    # 1 top + 5x body


def test_matches_real_scan_exactly():
    def scanned(a, ws):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, a, ws)
        return out
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    comp = jax.jit(scanned).lower(a, ws).compile()
    t = trip_weighted_costs(comp.as_text(), trip_hints=(4,))
    assert t["flops"] == pytest.approx(4 * 2 * 64 ** 3, rel=0.02)


def test_xla_cost_analysis_counts_scan_body_once():
    """The empirical fact that motivates hlo_costs (EXPERIMENTS §Roofline)."""
    def scanned(a, ws):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, a, ws)
        return out
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    comp = jax.jit(scanned).lower(a, ws).compile()
    # keep cost_analysis()'s numeric properties only
    ca = normalize_cost_analysis(comp.cost_analysis())
    assert ca["flops"] == pytest.approx(2 * 64 ** 3, rel=0.02)
