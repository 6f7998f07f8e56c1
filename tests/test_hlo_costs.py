"""Validate the trip-count-aware HLO cost parser against XLA's own
cost_analysis (unscanned) and against trip-count scaling (scanned)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.hlo_costs import analyse_hlo, split_computations
from repro.launch.mesh import make_mesh


def _body(x, w):
    return jnp.tanh(x @ w), None


def _scanned(x, ws):
    y, _ = jax.lax.scan(_body, x, ws)
    return y


def _unrolled(x, ws):
    for i in range(8):
        x, _ = _body(x, ws[i])
    return x


@pytest.fixture(scope="module")
def compiled_pair():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    cs = jax.jit(_scanned).lower(x, ws).compile()
    cu = jax.jit(_unrolled).lower(x, ws).compile()
    return cs, cu


class TestHloCosts:
    def test_matches_xla_on_unrolled(self, compiled_pair):
        _, cu = compiled_pair
        ours = analyse_hlo(cu.as_text()).flops
        xla = cu.cost_analysis()["flops"]
        assert ours == pytest.approx(xla, rel=0.01)

    def test_scan_trip_count_correction(self, compiled_pair):
        cs, cu = compiled_pair
        ours_scan = analyse_hlo(cs.as_text()).flops
        xla_unrolled = cu.cost_analysis()["flops"]
        # corrected scan flops == unrolled flops (8 matmuls)
        assert ours_scan == pytest.approx(xla_unrolled, rel=0.01)
        # and XLA's own number on the scanned version is ~8x too small
        assert cs.cost_analysis()["flops"] == pytest.approx(
            xla_unrolled / 8, rel=0.01)

    def test_nested_scan(self):
        def inner(x, w):
            return jnp.tanh(x @ w), None

        def outer(x, ws):
            def step(c, w_outer):
                y, _ = jax.lax.scan(inner, c, ws_inner)
                return y @ w_outer, None
            y, _ = jax.lax.scan(step, x, ws)
            return y

        ws_inner = jnp.ones((4, 64, 64))
        x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
        c = jax.jit(outer).lower(x, ws).compile()
        flops = analyse_hlo(c.as_text()).flops
        # 3 outer iters x (4 inner matmuls + 1) = 15 matmuls of 2*32*64*64
        expect = 15 * 2 * 32 * 64 * 64
        assert flops == pytest.approx(expect, rel=0.05)

    def test_collectives_scaled_by_trips(self):
        mesh = make_mesh((1,), ("x",))

        def f(xs):
            def step(c, x):
                return c + jax.lax.psum(x, "x"), None
            y, _ = jax.lax.scan(step, jnp.zeros((16,)), xs)
            return y

        sm = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("x"),
            out_specs=jax.sharding.PartitionSpec()))
        xs = jax.ShapeDtypeStruct((5, 16), jnp.float32)
        c = sm.lower(xs).compile()
        costs = analyse_hlo(c.as_text(), n_devices=1)
        # 5 loop iterations => ~5 all-reduce executions counted
        n_ar = costs.collective_counts.get("all-reduce", 0)
        assert n_ar >= 5 or not costs.collective_counts  # 1-dev may elide
