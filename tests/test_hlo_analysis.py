"""The HLO static profiler: exact dot-flop counting through scan loops."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis as H


def _compiled_scan_matmul(reps=7, n=64, k=32):
    def f(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()
    ws = jax.ShapeDtypeStruct((reps, n, n), jnp.float32)
    x = jax.ShapeDtypeStruct((k, n), jnp.float32)
    return jax.jit(f).lower(ws, x).compile()


def test_flops_exact_through_while_loops():
    reps, n, k = 7, 64, 32
    compiled = _compiled_scan_matmul(reps, n, k)
    res = H.analyze(compiled.as_text())
    true = 2 * reps * k * n * n
    assert abs(res["flops"] - true) / true < 0.01


def test_trip_count_multipliers():
    compiled = _compiled_scan_matmul(reps=5)
    comps = H.parse_hlo(compiled.as_text())
    mult = H.execution_multipliers(comps)
    assert any(abs(m - 5.0) < 1e-6 for m in mult.values())


def test_collective_parser_on_psum():
    def f(x):
        return jax.lax.psum(x, "i")
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh
    mesh = make_mesh((1,), ("i",))
    with jax.set_mesh(mesh):
        g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("i"),
                                  out_specs=P()))
        compiled = g.lower(
            jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
    coll = H.collective_bytes(compiled.as_text())
    # single-device: collective may be optimized away; parser must not
    # crash and must return the full kind map
    assert set(coll) >= {"all-reduce", "all-gather", "all-to-all"}


def test_roofline_terms_and_dominant():
    terms = H.roofline_terms(1e15, 1e12, {"all-reduce": 4e9}, chips=256)
    assert terms["compute_s"] > 0
    assert H.dominant_term(terms) == "compute_s"
    terms2 = H.roofline_terms(1e12, 8.19e12, {"all-reduce": 0.0},
                              chips=256)
    assert H.dominant_term(terms2) == "memory_s"
