"""The HLO static analyzer (roofline source of truth): trip-count
weighting, dot FLOP formulas, collective byte extraction — validated on
small compiled modules with analytically known answers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze, parse_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_single_matmul_flops():
    M, K, N = 128, 256, 64
    co = _compile(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((M, K), jnp.float32),
                  jax.ShapeDtypeStruct((K, N), jnp.float32))
    s = analyze(co.as_text())
    assert s.flops == pytest.approx(2 * M * K * N, rel=0.01)


def test_scan_multiplies_by_trip_count():
    M, trips = 64, 10

    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=trips)
        return y

    co = _compile(f, jax.ShapeDtypeStruct((M, M), jnp.float32),
                  jax.ShapeDtypeStruct((M, M), jnp.float32))
    s = analyze(co.as_text())
    assert s.flops == pytest.approx(trips * 2 * M ** 3, rel=0.01)
    assert s.n_while >= 1 and s.max_trip == trips


def test_nested_scan_trip_product():
    M, outer, inner = 32, 4, 6

    def f(x, w):
        def obody(c, _):
            def ibody(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(ibody, c, None, length=inner)
            return ci, None
        y, _ = jax.lax.scan(obody, x, None, length=outer)
        return y

    co = _compile(f, jax.ShapeDtypeStruct((M, M), jnp.float32),
                  jax.ShapeDtypeStruct((M, M), jnp.float32))
    s = analyze(co.as_text())
    assert s.flops == pytest.approx(outer * inner * 2 * M ** 3, rel=0.01)


def test_remat_doubles_scan_flops():
    """jax.checkpoint recompute shows up as extra executed FLOPs — the
    useful-FLOP-ratio denominator the assignment asks about."""
    M, trips = 64, 8

    def run(remat):
        def body(c, _):
            return jnp.tanh(c @ c), None

        def f(x):
            b = jax.checkpoint(body) if remat else body
            y, _ = jax.lax.scan(b, x, None, length=trips)
            return jnp.sum(y)

        co = _compile(jax.grad(f), jax.ShapeDtypeStruct((M, M),
                                                        jnp.float32))
        return analyze(co.as_text()).flops

    assert run(True) > run(False) * 1.2


def test_collective_bytes_all_reduce():
    import os
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        import sys; sys.path.insert(0, "src")
        from repro.launch.hlo_analysis import analyze
        mesh = jax.make_mesh((8,), ("x",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        with jax.sharding.set_mesh(mesh):
            f = jax.jit(lambda a, b: a @ b,
                        in_shardings=(NamedSharding(mesh, P(None, "x")),
                                      NamedSharding(mesh, P("x", None))),
                        out_shardings=NamedSharding(mesh, P(None, None)))
            co = f.lower(jax.ShapeDtypeStruct((64, 512), jnp.float32),
                         jax.ShapeDtypeStruct((512, 64), jnp.float32)
                         ).compile()
        s = analyze(co.as_text())
        # contracting-dim sharding → one all-reduce of the (64,64) result
        assert s.collective_bytes.get("all-reduce", 0) == 64*64*4, \\
            s.collective_bytes
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.getcwd(),
                       timeout=300)
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_parse_handles_tuple_types():
    hlo = """HloModule test
%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[4,4]) tuple(%i, %gte)
}
ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4] parameter(0)
  ROOT %d = f32[4,4] dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    comps = parse_hlo(hlo)
    assert "__entry__" in comps
    s = analyze(hlo)
    assert s.flops == 2 * 4 * 4 * 4
