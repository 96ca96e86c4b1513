"""hlo_cost: trip-count-aware FLOP/byte/collective accounting vs known programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_cost


def compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_single_matmul_flops():
    a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
    txt = compile_text(lambda a, b: a @ b, a, b)
    out = hlo_cost.analyze(txt)
    assert out["flops"] == pytest.approx(2 * 128 * 256 * 64, rel=0.01)


def test_scan_multiplies_trip_count():
    w = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y

    out = hlo_cost.analyze(compile_text(f, x, w))
    expected = 10 * 2 * 128 ** 3
    assert out["flops"] == pytest.approx(expected, rel=0.01)


def test_nested_scan():
    w = jax.ShapeDtypeStruct((4, 3, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x, w):
        def outer(c, wo):
            def inner(ci, wi):
                return ci @ wi, None
            c2, _ = jax.lax.scan(inner, c, wo)
            return c2, None
        y, _ = jax.lax.scan(outer, x, w)
        return y

    out = hlo_cost.analyze(compile_text(f, x, w))
    assert out["flops"] == pytest.approx(12 * 2 * 64 ** 3, rel=0.01)


def test_batched_dot_flops():
    a = jax.ShapeDtypeStruct((8, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 32, 16), jnp.float32)
    txt = compile_text(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), a, b)
    out = hlo_cost.analyze(txt)
    assert out["flops"] == pytest.approx(2 * 8 * 64 * 32 * 16, rel=0.01)


def test_bytes_scale_with_scan():
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)

    def f(x):
        def body(c, _):
            return c * 1.0001 + 1.0, None
        y, _ = jax.lax.scan(body, x, None, length=16)
        return y

    out = hlo_cost.analyze(compile_text(f, x))
    # 16 iterations each read+write ~4MB
    assert out["bytes"] >= 16 * 2 * 1024 * 1024 * 4 * 0.9


def test_collectives_trip_scaled():
    import os
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.launch import hlo_cost
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("model",))
w = jax.ShapeDtypeStruct((4, 256, 256), jnp.float32)
x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
def f(x, w):
    def body(c, wi):
        return c @ wi, None
    y, _ = jax.lax.scan(body, x, w)
    return y
with mesh:
    j = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, None)),
                                 NamedSharding(mesh, P(None, None, "model"))),
                out_shardings=NamedSharding(mesh, P(None, None)))
    txt = j.lower(x, w).compile().as_text()
out = hlo_cost.analyze(txt)
coll = out["collectives"]["total"]
# 4 iterations + final: all-gather of the per-device shard 128 x 32 fp32
assert coll >= 5 * 128 * 32 * 4 * 0.9, coll
assert coll <= 6 * 128 * 256 * 4, coll
print("OK", coll)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.getcwd(),
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stdout + r.stderr
