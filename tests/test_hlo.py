"""HLO analysis: the trip-count-aware cost walk vs known ground truths,
including the proof that XLA's own cost_analysis counts loop bodies once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils import collective_bytes, hlo_cost, op_histogram, shape_bytes


def test_shape_bytes():
    assert shape_bytes("f32[128,4]") == 128 * 4 * 4
    assert shape_bytes("bf16[2,3]{1,0}") == 12
    assert shape_bytes("(f32[2,2], s32[4])") == 16 + 16
    assert shape_bytes("pred[8]") == 8
    assert shape_bytes("f32[]") == 4


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


def test_xla_counts_loop_bodies_once():
    """The motivation for hlo_cost: scan x10 reports ~1x matmul flops."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.dot(c, w), None), x, ws)[0]

    comp = jax.jit(scanned).lower(x, ws).compile()
    xla = comp.cost_analysis()["flops"]
    assert xla < 2 * 2 * 128**3          # ~1 matmul, NOT 10


def test_hlo_cost_scan_flops_exact():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.dot(c, w), None), x, ws)[0]

    c = hlo_cost(_compile(scanned, x, ws))
    assert c.flops == 10 * 2 * 128**3
    assert 10 in c.while_trip_counts
    assert c.unresolved_loops == 0


def test_hlo_cost_nested_loops():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)

    def nested(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return jnp.dot(ci, w), None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, ws)[0]

    c = hlo_cost(_compile(nested, x, ws))
    assert c.flops == 15 * 2 * 64**3
    assert sorted(c.while_trip_counts) == [3, 5]


def test_hlo_cost_plain_dot():
    a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((64, 16), jnp.float32)
    c = hlo_cost(_compile(lambda a, b: a @ b, a, b))
    assert c.flops == 2 * 32 * 64 * 16


def test_collective_parser_on_sharded_module():
    """A psum under shard_map must be found with the right byte count
    (in a child process with 4 fake CPU devices, kept off any chip)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        import sys
        sys.path.insert(0, "src")
        from repro.utils import collective_bytes, hlo_cost
        mesh = jax.make_mesh((4,), ("x",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        f = jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                      in_specs=P(), out_specs=P(), axis_names={"x"},
                      check_vma=False)
        txt = jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
        st = collective_bytes(txt)
        assert st.total_bytes >= 64 * 64 * 4, st
        hc = hlo_cost(txt)
        assert hc.collective_bytes >= 64 * 64 * 4
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=".")
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_op_histogram():
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    hist = dict(op_histogram(_compile(lambda a: a @ a + a, x, ), top=50))
    assert sum(hist.values()) > 0
