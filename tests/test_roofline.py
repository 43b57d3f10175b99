"""Roofline HLO analyzer: trip counts, dot FLOPs, collectives, VMEM scopes.

The analyzer's whole point is fixing XLA cost-analysis' count-scan-body-once
behavior, so the key test compiles a scan and checks the ×N multiplication.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import roofline as R


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestFlops:
    def test_single_dot(self):
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        rep = R.analyze(_compile(lambda x, y: x @ y, a, b).as_text())
        assert rep.flops == 2 * 64 * 128 * 32

    def test_scan_multiplies_by_trip_count(self):
        n = 9

        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y

        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((n, 32, 32), jnp.float32)
        compiled = _compile(f, x, ws)
        rep = R.analyze(compiled.as_text())
        want = n * 2 * 32 * 32 * 32
        assert rep.flops == want
        # XLA's own counter reports one body (the bug we fix); newer jax
        # returns one cost dict per device instead of a bare dict
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        xla = cost["flops"]
        assert xla < want / 2

    def test_nested_scan(self):
        def f(x, ws):
            def outer(c, w):
                def inner(ci, _):
                    return ci @ w, None
                ci, _ = jax.lax.scan(inner, c, jnp.arange(3))
                return ci, None
            y, _ = jax.lax.scan(outer, x, ws)
            return y

        x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
        ws = jax.ShapeDtypeStruct((4, 16, 16), jnp.float32)
        rep = R.analyze(_compile(f, x, ws).as_text())
        assert rep.flops == 4 * 3 * 2 * 16 ** 3


class TestHbmBytes:
    def test_elementwise_traffic(self):
        x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
        rep = R.analyze(_compile(lambda a: jnp.tanh(a) * 2 + 1, x).as_text())
        nbytes = 1024 * 1024 * 4
        # roughly read + write (fusions may add small copies)
        assert nbytes * 1.5 <= rep.hbm_bytes <= nbytes * 4

    def test_scan_stack_writes_counted_per_slice(self):
        """A scan saving per-iteration outputs must charge the slice, not
        the whole stacked buffer, per iteration."""
        n, m = 16, 256

        def f(x):
            def body(c, _):
                c = jnp.sin(c)
                return c, c
            _, ys = jax.lax.scan(body, x, None, length=n)
            return ys

        x = jax.ShapeDtypeStruct((m, m), jnp.float32)
        rep = R.analyze(_compile(f, x).as_text())
        slice_bytes = m * m * 4
        # per iteration ≈ read c + write c + write ys slice (+ fusion
        # copies); the failure mode being guarded is charging the WHOLE
        # (n, m, m) stack per iteration (n× overcount)
        assert rep.hbm_bytes < n * slice_bytes * 10
        assert rep.hbm_bytes > n * slice_bytes * 1.5


class TestParser:
    def test_tuple_types_with_index_comments(self):
        line = ("  %while.163 = (s32[], f32[256,1,2,4096]{3,2,1,0}, "
                "/*index=5*/f32[4,256,1,1024,80]{4,3,2,1,0}) "
                "while(%tuple.1), condition=%cond.1, body=%body.1")
        op = R._parse_op(line)
        assert op is not None and op.opcode == "while"
        assert "body.1" in op.line

    def test_dtype_layout_T_not_an_opcode(self):
        line = ("  %copy.1 = f32[64,512]{1,0:T(8,128)} copy(%x)")
        op = R._parse_op(line)
        assert op.opcode == "copy"

    def test_shape_bytes(self):
        assert R._shape_bytes("bf16[4,8]{1,0}") == 64
        assert R._shape_bytes("(s32[], f32[2,2])") == 4 + 16
        assert R._shape_bytes("pred[16]") == 16


@pytest.mark.slow
class TestSharded:
    """Collective accounting needs >1 device — run in a subprocess with
    forced host devices (never force devices in the test process itself)."""

    def test_collectives_counted(self, tmp_path):
        import subprocess
        import sys
        script = tmp_path / "probe.py"
        script.write_text("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.analysis import roofline as R
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("d",))
xsh = NamedSharding(mesh, P("d", None))
x = jax.ShapeDtypeStruct((1024, 64), jnp.float32, sharding=xsh)
rep = R.analyze(jax.jit(
    lambda a: a.sum(), in_shardings=(xsh,), out_shardings=None
).lower(x).compile().as_text())
assert rep.collective_bytes > 0, rep.as_dict()
assert "all-reduce" in rep.collective_by_kind
print("OK")
""")
        r = subprocess.run([sys.executable, str(script)],
                           cwd=Path(__file__).resolve().parents[1],
                           capture_output=True, text=True, timeout=300)
        assert "OK" in r.stdout, r.stderr[-2000:]
