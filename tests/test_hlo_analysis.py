"""Collective-byte parsing over real (captured) partitioned-HLO text."""
import pytest

from repro.core.hlo_analysis import (_parse_groups,
                                     _shape_bytes, parse_collectives)

# real lines captured from jax 0.8.2 XLA:CPU SPMD output on 8 fake devices
REAL_HLO = """
HloModule jit_step, is_scheduled=true

%region_0.0.clone (x: f32[], y: f32[]) -> f32[] { ... }

ENTRY %main {
  %all-reduce = f32[] all-reduce(%wrapped_reduce), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%region_0.0.clone
  ROOT %all-reduce.1 = f32[] all-reduce(%all-reduce), channel_id=2, replica_groups=[4,2]<=[2,4]T(1,0), use_global_device_ids=true, to_apply=%region_0.0.clone.1
}
"""

SYNTH_HLO = """
  %ag = bf16[256,4096]{1,0} all-gather(%p0), channel_id=3, replica_groups=[4,4]<=[16], dimensions={0}
  %rs = f32[64,1024]{1,0} reduce-scatter(%g0), channel_id=4, replica_groups=[2,8]<=[16], dimensions={0}, to_apply=%add
  %a2a = bf16[128,512]{1,0} all-to-all(%x), channel_id=5, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %cp = f32[32,32]{1,0} collective-permute(%y), channel_id=6, source_target_pairs={{0,1},{1,0}}
  %tup = (f32[100]{0}, f32[200]{0}) all-reduce(%a, %b), channel_id=7, replica_groups=[1,16]<=[16], to_apply=%add
  %ars = (f32[50]{0}, f32[50]{0}) all-reduce-start(%c), channel_id=8, replica_groups=[1,16]<=[16], to_apply=%add
"""


class TestShapeParsing:
    def test_dtype_bytes(self):
        assert _shape_bytes("bf16", "256,4096") == 256 * 4096 * 2
        assert _shape_bytes("f32", "") == 4          # scalar f32[]
        assert _shape_bytes("s8", "10") == 10

    def test_iota_groups(self):
        n, g = _parse_groups("replica_groups=[2,4]<=[8]", 8)
        assert n == 4 and g.shape == (2, 4) and list(g[0]) == [0, 1, 2, 3]

    def test_iota_transposed_groups(self):
        n, g = _parse_groups("replica_groups=[4,2]<=[2,4]T(1,0)", 8)
        assert n == 2 and g.shape == (4, 2)
        # transpose of arange(8).reshape(2,4) -> column pairs (0,4),(1,5)...
        assert list(g[0]) == [0, 4]

    def test_explicit_groups(self):
        n, g = _parse_groups("replica_groups={{0,1,2,3},{4,5,6,7}}", 8)
        assert n == 4 and g.shape == (2, 4)


class TestWireBytes:
    def test_real_scalar_allreduces(self):
        s = parse_collectives(REAL_HLO, 8)
        assert len(s.ops) == 2
        # f32[] = 4 bytes; ring factors 2*(4-1)/4 and 2*(2-1)/2
        assert s.ops[0].wire_bytes == pytest.approx(4 * 2 * 3 / 4)
        assert s.ops[1].wire_bytes == pytest.approx(4 * 2 * 1 / 2)

    def test_synthetic_kinds(self):
        s = parse_collectives(SYNTH_HLO, 16)
        kinds = s.by_kind()
        # all-gather: result 256*4096*2 bytes, n=4 -> (n-1)/n
        assert kinds["all-gather"][1] == pytest.approx(
            256 * 4096 * 2 * 3 / 4)
        # reduce-scatter: result is the shard -> factor (n-1)
        assert kinds["reduce-scatter"][1] == pytest.approx(
            64 * 1024 * 4 * 7)
        # all-to-all n=4
        assert kinds["all-to-all"][1] == pytest.approx(128 * 512 * 2 * 3 / 4)
        # collective-permute factor 1
        assert kinds["collective-permute"][1] == pytest.approx(32 * 32 * 4)
        # tuple all-reduce sums elements; -start takes max element only
        ar = kinds["all-reduce"][1]
        assert ar == pytest.approx(
            (100 + 200) * 4 * 2 * 15 / 16 + 50 * 4 * 2 * 15 / 16)

    def test_cross_pod_attribution(self):
        # groups spanning 2 pods of 8: [1,16]<=[16] ring crosses pods twice
        s = parse_collectives(SYNTH_HLO, 16, pod_size=8)
        tup = [o for o in s.ops if o.kind == "all-reduce"
               and o.group_size == 16]
        assert tup and all(o.cross_pod_fraction == pytest.approx(2 / 16)
                           for o in tup)
        # groups inside one pod: all-gather [4,4]<=[16] stays intra-pod
        ag = [o for o in s.ops if o.kind == "all-gather"][0]
        assert ag.cross_pod_fraction == 0.0


@pytest.mark.slow
class TestPerDeviceSemantics:
    """cost_analysis is per-device: verified by an 8-device subprocess
    compile (jax device count is locked at first init, so this cannot run
    in-process)."""

    def test_sharded_matmul_flops(self, tmp_path):
        import subprocess, sys, os, textwrap
        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("d",))
            s = NamedSharding(mesh, P("d", None))
            x = jax.ShapeDtypeStruct((1024, 512), jnp.float32, sharding=s)
            w = jax.ShapeDtypeStruct((512, 256), jnp.float32)
            c = jax.jit(lambda x, w: x @ w).lower(x, w).compile()
            flops = c.cost_analysis()["flops"]
            total = 2 * 1024 * 512 * 256
            assert abs(flops - total / 8) / total < 0.01, flops
            print("PER_DEVICE_OK")
        """)
        p = tmp_path / "probe.py"
        p.write_text(script)
        env = dict(os.environ, PYTHONPATH="src")
        out = subprocess.run([sys.executable, str(p)], capture_output=True,
                             text=True, env=env, timeout=300)
        assert "PER_DEVICE_OK" in out.stdout, out.stderr


import os  # noqa: E402  (used in the slow test)


def test_scan_body_counted_once():
    """XLA cost_analysis does NOT multiply while-loop bodies by trip count —
    the reason dryrun uses unrolled k-layer cost probes."""
    import jax
    import jax.numpy as jnp

    def body(x, w):
        return x @ w, None

    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    scan = jax.jit(lambda x, w: jax.lax.scan(body, x, w)[0]).lower(x, w).compile()
    unroll = jax.jit(lambda x, w: jax.lax.scan(body, x, w, unroll=8)[0]
                     ).lower(x, w).compile()
    f_scan = scan.cost_analysis()["flops"]
    f_unroll = unroll.cost_analysis()["flops"]
    assert f_unroll == pytest.approx(8 * f_scan, rel=0.01)
