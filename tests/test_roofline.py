"""Roofline machinery tests: HLO collective parsing + term math."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import roofline as RL


HLO_SAMPLE = """
ENTRY %main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ag = f32[16,2048]{1,0} all-gather(%p0), dimensions={1}
  %ar = f32[16,128]{1,0} all-reduce(%p0), to_apply=%add
  %rs = bf16[4,128]{1,0} reduce-scatter(%p1), dimensions={0}
  %cp = s8[64]{0} collective-permute(%p2), source_target_pairs={{0,1}}
  %a2a = (f32[2,4]{1,0}, f32[2,4]{1,0}) all-to-all(%x, %y), dimensions={0}
  %ard = f32[9]{0} all-reduce-done(%foo)
}
"""


def test_collective_bytes_parsing():
    out = RL.collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 16 * 2048 * 4
    assert out["all-reduce"] == 16 * 128 * 4 * 2  # 2x ring weighting
    assert out["reduce-scatter"] == 4 * 128 * 2
    assert out["collective-permute"] == 64
    assert out["all-to-all"] == 2 * (2 * 4 * 4)
    assert out["_counts"]["all-reduce"] == 1  # -done not double counted


def test_collective_bytes_real_program():
    """End-to-end: a sharded matmul's psum shows up in the parse."""
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import make_mesh
from repro.core.roofline import collective_bytes
mesh = make_mesh((8,), ("tp",))
x = jax.ShapeDtypeStruct((64, 512), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, "tp")))
w = jax.ShapeDtypeStruct((512, 32), jnp.float32,
                         sharding=NamedSharding(mesh, P("tp", None)))
hlo = jax.jit(lambda x, w: x @ w).lower(x, w).compile().as_text()
c = collective_bytes(hlo)
assert c["all-reduce"] >= 64 * 32 * 4, c
print("PARSE_OK")
"""
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, cwd=".",
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "PARSE_OK" in proc.stdout


def test_roofline_terms_and_bottleneck():
    r = RL.analyze(
        arch="a", shape="s", mesh_name="16x16", chips=256,
        cost={"flops": 197e12, "bytes accessed": 819e9 * 2},
        hlo_text="", model_flops=197e12 * 256 * 0.5,
        device_kind=RL.V5E)
    assert abs(r.t_compute - 1.0) < 1e-6
    assert abs(r.t_memory - 2.0) < 1e-6
    assert r.bottleneck == "memory"
    assert abs(r.useful_ratio - 0.5) < 1e-6
    assert abs(r.roofline_fraction() - 0.5) < 1e-6


def test_accounting_probe_combination():
    from repro.launch.accounting import combine_probe

    c1 = {"flops": 100.0, "bytes accessed": 10.0}
    c2 = {"flops": 160.0, "bytes accessed": 14.0}
    coll1 = {"all-reduce": 8.0}
    coll2 = {"all-reduce": 11.0}
    flops, nbytes, coll = combine_probe(c1, coll1, c2, coll2, scaling=10)
    assert flops == 100 + 10 * 60
    assert nbytes == 10 + 10 * 4
    assert coll["all-reduce"] == 8 + 10 * 3


def test_probe_configs_layer_counts():
    from repro.configs import get_config
    from repro.launch.accounting import probe_configs

    cfg = get_config("deepseek-v2-236b")
    small, big, lsmall, scaling = probe_configs(cfg)
    assert small.n_layers == 2 and big.n_layers == 3  # 1 dense + 1/2 moe
    assert scaling == (60 - 1) - 1  # n_moe - 1 = 58
    assert small.scan_unroll and big.scan_unroll

    cfg = get_config("zamba2-1.2b")
    small, big, _, scaling = probe_configs(cfg)
    assert small.n_layers == 8 and big.n_layers == 14  # seg(6)+rem(2)
    assert scaling == 5


def test_peaks_keyed_by_device_kind():
    pk = RL.peaks("TPU v5 lite")
    assert (pk.bf16_flops, pk.int8_ops, pk.hbm_bw) == (197e12, 394e12, 819e9)
    assert "TPU v5e" in pk.source
    with pytest.raises(KeyError, match="no published peaks"):
        RL.peaks("cpu")
