"""Compile rehearsals of the sweep kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached, so Mosaic's refusals (block shapes off
the (8, 128) tiling, scalar-memory overflow, unsupported ops) surface
here, at no chip time, in a process held to the CPU.  Shapes are the
Music-100 deployment's: d + 1 = 101 columns (lane-padded to 128),
n0 = 128, query blocks of 8, k = 10; the stacked passes also compile at
SUN397's widths: d + 1 = 513 columns (lane-padded to 640), k = 50, and
at GIST1M's: d + 1 = 961 (lane-padded to 1,024), k = 10, whose mesh
launch also compiles across the four described chips.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers import every
test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.p2h_scan import p2h_sweep
from repro.kernels.stacked_sweep import stacked_sweep

DP, N0, BQ, K = 128, 128, 8, 10
B = 32  # query rows: 4 blocks of 8

#: (lane-padded width, k) of each deployment the stacked passes serve
WIDTHS = pytest.mark.parametrize("dp,k", [(DP, K), (640, 50), (1024, K)],
                                 ids=["music100", "sun397", "gist"])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernel(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


def test_p2h_sweep_f32_compiles(one_chip):
    S = functools.partial(_spec, one_chip)
    L = 1024
    args = (S((L, N0, DP)), S((L, N0), jnp.int32), S((L, N0)), S((L, N0)),
            S((L, N0)), S((L, 1)), S((B, DP)), S((B, 1)), S((B, 1)),
            S((B, L)), S((B, L)), S((B // BQ, L), jnp.int32))
    _compiled_kernel(functools.partial(p2h_sweep, k=K, bq=BQ,
                                       interpret=False), args)


def _stacked_args(S, N, L, n_visit, dtype, dp=DP):
    return (S((N, L, N0, dp), dtype), S((N, L, N0), jnp.int32),
            S((N, L, N0)), S((N, L, N0)), S((N, L, N0)), S((N, L, 1)),
            S((B, dp), dtype), S((B, 1)), S((B, 1)), S((N, B, L)),
            S((N, B, L)), S((N, B // BQ, n_visit), jnp.int32))


@WIDTHS
def test_stacked_sweep_f32_main_pass_compiles(one_chip, dp, k):
    """Pass B: the f32 rescan, seeded with pass A's per-segment state and
    the in-launch global top-k, with the scan and insertion counts."""
    S = functools.partial(_spec, one_chip)
    N, L = 4, 512

    def main_pass(*a):
        *ops, sd, si, gs = a
        return stacked_sweep(*ops, k=k, bq=BQ, interpret=False, seed_d=sd,
                             seed_i=si, global_seed=gs)

    args = _stacked_args(S, N, L, L, jnp.float32, dp) + (
        S((N, B, k)), S((N, B, k), jnp.int32), S((B, k)))
    _compiled_kernel(main_pass, args)


@WIDTHS
def test_stacked_sweep_bf16_probe_pass_compiles(one_chip, dp, k):
    """Pass A: the bf16 probe over the first preferred tiles, scores
    widened by the per-tile quantization slack."""
    S = functools.partial(_spec, one_chip)
    N, L, probe = 4, 512, 4

    def probe_pass(*a):
        *ops, sq, sa, sb = a
        return stacked_sweep(*ops, k=k, bq=BQ, interpret=False,
                             probe_dtype="bf16", sq=sq, slack_a=sa,
                             slack_b=sb)

    args = _stacked_args(S, N, L, probe, jnp.bfloat16, dp) + (
        S((B, 1)), S((N, L, 1)), S((N, L, 1)))
    _compiled_kernel(probe_pass, args)


def test_placed_mesh_launch_compiles_on_four_chips(topo):
    """Round 2 of a placed four-shard index at GIST1M's widths: each
    chip's block of 4 segments, its own kernel, one all-gather."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels.stacked_sweep import _run_stacked_mesh

    mesh = Mesh(np.array(topo.devices), ("shard",))
    Np, L, d, dp = 16, 64, 961, 1024

    def S(shape, dtype=jnp.float32, replicated=False):
        spec = P() if replicated else P("shard", *(None,) * (len(shape) - 1))
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    arrays = dict(pts=S((Np, L, N0, dp)), ids=S((Np, L, N0), jnp.int32),
                  rx=S((Np, L, N0)), xc=S((Np, L, N0)), xs=S((Np, L, N0)),
                  leaf_centers=S((Np, L, d)), leaf_radii=S((Np, L)),
                  leaf_cnorm=S((Np, L, 1)), valid=S((Np, L), jnp.bool_),
                  n_leaves=S((Np,), jnp.int32))
    compiled = _run_stacked_mesh.lower(
        arrays, S((BQ, d), replicated=True), S((BQ,), replicated=True),
        None, None, S((Np,), jnp.int32, replicated=True),
        S((Np,), jnp.bool_, replicated=True), mesh=mesh, mesh_axis="shard",
        n0=N0, d=d, k=K, frac=1.0, bq=BQ, use_ball=True, use_cone=True,
        use_kernel=True, interpret=False, probe_tiles=0, probe_dtype="f32",
        num_shards=4, has_extra=False, sort_planes=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-gather" in text
    # each chip holds its own quarter of the points plane
    assert compiled.memory_analysis().argument_size_in_bytes < (
        Np * L * N0 * dp * 4) // 2
