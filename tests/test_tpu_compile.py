"""AOT compiles of the main path's Pallas kernels for a described v5e.

Rehearsal 3 of the on-chip-measurement guide: Mosaic refuses here what
the chip would refuse (unaligned slices, scoped-VMEM overruns, kernels
that cannot be partitioned), at no chip time.  Nothing runs, so these
tests prove compilation only, never results or times.

The topology is described inside a module fixture: only the worker that
runs this file loads libtpu, and only once its first test starts.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from parallel_convolution_tpu.ops import pallas_rdma, pallas_stencil
from parallel_convolution_tpu.ops.filters import get_filter, make_filter
from parallel_convolution_tpu.parallel import step
from parallel_convolution_tpu.parallel.mesh import AXES, make_grid_mesh


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def magic_round_and_no_cache(monkeypatch):
    """Steer the magic-round selector to the form the chip runs (its probe
    cannot execute off-chip), and keep these compiles out of the
    persistent cache, which cannot read them back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_stencil, "_MAGIC_GUARD",
                        {"ok": True, "probing": False, "cause": None})
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def test_correlate_shifted_f32_8192(one_chip):
    filt = get_filter("blur3")
    x = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.float32, sharding=one_chip)
    _compile(functools.partial(pallas_stencil.correlate_shifted_pallas,
                               filt=filt, quantize=True, interpret=False), x)


def _fused(filt, T, dtype, separable, sharding, hw=(8192, 8192),
           tile=None):
    H, W = hw
    d = filt.radius * T
    padded = jax.ShapeDtypeStruct((1, H + 2 * d, W + 2 * d), dtype,
                                  sharding=sharding)
    offsets = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=sharding)
    return _compile(functools.partial(
        pallas_stencil.fused_iterate_pallas, filt=filt, T=T, valid_hw=hw,
        interpret=False, quantize=True, separable=separable, tile=tile),
        padded, offsets)


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.bfloat16],
                         ids=["u8", "bf16"])
def test_fused_separable_T4(one_chip, dtype):
    # The magic round form: the selector is steered to the compiled
    # default, and the probe is never consulted.
    assert pallas_stencil._round_mode_for(
        tuple(float(t) for t in get_filter("blur3").taps.reshape(-1)),
        interpret=False) == "magic"
    _fused(get_filter("blur3"), 4, dtype, True, one_chip)


@pytest.mark.parametrize("tile", [None, (512, 512)], ids=["default", "512"])
def test_fused_edge5_T2(one_chip, tile):
    # edge5 in quantize mode takes the box-plus-sparse tap form.
    _fused(get_filter("edge5"), 2, jnp.bfloat16, False, one_chip, tile=tile)


def test_fused_direct_loop_T2(one_chip):
    # The direct 2D tap loop (edge5's taps outside the registry, so not
    # dyadic): at (512, 512) its ~k² live level-0 temporaries take ~27 MiB
    # of scoped VMEM, over Mosaic's 16 MiB default and inside the
    # kernel's raised cap (the limit that stopped edge5 at fuse 8).
    filt = make_filter("edge5_taps", get_filter("edge5").taps)
    assert pallas_stencil.tap_form(filt, True, True).name == "direct"
    _fused(filt, 2, jnp.bfloat16, True, one_chip, tile=(512, 512))


def test_fused_edge5_cell_T8(one_chip):
    # The edge5 cells' launch: a 2048² bf16 block at fuse 8.
    _fused(get_filter("edge5"), 8, jnp.bfloat16, True, one_chip,
           hw=(2048, 2048))


@pytest.mark.parametrize("overlap", [False, True], ids=["serial", "overlap"])
def test_rdma_step_on_2x2_mesh(topo, overlap):
    # A 2048² f32 block takes the tiled (HBM-pad) kernel; the overlapped
    # form once crashed Mosaic's lowering (nested guards, PR 21).
    filt = get_filter("blur3")
    mesh = make_grid_mesh(topo.devices, (2, 2))
    x = jax.ShapeDtypeStruct((1, 4096, 4096), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, *AXES)))

    def step(v):
        return pallas_rdma.fused_rdma_step(
            v, filt, (2, 2), "zero", quantize=True, interpret=False,
            valid_hw=(4096, 4096), overlap=overlap)

    hlo = _compile(jax.shard_map(step, mesh=mesh, in_specs=P(None, *AXES),
                                 out_specs=P(None, *AXES), check_vma=False), x)
    assert hlo.count("tpu_custom_call") >= 1


def test_rgb_2520_cell_launch_on_2x2_mesh(topo):
    # The rgb_2520.mesh2x2 cell's job program: BASELINE config 2's
    # 1920x2520 RGB image on 2x2 at u8, fuse 16, through the step layer's
    # job program.  The RDMA form pads each 960x1260 block to whole
    # (32, 128) u8 tiles, 960x1280; Mosaic refuses the 1260-wide band
    # slices.  The fuse-16 chunk and the 4-deep tail are the program's
    # two kernels; its only collectives are the pad's and the crop's
    # column moves, under pctpu.job_io.  Both kernels run blur3 in the
    # rank-1 separable form, so Mosaic takes its two-pass slices at u8
    # tiling.
    mesh = make_grid_mesh(topo.devices, (2, 2))
    filt = get_filter("blur3")
    text = step.iterate_text((3, 1920, 2520), filt, 100,
                             mesh=mesh, backend="pallas_rdma",
                             storage="u8", fuse=16)
    assert text.count("tpu_custom_call") == 2
    scopes = step.op_scopes(text)
    moves = {n: s for n, s in scopes.items()
             if n.startswith(("collective-permute", "all-"))}
    assert moves and set(moves.values()) == {"pctpu.job_io"}
    assert "u8[3,960,1280]" in text
    launch = step.resolve_launch(mesh, filt, (3, 1920, 2520),
                                 backend="pallas_rdma", storage="u8",
                                 fuse=16)
    assert launch.block_hw == step.block_extent(
        (1920, 2520), (2, 2), "pallas_rdma", "u8", "zero")
    runner = step._iterate_runner(mesh, filt, 100, True, (1920, 2520),
                                  launch)
    assert runner.tap_form == "separable"
