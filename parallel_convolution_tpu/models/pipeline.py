"""The flagship model: distributed iterative image convolution end-to-end.

Equivalent user surface to the reference's parallel ``main()`` (SURVEY.md
§3.2) — read raw image, decompose over the device grid, iterate the stencil
with halo exchange, write raw output — as a reusable object instead of an
inlined program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from parallel_convolution_tpu.obs import trace as obs_trace
from parallel_convolution_tpu.ops.filters import Filter, get_filter
from parallel_convolution_tpu.parallel import step as step_lib
from parallel_convolution_tpu.parallel.mesh import make_grid_mesh
from parallel_convolution_tpu.utils import imageio


@dataclasses.dataclass
class ConvolutionModel:
    """Iterative stencil filtering of grey/RGB images over a 2D TPU mesh.

    Args:
      filt: a :class:`Filter` or registry name (default: the reference's
        blur kernel).
      mesh: the 2D ('x','y') device mesh; defaults to all devices in a
        near-square grid (the MPI_Dims_create default).
      backend: 'shifted' (normative XLA path), 'pallas' (TPU stencil
        kernel), or 'xla_conv' (conv_general_dilated).
      quantize: apply uint8 store-back semantics each iteration (the
        reference's behavior for images); False = float Jacobi mode.
      fallback: probe the backend once per (mesh, config) and walk the
        degradation chain pallas_rdma → pallas → shifted on a
        classified-transient compile/launch failure instead of dying
        (resilience.degrade).  The backend actually used is recorded in
        ``self.effective_backend`` after each run — a degraded run can
        always be told apart from the requested tier.
    """

    filt: Filter | str = "blur3"
    mesh: Mesh | None = None
    backend: str = "shifted"  # any BACKENDS name, or "auto": resolve
    #                backend (and any None knobs below) through the tuning
    #                subsystem — plan cache first, cost model otherwise
    quantize: bool = True
    storage: str = "f32"  # 'bf16' halves HBM/ICI traffic, still bit-exact
    #                        in quantize mode (u8 values are exact in bf16)
    fuse: int | None = 1  # iterations per halo exchange (temporal fusion,
    #                T*r-deep halos once instead of r-deep every iteration);
    #                None = let backend="auto" tune the depth
    boundary: str = "zero"  # 'periodic' = torus wrap (ring topology)
    tile: tuple[int, int] | None = None  # Pallas kernel output-tile (TH, TW)
    #                override; None = per-kernel tuned default
    overlap: bool | None = None  # interior-first overlapped halo pipeline
    #                (RDMA kernels): None = off for explicit backends /
    #                tuned for backend="auto"; True is a clamped request —
    #                the resolved knob lands in self.effective_overlap
    col_mode: str | None = None  # RDMA column-slab transport (packed |
    #                strided | auto; None = auto) — the resolved value
    #                lands in self.effective_col_mode
    fallback: bool = False  # graceful backend degradation on transient
    #                compile/launch failure (resilience.degrade)

    def __post_init__(self) -> None:
        if isinstance(self.filt, str):
            self.filt = get_filter(self.filt)
        if self.mesh is None:
            self.mesh = make_grid_mesh()
        step_lib._check_storage(self.storage, self.quantize)
        if self.fuse is None and self.backend != "auto":
            raise ValueError(
                "fuse=None means 'tune it' and needs backend='auto'")
        # The backend the last run ACTUALLY used (== self.backend unless
        # auto resolved it / fallback degraded it); None until a run
        # happens.  plan_source records the auto resolution's provenance
        # (measured|interpolated|predicted), or 'explicit'.
        self.effective_backend: str | None = None
        self.plan_source: str = "explicit"
        # The overlap knob the last run ACTUALLY compiled with (clamped
        # request / tuned decision / degrade re-clamp); None until a run.
        self.effective_overlap: bool | None = None
        # The column transport the last run ACTUALLY compiled with.
        self.effective_col_mode: str | None = None

    def set_mesh(self, mesh) -> "ConvolutionModel":
        """Swap the device mesh mid-object (elastic recovery).

        ``mesh`` is a Mesh or an ``"RxC"`` spec string.  Only mesh-scoped
        state resets (the recorded effective backend / plan provenance —
        both are per-mesh verdicts); everything else, including compiled
        runners for OTHER meshes, is untouched: ``parallel.step``'s build
        caches and ``resilience.degrade``'s probe cache both key on the
        mesh, so swapping back later reuses the old executables with zero
        re-tracing.  Output bytes are mesh-invariant by the framework's
        core contract, so a swap never changes results — only topology.
        """
        from parallel_convolution_tpu.parallel.mesh import mesh_from_spec

        self.mesh = mesh_from_spec(mesh) if isinstance(mesh, str) else mesh
        self.effective_backend = None
        self.plan_source = "explicit"
        self.effective_overlap = None
        self.effective_col_mode = None
        return self

    def _resolved_knobs(self, shape, block_hw=None) -> step_lib.Launch:
        """The launch for a (C, H, W) input of ``shape``, resolved once
        (``step.resolve_launch``) inside the ``pctpu.resolve`` span, with
        what it decided stamped on the model."""
        with obs_trace.span("pctpu.resolve"):
            launch = step_lib.resolve_launch(
                self.mesh, self.filt, shape, backend=self.backend,
                storage=self.storage, quantize=self.quantize,
                boundary=self.boundary, fuse=self.fuse, tile=self.tile,
                overlap=self.overlap, col_mode=self.col_mode,
                fallback=self.fallback, block_hw=block_hw)
        self.plan_source = launch.plan_source
        self.effective_backend = launch.backend
        self.effective_overlap = launch.overlap
        self.effective_col_mode = launch.col_mode
        return launch

    # -- array-level API ----------------------------------------------------
    def run_planar(self, x, iters: int) -> jnp.ndarray:
        """(C, H, W) f32 in → (C, H, W) f32 out after ``iters`` iterations.

        One device program a call (``step._build_job``: cast, pad,
        iterate, crop, cast back); ``x`` is left as it was, in every
        storage.  Its host work sits in the profiler span
        ``pctpu.run_planar``, with ``pctpu.resolve`` and the step layer's
        ``pctpu.step.*`` inside."""
        with obs_trace.span("pctpu.run_planar"):
            return step_lib._run_launch(
                x, self.filt, iters, self.mesh,
                self._resolved_knobs(x.shape), self.quantize)

    def iterate_text(self, shape, iters: int) -> str:
        """The compiled text of the job program ``run_planar`` runs for a
        (C, H, W) float32 input of ``shape`` in the mesh's block sharding:
        ``step.op_scopes`` maps its instructions, as a device trace names
        them, to their layer."""
        return step_lib._launch_text(shape, self.filt, iters, self.mesh,
                                     self._resolved_knobs(shape),
                                     self.quantize)

    def run_image(self, img: np.ndarray, iters: int) -> np.ndarray:
        """uint8 (H, W[, 3]) in → uint8 out; the one-call user entrypoint."""
        x = imageio.interleaved_to_planar(img).astype(np.float32)
        out = self.run_planar(x, iters)
        return imageio.planar_to_interleaved(
            np.asarray(out).astype(np.uint8)
        )

    def run_images(self, imgs, iters: int) -> list[np.ndarray]:
        """Batch of same-sized images in one device program.

        Channels are independent in the stencil, so a batch is just more
        planes on the leading axis — the framework's data-parallel tier
        (SURVEY.md §2 parallelism inventory: DP 'falls out free').
        """
        planar = [imageio.interleaved_to_planar(im) for im in imgs]
        counts = [p.shape[0] for p in planar]
        x = np.concatenate(planar, axis=0).astype(np.float32)
        out = np.asarray(self.run_planar(x, iters)).astype(np.uint8)
        res, i0 = [], 0
        for c in counts:
            res.append(imageio.planar_to_interleaved(out[i0 : i0 + c]))
            i0 += c
        return res

    # -- file-level API (the reference CLI contract) ------------------------
    def run_raw_file(
        self, src: str, dst: str, rows: int, cols: int, mode: str, iters: int
    ) -> None:
        """raw file → raw file, the reference's ``main()`` end to end."""
        img = imageio.read_raw(src, rows, cols, mode)
        imageio.write_raw(dst, self.run_image(img, iters))

    def run_raw_file_sharded(
        self, src: str, dst: str, rows: int, cols: int, mode: str, iters: int
    ) -> None:
        """Huge-image path: block-reads from disk straight into the device
        sharding, iterates, block-writes back — the full image never exists
        in one host buffer (the MPI-IO workflow, SURVEY.md §7)."""
        import numpy as np

        from parallel_convolution_tpu.parallel.mesh import grid_shape
        from parallel_convolution_tpu.parallel.step import STORAGE_DTYPES
        from parallel_convolution_tpu.utils import sharded_io

        xs = sharded_io.load_sharded(
            src, rows, cols, mode, self.mesh,
            dtype=np.dtype(STORAGE_DTYPES[self.storage]),
        )
        R, C = grid_shape(self.mesh)
        launch = self._resolved_knobs(
            (xs.shape[0], rows, cols),
            block_hw=(xs.shape[1] // R, xs.shape[2] // C))
        out = step_lib._iterate_launch(xs, self.filt, iters, self.mesh,
                                       (rows, cols), launch, self.quantize)
        sharded_io.save_sharded(dst, out, rows, cols, mode)
