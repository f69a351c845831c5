"""Run-to-convergence Jacobi smoothing (BASELINE config 5; component C6).

The reference's optional early-stop — every N iterations each rank computes
a local diff flag and the grid agrees via ``MPI_Allreduce`` — generalized
into a proper iterative solver: float32 carry, max-abs convergence norm,
``lax.while_loop`` + ``lax.pmax`` entirely on-device.
"""

from __future__ import annotations

import dataclasses

import numpy as np
from jax.sharding import Mesh

from parallel_convolution_tpu.ops.filters import Filter, get_filter
from parallel_convolution_tpu.parallel import step as step_lib
from parallel_convolution_tpu.parallel.mesh import make_grid_mesh


@dataclasses.dataclass
class JacobiSolver:
    """Iterate a smoothing stencil until the field stops changing.

    ``tol`` is the max-abs single-iteration change below which the run
    stops; ``check_every`` matches the reference's every-N reduction cadence
    (larger = fewer collectives, up to N-1 extra iterations).
    """

    filt: Filter | str = "jacobi3"
    tol: float = 1e-3
    max_iters: int = 10_000
    check_every: int = 10
    mesh: Mesh | None = None
    backend: str = "shifted"
    quantize: bool = False
    boundary: str = "zero"
    storage: str = "f32"  # iteration-carry dtype (see sharded_converge)
    fuse: int | None = 1  # fused iterations between convergence checks;
    #                None = tune it (backend="auto", resolved in
    #                sharded_converge through the tuning subsystem)
    tile: tuple[int, int] | None = None  # Pallas kernel tile override
    overlap: bool | None = None  # interior-first overlapped halo pipeline
    #                (see ConvolutionModel; resolved in sharded_converge)
    col_mode: str | None = None  # RDMA column-slab transport (packed |
    #                strided | auto; see ConvolutionModel)
    solver: str = "jacobi"  # convergence strategy (utils.config.SOLVERS):
    #                "jacobi" = the reference's sweep loop; "multigrid" =
    #                the geometric V-cycle (solvers.multigrid) — same
    #                stopping measure, ~orders of magnitude fewer
    #                fine-grid work units on smooth problems
    mg_levels: int | None = None  # multigrid level-count cap (None =
    #                coarsen to the planner's floor); ignored for jacobi
    last_mg: object = dataclasses.field(default=None, repr=False,
                                        compare=False)  # the MGResult of
    #                the most recent multigrid solve (cycles, work_units,
    #                per-level grids) — None until one runs

    def __post_init__(self) -> None:
        from parallel_convolution_tpu.utils.config import SOLVERS

        if self.solver not in SOLVERS:
            raise ValueError(
                f"solver must be one of {SOLVERS}, got {self.solver!r}")
        if isinstance(self.filt, str):
            self.filt = get_filter(self.filt)
        if self.mesh is None:
            self.mesh = make_grid_mesh()

    def set_mesh(self, mesh) -> "JacobiSolver":
        """Swap the device mesh (elastic recovery) — same contract as
        ``ConvolutionModel.set_mesh``: solver config and compiled state
        for other meshes are untouched, results are mesh-invariant."""
        from parallel_convolution_tpu.parallel.mesh import mesh_from_spec

        self.mesh = mesh_from_spec(mesh) if isinstance(mesh, str) else mesh
        return self

    def solve(self, x) -> tuple[np.ndarray, int]:
        """(C, H, W) f32 field → (solved field, work count).

        The work count is solver-shaped: Jacobi iterations run, or
        V-cycles run for ``solver="multigrid"`` (whose full accounting —
        fine-grid ``work_units``, the per-level schedule — lands in
        ``self.last_mg``, an :class:`solvers.multigrid.MGResult`).
        """
        if self.solver == "multigrid":
            from parallel_convolution_tpu.solvers import multigrid

            out, res = multigrid.mg_converge(
                x, self.filt, tol=self.tol, max_iters=self.max_iters,
                mesh=self.mesh, quantize=self.quantize,
                backend=self.backend, storage=self.storage,
                boundary=self.boundary, fuse=self.fuse, tile=self.tile,
                overlap=self.overlap, mg_levels=self.mg_levels,
                col_mode=self.col_mode,
            )
            self.last_mg = res
            return np.asarray(out), res.cycles
        out, iters = step_lib.sharded_converge(
            x, self.filt, tol=self.tol, max_iters=self.max_iters,
            check_every=self.check_every, mesh=self.mesh,
            quantize=self.quantize, backend=self.backend,
            boundary=self.boundary, storage=self.storage,
            fuse=self.fuse, tile=self.tile,
            overlap=self.overlap,
            col_mode=self.col_mode,
        )
        return np.asarray(out), iters
