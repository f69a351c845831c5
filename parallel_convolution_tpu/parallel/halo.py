"""Ghost-cell (halo) exchange via XLA collective-permute (component C5).

This is the heart of the port (SURVEY.md §2 C5): the reference posts eight
``MPI_Isend/Irecv`` pairs per iteration — N/S/E/W edges plus four corner
diagonals — into the ghost ring of a ``(rows+2)×(cols+2)`` padded block,
with ``MPI_Type_vector`` datatypes for strided columns.

The TPU equivalent is :func:`jax.lax.ppermute` (XLA ``collective-permute``
over ICI) applied in **two sequential phases**:

1. shift r-row edge slabs along mesh axis 'x' (top/bottom ghosts);
2. shift r-column edge slabs of the *already row-padded* block along 'y'.

Phase 2's column slabs include the freshly received row ghosts, so corner
ghost cells arrive after two hops — no diagonal messages, 4 permutes total
instead of the reference's 8 sends.  Strided-column datatypes have no
equivalent because XLA slices lay out transfers itself.

Boundary condition: a ``ppermute`` leaves devices with no inbound edge in
the permutation holding **zeros**, which is exactly the reference's zero
ghost ring at the image boundary — non-periodic borders come for free.

Everything here runs *inside* ``jax.shard_map`` over the ('x', 'y') mesh;
``block`` is one device's planar (C, h, w) float32 tile.

Measurement: :func:`halo_exchange` runs under the named scope
``pctpu.halo``, so its ghost pads, concatenates and collective-permutes
carry that scope in the compiled executable (``step.op_scopes``).  While
a :func:`counting` block is open, tracing an exchange tallies what it
built — collective rounds, and each ppermute's slab bytes times the
pairs of its permutation across the whole mesh — into a
:class:`HaloCount` (the iterate runner keeps one per traced shape).  A
kernel that exchanges by remote DMA counts the ghosts the same way
(:func:`count_remote_exchange`) and, apart, the whole bands its copies
move (:func:`count_wire`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading

import jax
import jax.numpy as jnp
from jax import lax

from parallel_convolution_tpu.obs.attribution import DIRECTIONS

SCOPE = "pctpu.halo"
# (mesh axis, toward higher index) -> where the ghost band travels, in
# obs.attribution's names: 'south' = toward higher rows, 'east' = cols.
_DIRECTION = {("x", True): "south", ("x", False): "north",
              ("y", True): "east", ("y", False): "west"}


@dataclasses.dataclass
class HaloCount:
    """What traced halo exchanges built, summed over the whole mesh:
    rounds (exchanges that sent anything), ghost bytes per direction,
    and ``wire_bytes``, what remote-DMA kernels' copies move (None where
    no kernel exchanged by remote DMA)."""

    rounds: int = 0
    bytes: dict = dataclasses.field(
        default_factory=lambda: dict.fromkeys(DIRECTIONS, 0))
    wire_bytes: int | None = None

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes.values())

    def __mul__(self, k: int) -> "HaloCount":
        return HaloCount(self.rounds * k,
                         {d: b * k for d, b in self.bytes.items()},
                         None if self.wire_bytes is None
                         else self.wire_bytes * k)

    def __add__(self, other: "HaloCount") -> "HaloCount":
        wire = (None if self.wire_bytes is None and other.wire_bytes is None
                else (self.wire_bytes or 0) + (other.wire_bytes or 0))
        return HaloCount(self.rounds + other.rounds,
                         {d: b + other.bytes[d]
                          for d, b in self.bytes.items()}, wire)


_TALLY = threading.local()


@contextlib.contextmanager
def counting():
    """Tally the halo exchanges traced inside the block into a fresh
    :class:`HaloCount` (innermost block wins; trace time only)."""
    outer = getattr(_TALLY, "count", None)
    _TALLY.count = count = HaloCount()
    try:
        yield count
    finally:
        _TALLY.count = outer


def _note_ppermute(x, axis_name: str, n: int, down: bool, perm) -> None:
    count = getattr(_TALLY, "count", None)
    if count is None:
        return
    # One ppermute inside shard_map runs in every group of the other mesh
    # axes: its global permutation has len(perm) pairs per group.
    devices = math.prod(jax.sharding.get_abstract_mesh().shape.values())
    groups = max(1, devices // n)
    count.bytes[_DIRECTION[axis_name, down]] += (
        x.size * x.dtype.itemsize * len(perm) * groups)


def count_remote_exchange(block, r: int, grid: tuple[int, int],
                          boundary: str = "zero") -> None:
    """Tally one exchange that a kernel makes with remote DMAs, not
    ppermutes (``pallas_rdma``): the ghost bands :func:`halo_exchange`
    would move for this (C, h, w) block."""
    count = getattr(_TALLY, "count", None)
    if count is None:
        return
    C, h, w = block.shape
    R, Cc = grid
    B = jnp.dtype(block.dtype).itemsize
    sent = count.total_bytes
    for axis, n, groups, slab in (("x", R, Cc, C * r * w * B),
                                  ("y", Cc, R, C * (h + 2 * r) * r * B)):
        pairs = 0 if n == 1 else n if boundary == "periodic" else n - 1
        for down in (True, False):
            count.bytes[_DIRECTION[axis, down]] += slab * pairs * groups
    count.rounds += count.total_bytes > sent


def count_wire(nbytes: int) -> None:
    """Tally what one remote-DMA exchange's copies move over the mesh
    (``pallas_rdma.Launch.wire_bytes``): whole aligned bands, where
    :func:`count_remote_exchange` counts the ghosts inside them."""
    count = getattr(_TALLY, "count", None)
    if count is not None:
        count.wire_bytes = (count.wire_bytes or 0) + int(nbytes)


def _shift(x: jnp.ndarray, axis_name: str, n: int, down: bool,
           periodic: bool = False) -> jnp.ndarray:
    """ppermute ``x`` one step along ``axis_name`` (n devices on that axis).

    ``down=True`` sends toward higher indices (each device receives its
    lower-index neighbor's slab).  Non-periodic boundaries fall out of
    ppermute semantics — devices with no inbound edge receive zeros (the
    zero ghost ring).  ``periodic=True`` closes the ring (the wrap-around
    rotation of ring attention's KV pass, SURVEY.md §5 long-context row):
    every device has an inbound edge, modulo n.
    """
    if n == 1:
        if periodic:
            return x  # my own opposite edge wraps to me
        return jnp.zeros_like(x)
    if periodic:
        if down:
            perm = [(i, (i + 1) % n) for i in range(n)]
        else:
            perm = [(i, (i - 1) % n) for i in range(n)]
    elif down:
        perm = [(i, i + 1) for i in range(n - 1)]
    else:
        perm = [(i + 1, i) for i in range(n - 1)]
    _note_ppermute(x, axis_name, n, down, perm)
    return lax.ppermute(x, axis_name, perm)


def halo_pad_axis(
    block: jnp.ndarray, r: int, axis_name: str, n: int, dim: int,
    periodic: bool = False,
) -> jnp.ndarray:
    """Pad one spatial dim of ``block`` with r-wide halos from mesh neighbors."""
    lo_slice = [slice(None)] * block.ndim
    hi_slice = [slice(None)] * block.ndim
    lo_slice[dim] = slice(0, r)          # my first r rows/cols → upper neighbor
    hi_slice[dim] = slice(block.shape[dim] - r, block.shape[dim])
    # Ghosts I receive: lower neighbor's last r (becomes my leading ghost),
    # higher neighbor's first r (trailing ghost).
    lead_ghost = _shift(block[tuple(hi_slice)], axis_name, n, down=True,
                        periodic=periodic)
    trail_ghost = _shift(block[tuple(lo_slice)], axis_name, n, down=False,
                         periodic=periodic)
    return jnp.concatenate([lead_ghost, block, trail_ghost], axis=dim)


def halo_exchange(block: jnp.ndarray, r: int, grid: tuple[int, int],
                  boundary: str = "zero") -> jnp.ndarray:
    """Full two-phase halo pad of a planar (C, h, w) block → (C, h+2r, w+2r).

    Phase order (rows then columns of the row-padded slab) propagates corner
    ghosts correctly — SURVEY.md §8 item 5: outputs must match the
    reference's explicit 8-neighbor exchange bit-for-bit, and do, because
    corner values take the same two-hop path the diagonal message shortcuts.

    ``boundary``: 'zero' (the reference's ghost ring) or 'periodic' (torus
    wrap — ring-collective topology for simulation workloads).
    """
    from parallel_convolution_tpu.utils.config import BOUNDARIES

    if boundary not in BOUNDARIES:
        raise ValueError(f"boundary must be one of {BOUNDARIES}, got {boundary!r}")
    periodic = boundary == "periodic"
    R, C = grid
    count = getattr(_TALLY, "count", None)
    sent = count.total_bytes if count is not None else 0
    with jax.named_scope(SCOPE):
        padded = halo_pad_axis(block, r, "x", R, dim=1, periodic=periodic)
        out = halo_pad_axis(padded, r, "y", C, dim=2, periodic=periodic)
    if count is not None and count.total_bytes > sent:
        count.rounds += 1  # a round is an exchange that built a collective
    return out
