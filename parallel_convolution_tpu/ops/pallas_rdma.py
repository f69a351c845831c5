"""Fused remote-DMA halo + stencil kernel (SURVEY.md §7 frontier).

The standard path (``parallel/halo.py``) rides XLA ``collective-permute``:
edge slabs are ppermuted, concatenated into a padded block *outside* the
kernel, and the Pallas kernel then re-reads the padded block from HBM.
This module is the design SURVEY.md §7 names as the halo-latency
optimization: ONE kernel per device per iteration that

1. pushes its edge slabs straight into its neighbors' VMEM with
   ``pltpu.make_async_remote_copy`` (RDMA over ICI — the reference's
   ``MPI_Isend`` with the network card writing into the remote ghost ring,
   except here it is the TPU's own DMA engines, no copy through XLA), and
2. computes the stencil level in the same program once its own ghosts
   arrive — no HBM round trip between exchange and compute.

Temporal fusion (``fuse=T``): both kernels also amortize the exchange
itself — the ghost transfers widen to depth ``T*r`` and T level-shrinking
stencil iterations run in-kernel (the shared
``pallas_stencil._iterate_levels`` loop, so quantize/round/tap threading
is identical to the ppermute fused path).  One barrier + one exchange +
one launch per T iterations is the persistent/partitioned-communication
recipe for latency-bound stencils (PAPERS.md: persistent MPI stencils;
the Cerebras wafer-scale in-fabric neighbor transfer), i.e. this tier's
reason to exist at small blocks.  See DESIGN.md "RDMA temporal fusion"
for the band-depth math and the win/retire decision rule.

Overlapped pipeline (``overlap=True``, round 12): both kernels can run
interior-first — start the ghost-band DMAs, compute every output pixel
whose level-0 window needs no ghost byte while they fly, and retire each
phase's receive semaphores immediately before the first compute that
reads them (monolithic: the ``overlap_regions`` 5-region schedule;
tiled: rim-last rotated traversal + an SMEM deferred-wait ledger).
Byte-identical to the serialized order by construction — see DESIGN.md
"Overlapped halo pipeline" and tests/test_overlap.py.

Corner propagation uses the same two-phase trick as halo.py: column slabs
are sent at full padded height *after* the row-ghost receive semaphores
fire, so corners take two hops and no diagonal messages exist.  Ghost
regions with no inbound copy (image boundary, zero mode) are zeroed
locally — writes and inbound RDMA targets are disjoint by construction, so
there is no initialization race (checked by the interpreter's race
detector in tests/test_rdma.py).

Cross-invocation safety: within one invocation, waits on both the send and
receive semaphores retire every DMA before the kernel exits — but back-to-
back invocations (the fori_loop iteration driver) add a hazard the
per-invocation race detector cannot see: a fast device entering iteration
N+1 could push ghost bytes into a slow neighbor's scratch while the
neighbor still computes iteration N.  ``_neighbor_barrier`` closes it with
the canonical start-of-kernel rendezvous on the collective barrier
semaphore: no remote copy is issued until every RDMA partner has entered
the current invocation (tests/test_rdma.py::test_rdma_back_to_back_race
runs the multi-invocation protocol under the race detector).

STATUS: bit-exact against the oracle on the multi-device CPU mesh under
TPU interpret mode (which simulates remote DMAs, semaphores, and the
barrier), and on silicon.  On a multi-chip v5e mesh only the tiled
variant compiles (operand-backed pad, ``pad_operand``; AOT-checked in
tests/test_tpu_compile.py): Mosaic refuses the monolithic kernel's
1-row VMEM ghost slices, which are not (8, 128)-tile aligned, and any
block that is not whole (sublane, 128) tiles (see the alignment note
below — the step layer pads for it).  On a 2×2 v5e mesh the tiled
variant runs the benchmark's ``rgb_2520.mesh2x2`` cell: three planes of
u8 at fuse 16 with a 4-deep tail, on 960×1280 blocks of a 1920×2520
image (the right-hand blocks hold 40 columns of rim), byte-identical to
the plain reference on every run; bf16 at fuse 8 and 16 and u8 at fuse
32 ran correct in the same sweep, and ``chip_smoke.py --chips 4`` runs
one grey f32 level (CHANGES.md records each chip run).

VMEM budget: the monolithic kernel holds the whole (C, h+2r, w+2r) f32
padded block plus the (C, h, w) output in VMEM (~16 MB limit ≈ 1400²
grey f32 for the pair).  Blocks beyond ``_TILED_VMEM_BYTES`` auto-select
``_rdma_tiled_kernel``: the padded buffer moves to HBM scratch (storage
dtype), the exchange uses tiling-aligned band transfers, and compute
runs the same double-buffered windowed-DMA grid as ``_stencil_kernel``
— per-program VMEM is two ~1 MB window slots regardless of block size
(tests: test_rdma_auto_tiles_beyond_vmem_bound and the forced-tiled
corner/periodic/radius-2 suite).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallel_convolution_tpu.ops.collective_ids import collective_id
from parallel_convolution_tpu.ops.filters import Filter
from parallel_convolution_tpu.parallel import channels
from parallel_convolution_tpu.ops.pallas_stencil import (
    DEFAULT_TILE, KERNEL_IO, _from_f32, _iterate_levels, _prefetch_window,
    _round_mode_for, _round_up, _sublane, _to_f32, on_tpu, rank1_exact,
    tap_form,
)

# Semaphore slots: one (send, recv) pair per direction.
_UP, _DOWN, _LEFT, _RIGHT = 0, 1, 2, 3


def _when(pred):
    """``pl.when`` that statically elides python-bool predicates.

    ``_topology`` reports extent-1 axes as python ``False`` (and periodic
    multi-device axes as python ``True``); resolving those here keeps dead
    guarded ops — remote-copy starts/waits that can never run — out of
    the program entirely instead of emitting always-false branches.  The
    degenerate single-device grid then contains no RDMA constructs at
    all, which is also what lets it run under interpreters that lack the
    remote-DMA/semaphore simulation.
    """
    if isinstance(pred, bool):
        return (lambda f: f()) if pred else (lambda f: None)
    return pl.when(pred)


def _unless(pred):
    """``pl.when(not pred)`` with the same static-bool elision."""
    if isinstance(pred, bool):
        return (lambda f: None) if pred else (lambda f: f())
    return pl.when(jnp.logical_not(pred))


def _neighbor_barrier(up_in, down_in, left_in, right_in, nbr):
    """Start-of-kernel rendezvous with every RDMA partner.

    Arguments are ``_topology``'s returns.  Each device signals the
    global barrier semaphore of every
    existing neighbor, then waits until all of ITS neighbors have signaled
    it.  This closes the cross-invocation race the per-invocation race
    detector cannot see: without it, a fast device's iteration-N+1 remote
    copy could land in a slow neighbor's scratch while that neighbor is
    still computing iteration N.  After the barrier, every partner has
    entered the current invocation — and kernel invocations serialize on a
    core, so all of its previous-invocation reads have retired before any
    new ghost bytes arrive.

    Skew safety: a neighbor can run at most one invocation ahead, because
    completing invocation N+1 requires its own ``wait_recv`` on ghosts we
    only send after passing this barrier — so the wait below can never be
    satisfied by two signals from one fast neighbor standing in for a slow
    one.  Leftover signals (a neighbor already in N+2's barrier) simply
    pre-credit the next wait; counts stay balanced.
    """
    if all(isinstance(e, bool) and not e
           for e in (up_in, down_in, left_in, right_in)):
        # No RDMA partner exists at all (single-device grid, or a torus
        # of pure self-wrap axes): the rendezvous is vacuous — emit no
        # barrier-semaphore traffic.
        return
    dirs = [(up_in, nbr(-1, 0)), (down_in, nbr(+1, 0)),
            (left_in, nbr(0, -1)), (right_in, nbr(0, +1))]
    bsem = pltpu.get_barrier_semaphore()
    n_wait = jnp.int32(0)
    for exists, dev in dirs:
        if isinstance(exists, bool):
            if not exists:
                continue
            pltpu.semaphore_signal(bsem, inc=1, device_id=dev)
            n_wait = n_wait + 1
        else:
            @pl.when(exists)
            def _(dev=dev):
                pltpu.semaphore_signal(bsem, inc=1, device_id=dev)

            n_wait = n_wait + exists.astype(jnp.int32)
    pltpu.semaphore_wait(bsem, n_wait)


def _topology(R, Cc, periodic):
    """Shared neighbor scaffolding: existence predicates + wrap helper.

    Returns ``(up_in, down_in, left_in, right_in, nbr)`` for the calling
    device.  Predicates are python bools when static (periodic axes);
    a periodic self-wrap axis (extent 1) reports False — the kernels
    handle it with local copies, not remote sends.
    """
    x = lax.axis_index("x")
    y = lax.axis_index("y")
    if periodic:
        up_in = down_in = R > 1
        left_in = right_in = Cc > 1
    else:
        # Extent-1 axes have statically no neighbor: report python False
        # (not the always-false traced `x > 0`) so `_when` can elide the
        # dead exchange ops entirely.
        up_in = (x > 0) if R > 1 else False
        down_in = (x < R - 1) if R > 1 else False
        left_in = (y > 0) if Cc > 1 else False
        right_in = (y < Cc - 1) if Cc > 1 else False

    def nbr(dx, dy):
        if periodic:
            return (lax.rem(x + dx + R, R), lax.rem(y + dy + Cc, Cc))
        return (x + dx, y + dy)

    return up_in, down_in, left_in, right_in, nbr


def overlap_regions(h: int, w: int, d: int):
    """The interior-first output partition of one (h, w) block at ghost
    depth ``d``, as ``(interior, row_bands, col_bands)`` — each a list of
    half-open ``(r0, r1, c0, c1)`` output rectangles (empties dropped).

    * ``interior`` needs NO ghost data (its level-0 window is the local
      block) — computed while the row DMAs are in flight;
    * ``row_bands`` (top/bottom strips restricted to interior columns)
      need the ROW ghosts only — computed while the column DMAs fly;
    * ``col_bands`` (full-height left/right strips) read column ghosts
      (and, via the full padded height, the two-hop corners) — computed
      after the column receive semaphores clear.

    The three groups tile the block exactly (no overlap, no gap) for any
    geometry, including degenerate blocks where ``min(h, w) <= 2*d``
    (interior empties out and the bands absorb everything).  Shared by
    the monolithic kernel and the cost model's legality predicate; unit
    pinned in tests/test_overlap.py.
    """
    t, b = min(d, h), max(h - d, min(d, h))
    l, rt = min(d, w), max(w - d, min(d, w))
    interior = [(t, b, l, rt)]
    row_bands = [(0, t, l, rt), (b, h, l, rt)]
    col_bands = [(0, h, 0, l), (0, h, rt, w)]
    keep = lambda rs: [x for x in rs if x[0] < x[1] and x[2] < x[3]]
    return keep(interior), keep(row_bands), keep(col_bands)


def overlap_region_slabs(h: int, w: int, d: int):
    """The labeled interior-first partition with each region's SLAB WAIT
    SET: ``[(label, (r0, r1, c0, c1), frozenset(directions))]`` in the
    partitioned schedule's compute order (interior, top, bottom, left,
    right; empties dropped).

    A region's wait set is exactly the slab channels whose inbound write
    rectangle its ``(rows + 2d, cols + 2d)`` pad-coordinate read window
    overlaps — derived here by interval intersection against the ghost
    write rects (row slabs write interior columns only; column slabs
    write the FULL padded height, which is how the two-hop corners
    ride them).  Shared by the monolithic kernel's per-slab schedule and
    the soundness property test in tests/test_channels.py: no missed
    wait (a race), no extra wait (lost overlap).
    """
    interior, row_bands, col_bands = overlap_regions(h, w, d)
    # Inbound write rectangles per slab channel, in pad coordinates.
    writes = {
        "up": ((0, d), (d, d + w)),
        "down": ((h + d, h + 2 * d), (d, d + w)),
        "left": ((0, h + 2 * d), (0, d)),
        "right": ((0, h + 2 * d), (w + d, w + 2 * d)),
    }

    def waits(rect):
        r0, r1, c0, c1 = rect
        rr, cc = (r0, r1 + 2 * d), (c0, c1 + 2 * d)
        return frozenset(
            name for name, (gr, gc) in writes.items()
            if rr[0] < gr[1] and gr[0] < rr[1]
            and cc[0] < gc[1] and gc[0] < cc[1])

    out = [("interior", rect, waits(rect)) for rect in interior]
    for rect in row_bands:
        out.append(("top" if rect[0] == 0 else "bottom", rect, waits(rect)))
    for rect in col_bands:
        out.append(("left" if rect[2] == 0 else "right", rect, waits(rect)))
    return out


def tiled_window_hazards(wi, wj, *, th, tw, h, w, sub_v, lane=128):
    """Per-slab hazard geometry of one tiled-kernel window: whether the
    ``(wi, wj)`` window's ``(th + 2*sub_v, tw + 2*lane)`` read region
    overlaps each direction's transferred band (the region an in-flight
    slab DMA writes).  Pure geometry — existence predicates (is there a
    neighbor?) are applied by the caller.  Works on python ints (the
    soundness property test) AND traced values (the kernel's deferred-
    wait guards), so the two can never drift.
    """
    ext_h, ext_w = th + 2 * sub_v, tw + 2 * lane
    return {"up": wi == 0, "down": wi * th + ext_h > h + sub_v,
            "left": wj == 0, "right": wj * tw + ext_w > w + lane}


# Packed column-transport staging slots (both kernels, one convention):
# 0/1 = my contiguous outbound left/right slab; 2 = inbound payload for
# my RIGHT ghost (the right neighbor's "left" channel lands here, SPMD
# symmetry); 3 = inbound payload for my LEFT ghost.
_PK_SEND = {"left": 0, "right": 1}
_PK_LAND = {"left": 2, "right": 3}   # where MY channel lands on the receiver
_PK_GHOST = {"right": 2, "left": 3}  # the slot holding MY side's ghost bytes


def _packed_slab_copy(cstage, s, send_sem, recv_sem, nbr):
    """The packed transport's ONE dense stage→stage RDMA for column slab
    ``s`` — shared by both kernels so the staging-slot convention and
    the semaphore pairing can never desynchronize between kernel forms
    (the strided gather/scatter moves to local pack/unpack copies)."""
    return pltpu.make_async_remote_copy(
        cstage.at[_PK_SEND[s.direction]], cstage.at[_PK_LAND[s.direction]],
        send_sem.at[s.sem], recv_sem.at[s.sem], device_id=nbr(*s.nbr))


def _rdma_kernel(in_ref, out_ref, pad, send_sem, recv_sem, *scr, plan,
                 form, k, r, T, C, h, w, R, Cc, periodic, quantize,
                 convex, round_mode, valid_hw, overlap=False,
                 partitioned=True):
    """One device's program: exchange T·r-deep ghosts in-kernel, then run
    T stencil levels (temporal fusion — ONE exchange buys T iterations).

    ``pad`` is the (C, h+2d, w+2d) f32 working buffer, d = r*T; interior =
    my block, ghost ring = RDMA'd from neighbors (or zeros at a
    non-periodic image boundary).  All slab math mirrors
    halo.halo_exchange at depth d.  The compute is the shared
    level-shrinking loop (``pallas_stencil._iterate_levels``): for T > 1,
    ``valid_hw`` re-zeroes out-of-image positions after every level — the
    oracle's ghost ring at each intermediate — so results stay bit-exact
    with T single-exchange steps.  ``valid_hw=None`` (fuse=1, or the
    periodic torus) statically drops the masks: the validated
    single-level protocol is byte-identical to before.

    ``overlap=True`` is the interior-first pipeline (ROADMAP item 1, the
    persistent/partitioned-MPI overlap recipe): compute is split into the
    :func:`overlap_regions` partition and interleaved with the exchange.
    ``partitioned=True`` (the round-16 default) retires each SLAB
    independently — every region waits on exactly the channels whose
    inbound write rect its read window overlaps
    (:func:`overlap_region_slabs`), so a band computes the moment ITS
    OWN ghosts land; ``partitioned=False`` keeps the r12 phase-granular
    order (both row slabs, then both column slabs) as the A/B reference.
    Bit-exact vs the serialized order either way because every output
    pixel's level chain is a pure function of its own level-0 dependency
    cone, which each region's window contains by construction; the only
    reordering is BETWEEN independent pixels.  Safe vs the in-flight
    DMAs because each region reads only pad cells that are either local
    or already received (inbound ghost writes are disjoint from the
    region reads until their semaphore is waited — the wait-set
    derivation IS that disjointness proof, pinned by the soundness test).

    ``plan`` is the bound channel structure (``parallel.channels``):
    slab rectangles, partners, and semaphore pairing come from the
    cached per-identity plan instead of inline arithmetic.  A plan with
    packed columns (``plan.packed_cols``) receives the staging scratch
    as ``scr[0]`` and moves each column slab as pack → one dense RDMA →
    unpack; the strided plan issues the direct strided copy.  Byte-
    identical by construction — the unpack writes exactly the ghost
    cells the strided copy would.
    """
    cstage = scr[0] if scr else None
    d = r * T
    # Interior + boundary-ghost initialization.  Inbound RDMA targets are
    # exactly the ghost regions owned by an existing neighbor (packed
    # columns land in the staging scratch first), so local writes below
    # never overlap a remote write (no ordering needed).
    pad[:, d : d + h, d : d + w] = _to_f32(in_ref[...])

    up_in, down_in, left_in, right_in, nbr = _topology(R, Cc, periodic)
    exists = {"up": up_in, "down": down_in,
              "left": left_in, "right": right_in}

    zero_row = jnp.zeros((C, d, w), jnp.float32)
    zero_col = jnp.zeros((C, h + 2 * d, d), jnp.float32)

    @_unless(up_in)
    def _():
        pad[:, 0:d, d : d + w] = zero_row

    @_unless(down_in)
    def _():
        pad[:, h + d : h + 2 * d, d : d + w] = zero_row

    if plan.row_wrap:
        # Torus of height 1: my own opposite edge wraps to me (static).
        pad[:, 0:d, d : d + w] = pad[:, h : h + d, d : d + w]
        pad[:, h + d : h + 2 * d, d : d + w] = pad[:, d : 2 * d, d : d + w]

    # Cross-invocation safety: no remote copy may be issued until every
    # RDMA partner has entered THIS invocation (see _neighbor_barrier).
    # Self-wrap axes (periodic R==1 / Cc==1) have python-False predicates
    # and drop out statically.
    _neighbor_barrier(up_in, down_in, left_in, right_in, nbr)

    def compute(regions):
        """T stencil levels for each output rectangle (shared level loop
        — identical op order / quantize / tap threading to the ppermute
        fused path and to the serialized whole-block call; a region's
        level-0 window is the same pad cells the whole-block window
        reads for those pixels, so bytes cannot differ).  Level-0
        out-of-image positions are already exact zeros (boundary ghosts
        zeroed above; the pad-to-multiple rim is zero by the iterate's
        masking invariant), so no level-0 select tier is needed — only
        the per-level rank-1 re-zeroing against the region-shifted
        global-coordinate iotas."""
        for (r0, r1, c0, c1) in regions:
            rows0 = cols0 = None
            if valid_hw is not None:
                rows0 = (lax.axis_index("x") * h - d + r0
                         + lax.broadcasted_iota(
                             jnp.int32, (r1 - r0 + 2 * d, 1), 0))
                cols0 = (lax.axis_index("y") * w - d + c0
                         + lax.broadcasted_iota(
                             jnp.int32, (1, c1 - c0 + 2 * d), 1))
            for c in range(C):
                acc = _iterate_levels(
                    pad[c, r0 : r1 + 2 * d, c0 : c1 + 2 * d],
                    form=form, k=k, r=r, T=T,
                    out_hw=(r1 - r0, c1 - c0),
                    quantize=quantize, convex=convex,
                    round_mode=round_mode,
                    rows0=rows0, cols0=cols0, valid_hw=valid_hw)
                out_ref[c, r0:r1, c0:c1] = _from_f32(acc, out_ref.dtype)

    # --- Channel descriptors, bound from the PLAN's slab table.  On a
    # degenerate axis the plan simply has no slab — not even the
    # descriptor is constructed, so the 1x1 program is the serialized
    # local program verbatim, independent of col_mode.
    def _slab_copy(s):
        if s.direction in ("left", "right") and cstage is not None:
            return _packed_slab_copy(cstage, s, send_sem, recv_sem, nbr)
        return pltpu.make_async_remote_copy(
            pad.at[:, s.src_rows[0] : s.src_rows[1],
                   s.src_cols[0] : s.src_cols[1]],
            pad.at[:, s.dst_rows[0] : s.dst_rows[1],
                   s.dst_cols[0] : s.dst_cols[1]],
            send_sem.at[s.sem], recv_sem.at[s.sem], device_id=nbr(*s.nbr))

    copies = {s.direction: _slab_copy(s) for s in plan.slabs()}

    def retire(direction):
        """Retire ONE slab channel: wait my outbound send plus the
        inbound ghost write (the OPPOSITE channel's recv semaphore —
        SPMD symmetry: my top ghost is written by the upper neighbor's
        "down" channel), and unpack the staged payload for packed
        columns.  No-op for directions with no channel."""
        if direction not in copies:
            return
        g = exists[direction]
        _when(g)(copies[direction].wait_send)
        _when(g)(copies[channels.OPPOSITE[direction]].wait_recv)
        if cstage is not None and direction == "left":
            @_when(g)
            def _():
                pad[:, :, 0:d] = cstage[_PK_GHOST["left"]]
        if cstage is not None and direction == "right":
            @_when(g)
            def _():
                pad[:, :, w + d : w + 2 * d] = cstage[_PK_GHOST["right"]]

    def start_cols():
        # Phase 2: column channels at FULL padded height — they carry
        # the just-arrived row ghosts, so corners propagate in two hops
        # exactly as in halo.py.  Callable only after both row slabs
        # retired (the schedules below guarantee it).
        if plan.col_wrap:
            pad[:, :, 0:d] = pad[:, :, w : w + d]
            pad[:, :, w + d : w + 2 * d] = pad[:, :, d : 2 * d]
            return

        @_unless(left_in)
        def _():
            pad[:, :, 0:d] = zero_col

        @_unless(right_in)
        def _():
            pad[:, :, w + d : w + 2 * d] = zero_col

        if cstage is not None:
            # Pack: gather each strided column slab into its contiguous
            # send slot so the RDMA below is one dense descriptor.
            @_when(left_in)
            def _():
                cstage[_PK_SEND["left"]] = pad[:, :, d : 2 * d]

            @_when(right_in)
            def _():
                cstage[_PK_SEND["right"]] = pad[:, :, w : w + d]

        for s in plan.col_slabs:
            _when(exists[s.direction])(copies[s.direction].start)

    # --- Phase 1: rows.  My top d interior rows -> upper neighbor's
    # bottom ghost; my bottom d interior rows -> lower neighbor's top
    # ghost (d <= h, enforced at the launch).
    for s in plan.row_slabs:
        _when(exists[s.direction])(copies[s.direction].start)

    # --- Schedule.  Each region computes after exactly its wait set has
    # retired; the first column-ghost reader starts phase 2 (which
    # itself requires both row slabs landed — full-height column slabs
    # read the row ghosts).
    regions = (overlap_region_slabs(h, w, d) if overlap
               else [("whole", (0, h, 0, w),
                      frozenset(("up", "down", "left", "right")))])
    retired: set = set()
    cols_started = [False]

    def ensure(waits):
        for direction in ("up", "down"):
            if direction in waits and direction not in retired:
                retire(direction)
                retired.add(direction)
        # Start the column phase the MOMENT both row slabs have retired
        # (full-height column slabs read the row ghosts — the corner
        # dependency), not only when a column reader appears: the
        # regions computed between here and the first column reader
        # (the bottom band, in the partitioned schedule) then run under
        # the in-flight column DMAs.
        if not cols_started[0] and {"up", "down"} <= retired:
            start_cols()
            cols_started[0] = True
        if waits & {"left", "right"} and not cols_started[0]:
            for direction in ("up", "down"):
                if direction not in retired:
                    retire(direction)
                    retired.add(direction)
            start_cols()
            cols_started[0] = True
        for direction in ("left", "right"):
            if direction in waits and direction not in retired:
                retire(direction)
                retired.add(direction)

    if overlap and partitioned:
        # Per-slab: interior under the in-flight row DMAs (empty wait
        # set), each band the moment its own ghosts land, the bottom
        # band under the in-flight column DMAs.
        for _label, rect, waits in regions:
            ensure(waits)
            compute([rect])
    elif overlap:
        # r12 phase-granular order (the A/B reference): interior under
        # the row DMAs, both row slabs retire together, the row bands
        # hide the column phase, both column slabs retire together.
        compute([rect for _l, rect, ws in regions if not ws])
        ensure(frozenset(("up", "down")))  # retires rows AND starts cols
        compute([rect for _l, rect, ws in regions
                 if ws and not ws & {"left", "right"}])
        ensure(frozenset(("left", "right")))
        compute([rect for _l, rect, ws in regions
                 if ws & {"left", "right"}])
    else:
        # Serialized: the whole exchange completes before the one
        # whole-block compute — the validated pre-overlap protocol
        # (ensure() starts the column phase once both row slabs retire).
        ensure(frozenset(("up", "down")))
        ensure(frozenset(("left", "right")))
        compute([rect for _l, rect, _w in regions])

    # Channel hygiene: every live slab's semaphores retire before exit
    # even when its band was empty (degenerate geometry can drop a band
    # whose channel still flew); ensure() starts the column phase here
    # if nothing did earlier.
    ensure(frozenset(("up", "down")))
    ensure(frozenset(("left", "right")))


# ---------------------------------------------------------------------------
# Tiled variant: HBM-resident padded buffer + windowed-DMA compute grid.
# ---------------------------------------------------------------------------
#
# The monolithic kernel above holds the whole (C, h+2r, w+2r) f32 block in
# VMEM — a hard ~16 MB bound (≈2048² grey f32).  The tiled variant lifts
# it: the padded buffer lives in HBM scratch (storage dtype, not f32), the
# ghost exchange lands there, and the compute phase is the same
# double-buffered windowed-DMA grid as ``_stencil_kernel``.  Two design
# points keep HBM DMA *starts* tiling-aligned (Mosaic requires aligned
# slice starts; interpret mode does not check — see ``_sublane``):
#
# 1. **Aligned-band transfers.**  Ghost slabs are r*T wide (T = temporal
#    fusion depth), which is never aligned.  Instead each transfer moves
#    a full (sublane, 128)-aligned band — ``sub_v`` rows / 128 cols of
#    interior — whose LAST (first) r*T rows/cols land exactly on the
#    receiver's ghost positions (hence the r*T <= min(sub_v, 128)
#    constraint); the rest of the band falls on never-read buffer and is
#    masked at compute.
# 2. **No ghost zeroing.**  Image-boundary ghosts stay uninitialized in
#    HBM; every compute window applies one select against the block's
#    valid [row_lo, row_hi) × [col_lo, col_hi) box (which also kills any
#    non-finite DMA garbage — a multiplicative mask would leak NaN).
#
# VMEM per program: 2 window slots of (th + 2·sub_v, tw + 256) storage
# dtype — ~1.7 MB at the 256×512 f32 default, independent of block size.
#
# Alignment: the scheme is fully aligned (every start and every extent)
# exactly when the block shape itself is (sub_v, 128)-aligned — then the
# h/w-derived starts (row h, h+sub_v; col w, w+LANE) and the orthogonal
# extents (h, w) are all multiples.  Mosaic refuses anything else ("Slice
# shape along dimension 2 must be aligned to tiling (128), but is 1260"
# for BASELINE config 2's 960x1260 blocks), so the caller pads: the form
# is registered ``tile_aligned_blocks`` (``parallel.kernels``) and
# ``parallel.step.block_extent`` rounds every block up to whole tiles;
# the valid-box mask keeps that rim out of the result.

_TILED_VMEM_BYTES = 10 * 2**20  # monolithic-kernel budget before auto-tiling


def _and2(a, b):
    """``a & b`` with python-bool static folding on either side."""
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return jnp.logical_and(a, b)


def _or2(a, b):
    """``a | b`` with python-bool static folding on either side."""
    if isinstance(a, bool):
        return True if a else b
    if isinstance(b, bool):
        return True if b else a
    return jnp.logical_or(a, b)


def _rdma_tiled_kernel(in_ref, out_ref, pad, *rest, plan, form, k, r,
                       T, C, h, w, R, Cc, periodic, quantize, convex, th,
                       tw, sub_v, round_mode, valid_hw, overlap=False,
                       partitioned=True):
    """HBM-pad windowed variant; ``overlap=True`` is the interior-first
    pipeline at window granularity.

    Serialized (``overlap=False``): the step-0 program completes the
    whole two-phase exchange before any window is copied — the validated
    protocol, byte-identical to before this knob existed.

    Overlapped: step 0 only STARTS the row-band DMAs; the window
    traversal is rotated by one on both grid axes so the rim windows
    (the only ones whose (ext_h, ext_w) read window reaches a ghost
    band) are visited last, and an SMEM ledger defers every semaphore
    wait to the first window whose read window actually overlaps a
    still-pending transfer — interior windows stream and compute under
    the in-flight exchange.  ``partitioned=True`` (round 16) is the
    PER-SLAB ledger: one flag per slab channel (up/down/left/right —
    the fused ghost depth rides each band's geometry) plus a
    column-phase-started flag, so a window waits on exactly the slabs
    its read region overlaps (:func:`tiled_window_hazards`) and a tile
    computes the moment ITS OWN ghosts land.  ``partitioned=False``
    keeps the r12 3-state phase ledger (``flags[0]``: 0 = rows in
    flight, 1 = rows done + columns in flight, 2 = all landed) as the
    A/B reference.  Sound either way because grid programs run
    sequentially on one core with shared scratch (the same property the
    step-0-exchange design already relies on), waits recreate the
    identical copy descriptors from the bound channel plan, the ledger
    transitions are monotonic, and the rim windows that trigger each
    retirement provably exist in every grid (window row 0 / last row,
    column 0 / last column).  The column phase still starts only after
    BOTH row receives (its full-height bands carry the two-hop corner
    bytes), so the exchange protocol — order, slabs, semaphore pairing
    — is unchanged; only the waits move later and split finer.

    ``plan`` is the bound channel structure (``parallel.channels``).
    ``plan.packed_cols`` receives the HBM staging scratch as ``rest[0]``
    and moves each column band as pack → one dense RDMA → unpack
    (byte-identical: the unpack writes exactly the band the strided
    copy would); the strided plan issues the direct strided band copy.
    """
    if plan.packed_cols:
        cstage, win, wsems, xsem, send_sem, recv_sem, flags = rest
    else:
        win, wsems, xsem, send_sem, recv_sem, flags = rest
        cstage = None
    LANE = 128
    d = r * T  # ghost depth; <= min(sub_v, LANE) so one band carries it
    ext_h, ext_w = th + 2 * sub_v, tw + 2 * LANE
    c, vi, vj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ni, nj = pl.num_programs(1), pl.num_programs(2)
    step = (c * ni + vi) * nj + vj

    up_in, down_in, left_in, right_in, nbr = _topology(R, Cc, periodic)
    exists = {"up": up_in, "down": down_in,
              "left": left_in, "right": right_in}

    row_remote = R > 1   # remote row-band DMAs exist in this program
    col_remote = Cc > 1  # remote column-band DMAs exist
    # Periodic self-wrap columns on a multi-row grid: the local wrap
    # copies read the FULL padded height, so under overlap they must
    # run after the row receives — i.e. at the column-phase transition,
    # not at step 0 — and windows reading column ghosts must wait on
    # that transition even though no remote column DMA exists.
    col_wrap_deferred = periodic and Cc == 1 and row_remote
    engage = overlap and (row_remote or col_remote)
    # Window (wi, wj) this program computes: the rotated traversal
    # visits rim windows last under the overlapped pipeline.  The out
    # BlockSpec index map applies the SAME rotation (fused_rdma_step).
    if engage:
        i, j = lax.rem(vi + 1, ni), lax.rem(vj + 1, nj)
    else:
        i, j = vi, vj

    # Ledger slots in the SMEM scratch (shared across the sequential
    # grid programs of one core): slot 0 is the r12 3-state phase
    # ledger; slots 1..5 are the per-slab map — each slab's landed flag
    # plus the column-phase-started flag.
    F_PHASE, F_UP, F_DOWN, F_COL, F_LEFT, F_RIGHT = 0, 1, 2, 3, 4, 5

    # -- exchange pieces, each buildable at any program (descriptors are
    # bound from the PLAN's slab table; a wait only needs the semaphore).
    def _local_row_wrap():
        for src, dst, sl in (((sub_v, 2 * sub_v),
                              (h + sub_v, h + 2 * sub_v), _UP),
                             ((h, h + sub_v), (0, sub_v), _DOWN)):
            cp = pltpu.make_async_copy(
                pad.at[:, src[0] : src[1], LANE : LANE + w],
                pad.at[:, dst[0] : dst[1], LANE : LANE + w],
                send_sem.at[sl])
            cp.start()
            cp.wait()

    def _local_col_wrap():
        for src, dst, sl in (((LANE, 2 * LANE),
                              (w + LANE, w + 2 * LANE), _LEFT),
                             ((w, w + LANE), (0, LANE), _RIGHT)):
            cp = pltpu.make_async_copy(
                pad.at[:, :, src[0] : src[1]],
                pad.at[:, :, dst[0] : dst[1]],
                send_sem.at[sl])
            cp.start()
            cp.wait()

    def _slab_copy(s):
        if s.direction in ("left", "right") and cstage is not None:
            return _packed_slab_copy(cstage, s, send_sem, recv_sem, nbr)

        def ref(rows, cols):
            if rows is None:  # column bands run the full padded height
                return pad.at[:, :, cols[0] : cols[1]]
            return pad.at[:, rows[0] : rows[1], cols[0] : cols[1]]

        return pltpu.make_async_remote_copy(
            ref(s.src_rows, s.src_cols), ref(s.dst_rows, s.dst_cols),
            send_sem.at[s.sem], recv_sem.at[s.sem], device_id=nbr(*s.nbr))

    def _row_copies():
        return _slab_copy(plan.slab("up")), _slab_copy(plan.slab("down"))

    def _col_copies():
        return _slab_copy(plan.slab("left")), _slab_copy(plan.slab("right"))

    def _pack_cols():
        # Gather each strided column band into its contiguous send slot
        # (aligned local HBM copies) so the RDMA is one dense descriptor.
        for direction, src in (("left", pad.at[:, :, LANE : 2 * LANE]),
                               ("right", pad.at[:, :, w : w + LANE])):
            @_when(exists[direction])
            def _(direction=direction, src=src):
                cp = pltpu.make_async_copy(
                    src, cstage.at[_PK_SEND[direction]], xsem)
                cp.start()
                cp.wait()

    def _unpack_col(direction):
        dst = (pad.at[:, :, 0:LANE] if direction == "left"
               else pad.at[:, :, w + LANE : w + 2 * LANE])
        cp = pltpu.make_async_copy(
            cstage.at[_PK_GHOST[direction]], dst, xsem)
        cp.start()
        cp.wait()

    def _start_rows():
        su, sd = _row_copies()
        _when(up_in)(su.start)
        _when(down_in)(sd.start)

    def _retire_up():
        # My top ghost is written by my upper neighbor's "down" channel
        # (it signals MY recv_sem[_DOWN]) — SPMD symmetry; pairing my
        # outbound up-send's wait here keeps each slab's semaphore
        # hygiene self-contained.  No row channels (R==1) = statically
        # nothing to retire: these helpers are traced inside guards
        # whose predicates can be dynamic (the legacy phase ledger's
        # need_any), so they must be constructible on ANY grid — the
        # same rule the monolithic kernel's copies-dict lookup applies.
        if not plan.row_slabs:
            return
        su, sd = _row_copies()
        _when(up_in)(su.wait_send)
        _when(up_in)(sd.wait_recv)

    def _retire_down():
        if not plan.row_slabs:
            return
        su, sd = _row_copies()
        _when(down_in)(sd.wait_send)
        _when(down_in)(su.wait_recv)

    def _wait_rows():
        _retire_up()
        _retire_down()

    def _start_cols():
        # Phase 2 initiation: column bands at FULL padded height — the
        # transferred bands carry the just-arrived row ghosts, so
        # corners propagate in two hops exactly as in halo.py / the
        # monolithic kernel.  Callable only after the row phase landed.
        if periodic and Cc == 1:
            _local_col_wrap()
        elif col_remote:
            if cstage is not None:
                _pack_cols()
            sl_, sr = _col_copies()
            _when(left_in)(sl_.start)
            _when(right_in)(sr.start)

    def _retire_left():
        if not plan.col_slabs:
            return
        sl_, sr = _col_copies()
        _when(left_in)(sl_.wait_send)
        _when(left_in)(sr.wait_recv)
        if cstage is not None:
            _when(left_in)(lambda: _unpack_col("left"))

    def _retire_right():
        if not plan.col_slabs:
            return
        sl_, sr = _col_copies()
        _when(right_in)(sr.wait_send)
        _when(right_in)(sl_.wait_recv)
        if cstage is not None:
            _when(right_in)(lambda: _unpack_col("right"))

    def _wait_cols():
        _retire_left()
        _retire_right()

    @pl.when(step == 0)
    def _exchange():
        # Interior: one aligned HBM->HBM copy (dst starts at (sub_v, 128)).
        intr = pltpu.make_async_copy(
            in_ref, pad.at[:, sub_v : sub_v + h, LANE : LANE + w], xsem)
        intr.start()
        intr.wait()

        _neighbor_barrier(up_in, down_in, left_in, right_in, nbr)

        # Phase 1: row bands (interior cols only; ghost cols not yet
        # live).  Torus of height 1: own opposite edge, local aligned
        # copies — complete synchronously here either way.
        if periodic and R == 1:
            _local_row_wrap()
        if not engage:
            # Serialized: the whole exchange completes before any window.
            if row_remote:
                _start_rows()
                _wait_rows()
            if periodic and Cc == 1:
                _local_col_wrap()
            elif col_remote:
                _start_cols()
                _wait_cols()
        elif not partitioned:
            # r12 phase ledger (the A/B reference).
            if row_remote:
                _start_rows()
                flags[F_PHASE] = jnp.int32(0)
            else:
                # Rows already complete (local wrap / no axis): the
                # column phase can start under the very first windows.
                _start_cols()
                flags[F_PHASE] = jnp.int32(1 if col_remote else 2)
        else:
            # Per-slab ledger: every slab flag initialized here (SMEM
            # scratch is uninitialized and shared across programs).
            if row_remote:
                _start_rows()
                flags[F_UP] = jnp.int32(0)
                flags[F_DOWN] = jnp.int32(0)
                flags[F_COL] = jnp.int32(0)
            else:
                _start_cols()
                flags[F_UP] = jnp.int32(1)
                flags[F_DOWN] = jnp.int32(1)
                flags[F_COL] = jnp.int32(1)
            flags[F_LEFT] = jnp.int32(0 if col_remote else 1)
            flags[F_RIGHT] = jnp.int32(0 if col_remote else 1)

    # -- deferred-wait guard: runs before a window copy is ISSUED, with
    # the window's indices — waits exactly when that window's read
    # region overlaps a still-pending transfer, advancing the ledger.
    def _ensure(wi, wj):
        if not engage:
            return
        # Geometric touch: the (ext_h, ext_w) read window vs the four
        # transferred bands (tiled_window_hazards — shared with the
        # soundness property test); hazardous only where an actual
        # transfer writes (the _in predicates — non-live ghost regions
        # hold garbage the valid-box mask kills, no ordering needed).
        hz = tiled_window_hazards(wi, wj, th=th, tw=tw, h=h, w=w,
                                  sub_v=sub_v)
        top, bot, lef, rig = hz["up"], hz["down"], hz["left"], hz["right"]
        if col_remote:
            need_col = _or2(_and2(lef, left_in), _and2(rig, right_in))
        elif col_wrap_deferred:
            # Self-wrap ghosts are VALID data (periodic valid box), but
            # written only at the column transition — any reader waits.
            need_col = _or2(lef, rig)
        else:
            need_col = False
        if not partitioned:
            # r12 3-state phase ledger (kept as the A/B reference).
            need_row = (_or2(_and2(top, up_in), _and2(bot, down_in))
                        if row_remote else False)
            need_any = _or2(need_row, need_col)

            @_when(_and2(need_any, flags[F_PHASE] == 0))
            def _():
                _wait_rows()
                _start_cols()
                flags[F_PHASE] = jnp.int32(1 if col_remote else 2)

            if col_remote and need_col is not False:
                @_when(_and2(need_col, flags[F_PHASE] == 1))
                def _():
                    _wait_cols()
                    flags[F_PHASE] = jnp.int32(2)
            return
        # Per-slab retirement: each slab the moment a window first
        # overlaps its band — the window computes once ITS OWN ghosts
        # land, not once the whole phase does.
        if row_remote:
            @_when(_and2(top, flags[F_UP] == 0))
            def _():
                _retire_up()
                flags[F_UP] = jnp.int32(1)

            @_when(_and2(bot, flags[F_DOWN] == 0))
            def _():
                _retire_down()
                flags[F_DOWN] = jnp.int32(1)
        if need_col is not False:
            # Column transition: the full-height column bands read the
            # row ghosts, so any still-pending row slab retires first.
            # Flat guards, not a `when` nested in a `when`: Mosaic fails
            # to lower the nested form on v5e (PR 21, CHANGES.md).
            col_start = _and2(need_col, flags[F_COL] == 0)
            if row_remote:
                @_when(_and2(col_start, flags[F_UP] == 0))
                def _():
                    _retire_up()
                    flags[F_UP] = jnp.int32(1)

                @_when(_and2(col_start, flags[F_DOWN] == 0))
                def _():
                    _retire_down()
                    flags[F_DOWN] = jnp.int32(1)

            @_when(col_start)
            def _():
                _start_cols()
                flags[F_COL] = jnp.int32(1)
        if col_remote:
            # Per-slab column retirement (guarded on the phase having
            # started — never wait a DMA that was not issued).
            @_when(_and2(lef, _and2(flags[F_COL] == 1,
                                    flags[F_LEFT] == 0)))
            def _():
                _retire_left()
                flags[F_LEFT] = jnp.int32(1)

            @_when(_and2(rig, _and2(flags[F_COL] == 1,
                                    flags[F_RIGHT] == 0)))
            def _():
                _retire_right()
                flags[F_RIGHT] = jnp.int32(1)

    # --- Compute: the _stencil_kernel windowed-DMA grid over the HBM pad.
    def window_copy(cc, ai, aj, s):
        if engage:
            wi, wj = lax.rem(ai + 1, ni), lax.rem(aj + 1, nj)
        else:
            wi, wj = ai, aj
        _ensure(wi, wj)
        return pltpu.make_async_copy(
            pad.at[cc, pl.ds(wi * th, ext_h), pl.ds(wj * tw, ext_w)],
            win.at[s], wsems.at[s])

    slot = _prefetch_window(window_copy)

    # Valid box of the block in padded coords (ghost ring d deep); outside
    # it live image-boundary ghosts (zero semantics) and never-written
    # buffer.  Periodic: EVERY ghost is valid (filled by wrap or remote
    # band) even on a self-wrap axis, where the exchange predicate is
    # False.
    def _i32(p):
        return jnp.int32(p) if isinstance(p, bool) else p.astype(jnp.int32)

    row_lo = sub_v - (d if periodic else d * _i32(up_in))
    row_hi = sub_v + h + (d if periodic else d * _i32(down_in))
    col_lo = LANE - (d if periodic else d * _i32(left_in))
    col_hi = LANE + w + (d if periodic else d * _i32(right_in))

    w0h, w0w = th + 2 * d, tw + 2 * d
    rows = (i * th + (sub_v - d)
            + lax.broadcasted_iota(jnp.int32, (w0h, 1), 0))
    cols = (j * tw + (LANE - d)
            + lax.broadcasted_iota(jnp.int32, (1, w0w), 1))
    ok = (((rows >= row_lo) & (rows < row_hi))
          & ((cols >= col_lo) & (cols < col_hi)))
    cur = _to_f32(win[slot][sub_v - d : sub_v + d + th,
                           LANE - d : LANE + d + tw])
    cur = jnp.where(ok, cur, 0.0)

    # T in-VMEM levels (shared level loop).  For T > 1 the per-level
    # re-zeroing needs GLOBAL image coordinates (the pad-to-multiple rim
    # is in-block but out-of-image); pad row p maps to global row
    # x*h + p - sub_v, so shift the hoisted pad-coordinate iotas.  The
    # tier-1 select above already killed every non-finite DMA garbage
    # value, so the rank-1 multiplies are exact.
    rows0 = cols0 = None
    if valid_hw is not None:
        rows0 = rows + (lax.axis_index("x") * h - sub_v)
        cols0 = cols + (lax.axis_index("y") * w - LANE)
    acc = _iterate_levels(cur, form=form, k=k, r=r, T=T,
                          out_hw=(th, tw), quantize=quantize, convex=convex,
                          round_mode=round_mode, rows0=rows0, cols0=cols0,
                          valid_hw=valid_hw)
    out_ref[0] = _from_f32(acc, out_ref.dtype)


@dataclasses.dataclass(frozen=True)
class Launch:
    """What :func:`fused_rdma_step` compiles for one (C, h, w) block: the
    kernel form, its bound channel plan and the pad buffer's extent
    (the tiled form's output tile ``th``/``tw`` and sublane ``sub_v``;
    0 on the monolithic form)."""

    kernel: str                      # "tiled" | "monolithic"
    plan: channels.ChannelPlan
    planes: int
    pad_hw: tuple[int, int]
    wire_itemsize: int               # bytes per element of the pad buffer
    th: int = 0
    tw: int = 0
    sub_v: int = 0

    @property
    def wire_bytes(self) -> int:
        """Bytes the kernel's slab copies move over the mesh in one
        exchange: whole aligned bands on the tiled form, not the r*fuse
        ghosts inside them."""
        return self.plan.wire_bytes(self.planes, self.wire_itemsize,
                                    self.pad_hw[0])


def launch_plan(shape, dtype, out_dtype, radius: int, fuse: int, grid,
                boundary: str = "zero", col_mode: str = "strided",
                tiled: bool | None = None, tile=None) -> Launch:
    """The kernel form, channel plan and pad geometry of one launch.

    ``tiled=None`` auto-selects: blocks whose monolithic VMEM footprint
    (f32 padded buffer + output) exceeds ``_TILED_VMEM_BYTES`` take the
    tiled kernel.  Raises where the tiled kernel cannot carry the ghost
    depth or the block is narrower than one band."""
    C, h, w = (int(v) for v in shape)
    d = int(radius) * int(fuse)
    sub_v = _sublane(dtype)
    if tiled is None:
        mono_bytes = (C * (h + 2 * d) * (w + 2 * d) * 4
                      + C * h * w * jnp.dtype(out_dtype).itemsize)
        if col_mode == "packed" and grid[1] > 1:
            # The packed transport's 4 f32 staging slots live in VMEM
            # for the monolithic kernel — they count against the same
            # budget (mirrored in costmodel.rdma_is_tiled).
            mono_bytes += 4 * C * (h + 2 * d) * d * 4
        tiled = mono_bytes > _TILED_VMEM_BYTES
        if tiled and (d > min(sub_v, 128) or h < sub_v or w < 128):
            # Silently falling back to the monolithic kernel here would
            # trade this clear error for an opaque Mosaic VMEM failure.
            raise ValueError(
                f"block {(C, h, w)} needs ~{mono_bytes >> 20} MB of VMEM "
                f"(over the {_TILED_VMEM_BYTES >> 20} MB monolithic "
                f"budget) but the tiled kernel requires ghost depth "
                f"r*fuse <= {min(sub_v, 128)} (got {d}) and blocks >= "
                f"({sub_v}, 128); use a finer or differently-shaped mesh, "
                "or a shallower fuse")
    # The persistent channel plan: descriptor geometry bound ONCE per
    # exchange identity (parallel.channels) and fetched from the
    # process-global cache by every trace that shares it — fused
    # iteration chunks, converge chunks, multigrid V-cycle levels.
    plan = channels.plan_for(channels.ChannelKey(
        grid=(int(grid[0]), int(grid[1])), block_hw=(h, w),
        radius=int(radius), fuse=int(fuse),
        dtype=str(jnp.dtype(dtype).name), boundary=boundary,
        kernel="tiled" if tiled else "monolithic", col_mode=col_mode))
    if not tiled:
        return Launch("monolithic", plan, C, (h + 2 * d, w + 2 * d), 4)
    if d > min(sub_v, 128):
        raise ValueError(
            f"tiled RDMA kernel needs ghost depth r*fuse <= "
            f"{min(sub_v, 128)} (the aligned transfer bands are one "
            f"({sub_v}, 128) tile deep and their trailing/leading r*fuse "
            f"rows/cols must all be live ghosts), got r*fuse = {d}")
    if h < sub_v or w < 128:
        # A band narrower than the block would make src/dst of the band
        # copies overlap (undefined for real DMA engines even though the
        # interpreter's atomic copies happen to produce the right bytes).
        raise ValueError(
            f"tiled RDMA kernel needs blocks >= ({sub_v}, 128) for "
            f"non-overlapping band transfers, got {(h, w)}; blocks this "
            "small fit the monolithic kernel (tiled=False) unless the "
            "other dimension is huge — then reshape the mesh")
    LANE = 128
    t0, t1 = tile if tile is not None else DEFAULT_TILE
    th = min(_round_up(t0, sub_v), _round_up(h, sub_v))
    tw = min(_round_up(t1, LANE), _round_up(w, LANE))
    # Pad buffer: interior at (sub_v, LANE); sized so the LAST window
    # [(gh-1)·th, +th+2·sub_v) fits — any rim beyond the ghost ring is
    # never valid (masked) and never sent (transfers address interior/
    # ghost coordinates only).
    h_pad = -(-h // th) * th + 2 * sub_v
    w_pad = -(-w // tw) * tw + 2 * LANE
    return Launch("tiled", plan, C, (h_pad, w_pad),
                  jnp.dtype(dtype).itemsize, th, tw, sub_v)


@functools.partial(
    jax.jit,
    static_argnames=("filt", "grid", "boundary", "quantize", "out_dtype",
                     "interpret", "tiled", "tile", "pad_operand", "fuse",
                     "valid_hw", "overlap", "col_mode", "partitioned"),
)
def fused_rdma_step(
    block: jnp.ndarray,
    filt: Filter,
    grid: tuple[int, int],
    boundary: str = "zero",
    quantize: bool = True,
    out_dtype=None,
    interpret=None,
    tiled: bool | None = None,
    tile: tuple[int, int] | None = None,
    pad_operand: bool | None = None,
    fuse: int = 1,
    valid_hw: tuple[int, int] | None = None,
    overlap: bool = False,
    col_mode: str = "strided",
    partitioned: bool = True,
) -> jnp.ndarray:
    """``fuse`` halo-fused stencil iterations, entirely inside one kernel.

    Must be called inside ``shard_map`` over the ('x','y') mesh; ``block``
    is the local (C, h, w) tile.  Semantically identical to a depth
    ``r*fuse`` ``halo.halo_exchange`` followed by ``fuse`` level-shrinking
    correlates (+ optional u8 quantization per level) — see
    tests/test_rdma.py for the bit-exactness proof.

    ``fuse=T>1`` is temporal fusion INSIDE the RDMA tier: the ghost
    transfers widen to depth T·r and the kernel runs T stencil levels
    before returning to HBM — one exchange setup, one neighbor barrier,
    one kernel launch per T iterations, which is exactly the lever the
    latency-bound small-block regime this tier exists for needs
    (DESIGN.md "RDMA temporal fusion").  It requires ``valid_hw`` — the
    global (H, W) image extent — for zero boundaries, because each
    intermediate level must re-zero out-of-image positions (the oracle's
    ghost ring); the caller (``parallel/step.py``) threads it
    automatically.  Constraints: ``min(h, w) >= r*fuse`` (monolithic slab
    depth), and for the tiled variant ``r*fuse <= min(sublane, 128)`` so
    the one-tile-deep aligned transfer bands still carry every live ghost
    row/col.

    ``tiled=None`` auto-selects: blocks whose monolithic VMEM footprint
    (f32 padded buffer + output) exceeds ``_TILED_VMEM_BYTES`` use the
    HBM-pad + windowed-DMA variant (``_rdma_tiled_kernel``); small blocks
    keep the all-VMEM kernel (lower latency, no per-window DMA).  ``tile``
    sets the tiled variant's output tile (default ``DEFAULT_TILE``).

    ``overlap=True`` selects the interior-first overlapped pipeline in
    BOTH kernels (see ``_rdma_kernel`` / ``_rdma_tiled_kernel``): the
    ghost-band DMAs fly while ghost-free compute proceeds, and the
    receive waits retire immediately before the first compute that
    reads them — byte-identical to the serialized order for every
    (boundary, fuse, grid, storage) combination, because only
    independent per-pixel work is reordered (proven in
    tests/test_overlap.py; multi-device cells need the faithful
    interpreter or silicon).  The monolithic kernel always emits the
    region-split program when asked (degenerate regions clamp away);
    the tiled kernel engages only when a remote axis exists — on a 1x1
    grid its program is the serialized one verbatim.  The dispatch
    layer (``parallel/step.py``) resolves when this knob is on; callers
    there never pass it blindly.

    ``col_mode`` selects the COLUMN-SLAB transport (round 16, the
    derived-datatypes A/B): ``"strided"`` (the default — the historical
    program) issues the direct strided copy; ``"packed"`` gathers each
    column slab into a contiguous staging buffer (VMEM for the
    monolithic kernel, HBM for the tiled one), moves it with ONE dense
    RDMA, and scatters it into the ghost ring on the receiver —
    byte-identical by construction, a pure descriptor-shape trade the
    cost model prices (``tuning.costmodel.pick_col_mode``; the dispatch
    layer resolves ``"auto"`` before calling here).  On a grid with no
    remote column partner both modes compile the identical statically-
    elided program (no staging scratch is even allocated).

    ``partitioned`` selects the completion granularity of the
    overlapped pipeline (round 16): ``True`` (default) retires each
    ghost slab independently — a region/window computes the moment its
    own ghosts land (``parallel.channels`` per-slab semaphore map);
    ``False`` keeps the r12 phase-granular ledger as the A/B reference.
    Serialized launches (``overlap=False``) ignore it.

    ``pad_operand`` (tiled variant only) chooses how the HBM pad buffer
    is provided.  ``False``: as an ``pltpu.MemorySpace.HBM``
    ``scratch_shapes`` entry — the natural form, which a round-5 compile
    service rejected.  ``True``: as a second ANY-space OUTPUT that the
    caller discards — allocated uninitialized by XLA just like the
    scratch it replaces (no init cost).  ``None`` resolves to ``True``
    when actually compiling for silicon (``interpret is False``),
    ``False`` under the interpreter — so interpreter tests keep
    covering the scratch form regardless of the process's global
    backend.
    """
    from parallel_convolution_tpu.resilience.faults import fault_point
    from parallel_convolution_tpu.utils.config import BOUNDARIES

    # Trace-time consult: models the in-kernel exchange failing to build
    # (the round-5 tiled-RDMA compile crash class).  Zero overhead when no
    # fault plan is installed, and runs only while tracing — never on the
    # device hot path.
    fault_point("halo_exchange")
    if boundary not in BOUNDARIES:
        raise ValueError(f"boundary must be one of {BOUNDARIES}, got {boundary!r}")
    if col_mode not in channels.COL_MODES:
        raise ValueError(
            f"col_mode must be one of {channels.COL_MODES} at the kernel "
            f"layer ('auto' is resolved by dispatch — "
            f"parallel.step.resolve_col_mode), got {col_mode!r}")
    if interpret is None:
        interpret = not on_tpu()
    if interpret is True:
        # Plain-bool callers (the step builder resolves interpret from the
        # MESH platform) get the DMA-faithful interpreter configuration.
        interpret = pltpu.InterpretParams(dma_execution_mode="on_wait")
    if out_dtype is None:
        out_dtype = block.dtype
    C, h, w = block.shape
    r, k = filt.radius, filt.size
    T = int(fuse)
    if T < 1:
        raise ValueError(f"fuse must be >= 1, got {fuse}")
    d = r * T
    if min(h, w) < d:
        raise ValueError(
            f"block {(h, w)} smaller than the ghost depth r*fuse = {d} "
            f"(radius {r} x fuse {T}); use a smaller fuse or coarser mesh")
    periodic = boundary == "periodic"
    if T > 1 and not periodic and valid_hw is None:
        raise ValueError(
            "fuse > 1 with a zero boundary needs valid_hw — the global "
            "(H, W) image extent — so every intermediate level can re-zero "
            "its out-of-image positions (the oracle's ghost ring)")
    # Normalized static mask key for the kernels: None statically drops
    # per-level masking (single level, or the torus where every position
    # is valid).
    kern_valid = (None if (T == 1 or periodic)
                  else (int(valid_hw[0]), int(valid_hw[1])))
    # The rank-1 form wherever its bytes are the normative order's.
    form = tap_form(filt, rank1_exact(filt, quantize), quantize)
    taps = tuple(float(t) for t in filt.taps.reshape(-1))
    vma = jax.typeof(block).vma
    cparams = pltpu.CompilerParams(
        collective_id=collective_id("rdma_halo_stencil"),
        has_side_effects=True,
    )

    geo = launch_plan((C, h, w), block.dtype, out_dtype, r, T, grid,
                      boundary, col_mode, tiled, tile)
    plan = geo.plan
    # interpret here is False (silicon) or InterpretParams — the barrier
    # form is needed exactly when XLA (not Mosaic) executes the kernel.
    # round_mode is dead when not quantizing: skip the selector (and the
    # compiled-probe guard it consults on silicon) entirely.
    round_mode = (_round_mode_for(taps, interpret is not False)
                  if quantize else "rint")
    if geo.kernel == "monolithic":
        kernel = functools.partial(
            _rdma_kernel, plan=plan, form=form, k=k, r=r, T=T,
            C=C, h=h, w=w, R=grid[0], Cc=grid[1], periodic=periodic,
            quantize=quantize, convex=filt.convex, round_mode=round_mode,
            valid_hw=kern_valid, overlap=bool(overlap),
            partitioned=bool(partitioned),
        )
        scratch = [
            pltpu.VMEM((C, h + 2 * d, w + 2 * d), jnp.float32),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.DMA((4,)),
        ]
        if plan.packed_cols:
            # Column staging: 2 contiguous outbound + 2 inbound slots
            # (the dense-RDMA endpoints of the packed transport).
            scratch.append(
                pltpu.VMEM((4, C, h + 2 * d, d), jnp.float32))
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((C, h, w), out_dtype, vma=vma),
            scratch_shapes=scratch,
            compiler_params=cparams,
            interpret=interpret,
        )(block)

    # ---- tiled variant ----
    LANE = 128
    sub_v, th, tw = geo.sub_v, geo.th, geo.tw
    gh, gw = -(-h // th), -(-w // tw)
    ext_h, ext_w = th + 2 * sub_v, tw + 2 * LANE
    h_pad, w_pad = geo.pad_hw

    kernel = functools.partial(
        _rdma_tiled_kernel, plan=plan, form=form, k=k, r=r, T=T,
        C=C, h=h, w=w, R=grid[0], Cc=grid[1], periodic=periodic,
        quantize=quantize, convex=filt.convex, th=th, tw=tw, sub_v=sub_v,
        round_mode=round_mode, valid_hw=kern_valid, overlap=bool(overlap),
        partitioned=bool(partitioned),
    )
    # Rim-last traversal under the overlapped pipeline: the out index
    # map applies the same +1 rotation the kernel applies to its window
    # indices, so program p's out block IS the window it computed.
    engage = bool(overlap) and (grid[0] > 1 or grid[1] > 1)
    vmem_scratch = [
        pltpu.VMEM((2, ext_h, ext_w), block.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((4,)),
        pltpu.SemaphoreType.DMA((4,)),
        pltpu.SMEM((8,), jnp.int32),  # deferred-wait ledger: slot 0 =
        #                               r12 phase state, 1..5 = per-slab
        #                               map (partitioned, round 16)
    ]
    # Packed column staging (HBM, full padded height x one lane band
    # per slot): the dense-RDMA endpoints of the packed transport.
    stage_shape = (4, C, h_pad, LANE)
    if engage:
        out_idx = lambda c, a, b: (c, (a + 1) % gh, (b + 1) % gw)
    else:
        out_idx = lambda c, i, j: (c, i, j)
    if pad_operand is None:
        # Resolve from the EXECUTION mode already decided above, not the
        # global backend: a TPU-default process driving a forced-CPU mesh
        # passes interpret=True and must keep the scratch form covered.
        pad_operand = interpret is False
    if pad_operand:
        # Operand-backed pad: identical kernel body, but the HBM buffer
        # is a second OUTPUT (discarded) instead of a scratch entry.  An
        # output-only buffer is allocated uninitialized by
        # XLA, exactly like the scratch it replaces — no zero-fill tax —
        # and exactly as safe: the kernel overwrites the interior and
        # every ghost band it reads, and masks everything else
        # (the `ok` window mask).  The packed staging buffer rides the
        # same trick as a third discarded output.
        # (inputs, outputs, scratch) positional order makes the operand
        # form's ref list identical to the scratch form's signature —
        # the same kernel serves both.
        outs = (pl.BlockSpec((1, th, tw), out_idx),
                pl.BlockSpec(memory_space=pl.ANY))
        shapes = (jax.ShapeDtypeStruct((C, gh * th, gw * tw), out_dtype,
                                       vma=vma),
                  jax.ShapeDtypeStruct((C, h_pad, w_pad), block.dtype,
                                       vma=vma))
        if plan.packed_cols:
            outs = outs + (pl.BlockSpec(memory_space=pl.ANY),)
            shapes = shapes + (jax.ShapeDtypeStruct(
                stage_shape, block.dtype, vma=vma),)
        out = pl.pallas_call(
            kernel,
            grid=(C, gh, gw),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=outs,
            out_shape=shapes,
            scratch_shapes=vmem_scratch,
            compiler_params=cparams,
            interpret=interpret,
        )(block)[0]
        with jax.named_scope(KERNEL_IO):   # the crop of the whole tiles
            return out[:, :h, :w]
    hbm = [pltpu.MemorySpace.HBM((C, h_pad, w_pad), block.dtype)]
    if plan.packed_cols:
        hbm.append(pltpu.MemorySpace.HBM(stage_shape, block.dtype))
    out = pl.pallas_call(
        kernel,
        grid=(C, gh, gw),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, th, tw), out_idx),
        out_shape=jax.ShapeDtypeStruct((C, gh * th, gw * tw), out_dtype,
                                       vma=vma),
        scratch_shapes=hbm + vmem_scratch,
        compiler_params=cparams,
        interpret=interpret,
    )(block)
    with jax.named_scope(KERNEL_IO):
        return out[:, :h, :w]
