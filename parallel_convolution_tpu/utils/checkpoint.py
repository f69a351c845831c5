"""Checkpoint / resume for long runs (SURVEY.md §5 aux subsystems).

The reference has none (runs are minutes long, output written once at the
end); this framework adds the natural TPU-native version: every K
iterations the sharded state is snapshotted **per addressable shard** (no
host gather — each device block becomes one ``.npy`` keyed by its grid
coordinates) together with a JSON sidecar recording progress and config.
A restarted run validates the sidecar against its own config and continues
from the saved iteration.

Chunked execution does not perturb semantics: in u8 mode every iteration
ends quantized to exact integers, and float-mode shards are saved as raw
float32, so save/restore is lossless and the checkpointed run remains
bit-identical to an uninterrupted one.

Hardening (resilience PR): snapshots are now *verifiable*, not just
ordered.  Each shard is written atomically (tmp + rename) and its CRC32 +
byte length are recorded in ``meta.json``; loading validates completeness
and checksums and raises :class:`CheckpointCorrupt` on a torn snapshot
(the multi-host prune race: ``meta.json`` present but shard files
missing/truncated).  ``load_state(..., fallback=True)`` — the default
inside :func:`run_checkpointed` — then walks back to the newest snapshot
that does validate instead of crashing or, worse, resuming from garbage.
Injection sites ``checkpoint_write_shard`` / ``checkpoint_write_meta``
(resilience.faults) let tests kill a save between any two writes and
prove the resumed run byte-identical (tests/test_resilience.py).

Elastic recovery (round 10): snapshots are **grid-shape-agnostic**.  A
snapshot written on any ``(P, Q)`` mesh grid loads onto any ``(P', Q')``
grid: per-shard blocks are sliced and reassembled through the same
index maps that wrote them (``_coords`` / ``block_sharding``), the
pad-to-multiple rim is re-derived for the target grid via
``padded_extent``, and each target shard reads only the source shards it
overlaps — gather-free, no host buffer ever holds the full image.  Grid
therefore left the resume-compatibility config: losing a chip (or
getting handed a smaller slice) no longer strands every snapshot.
Validation failures are a **quarantine policy**: a corrupt shard marks
that snapshot degraded — the :class:`CheckpointWarning` names the
snapshot, the shard, and the cause (missing / truncated / checksum
mismatch / unreadable / torn meta) — and ``fallback=True`` reshards
around it from the newest snapshot that still validates.
"""

from __future__ import annotations

import io
import json
import os
import time
import warnings
import zlib
from pathlib import Path

import jax
import numpy as np
from jax.sharding import Mesh

from parallel_convolution_tpu.obs import events as obs_events, metrics as obs_metrics
from parallel_convolution_tpu.parallel.mesh import (
    block_sharding, grid_shape, padded_extent,
)
from parallel_convolution_tpu.resilience import diskio
from parallel_convolution_tpu.resilience.faults import (
    InjectedFault, fault_point,
)


def _note_ckpt(op: str, wall_s: float, nbytes: int, **fields) -> None:
    """One checkpoint op's telemetry: duration histogram, byte counter,
    and the typed timeline event.  One branch when obs is off."""
    if not obs_metrics.enabled():
        return
    obs_metrics.histogram(
        "pctpu_checkpoint_seconds", "checkpoint operation wall time",
        ("op",)).observe(wall_s, op=op)
    obs_metrics.counter(
        "pctpu_checkpoint_bytes_total", "checkpoint bytes written/read",
        ("op",)).inc(nbytes, op=op)
    obs_events.emit(f"checkpoint_{op}", wall_s=round(wall_s, 6),
                    bytes=int(nbytes), **fields)


def _note_quarantine(snap_name: str, problems) -> None:
    if not obs_metrics.enabled():
        return
    c = obs_metrics.counter(
        "pctpu_quarantines_total",
        "shard validation failures by cause (missing/truncated/...)",
        ("cause",))
    for cause, _shard in problems:
        c.inc(cause=cause)
    obs_events.emit("quarantine", snap=snap_name,
                    problems=[[c_, s] for c_, s in problems][:16])

META_NAME = "meta.json"
LATEST_NAME = "LATEST"
KEEP_SNAPSHOTS = 2


class CheckpointCorrupt(RuntimeError):
    """A snapshot's meta exists but its shard set is incomplete/damaged.

    ``snap`` names the snapshot directory; ``problems`` is the per-shard
    diagnosis — ``(cause, shard_name)`` pairs with cause one of
    ``missing | truncated | checksum | unreadable | torn-meta`` — so the
    quarantine warnings can say exactly what was wrong, not just "torn".
    """

    def __init__(self, msg: str, snap: str = "",
                 problems: tuple = ()):  # (cause, shard) pairs
        super().__init__(msg)
        self.snap = snap
        self.problems = tuple(problems)


class CheckpointWarning(UserWarning):
    """A corrupt snapshot was quarantined (skipped in favor of an older or
    fresh state), or a snapshot was resharded onto a different grid."""


def _coords(index, block_hw) -> tuple[int, int]:
    rs, cs = index[1], index[2]
    return (rs.start or 0) // block_hw[0], (cs.start or 0) // block_hw[1]


def _snap_dir(ckpt_dir, iters_done: int) -> Path:
    return Path(ckpt_dir) / f"it_{int(iters_done):08d}"


def _latest_snap(ckpt_dir) -> Path | None:
    p = Path(ckpt_dir) / LATEST_NAME
    if not p.exists():
        return None
    try:
        snap = Path(ckpt_dir) / p.read_text().strip()
    except OSError:  # pointer pruned/replaced mid-read by a sibling host
        return None
    return snap if (snap / META_NAME).exists() else None


def _candidate_snaps(ckpt_dir) -> list[Path]:
    """Snapshots to try, newest-claim first: the LATEST pointer's target,
    then every other ``it_*`` dir with a meta, newest iteration first.

    Robust against a concurrent writer/pruner: directory entries that
    vanish between the listing and the existence check simply drop out
    (the prune-vs-read race is benign by construction — a pruned
    snapshot was never the newest)."""
    d = Path(ckpt_dir)
    first = _latest_snap(d)
    out = [first] if first is not None else []
    rest: list[Path] = []
    try:
        for p in d.iterdir():
            try:
                if (p.is_dir() and p.name.startswith("it_")
                        and (p / META_NAME).exists()):
                    rest.append(p)
            except OSError:
                continue  # entry pruned mid-check
    except OSError:
        pass  # ckpt dir itself missing/unreadable: only LATEST's claim
    rest.sort(key=lambda p: p.name, reverse=True)
    out += [p for p in rest if first is None or p.name != first.name]
    return out


def _read_meta(snap: Path) -> dict:
    """Parse a snapshot's meta; unreadable/invalid JSON (a torn write or
    a dir pruned mid-read) raises :class:`CheckpointCorrupt` with cause
    ``torn-meta`` so the fallback walk can quarantine and continue."""
    try:
        return json.loads((snap / META_NAME).read_text())
    except (OSError, ValueError) as e:
        raise CheckpointCorrupt(
            f"snapshot {snap.name} is torn: unreadable meta ({e})",
            snap=snap.name, problems=(("torn-meta", META_NAME),),
        ) from e


def _expected_shards(meta: dict) -> list[str]:
    g0, g1 = meta["grid"]
    return [f"shard_{r}_{c}.npy" for r in range(g0) for c in range(g1)]


def _validate_snapshot(snap: Path, meta: dict) -> None:
    """Raise :class:`CheckpointCorrupt` unless every expected shard file is
    present and matches its recorded CRC32/length.

    Shards without a CRC record (a legacy snapshot, or — multi-host —
    shards another host wrote under its own meta) degrade to a header
    parse: presence + a readable ``.npy`` is the best that host can check.

    Each shard's verdict carries a cause — ``missing`` / ``truncated`` /
    ``checksum`` / ``unreadable`` — that the quarantine warnings surface
    verbatim; an I/O failure mid-read (``io_read`` fault site) counts as
    ``unreadable``, it quarantines the snapshot rather than killing the
    recovery walk.
    """
    problems: list[tuple[str, str]] = []   # (cause, shard)
    notes: list[str] = []
    recorded = meta.get("shards", {})
    for name in _expected_shards(meta):
        p = snap / name
        if not p.exists():
            problems.append(("missing", name))
            notes.append(f"missing {name}")
            continue
        rec = recorded.get(name)
        if rec is not None:
            # Stream the CRC in chunks: shards can be device-block-sized
            # (hundreds of MB at 65536² scale) — never a whole-file read.
            crc, n = 0, 0
            try:
                fault_point("io_read")  # one consult per shard validation
                with open(p, "rb") as f:
                    while chunk := f.read(1 << 20):
                        crc = zlib.crc32(chunk, crc)
                        n += len(chunk)
            except (OSError, InjectedFault) as e:
                problems.append(("unreadable", name))
                notes.append(f"unreadable {name} ({e})")
                continue
            if n != rec["bytes"]:
                problems.append(("truncated", name))
                notes.append(f"truncated {name} ({n} != {rec['bytes']} bytes)")
            elif crc != rec["crc32"]:
                problems.append(("checksum", name))
                notes.append(f"checksum mismatch in {name}")
        else:
            try:
                np.load(p, mmap_mode="r")
            except Exception:
                problems.append(("unreadable", name))
                notes.append(f"unreadable {name} (no CRC recorded)")
    if problems:
        raise CheckpointCorrupt(
            f"snapshot {snap.name} is torn: {'; '.join(notes[:8])}"
            + (f" (+{len(notes) - 8} more)" if len(notes) > 8 else ""),
            snap=snap.name, problems=problems,
        )


def save_state(ckpt_dir, arr: jax.Array, meta: dict) -> None:
    """Snapshot a sharded padded (C, Hp, Wp) array + metadata.

    Crash-safe by construction: each snapshot is its own
    ``it_<NNNNNNNN>/`` directory, every shard is serialized in memory
    first and written atomically (tmp + rename) with its CRC32 recorded,
    meta is written last inside the directory, and the ``LATEST`` pointer
    flips atomically only after the snapshot is complete — a crash at any
    point leaves the previous snapshot intact AND leaves the torn one
    detectable (:func:`_validate_snapshot`).  Older snapshots beyond
    KEEP_SNAPSHOTS are pruned.

    Fault sites: ``checkpoint_write_shard`` before each shard write;
    ``checkpoint_write_meta`` twice — before the meta write and before the
    LATEST flip — so tests can kill the save at every boundary.
    """
    t_save0 = time.perf_counter()
    d = Path(ckpt_dir)
    d.mkdir(parents=True, exist_ok=True)
    snap = _snap_dir(d, meta["iters_done"])
    snap.mkdir(exist_ok=True)
    R_blocks = meta["grid"]
    block_hw = (arr.shape[1] // R_blocks[0], arr.shape[2] // R_blocks[1])
    shards: dict[str, dict] = {}
    for shard in arr.addressable_shards:
        r, c = _coords(shard.index, block_hw)
        name = f"shard_{r}_{c}.npy"
        buf = io.BytesIO()
        np.save(buf, np.asarray(shard.data))
        raw = buf.getvalue()
        # Routed through diskio (round 24) so drills can shape the
        # failure (ENOSPC/EIO/slow); bare injections raise raw as ever.
        diskio.consult("checkpoint_write_shard")
        tmp = snap / (name + ".tmp")
        tmp.write_bytes(raw)
        os.replace(tmp, snap / name)
        shards[name] = {"crc32": zlib.crc32(raw), "bytes": len(raw)}
    meta = dict(meta, shards=shards)
    diskio.consult("checkpoint_write_meta")
    # Every host of a multi-host run writes the same meta and pointer:
    # a temp name of its own keeps one host's rename from taking the
    # other's file away mid-write.
    tmp = snap / f"{META_NAME}.{os.getpid()}.tmp"
    tmp.write_text(json.dumps(meta))
    os.replace(tmp, snap / META_NAME)
    diskio.consult("checkpoint_write_meta")
    ptr_tmp = d / f"{LATEST_NAME}.{os.getpid()}.tmp"
    ptr_tmp.write_text(snap.name)
    os.replace(ptr_tmp, d / LATEST_NAME)
    _note_ckpt("save", time.perf_counter() - t_save0,
               sum(s["bytes"] for s in shards.values()),
               snap=snap.name, iters_done=int(meta["iters_done"]),
               shards=len(shards))
    # prune old snapshots (multi-host: every host holds its own shards, so
    # each prunes the same dirs; missing-file AND missing-dir races are
    # ignored — a sibling host may have pruned the same dir already)
    try:
        snaps = sorted(p for p in d.iterdir()
                       if p.is_dir() and p.name.startswith("it_"))
    except OSError:
        snaps = []
    for old in snaps[:-KEEP_SNAPSHOTS]:
        try:
            entries = list(old.iterdir())
        except OSError:
            continue  # dir already pruned by a sibling
        for f in entries:
            try:
                f.unlink()
            except OSError:
                pass
        try:
            old.rmdir()
        except OSError:
            pass


def load_meta(ckpt_dir) -> dict | None:
    """The LATEST snapshot's meta, unvalidated (shards may still be torn —
    use :func:`load_state` for validated loading)."""
    snap = _latest_snap(ckpt_dir)
    if snap is None:
        return None
    return _read_meta(snap)


def _valid_hw_of(meta: dict) -> tuple[int, int]:
    """The snapshot's valid (unpadded) image extent.  ``valid_hw`` is in
    every meta :func:`run_checkpointed` writes; hand-rolled metas without
    it fall back to the saved padded extent (every pixel treated valid —
    exact when the source dims divided its grid)."""
    vh = meta.get("valid_hw")
    if vh:
        return int(vh[0]), int(vh[1])
    return int(meta["shape"][1]), int(meta["shape"][2])


def _reshard_callback(snap: Path, meta: dict, target_shape):
    """Per-target-shard assembly from a snapshot written on another grid.

    Gather-free: each target shard opens only the source ``.npy`` blocks
    it overlaps (memmap windows — never a full-file read) and fills its
    pad rim with zeros, re-deriving the target grid's pad-to-multiple
    extents.  The source's own pad rim is never read: positions outside
    the valid image are zero on BOTH grids by the masking invariant, so
    resharding preserves bytes exactly.
    """
    src_grid = tuple(meta["grid"])
    src_shape = tuple(meta["shape"])
    sbh = src_shape[1] // src_grid[0]
    sbw = src_shape[2] // src_grid[1]
    H, W = _valid_hw_of(meta)
    C, Hp, Wp = target_shape
    dtype = np.load(snap / "shard_0_0.npy", mmap_mode="r").dtype

    def cb(index):
        rs, cs = index[1], index[2]
        r0, r1 = rs.start or 0, rs.stop or Hp
        c0, c1 = cs.start or 0, cs.stop or Wp
        out = np.zeros((C, r1 - r0, c1 - c0), dtype)
        vr1, vc1 = min(r1, H), min(c1, W)  # only in-image pixels exist
        if vr1 <= r0 or vc1 <= c0:
            return out  # target shard lies entirely in the new pad rim
        for sr in range(r0 // sbh, (vr1 - 1) // sbh + 1):
            for sc in range(c0 // sbw, (vc1 - 1) // sbw + 1):
                blk = np.load(snap / f"shard_{sr}_{sc}.npy", mmap_mode="r")
                gr0, gr1 = max(r0, sr * sbh), min(vr1, (sr + 1) * sbh)
                gc0, gc1 = max(c0, sc * sbw), min(vc1, (sc + 1) * sbw)
                out[:, gr0 - r0:gr1 - r0, gc0 - c0:gc1 - c0] = (
                    blk[:, gr0 - sr * sbh:gr1 - sr * sbh,
                        gc0 - sc * sbw:gc1 - sc * sbw])
        return out

    return cb


def load_state(ckpt_dir, mesh: Mesh,
               fallback: bool = False) -> tuple[jax.Array, dict]:
    """Restore the sharded array onto ``mesh`` — ANY mesh grid.

    Validates snapshot completeness + per-shard CRC32 before any device
    read; a torn snapshot raises :class:`CheckpointCorrupt` — unless
    ``fallback=True``, in which case the snapshot is *quarantined* (a
    :class:`CheckpointWarning` naming the snapshot, the shard, and the
    cause) and the walk continues to the newest OLDER snapshot that
    validates.  Returns ``(array, meta)`` of the snapshot actually
    loaded, so the caller resumes from its true iteration count.

    Grid-shape-agnostic (round 10): when the snapshot's grid differs
    from ``mesh``'s, shards are sliced and reassembled per target shard
    (:func:`_reshard_callback`) with the pad rim re-derived for the new
    grid — ``meta['resharded_from']`` then records the source grid.
    When the grids match, each device reads exactly its own shard file,
    as before.
    """
    t_load0 = time.perf_counter()
    candidates = _candidate_snaps(ckpt_dir)
    if not candidates:
        raise FileNotFoundError(f"no checkpoint at {ckpt_dir}")
    grid = grid_shape(mesh)
    last_err: CheckpointCorrupt | None = None
    for snap in candidates:
        try:
            meta = _read_meta(snap)
            _validate_snapshot(snap, meta)
        except CheckpointCorrupt as e:
            _note_quarantine(e.snap or snap.name, e.problems)
            if not fallback:
                raise
            warnings.warn(
                f"quarantined torn snapshot {e.snap or snap.name}: {e}",
                CheckpointWarning, stacklevel=2)
            last_err = e
            continue
        src_grid = tuple(meta["grid"])
        if src_grid == grid:
            shape = tuple(meta["shape"])
            block_hw = (shape[1] // grid[0], shape[2] // grid[1])

            def cb(index, snap=snap, block_hw=block_hw):
                r, c = _coords(index, block_hw)
                return np.load(snap / f"shard_{r}_{c}.npy")

        else:
            H, W = _valid_hw_of(meta)
            shape = (int(meta["shape"][0]),
                     padded_extent(H, grid[0]), padded_extent(W, grid[1]))
            cb = _reshard_callback(snap, meta, shape)
            meta = dict(meta, resharded_from=list(src_grid),
                        grid=list(grid), shape=list(shape))
            warnings.warn(
                f"resharding snapshot {snap.name} from grid "
                f"{src_grid[0]}x{src_grid[1]} onto {grid[0]}x{grid[1]}",
                CheckpointWarning, stacklevel=2)
        arr = jax.make_array_from_callback(shape, block_sharding(mesh), cb)
        nbytes = sum(s.get("bytes", 0)
                     for s in meta.get("shards", {}).values())
        op = "load" if src_grid == grid else "reshard"
        _note_ckpt(op, time.perf_counter() - t_load0, nbytes,
                   snap=snap.name, iters_done=int(meta.get("iters_done", 0)),
                   grid=f"{grid[0]}x{grid[1]}",
                   **({"resharded_from":
                       f"{src_grid[0]}x{src_grid[1]}"}
                      if src_grid != grid else {}))
        return arr, meta
    raise CheckpointCorrupt(
        f"no valid snapshot in {ckpt_dir}: every candidate is torn "
        f"(last: {last_err})"
    )


def run_checkpointed(
    xs: jax.Array,
    filt,
    total_iters: int,
    mesh: Mesh,
    valid_hw,
    ckpt_dir,
    every: int,
    quantize: bool = True,
    backend: str = "shifted",
    fuse: int = 1,
    boundary: str = "zero",
    tile: tuple[int, int] | None = None,
    fallback: bool = False,
    overlap: bool | None = None,
    col_mode: str | None = None,
) -> jax.Array:
    """Iterate with a snapshot every ``every`` iterations; auto-resume.

    If ``ckpt_dir`` holds a compatible checkpoint, continues from its
    iteration count (``xs`` may then be None).  Returns the padded sharded
    result after ``total_iters`` total iterations.

    Resume is resilient by default: a torn LATEST snapshot falls back to
    the newest valid one (:func:`load_state` with ``fallback=True``), and
    if *no* snapshot validates the run restarts from ``xs`` with a
    :class:`CheckpointWarning` — never from corrupt bytes.  The mesh may
    have a DIFFERENT grid than the one that wrote the checkpoint
    (elastic recovery: resume a 2x4 run on whatever slice survives) —
    shards reshard transparently and bytes stay identical.  ``fallback``
    here is the *backend* degradation knob, threaded to
    ``step.iterate_prepared`` (resilience.degrade).
    """
    from parallel_convolution_tpu.parallel import step as step_lib

    grid = grid_shape(mesh)
    # Resume-compatibility config.  Grid is deliberately NOT part of it:
    # the grid is a property of the hardware you resume on, not of the
    # run — snapshots reshard onto whatever mesh is alive.
    config = {
        "filter": filt.name,
        "quantize": quantize,
        "backend": backend,
        "fuse": fuse,
        "boundary": boundary,
        "valid_hw": list(valid_hw),
    }
    # Gate on the config FIRST (one small JSON read): a mismatch must not
    # cost shard validation + a full device load before raising.  All
    # snapshots in a dir come from one run, so the latest meta speaks for
    # every fallback candidate too.
    try:
        meta0 = load_meta(ckpt_dir)
    except CheckpointCorrupt:
        meta0 = None  # torn meta: the validated walk below handles it
    if meta0 is not None:
        saved_cfg = {k: meta0.get(k) for k in config}
        if saved_cfg != config:
            raise ValueError(
                f"checkpoint config mismatch: {saved_cfg} != {config}"
            )
    meta = None
    try:
        loaded_xs, meta = load_state(ckpt_dir, mesh, fallback=True)
    except FileNotFoundError:
        pass
    except CheckpointCorrupt as e:
        warnings.warn(
            f"no usable checkpoint in {ckpt_dir} ({e}); starting fresh",
            CheckpointWarning, stacklevel=2)
    done = 0
    if meta is not None:
        # Re-check against the snapshot actually loaded: with no LATEST
        # pointer yet (a first-save crash) meta0 above was None and the
        # pre-gate never ran.
        saved_cfg = {k: meta.get(k) for k in config}
        if saved_cfg != config:
            raise ValueError(
                f"checkpoint config mismatch: {saved_cfg} != {config}"
            )
        xs = loaded_xs
        done = int(meta["iters_done"])
    if xs is None:
        raise ValueError("no checkpoint found and no initial state given")
    # Validate the quantize-range contract ONCE on the entry state; chunk
    # inputs below are prior chunk outputs, in contract by induction
    # (quantized values are always in [0, 255]).
    step_lib._check_quantize_contract(xs, filt, quantize)

    while done < total_iters:
        chunk = min(every, total_iters - done)
        # tile and overlap are pure perf knobs (bit-identical for any
        # value in every mode), so they are deliberately NOT part of
        # the resume-compatibility config above.  fuse IS kept there: it
        # is only bit-identical under quantize=True — in float mode with a
        # narrow storage dtype the fused kernel keeps f32 intermediates
        # the unfused path would have rounded through storage every
        # iteration.
        xs = step_lib.iterate_prepared(
            xs, filt, chunk, mesh, valid_hw,
            quantize=quantize, backend=backend, fuse=min(fuse, chunk),
            boundary=boundary, tile=tile, check_contract=False,
            fallback=fallback, overlap=overlap, col_mode=col_mode,
        )
        done += chunk
        if done < total_iters:  # final state is the caller's to persist
            save_state(
                ckpt_dir, xs,
                {**config, "grid": list(grid), "iters_done": done,
                 "shape": list(xs.shape)},
            )
    return xs
