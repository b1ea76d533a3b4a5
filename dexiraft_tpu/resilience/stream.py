"""Data-stream position: the missing half of an exact resume.

The full TrainState already round-trips through checkpoints (params,
optimizer, schedule step, PRNG key), but the DATA stream restarted from
epoch 0 on every --resume: `Loader.batches(start_epoch=)` existed and
was never wired, and there was no intra-epoch offset at all. Because the
loader's shuffle and augmentation are counter-based PRNG streams keyed
on (seed, epoch, index), the whole sample sequence is a pure function of
(seed, epoch, batch-offset) — so resuming the exact sequence only needs
these two integers saved next to each checkpoint.

The position is stored as a JSON sidecar `<ckpt_dir>/stream/<step>.json`
rather than inside the orbax pytree: it must stay readable by humans and
by older/newer code, must not change the checkpoint tree structure (old
checkpoints keep restoring), and is deleted in lockstep by the retention
GC. A checkpoint without a sidecar resumes from epoch 0 — exactly the
pre-sidecar behavior, so old checkpoint dirs keep working.
"""

from __future__ import annotations

import dataclasses
import json
import os
import os.path as osp
from typing import Optional


class LoaderKindMismatch(ValueError):
    """--resume would swap the data plane under a run: the sidecar was
    written by one loader kind (raw files vs packed records) and the
    resuming process is using the other — or the same records kind but
    a DIFFERENT pack (manifest fingerprint changed: repacked tree,
    different mixture selector, different crop recipe). Refused loudly;
    a silent swap is exactly the kind of sequence divergence
    exact-resume exists to prevent."""


@dataclasses.dataclass(frozen=True)
class StreamPosition:
    """Position of the NEXT global batch to consume."""

    epoch: int = 0
    offset: int = 0  # global-batch index within the epoch

    def advance(self, batches: int, batches_per_epoch: int) -> "StreamPosition":
        """Position after consuming `batches` more global batches."""
        if batches_per_epoch <= 0:
            raise ValueError(
                f"batches_per_epoch must be positive, got {batches_per_epoch}")
        absolute = self.epoch * batches_per_epoch + self.offset + batches
        return StreamPosition(absolute // batches_per_epoch,
                              absolute % batches_per_epoch)


def _sidecar_path(directory: str, step: int) -> str:
    return osp.join(directory, "stream", f"{int(step)}.json")


def save_position(directory: str, step: int, pos: StreamPosition,
                  seed: Optional[int] = None,
                  loader_kind: Optional[str] = None,
                  fingerprint: Optional[str] = None) -> str:
    """Atomically write the position sidecar for checkpoint `step`.

    loader_kind ("raw" | "records") records which data plane produced
    the stream, so --resume can refuse a raw<->records swap;
    fingerprint (the pack's manifest fingerprint, records runs only)
    additionally refuses a records-to-DIFFERENT-records swap."""
    path = _sidecar_path(directory, step)
    os.makedirs(osp.dirname(path), exist_ok=True)
    record = {"epoch": int(pos.epoch), "offset": int(pos.offset)}
    if seed is not None:
        record["seed"] = int(seed)
    if loader_kind is not None:
        record["loader_kind"] = str(loader_kind)
    if fingerprint is not None:
        record["fingerprint"] = str(fingerprint)
    # the writer's own temp name: hosts that share the checkpoint
    # directory write the same record, and a shared name lets one rename
    # take the file the other is about to rename
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, path)
    return path


def load_position(directory: str, step: int,
                  seed: Optional[int] = None,
                  loader_kind: Optional[str] = None,
                  fingerprint: Optional[str] = None
                  ) -> Optional[StreamPosition]:
    """Read the sidecar for `step`; None when absent/unreadable (resume
    then starts at epoch 0, the pre-sidecar behavior). A seed recorded
    at save time that differs from the current one gets a loud warning —
    the sequence being resumed is then NOT the one that was running.
    A loader_kind recorded at save time that differs from the current
    one raises LoaderKindMismatch: a raw<->records swap mid-run is an
    operator error, not a degradation to absorb. Old sidecars without
    the field (pre-records checkpoints) resume unconditionally."""
    try:
        with open(_sidecar_path(directory, step)) as f:
            record = json.load(f)
        pos = StreamPosition(int(record["epoch"]), int(record["offset"]))
    except (OSError, ValueError, KeyError):
        return None
    saved_kind = record.get("loader_kind")
    if (loader_kind is not None and saved_kind is not None
            and saved_kind != loader_kind):
        fix = ("pass the matching --records_dir"
               if saved_kind == "records" else "drop --records_dir")
        raise LoaderKindMismatch(
            f"checkpoint step {step} was saved by the {saved_kind!r} "
            f"loader but this run uses the {loader_kind!r} loader — "
            f"resuming would follow a different sample sequence; {fix} "
            f"or start fresh without --resume")
    saved_fp = record.get("fingerprint")
    if (fingerprint is not None and saved_fp is not None
            and saved_fp != fingerprint):
        raise LoaderKindMismatch(
            f"checkpoint step {step} was saved from a records pack with "
            f"fingerprint {saved_fp[:12]} but --records_dir points at a "
            f"pack with fingerprint {fingerprint[:12]} — a repacked or "
            f"different dataset would follow a different sample "
            f"sequence; point --records_dir at the original pack or "
            f"start fresh without --resume")
    saved_seed = record.get("seed")
    if seed is not None and saved_seed is not None and saved_seed != seed:
        print(f"[resilience] WARNING: checkpoint step {step} was saved with "
              f"data seed {saved_seed}, resuming with seed {seed} — the "
              f"sample sequence will differ from the interrupted run")
    return pos


def delete_position(directory: str, step: int) -> None:
    """Drop the sidecar (retention GC calls this next to the step delete)."""
    try:
        os.remove(_sidecar_path(directory, step))
    except OSError:
        pass
