"""Flight recorder: one size-rotated JSONL event stream per run.

Layout: ``<flight_dir>/flight-00001.jsonl``, ``flight-00002.jsonl``,
... — the recorder continues the highest-numbered existing file on
(re)open, so a resumed run APPENDS to the same stream instead of
starting a parallel one (correlation by ``run`` id keeps restarted
runs distinguishable within it).

Crash-safety contract: every row is serialized first and written with
ONE ``os.write`` to an ``O_APPEND`` descriptor — a SIGKILL/SIGTERM
mid-write can tear at most the final line, never interleave rows, and
:func:`iter_rows` skips unparseable lines so a torn tail costs one
event, not the stream. Rotation closes the current file (already
final — rows are never rewritten) and opens the next index; files
beyond ``keep_files`` are pruned oldest-first.

Pure stdlib on purpose: ``scripts/flight_report.py`` and
``scripts/supervise.py`` consume/produce this format without
importing jax.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)

_FILE_RE = re.compile(r"^flight-(\d{5})\.jsonl$")


def flight_files(directory: str) -> List[str]:
    """Stream files in rotation order (oldest first)."""
    try:
        entries = os.listdir(directory)
    except OSError:
        return []
    found = sorted(
        (int(m.group(1)), e)
        for e in entries
        for m in [_FILE_RE.match(e)]
        if m
    )
    return [os.path.join(directory, e) for _, e in found]


def iter_rows(directory: str) -> Iterator[Dict[str, Any]]:
    """Parse every row of a flight stream in order, skipping torn /
    foreign lines (the reader half of the atomic-append contract)."""
    for path in flight_files(directory):
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        row = json.loads(line)
                    except ValueError:
                        continue  # torn tail from a mid-write kill
                    if isinstance(row, dict):
                        yield row
        except OSError:
            continue


class FlightRecorder:
    """Append typed events to the rotated stream. Thread-safe (the
    watchdog monitor thread records stall trips while the training
    thread records cycles); never raises past :meth:`append` — a
    recorder that cannot write logs nothing and stays quiet (the
    training loop must not die of observability)."""

    def __init__(
        self,
        directory: str,
        run_id: str,
        rotate_bytes: int = 4 * 1024 * 1024,
        keep_files: int = 8,
    ):
        self.directory = directory
        self.run_id = run_id
        self.rotate_bytes = max(int(rotate_bytes), 4096)
        self.keep_files = max(int(keep_files), 1)
        self._fd: Optional[int] = None
        self._index = 0
        self._lock = threading.Lock()
        self.rows_dropped = 0  # transient write failures (row skipped)

    # -- file management -------------------------------------------------

    def _path(self, index: int) -> str:
        return os.path.join(self.directory, f"flight-{index:05d}.jsonl")

    def _ensure_open(self) -> int:
        if self._fd is not None:
            return self._fd
        os.makedirs(self.directory, exist_ok=True)
        existing = flight_files(self.directory)
        if existing:
            self._index = int(_FILE_RE.match(os.path.basename(existing[-1])).group(1))
        else:
            self._index = 1
        path = self._path(self._index)
        # seal a torn tail from a mid-write kill: without a trailing
        # newline the next append would CONCATENATE onto the torn line
        # and corrupt a second row — a lone '\n' confines the damage to
        # the line the kill already tore
        try:
            with open(path, "rb") as f:
                f.seek(-1, os.SEEK_END)
                torn = f.read(1) != b"\n"
        except OSError:
            torn = False
        self._fd = os.open(
            path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644,
        )
        if torn:
            os.write(self._fd, b"\n")
        return self._fd

    def _maybe_rotate(self) -> None:
        try:
            size = os.fstat(self._fd).st_size
        except OSError:
            return
        if size < self.rotate_bytes:
            return
        os.close(self._fd)
        self._index += 1
        self._fd = os.open(
            self._path(self._index),
            os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644,
        )
        # prune beyond retention (oldest first; the live file survives)
        files = flight_files(self.directory)
        for path in files[: max(len(files) - self.keep_files, 0)]:
            try:
                os.unlink(path)
            except OSError:
                pass

    # -- writes ----------------------------------------------------------

    def append(self, kind: str, **fields: Any) -> None:
        """One event row. ``kind`` plus the caller's correlation fields
        (cycle / step / pv) and payload; ``t`` (epoch seconds) and
        ``run`` are stamped here. A TRANSIENT write failure (ENOSPC, an
        NFS blip) drops this one row and retries from a fresh open on
        the next append — it must not permanently disarm the observer
        the way an escaped exception would."""
        row = {"t": round(time.time(), 3), "run": self.run_id, "kind": kind}
        for k, v in fields.items():
            if v is not None:
                row[k] = v
        with self._lock:
            try:
                data = (json.dumps(row, default=str) + "\n").encode()
                fd = self._ensure_open()
                os.write(fd, data)  # one write = never interleaved
                self._maybe_rotate()
            except Exception as e:
                self.rows_dropped += 1
                if self.rows_dropped == 1:
                    logger.error(
                        "flight recorder: append failed (%s) — dropping "
                        "the row and retrying from a fresh open next "
                        "event (further drops counted silently)", e,
                    )
                if self._fd is not None:
                    try:
                        os.close(self._fd)
                    except OSError:
                        pass
                    self._fd = None

    def close(self) -> None:
        with self._lock:
            if self._fd is not None:
                try:
                    os.close(self._fd)
                except OSError:
                    pass
                self._fd = None


def append_external(directory: str, kind: str, **fields: Any) -> None:
    """One-shot append from OUTSIDE the run (the supervisor's restart
    ledger mirrors its decisions here so relaunches land in the same
    timeline as the run's own events). Same single-write contract;
    ``run`` is the external writer's name, correlation is by time."""
    os.makedirs(directory, exist_ok=True)
    files = flight_files(directory)
    path = files[-1] if files else os.path.join(directory, "flight-00001.jsonl")
    row = {"t": round(time.time(), 3), "run": fields.pop("run", "external"),
           "kind": kind}
    row.update({k: v for k, v in fields.items() if v is not None})
    data = (json.dumps(row, default=str) + "\n").encode()
    # same torn-tail seal as FlightRecorder._ensure_open: the exact
    # scenario this writer exists for (the supervisor mirroring a
    # relaunch after a mid-write kill) is the one where the stream's
    # last line may be torn — without the seal this row would
    # concatenate onto it and be lost
    try:
        with open(path, "rb") as f:
            f.seek(-1, os.SEEK_END)
            torn = f.read(1) != b"\n"
    except OSError:
        torn = False
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        if torn:
            os.write(fd, b"\n")
        os.write(fd, data)
    finally:
        os.close(fd)
