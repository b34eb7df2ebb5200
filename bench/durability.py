"""Holds a durable store to its promise: every deposit journaled and
fsync'd before the result that counts it is served.

Two readings, both independent of the store's own code:

* ``SyncLog`` wraps ``os.fsync`` and ``os.fdatasync`` for the run and
  notes, for each call on a file of the state directory, when it returned,
  the file's name and its size then.
* ``read_state`` reads the state directory as it stands once every answer
  of the window has come, before the engine shuts down: the snapshot
  (``snapshot.npz``) and then the journal (``journal.bin``, records
  ``b"ZMJ1" | u32 length | u32 crc32 | JSON``), folded the way a restart
  replays them, with the byte at which each journal record ends.

``compare`` counts, among the answered requests, those whose served sample
count and means the disk does not hold (``unjournaled``), and those served
before the bytes that hold them had been synced (``unsynced``).  Both are
exact: limit 0.  It assumes the journal is not compacted inside the run
but at start and at shutdown, as the engine does: a stream found only in
the snapshot counts as synced once a snapshot file has been.
"""

from __future__ import annotations

import base64
import json
import os
import struct
import zlib

import numpy as np

JOURNAL = "journal.bin"
SNAPSHOT = "snapshot.npz"
MAGIC = b"ZMJ1"
HEADER = struct.Struct("<II")


class SyncLog:
    """``(time, file name, size)`` of every sync of a file under ``root``."""

    def __init__(self, root: str, clock):
        self.root = os.path.realpath(root)
        self.clock = clock
        self.events: list[tuple[float, str, int]] = []
        self._saved = {}

    def _wrap(self, real):
        def sync(fd):
            out = real(fd)
            try:
                path = os.readlink(f"/proc/self/fd/{fd}")
                size = os.fstat(fd).st_size
            except OSError:
                return out
            if os.path.dirname(path) == self.root:
                self.events.append((self.clock(), os.path.basename(path),
                                    size))
            return out
        return sync

    def install(self) -> None:
        for name in ("fsync", "fdatasync"):
            real = getattr(os, name, None)
            if real is not None:
                self._saved[name] = real
                setattr(os, name, self._wrap(real))

    def remove(self) -> None:
        for name, real in self._saved.items():
            setattr(os, name, real)
        self._saved = {}


def _f32(text: str) -> np.ndarray:
    return np.frombuffer(base64.b64decode(text), "<f4").astype(np.float32)


def _journal(path: str):
    """``(payload, end byte)`` of every whole, intact record."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        return
    off = 0
    while off + len(MAGIC) + HEADER.size <= len(data):
        if data[off:off + len(MAGIC)] != MAGIC:
            return
        length, crc = HEADER.unpack_from(data, off + len(MAGIC))
        start = off + len(MAGIC) + HEADER.size
        end = start + length
        if end > len(data) or zlib.crc32(data[start:end]) != crc:
            return
        try:
            payload = json.loads(data[start:end])
        except ValueError:
            return
        yield payload, end
        off = end


def read_state(state_dir: str) -> dict:
    """``{stream: [(n, s1, end), ...]}``: each state a stream passes
    through on disk, in order; ``end`` is the journal byte at which it is
    complete, or None for the snapshot's."""
    streams: dict[str, list] = {}
    done: dict[str, int] = {}
    snap = os.path.join(state_dir, SNAPSHOT)
    if os.path.exists(snap):
        with np.load(snap, allow_pickle=False) as z:
            meta = json.loads(bytes(z["meta"]).decode("utf-8"))
            for i, e in enumerate(meta["entries"]):
                s1 = np.asarray(z[f"s1_{i:05d}"], np.float32)
                streams[e["chash"]] = [(int(e["n"]), s1, None)]
                done[e["chash"]] = int(e["rounds_done"])
    for rec, end in _journal(os.path.join(state_dir, JOURNAL)):
        c = rec.get("chash")
        if rec.get("t") == "alloc" and c not in streams:
            streams[c] = [(0, np.zeros(int(rec["n_fn"]), np.float32), end)]
            done[c] = 0
        elif rec.get("t") == "dep" and c in streams:
            # a restart folds round r only onto rounds 0..r-1
            if int(rec["round"]) != done[c]:
                continue
            n, s1, _ = streams[c][-1]
            streams[c].append((n + int(rec["n"]), s1 + _f32(rec["s1"]), end))
            done[c] += 1
    return streams


def _synced(syncs, journal_end: int, snapshot: bool, when: float) -> bool:
    """Whether, by ``when``, the journal had been synced up to byte
    ``journal_end`` and, where ``snapshot``, a snapshot file too."""
    done_j = journal_end <= 0
    done_s = not snapshot
    for t, name, size in syncs:
        if t <= when:
            done_j = done_j or (name == JOURNAL and size >= journal_end)
            done_s = done_s or name.startswith("snapshot")
    return done_j and done_s


def compare(records, streams: dict, syncs) -> dict:
    """The two exact numbers over the answered ``records`` (loadgen
    ``Record``s).  The cells' forms integrate over [0, 1]^dim, so a served
    mean is the stream's s1 / n."""
    unjournaled = unsynced = 0
    for r in records:
        if not r.ok:
            continue
        res = r.result
        means = np.asarray(res.means, np.float64)
        off, held = 0, True
        journal_end, snapshot = 0, False
        for chash, n in zip(res.stream_ids, res.n_per_family):
            state = next((s for s in streams.get(chash, ())
                          if s[0] == int(n)), None)
            if state is None:
                held = False
                break
            _, s1, end = state
            served = means[off:off + len(s1)]
            off += len(s1)
            if served.shape != s1.shape or not np.allclose(
                    s1.astype(np.float64) / int(n), served, rtol=1e-5,
                    atol=0.0):
                held = False
                break
            if end is None:
                snapshot = True
            else:
                journal_end = max(journal_end, end)
        if not held or off != len(means):
            unjournaled += 1
        elif not _synced(syncs, journal_end, snapshot, r.done_t):
            unsynced += 1
    return {"unjournaled": {"value": unjournaled, "limit": 0},
            "unsynced": {"value": unsynced, "limit": 0}}
