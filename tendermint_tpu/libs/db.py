"""KV store abstraction (reference: tmlibs/db — LevelDB/MemDB used for the
block store, state, tx index, addr book; chosen at node/node.go:51-53).

Three implementations:
- MemDB: in-memory dict (tests, fast-path).
- FileDB: append-journal with an in-memory key->offset index and
  periodic compaction (the r4 default; RAM grows with the key count).
- SqliteDB: stdlib sqlite3 behind a fixed page cache — the default
  since round 5: bounded steady-state RSS regardless of chain length
  (see its docstring for the soak numbers that motivated it).
"""

from __future__ import annotations

import os
import struct
import threading


class DB:
    def get(self, key: bytes) -> bytes | None:
        raise NotImplementedError

    def set(self, key: bytes, value: bytes) -> None:
        raise NotImplementedError

    def set_sync(self, key: bytes, value: bytes) -> None:
        self.set(key, value)

    def set_many(self, pairs) -> None:
        """`set` of every (key, value), as one write where the backend
        has one (upstream's batch write, used by the tx indexer)."""
        for key, value in pairs:
            self.set(key, value)

    def delete(self, key: bytes) -> None:
        raise NotImplementedError

    def has(self, key: bytes) -> bool:
        return self.get(key) is not None

    def iterate_prefix(self, prefix: bytes):
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemDB(DB):
    def __init__(self):
        self._data: dict[bytes, bytes] = {}
        self._mtx = threading.RLock()

    def get(self, key: bytes) -> bytes | None:
        with self._mtx:
            return self._data.get(key)

    def set(self, key: bytes, value: bytes) -> None:
        with self._mtx:
            self._data[bytes(key)] = bytes(value)

    def delete(self, key: bytes) -> None:
        with self._mtx:
            self._data.pop(key, None)

    def iterate_prefix(self, prefix: bytes):
        with self._mtx:
            items = sorted(
                (k, v) for k, v in self._data.items() if k.startswith(prefix)
            )
        yield from items

    def __len__(self):
        with self._mtx:
            return len(self._data)


_REC = struct.Struct("<BII")  # op, klen, vlen


class FileDB(DB):
    """Append-only journal of (op, key, value) records with load-time replay
    and size-triggered compaction. fsync on set_sync for the durability the
    reference gets from LevelDB's WAL.

    VALUES LIVE ON DISK: memory holds only a key -> (offset, length)
    index, so a long-running node's block store costs RAM proportional to
    the KEY count (~60 B/entry), not the chain's bytes — the property the
    reference gets from LevelDB. (A 30-min soak caught the prior design
    retaining ~9 KB of RAM per block, unbounded with chain length.)
    Reads seek the journal; the block-store/state hot paths read rarely
    (serving fast sync, RPC) while writes stay append-only."""

    _OP_SET = 1
    _OP_DEL = 2

    def __init__(self, path: str, compact_threshold: int = 64 * 1024 * 1024):
        self._path = path
        self._mtx = threading.RLock()
        self._index: dict[bytes, tuple[int, int]] = {}  # key -> (off, vlen)
        self._compact_threshold = compact_threshold
        self._compactions = 0  # observable: tests must prove live reads
        # survive a compaction, not just a restart replay
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._load()
        self._f = open(path, "ab")
        self._rf = open(path, "rb")

    def _load(self) -> None:
        if not os.path.exists(self._path):
            return
        with open(self._path, "rb") as f:
            buf = f.read()
        off = 0
        valid_end = 0
        while off + _REC.size <= len(buf):
            op, klen, vlen = _REC.unpack_from(buf, off)
            off += _REC.size
            if off + klen + vlen > len(buf):
                break  # torn tail record from a crash: drop it
            key = buf[off : off + klen]
            off += klen
            if op == self._OP_SET:
                self._index[key] = (off, vlen)
            elif op == self._OP_DEL:
                self._index.pop(key, None)
            off += vlen
            valid_end = off
        if valid_end < len(buf):
            # truncate the torn tail so subsequent appends don't concatenate
            # onto garbage and corrupt the journal for the next restart
            with open(self._path, "r+b") as f:
                f.truncate(valid_end)

    def _append(self, op: int, key: bytes, value: bytes, sync: bool) -> int:
        """Write one record; returns the VALUE's file offset. Compaction is
        the caller's follow-up (_maybe_compact) so the new record's index
        entry exists before the index is rewritten."""
        value_off = self._f.tell() + _REC.size + len(key)
        self._f.write(_REC.pack(op, len(key), len(value)) + key + value)
        self._f.flush()
        if sync:
            os.fsync(self._f.fileno())
        return value_off

    def _maybe_compact(self) -> None:
        if self._f.tell() > self._compact_threshold:
            self._compact()

    def _read_at(self, off: int, vlen: int) -> bytes:
        self._rf.seek(off)
        return self._rf.read(vlen)

    def _compact(self) -> None:
        tmp = self._path + ".compact"
        new_index: dict[bytes, tuple[int, int]] = {}
        with open(tmp, "wb") as f:
            for k, (off, vlen) in self._index.items():
                v = self._read_at(off, vlen)
                new_index[k] = (f.tell() + _REC.size + len(k), vlen)
                f.write(_REC.pack(self._OP_SET, len(k), vlen) + k + v)
            f.flush()
            os.fsync(f.fileno())
        self._f.close()
        self._rf.close()
        os.replace(tmp, self._path)
        self._index = new_index
        self._compactions += 1
        self._f = open(self._path, "ab")
        self._rf = open(self._path, "rb")

    def get(self, key: bytes) -> bytes | None:
        with self._mtx:
            ent = self._index.get(key)
            if ent is None:
                return None
            return self._read_at(*ent)

    def set(self, key: bytes, value: bytes) -> None:
        with self._mtx:
            key, value = bytes(key), bytes(value)
            off = self._append(self._OP_SET, key, value, sync=False)
            self._index[key] = (off, len(value))
            self._maybe_compact()

    def set_sync(self, key: bytes, value: bytes) -> None:
        with self._mtx:
            key, value = bytes(key), bytes(value)
            off = self._append(self._OP_SET, key, value, sync=True)
            self._index[key] = (off, len(value))
            self._maybe_compact()

    def delete(self, key: bytes) -> None:
        with self._mtx:
            if key in self._index:
                self._append(self._OP_DEL, key, b"", sync=False)
                del self._index[key]
                self._maybe_compact()

    def iterate_prefix(self, prefix: bytes):
        # snapshot KEYS only (filter before sorting); read each value via
        # get() at yield time — re-resolving the index per key keeps reads
        # correct across a concurrent compaction (stored offsets go stale
        # when the journal is rewritten) and never materializes the whole
        # matching range in RAM
        with self._mtx:
            keys = sorted(k for k in self._index if k.startswith(prefix))
        for k in keys:
            v = self.get(k)
            if v is not None:  # deleted since the snapshot: skip
                yield (k, v)

    def close(self) -> None:
        with self._mtx:
            self._f.close()
            self._rf.close()


class SqliteDB(DB):
    """KV store over stdlib sqlite3 — the BOUNDED-RAM persistent backend
    (the reference's LevelDB role, node/node.go:51-53).

    Why it exists (round-5 soak): FileDB keeps its whole key->offset
    index in RAM, so a node's RSS grows with chain length forever
    (~100 B x ~8 keys/block, measured ~90 KB/min at test cadence —
    scripts/soak_rss.py). Sqlite keeps the index in B-tree pages on disk
    behind a FIXED page cache, so steady-state RSS is flat no matter how
    long the chain gets.

    Durability split mirrors FileDB's: `set` commits in WAL mode with
    synchronous=NORMAL (fast; a power cut may lose the last commits but
    never corrupts), while `set_sync` runs on a second connection with
    synchronous=FULL, which fsyncs the WAL before returning — the
    guarantee the privval last-sign and state saves require."""

    _CACHE_KB = 2048  # fixed page-cache budget per DB (bounds RSS)

    def __init__(self, path: str):
        import sqlite3

        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._path = path
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute("PRAGMA journal_mode=WAL")
        self._conn.execute("PRAGMA synchronous=NORMAL")
        self._conn.execute(f"PRAGMA cache_size=-{self._CACHE_KB}")
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS kv (k BLOB PRIMARY KEY, v BLOB NOT NULL)"
        )
        self._conn.commit()
        self._sync_conn = sqlite3.connect(path, check_same_thread=False)
        self._sync_conn.execute("PRAGMA synchronous=FULL")
        self._sync_conn.execute(f"PRAGMA cache_size=-{self._CACHE_KB}")
        self._mtx = threading.RLock()

    def get(self, key: bytes) -> bytes | None:
        with self._mtx:
            row = self._conn.execute(
                "SELECT v FROM kv WHERE k = ?", (bytes(key),)
            ).fetchone()
        return None if row is None else bytes(row[0])

    def set(self, key: bytes, value: bytes) -> None:
        with self._mtx:
            self._conn.execute(
                "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)",
                (bytes(key), bytes(value)),
            )
            self._conn.commit()

    def set_many(self, pairs) -> None:
        """One transaction for all of them: a 10,000-tx block's index is
        one commit, not 20,000 (same durability as `set`)."""
        with self._mtx:
            self._conn.executemany(
                "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)",
                [(bytes(k), bytes(v)) for k, v in pairs],
            )
            self._conn.commit()

    def set_sync(self, key: bytes, value: bytes) -> None:
        with self._mtx:
            self._sync_conn.execute(
                "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)",
                (bytes(key), bytes(value)),
            )
            self._sync_conn.commit()

    def delete(self, key: bytes) -> None:
        with self._mtx:
            self._conn.execute("DELETE FROM kv WHERE k = ?", (bytes(key),))
            self._conn.commit()

    def iterate_prefix(self, prefix: bytes):
        # snapshot the matching KEYS (cheap), then re-read each value at
        # yield time — same concurrent-mutation semantics as FileDB's
        # iterator (deleted-since-snapshot keys are skipped)
        prefix = bytes(prefix)
        # exclusive upper bound = prefix with its last non-0xff byte
        # incremented (an all-0xff prefix has no upper bound); the range
        # is the index-friendly filter, startswith is the correctness one
        upper = None
        p = bytearray(prefix)
        for i in reversed(range(len(p))):
            if p[i] != 0xFF:
                p[i] += 1
                upper = bytes(p[: i + 1])
                break
        q = "SELECT k, v FROM kv WHERE k >= ? ORDER BY k"
        params: tuple = (prefix,)
        if upper is not None:
            q = "SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k"
            params = (prefix, upper)
        # one indexed range query, materialized under the lock (MemDB
        # yields snapshot-time values too; FileDB's re-read-per-key
        # exists only because compaction invalidates its offsets)
        with self._mtx:
            items = [
                (bytes(r[0]), bytes(r[1]))
                for r in self._conn.execute(q, params)
                if bytes(r[0]).startswith(prefix)
            ]
        yield from items

    def close(self) -> None:
        with self._mtx:
            self._conn.close()
            self._sync_conn.close()


def db_provider(name: str, backend: str, db_dir: str) -> DB:
    """node/node.go:51-53 DefaultDBProvider equivalent."""
    if backend in ("memdb", "mem"):
        return MemDB()
    if backend in ("sqlite", "sqlitedb"):
        return SqliteDB(os.path.join(db_dir, name + ".sqlite"))
    if backend in ("filedb", "file"):
        return FileDB(os.path.join(db_dir, name + ".db"))
    # fail LOUDLY: a silent FileDB fallback on a typo'd backend would
    # open a fresh empty store next to the real chain data
    raise ValueError(
        f"unknown db_backend {backend!r}: expected sqlite | filedb | memdb"
    )
