"""Durability: write-ahead log, checkpoints, recovery, barrier PITR.

The reference's per-node durability is WAL (src/backend/access/transam/
xlog.c) + checkpoints (src/backend/postmaster/checkpointer.c) + archive
recovery, and its cluster-consistent recovery points are CREATE BARRIER
records WAL-logged on every node (src/backend/pgxc/barrier/barrier.c).

Here the whole mini-cluster lives in one process space, so the cluster
WAL is a single ordered log of *committed* changes (commit timestamps
provide the order — redo is idempotent replay in commit order, which is
exactly what the reference's coordinator-consistent recovery achieves via
barrier alignment):

  record := u32 len | u8 tag | payload        (framed like the GTS wire)
  tags: 'D' DDL (json), 'I' insert (json hdr + npz columns),
        'X' delete (json hdr + npy indices), 'B' barrier (json)

Checkpoint = full npz snapshot of every shard store + catalog/shardmap
JSON + the WAL position it covers; recovery = load latest checkpoint,
replay the WAL tail (optionally stopping at a named barrier — PITR).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import struct
from typing import Optional

import numpy as np

from opentenbase_tpu import types as t
from opentenbase_tpu.analysis.racewatch import shared_state
import opentenbase_tpu.obs.statements as _stmtobs
from opentenbase_tpu.storage.table import ShardStore


def _type_to_str(ty: t.SqlType) -> str:
    if ty.id == t.TypeId.DECIMAL:
        return f"decimal({ty.precision},{ty.scale})"
    return ty.id.value


def _apply_constraints_meta(meta, cons: dict) -> None:
    meta.not_null = set(cons.get("not_null", ()))
    meta.defaults = dict(cons.get("defaults", {}))
    meta.primary_key = cons.get("primary_key")


def _type_from_str(s: str) -> t.SqlType:
    if s.startswith("decimal("):
        p, sc = s[8:-1].split(",")
        return t.decimal(int(p), int(sc))
    return t.SqlType(t.TypeId(s))


def encode_commit_group(writes, stores, catalog=None, dict_synced=None):
    """(sub, arrays) for one committed transaction — THE 'G'-frame body.
    Shared by WAL logging and the DN-shipped DML payload so a direct
    apply on a datanode is byte-identical to stream replay.

    ``writes``: iterable of (node, table, ins_ranges, del_idx).

    With ``catalog`` given, the frame ALSO carries each touched text
    column's dictionary delta — values above the ``dict_synced``
    watermark — as ``kind: "dict"`` sub-records ordered BEFORE the rows
    (VERDICT r4 ask #5: shipped DML must cover text tables; the delta
    rides the frame with its absolute start so the apply is idempotent
    against the stream's 'D' records). Entries are positional: array
    keys are indexed by each record's position in ``sub``, so dict
    records must be appended before any row record."""
    sub = []
    arrays: dict = {}
    if catalog is not None:
        for table in sorted({w[1] for w in writes}):
            tm = catalog.get(table)
            for col in sorted(tm.dictionaries):
                d = tm.dictionaries[col]
                start = (dict_synced or {}).get(f"{table}.{col}", 0)
                # emit even when the delta is EMPTY: the rows may carry
                # codes below ``start``, and the receiver's gap check
                # needs the watermark to see that its local dictionary
                # is still short of them
                sub.append({
                    "kind": "dict", "table": table, "column": col,
                    "start": int(start),
                    "values": list(d.values[start:]),
                })
    for node, table, ins_ranges, del_idx in writes:
        store = stores[node][table]
        for s, e in ins_ranges:
            i = len(sub)
            # delta-aware slicing: an ingest burst's ranges are served
            # straight from pending delta batches, so framing never
            # forces the base-array fold (storage/table.py)
            cols, vals, rid0 = store.slice_insert_arrays(s, e)
            for name in store.schema:
                arrays[f"w{i}_{name}"] = cols[name]
                vm = vals.get(name)
                if vm is not None:
                    arrays[f"w{i}__v_{name}"] = vm
            sub.append(
                # "cols" lets a direct-apply receiver detect a schema
                # it hasn't streamed yet (e.g. ADD COLUMN): a missing
                # column would silently drop shipped values otherwise
                {"node": node, "table": table, "kind": "ins",
                 "nrows": e - s, "cols": list(store.schema),
                 "row_id_start": rid0}
            )
        if len(del_idx):
            i = len(sub)
            idx = np.asarray(del_idx, dtype=np.int64)
            arrays[f"w{i}_del"] = store.peek_row_id_at(idx)
            sub.append({"node": node, "table": table, "kind": "del"})
    return sub, arrays


# WAL array payload framing. np.savez pays zipfile container + CRC +
# per-member header costs (~0.3 ms per commit record measured on the
# write bench — comparable to the fsync it sits next to); commit
# records are the hot path, so 1-D arrays frame RAW: magic, count,
# then (name, dtype.str, length, bytes) per array. The decoder
# recognizes the magic and falls back to np.load for anything else
# (pre-upgrade WAL tails, checkpoint spill files).
_ARR_MAGIC = b"OTB1"


def pack_arrays(arrays: dict) -> bytes:
    """Raw framing for a dict of 1-D numpy arrays; falls back to npz
    when an array is not 1-D (none in the WAL today)."""
    if any(np.asarray(a).ndim != 1 for a in arrays.values()):
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        return buf.getvalue()
    parts = [_ARR_MAGIC, struct.pack("<H", len(arrays))]
    for name, arr in arrays.items():
        a = np.ascontiguousarray(arr)
        nb = name.encode()
        ds = a.dtype.str.encode()
        parts.append(struct.pack("<HBI", len(nb), len(ds), a.size))
        parts.append(nb)
        parts.append(ds)
        parts.append(a.tobytes())
    return b"".join(parts)


def unpack_arrays(data: bytes) -> dict:
    """Decode a WAL array payload: raw framing by magic, npz otherwise
    (backward compatibility — the WAL may hold pre-upgrade records)."""
    if not data.startswith(_ARR_MAGIC):
        with np.load(io.BytesIO(data), allow_pickle=False) as z:
            return {k: z[k] for k in z.files}
    (cnt,) = struct.unpack_from("<H", data, 4)
    off = 6
    out: dict = {}
    for _ in range(cnt):
        ln, ld, size = struct.unpack_from("<HBI", data, off)
        off += 7
        name = data[off : off + ln].decode()
        off += ln
        dt = np.dtype(data[off : off + ld].decode())
        off += ld
        nbytes = size * dt.itemsize
        # copy: frombuffer views are read-only and would poison later
        # in-place store mutation during replay
        out[name] = np.frombuffer(
            data[off : off + nbytes], dtype=dt
        ).copy()
        off += nbytes
    return out


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n — the batch-size histogram bucket
    shared by the WAL group-flush and GTS-batcher halves of
    pg_stat_wal (one definition, so the two histograms cannot
    silently diverge)."""
    b = 1
    while b < n:
        b <<= 1
    return b


@shared_state("_mu", "_flush_cv")
class WAL:
    """Append-only framed log with group fsync (the WALWriteLock shape,
    xlog.c XLogFlush): every ``append`` writes + flushes its frame to
    the OS under ``_mu``; durability is a separate ``flush_to(end)``
    with LEADER ELECTION — concurrent committers piggyback on one
    fsync covering all their frames (``sync=True`` keeps the old
    fsync-per-append contract for callers outside the commit path)."""

    def __init__(self, path: str):
        self.path = path
        # A crash mid-append leaves a torn record at the tail; recovery
        # stops there, so anything appended after it would be unreachable
        # forever. Truncate the torn tail before reopening for append
        # (xlog.c does the same by zero-filling from the last valid
        # record on recovery).
        if os.path.exists(path):
            end = WAL.scan_end(path)
            if os.path.getsize(path) > end:
                with open(path, "r+b") as f:
                    f.truncate(end)
        self._f = open(path, "ab")
        # concurrent writers (table-granular statement gating) must not
        # interleave record bytes: one append = one atomic frame
        import threading as _threading

        self._mu = _threading.Lock()
        # group-flush state (everything on disk at open is durable)
        self._flush_cv = _threading.Condition(_threading.Lock())
        self._flushed = self._f.tell()
        self._flush_leader = False
        # commit records written-but-unsynced since the last fsync —
        # the leader's batch size (pg_stat_wal's histogram source)
        self._unsynced_commits = 0
        # lifetime counters (pg_stat_wal): fsync syscalls (group-flush
        # leader fsyncs counted separately — commit_flushes minus
        # group_fsyncs is the "fsyncs saved" headline), commits that
        # asked for durability, and the per-fsync batch-size histogram
        # {size_bucket: count} with power-of-two buckets
        self.fsyncs = 0
        self.group_fsyncs = 0
        self.commit_flushes = 0
        self.batch_hist: dict[int, int] = {}

    def append(
        self, tag: bytes, header: dict,
        arrays: Optional[dict] = None, sync: bool = True,
    ) -> int:
        from opentenbase_tpu.fault import FAULT

        # failpoint: WAL write (error = an fsync/disk failure surfacing
        # before any byte lands — the commit path must roll back; delay
        # models a saturated log device)
        FAULT("storage/wal_write", tag=tag.decode("latin1"))
        hdr = json.dumps(header).encode()
        payload = struct.pack("<I", len(hdr)) + hdr
        if arrays is not None:
            payload += pack_arrays(arrays)
        rec = struct.pack("<IB", 1 + len(payload), tag[0]) + payload
        # per-statement attribution (obs/statements.py): WAL bytes this
        # statement generated, billed on the appending thread; a sync
        # append is its own flush, group-commit flushes bill in flush_to
        led = _stmtobs.current()
        if led is not None:
            led.wal_bytes += len(rec)
            if sync:
                led.wal_flushes += 1
        with self._mu:
            self._f.write(rec)
            self._f.flush()
            if not sync:
                # group-commit path: durable later, via flush_to's
                # leader fsync (or never awaited: synchronous_commit=off)
                self._unsynced_commits += 1
                return self._f.tell()
            os.fsync(self._f.fileno())
            self.fsyncs += 1
            end = self._f.tell()
        with self._flush_cv:
            self._flushed = max(self._flushed, end)
        return end

    def flush_to(
        self, end: int, delay_us: int = 0, siblings_ok: bool = False,
    ) -> None:
        """Block until every byte up to ``end`` is fsynced. ONE leader
        fsyncs for everyone waiting (group commit); followers return
        when the leader's flush covers their offset. ``delay_us`` +
        ``siblings_ok`` are PG's commit_delay/commit_siblings: the
        leader naps briefly before the fsync — only when enough other
        sessions are mid-commit — so their records join this batch."""
        from opentenbase_tpu.fault import FAULT

        # failpoint: the group-flush boundary (error = the batch fsync
        # failing — every waiter in the batch must see it and abort;
        # delay = a saturated log device stretching the whole batch)
        FAULT("storage/group_flush")
        # fsyncs-shared: every waiter in the batch pays one flush in
        # its ledger even when a single leader fsync covers the group —
        # the per-statement bill reflects what the statement REQUIRED,
        # pg_stat_wal's fsyncs/group_fsyncs keep the savings headline
        led = _stmtobs.current()
        if led is not None:
            led.wal_flushes += 1
        with self._flush_cv:
            self.commit_flushes += 1
        while True:
            with self._flush_cv:
                if self._flushed >= end:
                    return
                if not self._flush_leader:
                    self._flush_leader = True
                    break
                self._flush_cv.wait(timeout=5.0)
        synced = None
        try:
            if delay_us > 0 and siblings_ok:
                import time as _time

                _time.sleep(delay_us / 1e6)
            with self._mu:
                target = self._f.tell()
                batch = self._unsynced_commits
                self._unsynced_commits = 0
            os.fsync(self._f.fileno())
            synced = target
            with self._mu:
                # counters share append()'s guard; the fsync itself ran
                # unlocked — that concurrency IS the group-commit win
                self.fsyncs += 1
                self.group_fsyncs += 1
                if batch:
                    b = pow2_bucket(batch)
                    self.batch_hist[b] = self.batch_hist.get(b, 0) + 1
        finally:
            with self._flush_cv:
                self._flush_leader = False
                # publish only on success; a failed fsync wakes the
                # waiters to elect a new leader (and likely fail too —
                # honestly, not silently)
                if synced is not None:
                    self._flushed = max(self._flushed, synced)
                self._flush_cv.notify_all()

    def close(self) -> None:
        from opentenbase_tpu.fault import FAULT

        # failpoint: the shutdown flush (error = the disk dying under
        # the final fsync — the synchronous_commit=off tail is then
        # only as durable as the OS cache, exactly what 'off' promises)
        FAULT("storage/wal_close")
        # the synchronous_commit=off tail: written + OS-flushed but not
        # yet fsynced bytes become durable at clean shutdown
        try:
            with self._mu:
                self._f.flush()
                os.fsync(self._f.fileno())
        except (OSError, ValueError):
            pass
        self._f.close()

    def truncate_to(self, offset: int) -> None:
        """Discard everything after ``offset`` (abandoning a timeline
        after PITR) and continue appending from there."""
        self._f.close()
        with open(self.path, "r+b") as f:
            f.truncate(offset)
        self._f = open(self.path, "ab")
        with self._flush_cv:
            self._flushed = min(self._flushed, offset)

    @property
    def position(self) -> int:
        return self._f.tell()

    def stat_snapshot(self) -> dict:
        """Counters for pg_stat_wal / the exporter, read under their
        guards — the view must not dirty-read ``@shared_state`` fields
        concurrent committers are writing."""
        with self._mu:
            snap = {
                "position": self._f.tell(),
                "fsyncs": self.fsyncs,
                "group_fsyncs": self.group_fsyncs,
                "batch_hist": dict(self.batch_hist),
            }
        with self._flush_cv:
            snap["commit_flushes"] = self.commit_flushes
            snap["flushed"] = self._flushed
        return snap

    @staticmethod
    def scan_end(path: str) -> int:
        """Offset just past the last intact record — frame headers only,
        seeking past bodies, so opening a multi-GB WAL stays O(records)
        not O(bytes parsed)."""
        end = 0
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            while True:
                head = f.read(5)
                if len(head) < 5:
                    return end
                (length, _tag) = struct.unpack("<IB", head)
                # minimum frame: tag + header-length word; a zero-filled
                # tail would otherwise parse as endless length-0 frames
                if length < 5:
                    return end
                nxt = end + 4 + length
                if nxt > size:
                    return end
                f.seek(nxt)
                end = nxt

    @staticmethod
    def read_stream(f, decode_arrays: bool = True):
        """Yield (tag, header, arrays_or_None, end_offset) from any
        binary file-like positioned at a record boundary. THE one parser
        of the record format — recovery and streaming replication both
        sit on it."""
        while True:
            head = f.read(5)
            if len(head) < 5:
                return
            length, tag = struct.unpack("<IB", head)
            if length < 5:
                return  # torn/zero-filled tail
            body = f.read(length - 1)
            if len(body) < length - 1:
                return  # torn tail: ignore (crash mid-append)
            (hlen,) = struct.unpack_from("<I", body, 0)
            header = json.loads(body[4 : 4 + hlen].decode())
            arrays = None
            rest = body[4 + hlen :]
            if rest and decode_arrays:
                arrays = unpack_arrays(rest)
            yield chr(tag), header, arrays, f.tell()

    @staticmethod
    def read_records(path: str, start: int = 0, decode_arrays: bool = True):
        """Yield (tag, header, arrays_or_None, end_offset) from a WAL
        file; see read_stream."""
        if not os.path.exists(path):
            return
        with open(path, "rb") as f:
            f.seek(start)
            yield from WAL.read_stream(f, decode_arrays)


class ClusterPersistence:
    """Checkpoint + WAL manager bound to one Cluster."""

    def __init__(self, cluster, data_dir: str):
        import threading as _threading

        self.cluster = cluster
        self.dir = data_dir
        os.makedirs(data_dir, exist_ok=True)
        self.wal = WAL(os.path.join(data_dir, "wal.log"))
        # per-dictionary count of values already WAL-logged: replaying
        # inserts needs the dictionary to contain the codes they carry,
        # so dictionary growth is logged as dict_extend records first
        self._dict_synced: dict[str, int] = {}
        # gid -> {"gxid", "writes": [...]} of replayed-but-undecided 2PC
        # transactions (populated during recover, drained by C/R records)
        self._pending: dict[str, dict] = {}
        # gid -> ("commit", commit_ts) | ("abort", None): the DURABLE
        # commit decision of every gid-tagged transaction this WAL knows
        # about — populated at log time AND during recovery replay, so
        # the in-doubt resolver (engine.py resolve_indoubt) can answer
        # "did this gid commit?" without rescanning the log. Bounded,
        # insertion-ordered eviction of the oldest (a resolver only ever
        # asks about recent gids; anything older was already retired).
        self._gid_decisions: dict[str, tuple] = {}
        self._gid_decisions_mu = _threading.Lock()
        # True while redo is applying records: side-effect feeds (e.g. the
        # GTM sequence-event bridge) must not re-log what they replay
        self._in_recovery = False
        # live WalSenders streaming this WAL (storage/replication.py
        # registers/deregisters) — the exporter's replication-lag gauges
        self.wal_senders: list = []

    def sync_dicts(self, table: str) -> None:
        tm = self.cluster.catalog.get(table)
        for col, d in tm.dictionaries.items():
            key = f"{table}.{col}"
            synced = self._dict_synced.get(key, 0)
            if len(d) > synced:
                self.log_ddl(
                    {
                        "op": "dict_extend",
                        "table": table,
                        "column": col,
                        "values": d.values[synced:],
                    }
                )
                self._dict_synced[key] = len(d)

    # -- WAL hooks (called by the engine at commit time) ------------------
    def log_ddl(self, op: dict) -> None:
        self.wal.append(b"D", op)

    def log_commit_group(
        self, writes, stores, commit_ts: int, gid=None, frame=None,
        sync_mode: str = "local", commit_delay_us: int = 0,
        commit_siblings: int = 5, group_commit: bool = True,
        commit_active: int = 1,
    ) -> Optional[int]:
        """Log one committed transaction as ONE frame ('G'): a commit that
        touches many tables/nodes must be atomic under the torn-tail rule,
        which holds per frame — per-table records would replay a torn,
        half-applied transaction after a crash mid-commit.

        ``writes``: iterable of (node, table, ins_ranges, del_idx).
        Deletes are logged by stable row id, not position: replayed stores
        omit aborted rows and may order interleaved commits differently,
        so positions drift while row ids never do.

        ``gid``: set when this transaction's writes were ALSO shipped to
        datanode processes inside their 2PC prepare — the tag lets a
        standby that direct-applied the prepared data skip this frame
        (exactly-once across the two delivery paths). ``frame``: the
        (sub, arrays) encoding when the caller already built it for the
        shipped payload — avoids encoding the write set twice.

        Returns the WAL offset just past this commit's 'G' frame (None
        when the transaction wrote nothing) — the exact LSN a
        synchronous_commit=on ack must see applied on the standbys.

        ``sync_mode`` is the synchronous_commit ladder's LOCAL rung:
        'off' writes + OS-flushes the frame but does not wait for the
        fsync (PG's off — a later group flush, checkpoint, or clean
        shutdown makes it durable; an OS crash may lose the tail, a
        process crash loses nothing); every other mode joins the group
        flush — ONE leader fsync covers every concurrent committer,
        napping commit_delay_us first when >= commit_siblings other
        sessions are mid-commit so their frames join the batch."""
        sub, arrays = (
            frame if frame is not None
            else encode_commit_group(writes, stores)
        )
        for table in {w[1] for w in writes}:
            self.sync_dicts(table)
        if sub:
            header = {"commit_ts": commit_ts, "writes": sub}
            if gid is not None:
                header["gid"] = gid
            if not group_commit and sync_mode != "off":
                # enable_group_commit=off: the seed's fsync-per-commit
                # path, byte-identical frames (an operator escape
                # hatch)
                return self._finish_commit_record(
                    header, arrays, gid, commit_ts, sync=True
                )
            end = self._finish_commit_record(
                header, arrays, gid, commit_ts, sync=False
            )
            if sync_mode != "off":
                # commit_active: sessions inside the commit path right
                # now, passed down by the engine like the other GUC
                # inputs (minus ourselves = PG's "siblings")
                siblings = int(commit_active) - 1
                self.wal.flush_to(
                    end,
                    delay_us=int(commit_delay_us),
                    siblings_ok=siblings >= int(commit_siblings),
                )
            return end
        return None

    def _finish_commit_record(
        self, header, arrays, gid, commit_ts, sync: bool
    ) -> int:
        end = self.wal.append(b"G", header, arrays or None, sync=sync)
        if gid is not None:
            self._record_decision(gid, "commit", commit_ts)
        return end

    def log_barrier(self, name: str, ts: int) -> None:
        self.wal.append(b"B", {"name": name, "ts": ts})

    # -- 2PC records (twophase.c's on-disk prepared-transaction state) ----
    def log_prepare(self, txn, stores) -> None:
        """Persist an explicitly PREPAREd transaction's pending writes so
        the in-doubt txn survives a crash and can still be COMMIT/ROLLBACK
        PREPARED after recovery."""
        writes = []
        arrays: dict = {}
        for table in {tb for tabs in txn.writes.values() for tb in tabs}:
            self.sync_dicts(table)
        for node, tabs in txn.writes.items():
            for table, tw in tabs.items():
                store = stores[node][table]
                for s, e in tw.ins_ranges:
                    i = len(writes)
                    cols, vals, rid0 = store.slice_insert_arrays(s, e)
                    for name in store.schema:
                        arrays[f"w{i}_{name}"] = cols[name]
                        vm = vals.get(name)
                        if vm is not None:
                            arrays[f"w{i}__v_{name}"] = vm
                    writes.append(
                        {"node": node, "table": table, "kind": "ins",
                         "nrows": e - s,
                         "row_id_start": rid0}
                    )
                if tw.del_idx:
                    i = len(writes)
                    idx = np.asarray(tw.del_idx, dtype=np.int64)
                    arrays[f"w{i}_del"] = store.peek_row_id_at(idx)
                    writes.append(
                        {"node": node, "table": table, "kind": "del"}
                    )
        self.wal.append(
            b"T",
            {"gid": txn.prepared_gid, "gxid": txn.gxid, "writes": writes},
            arrays or None,
        )

    def log_commit_prepared(self, gid: str, commit_ts: int) -> None:
        self.wal.append(b"C", {"gid": gid, "commit_ts": commit_ts})
        self._record_decision(gid, "commit", commit_ts)

    def log_rollback_prepared(self, gid: str) -> None:
        self.wal.append(b"R", {"gid": gid})
        self._record_decision(gid, "abort", None)

    def _record_decision(self, gid: str, outcome: str, ts) -> None:
        # concurrent session threads commit at once: the insert is
        # GIL-atomic but the evict-oldest loop is read-then-pop, and two
        # threads popping the same oldest key would raise KeyError AFTER
        # the commit record is already durable — hence the lock (reads
        # via gid_decision stay lock-free: a plain .get)
        with self._gid_decisions_mu:
            self._gid_decisions[gid] = (outcome, ts)
            while len(self._gid_decisions) > 8192:
                self._gid_decisions.pop(
                    next(iter(self._gid_decisions)), None
                )

    def gid_decision(self, gid: str):
        """("commit", commit_ts) / ("abort", None) / None (no durable
        decision — presumed abort under the 2PC protocol)."""
        # otb_race: ignore[race-guard-mismatch] -- deliberate lock-free .get on the resolver hot path (see _record_decision: only the evict loop needs the lock); a racing insert is invisible, never torn
        return self._gid_decisions.get(gid)

    # -- checkpoint -------------------------------------------------------
    def checkpoint(self) -> None:
        """Snapshot catalog + all shard stores; records the WAL position
        so recovery replays only the tail.

        Crash-safety: store snapshots are written under a fresh generation
        number and checkpoint.json (the atomic rename) names that
        generation — a crash mid-checkpoint leaves the previous json
        pointing at the previous generation's untouched files, never at a
        mixed set. Rows of in-flight *unprepared* transactions
        (xmin=PENDING, no 'T'/'prepared' record to decide them) are
        excluded: if they later commit, their 'G' record replays them; if
        not, they must not exist after recovery."""
        from opentenbase_tpu.fault import FAULT

        # failpoint: a crash/IO failure at checkpoint start — recovery
        # must still work from the previous generation + WAL tail
        FAULT("storage/checkpoint")
        c = self.cluster
        gen = self._next_ckpt_gen()
        # progress + server log (obs/): a long checkpoint is watchable
        # from another session through pg_stat_progress_checkpoint
        names_total = len(c.catalog.table_names())
        prog = None
        progress = getattr(c, "progress", None)
        if progress is not None:
            prog = progress.begin(
                "checkpoint", 0, f"gen{gen}",
                phase="snapshot_stores", tables_total=names_total,
                tables_done=0, wal_position=int(self.wal.position),
            )
        log = getattr(c, "log", None)
        if log is not None:
            log.emit(
                "debug", "checkpoint",
                f"checkpoint starting (gen {gen}, "
                f"{names_total} tables)",
            )
        # serialize against rebalance copy chunks: a chunk is (append
        # pending rows, log 'T', register) under the service's gate, so
        # holding it here means every chunk is either fully inside this
        # checkpoint (rows + prepared-meta, 'T' below wal_position) or
        # fully after it (nothing in the snapshot, 'T' replays) — never
        # half of each, which would double- or zero-materialize the rows
        svc = getattr(c, "rebalance", None)
        gate = svc.copy_gate if svc is not None else contextlib.nullcontext()
        try:
            with gate:
                self._checkpoint_inner(c, gen, prog)
        finally:
            if prog is not None:
                prog.finish(phase="done")
        if log is not None:
            log.emit(
                "log", "checkpoint",
                f"checkpoint complete (gen {gen}, "
                f"wal_position {int(self.wal.position)})",
            )

    def _checkpoint_inner(self, c, gen: int, prog) -> None:
        from opentenbase_tpu.fault import FAULT

        # failpoint distinct from storage/checkpoint (the entry gate):
        # this one sits where the snapshot files + meta fsyncs happen,
        # so an injected I/O failure mid-checkpoint leaves the previous
        # generation's json untouched — recovery must still work
        FAULT("storage/checkpoint_write", gen=gen)
        prep_ranges: dict[tuple[int, str], list[tuple[int, int]]] = {}
        for txn in getattr(c, "_prepared", {}).values():
            for node, tabs in txn.writes.items():
                for table, tw in tabs.items():
                    prep_ranges.setdefault((node, table), []).extend(
                        tw.ins_ranges
                    )
        # in-flight rebalance copy chunks are pending writes too: their
        # invisible destination rows must survive the snapshot exactly
        # like in-doubt 2PC rows (caller holds the service's copy_gate)
        rb_prepared: dict = {}
        svc = getattr(c, "rebalance", None)
        if svc is not None:
            rb_prepared, rb_ranges = svc.checkpoint_prepared()
            for key, rngs in rb_ranges.items():
                prep_ranges.setdefault(key, []).extend(rngs)
        meta = {
            "gen": gen,
            "wal_position": self.wal.position,
            "tables": {},
            "shardmap": c.shardmap.map.tolist(),
            "num_shards": c.shardmap.num_shards,
            "barriers": c.barriers,
            "literals": c.catalog.literals.values,
            "datanodes": [
                {"name": n.name, "mesh_index": n.mesh_index}
                for n in c.nodes.datanodes
            ],
            # in-doubt 2PC txns: their pending rows are inside the store
            # snapshots (xmin=PENDING); record which rows belong to which
            # gid so recovery can still decide them (twophase.c state files)
            "prepared": {
                **{
                    gid: {
                        "gxid": txn.gxid,
                        "writes": self._prepared_writes_meta(txn),
                    }
                    for gid, txn in getattr(c, "_prepared", {}).items()
                },
                **rb_prepared,
            },
            "groups": [
                {"name": g.name, "members": list(g.members),
                 "kind": g.kind}
                for g in c.nodes.all_groups()
            ],
            # un-done rebalance plans: their begin D-records sit below
            # wal_position, so the snapshot must carry them for resume
            "rebalance": (
                svc.checkpoint_journal() if svc is not None else []
            ),
            "partitions": {
                name: ps.spec for name, ps in c.partitions.items()
            },
            "views": {name: text for name, (_q, text) in c.views.items()},
            # matview defs ride the checkpoint (the backing + aux
            # tables are already in "tables"); refresh state lives in
            # the otb_matview_state table and needs nothing extra here
            "matviews": {
                name: {
                    "text": d.text,
                    "options": dict(d.options),
                    "aux_schema": d.aux_schema,
                }
                for name, d in c.matviews.items()
            },
            "users": c.users,
            "wlm": c.wlm.dump_state(),
            # fencing epoch: a checkpoint at wal_position P covers every
            # ha_generation record below P, so recovery-from-checkpoint
            # must restore the generation the replayed tail won't
            "node_generation": int(getattr(c, "node_generation", 0)),
        }
        done = 0
        for name in c.catalog.table_names():
            tm = c.catalog.get(name)
            meta["tables"][name] = {
                "schema": {k: _type_to_str(v) for k, v in tm.schema.items()},
                "strategy": tm.dist.strategy.value,
                "key_columns": list(tm.dist.key_columns),
                "group": tm.dist.group,
                "nodes": list(tm.node_indices),
                "dictionaries": {
                    col: d.values for col, d in tm.dictionaries.items()
                },
                "constraints": {
                    "not_null": sorted(getattr(tm, "not_null", ())),
                    "defaults": dict(getattr(tm, "defaults", {})),
                    "primary_key": getattr(tm, "primary_key", None),
                },
                "zone_cols": sorted(tm.zone_cols),
                "foreign": tm.foreign,
            }
            for node in tm.node_indices:
                store = c.stores[node].get(name)
                if store is None:
                    continue
                from opentenbase_tpu.storage.table import PENDING_TS

                # non-folding capture: a checkpoint must never compact
                # the store it snapshots (delta-resident rows write out
                # straight from their batches)
                sv = store.scan_view()
                n = sv.nrows
                xmin = sv.xmin()
                keep = xmin != PENDING_TS
                for s, e in prep_ranges.get((node, name), []):
                    keep[s:e] = True  # prepared rows are decidable: keep
                arrays = {"__xmin": xmin[keep],
                          "__xmax": sv.xmax()[keep],
                          "__rowid": sv.row_id()[keep]}
                for col in store.schema:
                    arrays[col] = sv.col(col, 0, n)[keep]
                    vm = sv.validity(col, 0, n)
                    if vm is not None:
                        arrays[f"__v_{col}"] = vm[keep]
                path = os.path.join(
                    self.dir, f"ckpt{gen}_dn{node}_{name}.npz"
                )
                with open(path + ".tmp", "wb") as f:
                    np.savez(f, **arrays)
                os.replace(path + ".tmp", path)
            done += 1
            if prog is not None:
                prog.update(tables_done=done)
        if prog is not None:
            prog.update(phase="write_meta")
        tmp = os.path.join(self.dir, "checkpoint.json.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(self.dir, "checkpoint.json"))
        self._gc_checkpoints(gen)
        # checkpoint covers all dictionary state up to now
        for name in c.catalog.table_names():
            tm = c.catalog.get(name)
            for col, d in tm.dictionaries.items():
                self._dict_synced[f"{name}.{col}"] = len(d)

    def _next_ckpt_gen(self) -> int:
        ckpt_path = os.path.join(self.dir, "checkpoint.json")
        if os.path.exists(ckpt_path):
            try:
                with open(ckpt_path) as f:
                    return int(json.load(f).get("gen", 0)) + 1
            except Exception as e:
                from opentenbase_tpu.obs.log import elog

                elog(
                    "warning", "storage",
                    "unreadable checkpoint manifest; restarting "
                    "checkpoint generations at 1",
                    path=ckpt_path, error=str(e),
                )
        return 1

    def _gc_checkpoints(self, live_gen: int) -> None:
        """Remove snapshot files of superseded generations."""
        prefix = f"ckpt{live_gen}_"
        for fn in os.listdir(self.dir):
            if fn.startswith("ckpt") and fn.split("_", 1)[0] != prefix[:-1]:
                if fn.endswith(".npz") or fn.endswith(".npz.tmp"):
                    try:
                        os.remove(os.path.join(self.dir, fn))
                    except OSError:
                        pass

    def _prepared_writes_meta(self, txn) -> list[dict]:
        c = self.cluster
        ws = []
        for node, tabs in txn.writes.items():
            for table, tw in tabs.items():
                store = c.stores[node][table]
                for s, e in tw.ins_ranges:
                    _c, _v, rid0 = store.slice_insert_arrays(s, e)
                    ws.append(
                        {"node": node, "table": table, "kind": "ins",
                         "nrows": e - s, "row_id_start": rid0}
                    )
                if tw.del_idx:
                    idx = np.asarray(tw.del_idx, dtype=np.int64)
                    ws.append(
                        {"node": node, "table": table, "kind": "del",
                         "rowids": store.peek_row_id_at(idx).tolist()}
                    )
        return ws

    # -- recovery ---------------------------------------------------------
    def recover(self, until_barrier: Optional[str] = None) -> int:
        """Rebuild cluster state: checkpoint restore + WAL tail replay.
        ``until_barrier`` stops redo at a named barrier (PITR,
        recovery_target_barrier in the reference). Returns the number of
        WAL records applied."""
        c = self.cluster
        ckpt_path = os.path.join(self.dir, "checkpoint.json")
        wal_path = os.path.join(self.dir, "wal.log")
        meta = None
        if os.path.exists(ckpt_path):
            with open(ckpt_path) as f:
                meta = json.load(f)
        barrier_end = None
        if until_barrier is not None:
            # locate the target barrier record first: a checkpoint taken
            # *after* the barrier covers state PITR must rewind, so it can
            # only be used when its WAL position precedes the barrier
            prev = 0
            for tag, header, _a, off in WAL.read_records(
                wal_path, decode_arrays=False
            ):
                if tag == "B" and header["name"] == until_barrier:
                    barrier_end = off
                    break
                prev = off
            if barrier_end is None:
                raise ValueError(
                    f"recovery target barrier {until_barrier!r} not in WAL"
                )
            if meta is not None and meta["wal_position"] > prev:
                meta = None  # checkpoint is past the barrier: replay from 0
        start = 0
        if meta is not None:
            start = meta["wal_position"]
            self._restore_checkpoint(meta)
        applied = 0
        wal_end = WAL.scan_end(wal_path) if os.path.exists(wal_path) else 0
        # progress + server log: recovery is the blackout window an
        # operator most wants to watch (pg_stat_progress_recovery)
        prog = None
        progress = getattr(c, "progress", None)
        if progress is not None:
            prog = progress.begin(
                "recovery", 0, self.dir, phase="redo",
                wal_replay_lsn=int(start), wal_end_lsn=int(wal_end),
                records_applied=0,
            )
        log = getattr(c, "log", None)
        if log is not None:
            log.emit(
                "log", "recovery",
                f"WAL recovery starting at {int(start)} "
                f"(end {int(wal_end)})",
                until_barrier=until_barrier,
            )
        self._in_recovery = True
        try:
            for tag, header, arrays, off in WAL.read_records(wal_path, start):
                if tag == "B":
                    c.barriers.append((header["name"], header["ts"]))
                    if barrier_end is not None and off >= barrier_end:
                        break
                    continue
                self._apply(tag, header, arrays)
                applied += 1
                if prog is not None:
                    prog.update(
                        wal_replay_lsn=int(off), records_applied=applied
                    )
        finally:
            self._in_recovery = False
            if prog is not None:
                prog.finish(phase="done")
        if log is not None:
            log.emit(
                "log", "recovery",
                f"WAL recovery complete: {applied} records replayed",
            )
        if barrier_end is not None:
            # abandon the old timeline: discard post-barrier WAL and
            # re-checkpoint the rewound state so the next recovery cannot
            # merge divergent histories (timeline switch, xlog.c)
            self.wal.truncate_to(barrier_end)
            self.checkpoint()
        self._finish_recovery()
        return applied

    def _finish_recovery(self) -> None:
        """Post-redo fixups: re-park still-undecided prepared transactions
        so COMMIT/ROLLBACK PREPARED work after a crash (the RecoverPrepared
        startup pass of twophase.c), and prime the dictionary sync state so
        the next commit doesn't re-log whole dictionaries."""
        from opentenbase_tpu.engine import Transaction

        c = self.cluster
        from opentenbase_tpu.storage.table import RESERVED_TS

        import time as _time

        # rebalance copy chunks are NOT in-doubt 2PC transactions: their
        # outcome is decided by the flip record (or aborted by resume),
        # never by an operator, so they must not reach c._prepared, the
        # GTS, or the RESERVED re-stamp below (which would resurrect the
        # source-row deletes on a later operator ROLLBACK PREPARED)
        from opentenbase_tpu.rebalance.journal import is_rebalance_gid

        svc = getattr(c, "rebalance", None)
        for gid in [g for g in self._pending if is_rebalance_gid(g)]:
            pend = self._pending.pop(gid)
            if svc is not None:
                svc.adopt_pending(gid, pend)
        for gid, pend in self._pending.items():
            txn = Transaction(pend["gxid"], 0)
            txn.prepared_gid = gid
            # fresh grace period after recovery: clean2pc must neither
            # insta-kill recovered in-doubt txns nor treat them as new
            # forever
            txn.prepared_at = _time.time()
            for wm in pend["writes"]:
                store = c.stores[wm["node"]][wm["table"]]
                tw = txn.w(wm["node"], wm["table"])
                if wm["kind"] == "ins":
                    tw.ins_ranges.append(tuple(wm["range"]))
                else:
                    pos = np.nonzero(
                        np.isin(store.scan_view().row_id(), wm["rowids"])
                    )[0]
                    tw.del_idx.extend(int(i) for i in pos)
                    # re-assert the PREPARE reservation so new writers
                    # conflict against the in-doubt delete
                    store.stamp_xmax(pos, RESERVED_TS)
                txn.pin(store)
            c.__dict__.setdefault("_prepared", {})[gid] = txn
            # the GTS must also know the in-doubt txn (native backend
            # journals it itself; the in-process backend lost it)
            try:
                known = {p.gid for p in c.gts.prepared_txns()}
            except Exception as e:
                from opentenbase_tpu.obs.log import elog

                elog(
                    "log", "storage",
                    "GTS prepared-txn listing unavailable during "
                    "recovery; re-preparing all pending gids",
                    gid=gid, error=str(e),
                )
                known = set()
            if gid not in known:
                c.gts.prepare(pend["gxid"], gid, tuple(txn.touched_nodes()))
            nx = getattr(c.gts, "_next_gxid", None)
            if nx is not None and pend["gxid"] >= nx:
                c.gts._next_gxid = pend["gxid"] + 1
        self._pending = {}
        for name in c.catalog.table_names():
            tm = c.catalog.get(name)
            for col, d in tm.dictionaries.items():
                self._dict_synced[f"{name}.{col}"] = len(d)

    def _restore_checkpoint(self, meta: dict) -> None:
        self.cluster.users.update(meta.get("users", {}))
        g = int(meta.get("node_generation", 0))
        if g > int(getattr(self.cluster, "node_generation", 0)):
            self.cluster.node_generation = g
        if meta.get("wlm"):
            self.cluster.wlm.load_state(meta["wlm"])
        import numpy as np

        from opentenbase_tpu.catalog.distribution import (
            DistStrategy,
            DistributionSpec,
        )
        from opentenbase_tpu.storage.column import Dictionary

        c = self.cluster
        c.shardmap.map = np.asarray(meta["shardmap"], dtype=np.int32)
        c.shardmap.num_shards = int(
            meta.get("num_shards", len(c.shardmap.map))
        )
        c.shardmap.row_stats = np.zeros(c.shardmap.num_shards, dtype=np.int64)
        # dynamically created datanodes must come back at their original
        # (stable) mesh indices before table/store restore references them
        for nd in meta.get("datanodes", []):
            if not c.nodes.has(nd["name"]):
                c.nodes.restore_datanode(nd["name"], nd["mesh_index"])
            c.stores.setdefault(nd["mesh_index"], {})
        for grec in meta.get("groups", []):
            if not c.nodes.has_group(grec["name"]):
                members = [
                    m for m in grec["members"] if c.nodes.has(m)
                ]
                c.nodes.create_group(
                    grec["name"], members, grec.get("kind", "hot")
                )
        for rrec in meta.get("rebalance", []):
            c.rebalance.replay_begin(rrec)
        c.barriers = [tuple(b) for b in meta["barriers"]]
        c.catalog.literals = Dictionary(meta.get("literals", []))
        for name, tmeta in meta["tables"].items():
            schema = {
                k: _type_from_str(v) for k, v in tmeta["schema"].items()
            }
            strategy = DistStrategy(tmeta["strategy"])
            spec = DistributionSpec(
                strategy, tuple(tmeta["key_columns"]),
                group=tmeta.get("group"),
            )
            if not c.catalog.has(name):
                c.catalog.create_table(name, schema, spec)
            tm = c.catalog.get(name)
            _apply_constraints_meta(tm, tmeta.get("constraints", {}))
            tm.zone_cols.update(tmeta.get("zone_cols", []))
            if tmeta.get("foreign"):
                tm.foreign = dict(tmeta["foreign"])
                tm.node_indices = tm.node_indices[:1]
                continue  # no shard stores: scans materialize via fdw
            tm.node_indices = list(tmeta["nodes"])
            # the locator binds its OWN node list (Locator copies at
            # construction) — restore it too, or group-placed / post-
            # rebalance tables would hash-route on the fresh-create set
            tm.locator.node_indices = list(tmeta["nodes"])
            for col, values in tmeta["dictionaries"].items():
                tm.dictionaries[col] = Dictionary(values)
            tm.locator.key_types = {
                k: schema[k] for k in spec.key_columns
            }
            gen = meta.get("gen", 0)
            for node in tm.node_indices:
                store = ShardStore(tm.schema, tm.dictionaries)
                path = os.path.join(
                    self.dir, f"ckpt{gen}_dn{node}_{name}.npz"
                )
                if os.path.exists(path):
                    with np.load(path, allow_pickle=False) as z:
                        n = len(z["__xmin"])
                        if n:
                            from opentenbase_tpu.storage.column import Column
                            from opentenbase_tpu.storage.table import ColumnBatch

                            cols = {}
                            for colname, ty in tm.schema.items():
                                vm = (
                                    z[f"__v_{colname}"]
                                    if f"__v_{colname}" in z.files
                                    else None
                                )
                                cols[colname] = Column(
                                    ty, z[colname], vm,
                                    tm.dictionaries.get(colname),
                                )
                            store.append_batch(ColumnBatch(cols, n), 0)
                            store.xmin_ts[:n] = z["__xmin"]
                            store.xmax_ts[:n] = z["__xmax"]
                            if "__rowid" in z.files:
                                store.row_id[:n] = z["__rowid"]
                                store.next_row_id = int(z["__rowid"].max()) + 1
                c.stores.setdefault(node, {})[name] = store
        from opentenbase_tpu.sql.parser import Parser

        for name, text in meta.get("views", {}).items():
            c.views[name] = (Parser(text).parse_select(), text)
        if meta.get("matviews"):
            from opentenbase_tpu.matview.defs import register

            for name, mrec in meta["matviews"].items():
                register(
                    c, name, mrec["text"], mrec.get("options") or {},
                    aux_schema=mrec.get("aux_schema"),
                )
        from opentenbase_tpu.plan.partition import PartitionSpec

        for name, pclause in meta.get("partitions", {}).items():
            if c.catalog.has(name):
                tm = c.catalog.get(name)
                ps = PartitionSpec.build(
                    name, pclause, tm.schema[pclause["column"]]
                )
                c.partitions[name] = ps
                # re-share dictionaries: the snapshot restored each child
                # with its own (equal) copy, but future inserts encode
                # against the parent's
                for child in ps.children():
                    if not c.catalog.has(child):
                        continue
                    cm = c.catalog.get(child)
                    cm.dictionaries = tm.dictionaries
                    for node in cm.node_indices:
                        store = c.stores.get(node, {}).get(child)
                        if store is not None:
                            store.dictionaries = tm.dictionaries
        # in-doubt txns captured by this checkpoint become pending again;
        # map their stable row ids back to restored positions
        for gid, p in meta.get("prepared", {}).items():
            ws = []
            for wm in p["writes"]:
                store = c.stores[wm["node"]][wm["table"]]
                rid = store.scan_view().row_id()
                if wm["kind"] == "ins":
                    rid0, n = wm["row_id_start"], wm["nrows"]
                    pos = np.nonzero((rid >= rid0) & (rid < rid0 + n))[0]
                    rng = (int(pos[0]), int(pos[-1]) + 1) if len(pos) else (0, 0)
                    ws.append({**wm, "range": rng})
                else:
                    ws.append(
                        {**wm,
                         "rowids": np.asarray(wm["rowids"], dtype=np.int64)}
                    )
            self._pending[gid] = {"gxid": p["gxid"], "writes": ws}

    def _apply(self, tag: str, header: dict, arrays) -> None:
        from opentenbase_tpu.catalog.distribution import (
            DistStrategy,
            DistributionSpec,
        )
        from opentenbase_tpu.storage.column import Column
        from opentenbase_tpu.storage.table import ColumnBatch

        c = self.cluster
        if tag == "D":
            # D-records are the DDL class: advance the serving plane's
            # catalog epoch so a standby (or post-recovery session)
            # never serves a plan cached against the pre-DDL catalog
            c.bump_catalog_epoch()
            op = header["op"]
            if op == "create_table":
                if c.catalog.has(header["name"]):
                    return
                schema = {
                    k: _type_from_str(v) for k, v in header["schema"].items()
                }
                spec = DistributionSpec(
                    DistStrategy(header["strategy"]),
                    tuple(header["key_columns"]),
                    group=header.get("group"),
                )
                meta = c.catalog.create_table(header["name"], schema, spec)
                _apply_constraints_meta(meta, header.get("constraints", {}))
                # partition children share the parent's dictionaries (the
                # create_parent record replays first and registers it);
                # exact membership check — a user table merely containing
                # "$p" must keep its own dictionaries
                parent = header["name"].split("$p")[0]
                if (
                    parent != header["name"]
                    and parent in c.partitions
                    and header["name"] in c.partitions[parent].children()
                ):
                    meta.dictionaries = c.catalog.get(parent).dictionaries
                c.create_table_stores(meta)
            elif op == "drop_table":
                if c.catalog.has(header["name"]):
                    c.catalog.drop_table(header["name"])
                    c.drop_table_stores(header["name"])
            elif op == "create_foreign_table":
                if not c.catalog.has(header["name"]):
                    from opentenbase_tpu.catalog.distribution import (
                        DistributionSpec as _DS,
                        DistStrategy as _St,
                    )

                    schema = {
                        k: _type_from_str(v)
                        for k, v in header["schema"].items()
                    }
                    meta = c.catalog.create_table(
                        header["name"], schema, _DS(_St.REPLICATED)
                    )
                    meta.node_indices = meta.node_indices[:1]
                    meta.foreign = dict(header["options"])
                    meta.foreign["server"] = header["server"]
            elif op == "create_user":
                c.users[header["name"]] = header["verifier"]
            elif op == "drop_user":
                c.users.pop(header["name"], None)
            elif op == "create_index":
                if c.catalog.has(header["table"]):
                    meta = c.catalog.get(header["table"])
                    for col in header["columns"]:
                        if col in meta.schema:
                            meta.zone_cols.add(col)
            elif op == "truncate":
                if c.catalog.has(header["name"]):
                    meta = c.catalog.get(header["name"])
                    for n in meta.node_indices:
                        c.stores[n][header["name"]] = ShardStore(
                            meta.schema, meta.dictionaries
                        )
                    c.bump_table_versions({header["name"]})
            elif op == "create_view":
                from opentenbase_tpu.sql.parser import Parser

                c.views[header["name"]] = (
                    Parser(header["text"]).parse_select(), header["text"]
                )
            elif op == "drop_view":
                c.views.pop(header["name"], None)
            elif op == "create_matview":
                if header["name"] not in c.matviews:
                    if not c.catalog.has(header["name"]):
                        schema = {
                            k: _type_from_str(v)
                            for k, v in header["schema"].items()
                        }
                        spec = DistributionSpec(
                            DistStrategy(header["strategy"]),
                            tuple(header["key_columns"]),
                        )
                        m = c.catalog.create_table(
                            header["name"], schema, spec
                        )
                        c.create_table_stores(m)
                    aux = header.get("aux_schema")
                    aux_name = f"{header['name']}$aux"
                    if aux and not c.catalog.has(aux_name):
                        am = c.catalog.create_table(
                            aux_name,
                            {
                                k: _type_from_str(v)
                                for k, v in aux.items()
                            },
                            DistributionSpec(DistStrategy.ROUNDROBIN),
                        )
                        c.create_table_stores(am)
                    from opentenbase_tpu.matview.defs import register

                    register(
                        c, header["name"], header["text"],
                        header.get("options") or {},
                        aux_schema=aux,
                    )
            elif op == "drop_matview":
                c.matviews.pop(header["name"], None)
                for tb in (
                    header["name"], f"{header['name']}$aux"
                ):
                    if c.catalog.has(tb):
                        c.catalog.drop_table(tb)
                        c.drop_table_stores(tb)
            elif op == "add_column":
                if c.catalog.has(header["name"]):
                    c.alter_add_column(
                        header["name"], header["column"],
                        _type_from_str(header["type"]),
                    )
            elif op == "drop_column":
                if c.catalog.has(header["name"]):
                    c.alter_drop_column(header["name"], header["column"])
            elif op == "redistribute":
                if c.catalog.has(header["name"]):
                    c.redistribute_table(
                        header["name"],
                        DistributionSpec(
                            DistStrategy(header["strategy"]),
                            tuple(header["key_columns"]),
                        ),
                    )
            elif op == "add_partitions":
                if header["name"] in c.partitions:
                    c.extend_partitions(header["name"], header["count"])
            elif op == "seq_event":
                ev, pl = header["event"], header["payload"]
                g = c.gts
                try:
                    if ev == "seq_create":
                        g.create_sequence(
                            pl["name"], pl.get("start", 1),
                            pl.get("increment", 1), pl.get("min", 1),
                            pl.get("max", 2**62), pl.get("cycle", False),
                        )
                    elif ev == "seq_drop":
                        g.drop_sequence(pl["name"])
                    elif ev in ("seq_next", "seq_set"):
                        name = pl["name"]
                        target = pl.get("next", pl.get("value"))
                        s = g._seqs.get(name)
                        if s is not None and target is not None:
                            advances = (
                                target > s.next_value
                                if s.increment >= 0
                                else target < s.next_value
                            )
                            # explicit setval always applies; replayed
                            # reservations only move forward so redo never
                            # regresses below gts.json.seq's durable mark
                            if ev == "seq_set" or advances:
                                g.setval(name, target)
                except ValueError:
                    pass  # create-of-existing on overlap with seq store
            elif op == "create_parent":
                from opentenbase_tpu.plan.partition import PartitionSpec

                if not c.catalog.has(header["name"]):
                    schema = {
                        k: _type_from_str(v)
                        for k, v in header["schema"].items()
                    }
                    spec = DistributionSpec(
                        DistStrategy(header["strategy"]),
                        tuple(header["key_columns"]),
                    )
                    pm = c.catalog.create_table(header["name"], schema, spec)
                    _apply_constraints_meta(
                        pm, header.get("constraints", {})
                    )
                    pclause = header["partition"]
                    c.partitions[header["name"]] = PartitionSpec.build(
                        header["name"], pclause, schema[pclause["column"]]
                    )
            elif op == "drop_parent":
                c.partitions.pop(header["name"], None)
                if c.catalog.has(header["name"]):
                    c.catalog.drop_table(header["name"])
            elif op == "shardmap":
                # version-bumping install: standbys / post-recovery
                # sessions must drop plans cached against the old map
                c.shardmap.apply_replayed_map(header["map"])
            elif op == "create_node":
                from opentenbase_tpu.catalog.nodes import NodeDef, NodeRole

                if not c.nodes.has(header["name"]):
                    role = NodeRole(header["role"])
                    if role == NodeRole.DATANODE:
                        c.nodes.restore_datanode(
                            header["name"], header["mesh_index"]
                        )
                        c.stores.setdefault(header["mesh_index"], {})
                    else:
                        c.nodes.create_node(NodeDef(header["name"], role))
            elif op == "drop_node":
                if c.nodes.has(header["name"]):
                    node = c.nodes.get(header["name"])
                    mi = getattr(node, "mesh_index", -1)
                    for grp in c.nodes.all_groups():
                        if header["name"] in grp.members:
                            grp.members.remove(header["name"])
                    c.nodes.drop_node(header["name"], force=True)
                    c.stores.pop(mi, None)
                    # REMOVE NODE stripped the victim from every
                    # table's placement before dropping it — replay
                    # must agree or routing diverges after recovery
                    for tname in c.catalog.table_names():
                        tm = c.catalog.get(tname)
                        if mi in tm.node_indices:
                            tm.node_indices = [
                                n for n in tm.node_indices if n != mi
                            ]
                            tm.locator.node_indices = [
                                n for n in tm.locator.node_indices
                                if n != mi
                            ]
            elif op == "create_group":
                if not c.nodes.has_group(header["name"]):
                    members = [
                        m for m in header["members"] if c.nodes.has(m)
                    ]
                    c.nodes.create_group(
                        header["name"], members,
                        header.get("kind", "hot"),
                    )
            elif op == "drop_group":
                if c.nodes.has_group(header["name"]):
                    c.nodes.drop_group(header["name"])
            elif op in (
                "rebalance_begin", "rebalance_flip", "rebalance_done"
            ):
                from opentenbase_tpu.rebalance import journal as _rbj

                _rbj.replay(c, self, header)
            elif op == "ha_generation":
                # fencing epoch (self-healing HA): a promotion bumped
                # the timeline's generation. Monotone max — replay
                # must never regress a generation learned elsewhere.
                g = int(header.get("generation", 0))
                if g > int(getattr(c, "node_generation", 0)):
                    c.node_generation = g
            elif op == "audit_state":
                c.audit.load_state(header["payload"])
            elif op == "wlm_state":
                # resource-group DDL replays as the full config dump
                # (wlm/manager.py dump_state/load_state)
                c.wlm.load_state(header["payload"])
            elif op == "create_function":
                if header.get("language") == "plpgsql":
                    from opentenbase_tpu.plan.plpgsql import (
                        PlpgsqlFunction as _FnCls,
                    )
                else:
                    from opentenbase_tpu.plan.functions import (
                        SqlFunction as _FnCls,
                    )

                c.functions[header["name"]] = _FnCls.create(
                    header["name"],
                    [tuple(a) for a in header["args"]],
                    header["rettype"],
                    header["body"],
                )
            elif op == "drop_function":
                c.functions.pop(header["name"], None)
            elif op == "create_publication":
                c.publications[header["name"]] = {
                    "tables": header["tables"], "nodes": header["nodes"]
                }
            elif op == "drop_publication":
                c.publications.pop(header["name"], None)
            elif op == "create_subscription":
                from opentenbase_tpu.storage.logical import (
                    SubscriptionWorker,
                )

                w = SubscriptionWorker(
                    c, header["name"], header["conninfo"],
                    header["publication"],
                )
                if not header.get("copy_data", True):
                    w.synced = True
                # NOT started here: Cluster.recover launches the workers
                # after redo finishes (the logical-replication launcher)
                c.subscriptions[header["name"]] = w
            elif op == "drop_subscription":
                w = c.subscriptions.pop(header["name"], None)
                if w is not None:
                    w.stop()
            elif op == "subscription_state":
                w = c.subscriptions.get(header["name"])
                if w is not None:
                    w.lsn = max(w.lsn, header["lsn"])
                    w.synced = w.synced or header.get("synced", False)
            elif op == "dict_extend":
                tm = c.catalog.get(header["table"])
                d = tm.dictionaries[header["column"]]
                for v in header["values"]:
                    d.encode_one(v)
            return
        if tag == "G":  # one committed transaction, atomically framed
            if header.get("gid"):
                self._record_decision(
                    header["gid"], "commit", header["commit_ts"]
                )
            writes = self._materialize_writes(
                header["writes"], arrays, header["commit_ts"]
            )
            for wm in writes:
                if wm["kind"] == "del":
                    store = c.stores[wm["node"]][wm["table"]]
                    pos = np.nonzero(
                        np.isin(store.scan_view().row_id(), wm["rowids"])
                    )[0]
                    store.stamp_xmax(pos, header["commit_ts"])
            c.bump_table_versions({wm["table"] for wm in writes})
            return
        if tag == "T":  # PREPARE TRANSACTION: materialize pending writes
            from opentenbase_tpu.storage.table import PENDING_TS

            self._pending[header["gid"]] = {
                "gxid": header["gxid"],
                "writes": self._materialize_writes(
                    header["writes"], arrays, PENDING_TS
                ),
            }
            return
        if tag in ("C", "R"):  # COMMIT / ROLLBACK PREPARED
            self._record_decision(
                header["gid"],
                "commit" if tag == "C" else "abort",
                header.get("commit_ts"),
            )
            pend = self._pending.pop(header["gid"], None)
            if pend is None:
                return
            from opentenbase_tpu.storage.table import RESERVED_TS

            for wm in pend["writes"]:
                store = c.stores[wm["node"]][wm["table"]]
                if wm["kind"] == "ins":
                    s, e = wm["range"]
                    if tag == "C":
                        store.stamp_xmin(s, e, header["commit_ts"])
                    else:
                        store.truncate_range(s, e)
                else:
                    pos = np.nonzero(
                        np.isin(store.scan_view().row_id(), wm["rowids"])
                    )[0]
                    if tag == "C":
                        store.stamp_xmax(pos, header["commit_ts"])
                    else:
                        # release a checkpoint-persisted PREPARE
                        # reservation on rollback
                        res = pos[store.peek_xmax_at(pos) == RESERVED_TS]
                        if len(res):
                            store.unstamp_xmax(res)
            if tag == "C":
                c.bump_table_versions(
                    {wm["table"] for wm in pend["writes"]}
                )
            return

    def _apply_dict_delta(self, wm: dict) -> None:
        """Idempotent absolutely-positioned dictionary extend. Values
        below ``start`` are already WAL-logged ('D' records precede the
        frame in WAL order), values present locally are skipped by
        encode_one's value dedup; a GAP (local dict shorter than
        ``start``) means earlier values haven't arrived — appending now
        would assign wrong codes, so callers that can defer (DN direct
        apply) pre-check with ``dict_delta_gap``; in stream order the
        gap is unreachable."""
        from opentenbase_tpu.storage.column import Dictionary

        c = self.cluster
        if not c.catalog.has(wm["table"]):
            return
        tm = c.catalog.get(wm["table"])
        d = tm.dictionaries.setdefault(wm["column"], Dictionary())
        if len(d) < int(wm.get("start", 0)):
            return
        for v in wm["values"]:
            d.encode_one(v)

    def frame_apply_gap(self, sub: list) -> bool:
        """True when a DIRECT apply of this frame would lose or corrupt
        data because our replica is behind the coordinator's WAL: a
        touched table's DDL hasn't streamed yet (materialize would
        silently skip it while the gid gets marked applied), or a dict
        record starts above our local dictionary length (appending
        across the gap would assign wrong codes). The caller defers to
        stream delivery, which replays the missing records in order."""
        c = self.cluster
        for wm in sub:
            if not c.catalog.has(wm["table"]):
                return True
            tm = c.catalog.get(wm["table"])
            if wm.get("kind") == "dict":
                d = tm.dictionaries.get(wm["column"])
                have = 0 if d is None else len(d)
                if have < int(wm.get("start", 0)):
                    return True
            elif wm.get("kind") == "ins":
                # a column this replica hasn't streamed yet (ADD
                # COLUMN in flight): materializing from the stale
                # schema would silently drop its values
                if not set(wm.get("cols", ())) <= set(tm.schema):
                    return True
        return False

    def _materialize_writes(
        self, writes: list[dict], arrays, xmin_ts: int
    ) -> list[dict]:
        """Apply the insert sub-records of a 'G'/'T' frame (with the given
        xmin stamp) and return the write list annotated with replayed
        positions; delete sub-records pass through with their rowids."""
        from opentenbase_tpu.storage.table import ColumnBatch

        c = self.cluster
        out = []
        for i, wm in enumerate(writes):
            if wm.get("kind") == "dict":
                # dictionary delta riding the frame (shipped DML for
                # text tables): apply BEFORE the rows that use the
                # codes; positional ``i`` stays aligned because encode
                # counted this record too
                self._apply_dict_delta(wm)
                continue
            if not c.catalog.has(wm["table"]):
                continue
            tm = c.catalog.get(wm["table"])
            node = wm["node"]
            store = c.stores.setdefault(node, {}).get(wm["table"])
            if store is None:
                store = ShardStore(tm.schema, tm.dictionaries)
                c.stores[node][wm["table"]] = store
            if wm["kind"] == "ins":
                from opentenbase_tpu.storage.column import Column

                n = wm["nrows"]
                cols = {}
                for colname, ty in tm.schema.items():
                    vm = arrays.get(f"w{i}__v_{colname}")
                    cols[colname] = Column(
                        ty, arrays[f"w{i}_{colname}"], vm,
                        tm.dictionaries.get(colname),
                    )
                # delta append: replay of an ingest-heavy WAL tail (or a
                # standby's continuous redo) parks batches and folds them
                # once, instead of one capacity-doubling copy per frame
                s, e = store.append_delta(
                    ColumnBatch(cols, n), xmin_ts,
                    row_id_start=wm["row_id_start"],
                )
                # redo of a MOVE DATA insert may land on a node the table
                # didn't cover at create time
                if node not in tm.node_indices:
                    tm.node_indices.append(node)
                    tm.locator.node_indices.append(node)
                out.append({**wm, "range": (s, e)})
            else:
                out.append({**wm, "rowids": arrays[f"w{i}_del"]})
        return out
