"""MasterFilesystem: the namespace + block management core.

Parity: curvine-server/src/master/fs/master_filesystem.rs (+ fs/context.rs,
master/meta/fs_dir.rs). All mutations flow through journaled apply-ops so a
restart (or a raft follower) reaches the same state by replay.

Two durability modes, selected by the metadata store:

* ``MemMetaStore`` — namespace in RAM; restart = snapshot + journal replay
  (the reference's journal-only mode).
* ``KvMetaStore`` — namespace in a log-structured KV
  (curvine-server/src/master/meta/store/rocks_inode_store.rs parity):
  every journal entry's effects commit as one atomic KV batch tagged with
  the entry seq, so cold start opens the KV and replays only the journal
  tail past ``applied_seq`` — restart cost is O(tail), not O(namespace),
  and the namespace can exceed RAM."""

from __future__ import annotations

import logging

from curvine_tpu.common import errors as err
from curvine_tpu.common.journal import Journal
from curvine_tpu.common.types import (
    CommitBlock, ExtendedBlock, FileBlocks, FileStatus, FileType, LocatedBlock,
    MasterInfo, SetAttrOpts, StoragePolicy, StorageState, StorageType,
    TtlAction, WorkerInfo, WorkerState, now_ms,
)
from curvine_tpu.master.block_map import BlockMap
from curvine_tpu.master.inode import Inode, InodeTree, ROOT_ID
from curvine_tpu.master.placement import PlacementPolicy, create_policy
from curvine_tpu.master.store import KvMetaStore, MemMetaStore
from curvine_tpu.master.worker_map import WorkerMap

log = logging.getLogger(__name__)

# default storage policy in wire form, hoisted off the create hot path
# (copied per entry — journal args must never share mutable state)
_DEFAULT_POLICY_WIRE = StoragePolicy().to_wire()


class MasterFilesystem:
    def __init__(self, journal: Journal | None = None,
                 placement: str | PlacementPolicy = "local",
                 lost_timeout_ms: int = 30_000,
                 snapshot_interval: int = 100_000,
                 store: MemMetaStore | KvMetaStore | None = None,
                 id_stride: int = 1, id_offset: int = 0,
                 ici_mesh_shape: list[int] | None = None):
        self.store = store if store is not None else MemMetaStore()
        self.tree = InodeTree(self.store, id_stride=id_stride,
                              id_offset=id_offset)
        self.blocks = BlockMap(self.store)
        self.workers = WorkerMap(lost_timeout_ms=lost_timeout_ms)
        self.journal = journal
        self.snapshot_interval = snapshot_interval
        self._entries_since_snapshot = 0
        if isinstance(placement, str):
            placement = create_policy(placement,
                                      mesh_shape=ici_mesh_shape or None)
        self.policy = placement
        # worker_id -> block ids scheduled for deletion (drained by heartbeat)
        self.pending_deletes: dict[int, set[int]] = {}
        self.mounts = None          # set by MountManager
        # inode ids of files open for writing (is_complete=False):
        # lease recovery iterates THIS, not the whole namespace. None
        # until first use after a restart (built by one lazy scan, then
        # maintained incrementally by the journaled applies).
        self.open_files: set[int] | None = None
        self.on_worker_lost = None  # hook: ReplicationManager
        self.on_mutation = None     # hook: RaftLite journal replication
        # active raft membership config, set by journaled raft_conf
        # entries (master/ha.py) and carried through snapshots so a
        # fresh/restarted replica adopts the journaled config, not its
        # possibly-stale boot peers
        self.raft_conf: dict | None = None
        self.acl = None             # set by AclEnforcer (permission checks)
        # runtime mirrors of the durable EC stripe map (store.iter_ec):
        # logical block id -> stripe wire, and the reverse cell index
        # cell block id -> (logical block id, cell index). Kept hot so
        # the get_block_locations path never pays a KV read per block.
        self.ec_stripes: dict[int, dict] = {}
        self.ec_cells: dict[int, tuple[int, int]] = {}
        # GroupCommitter (common/journal.py), installed by MasterServer:
        # when present, _log journals unflushed + stages KV writes; the
        # RPC handler awaits committer.sync() before replying.
        self.committer = None
        self._walk_hint = None          # leader-local walk pass-through
        self.start_ms = now_ms()

    @property
    def _kv(self) -> bool:
        return self.store.kind == "kv"

    # ==================== journal plumbing ====================

    def _rebuild_ec_index(self) -> None:
        self.ec_stripes = {}
        self.ec_cells = {}
        for bid, stripe in self.store.iter_ec():
            self._ec_index(bid, stripe)

    def _ec_index(self, block_id: int, stripe: dict) -> None:
        old = self.ec_stripes.get(block_id)
        if old is not None:
            for cid in old.get("cells", []):
                self.ec_cells.pop(cid, None)
        self.ec_stripes[block_id] = stripe
        for idx, cid in enumerate(stripe.get("cells", [])):
            self.ec_cells[cid] = (block_id, idx)

    def recover(self) -> None:
        if self.journal is None:
            self._rebuild_ec_index()
            return
        snap, entries = self.journal.recover()
        if self._kv:
            applied = self.store.get_counter("applied_seq", 0)
            snap_seq = getattr(self.journal, "last_snapshot_seq", 0)
            if snap is not None and applied < snap_seq:
                # KV is behind the newest snapshot (migration from mem mode
                # or an HA snapshot install mid-crash): load it wholesale.
                self._load_snapshot(snap)
                applied = snap_seq
                self.store.commit_applied(applied)
            replayed = 0
            tail_seq = applied
            for seq, op, args, _term in entries:
                if seq <= applied:
                    continue
                try:
                    self._apply(op, args)
                    self.store.stage_entry()
                except err.CurvineError as e:
                    self.store.rollback()
                    log.warning("journal replay: %s(%s) -> %s", op, args, e)
                tail_seq = seq
                replayed += 1
                # batched replay: one KV write_batch per ~4096 entries
                # makes restart cost track the group-commit write path
                if replayed % 4096 == 0:
                    self.store.commit_applied(tail_seq)
            if tail_seq > applied:
                self.store.commit_applied(tail_seq)
            self.journal.seq = max(self.journal.seq, applied)
            log.info("kv recovery: %d inodes, %d blocks, applied_seq=%d, "
                     "replayed %d tail entries",
                     self.tree.count(), self.blocks.count(),
                     self.store.get_counter("applied_seq"), replayed)
            self._rebuild_ec_index()
            return
        if snap is not None:
            self._load_snapshot(snap)
        for _seq, op, args, _term in entries:
            try:
                self._apply(op, args)
            except err.CurvineError as e:
                log.warning("journal replay: %s(%s) -> %s", op, args, e)
        if snap is not None or entries:
            log.info("recovered namespace: %d inodes, %d blocks, seq=%d",
                     self.tree.count(), self.blocks.count(), self.journal.seq)
        self._rebuild_ec_index()

    audit_log = False   # set from MasterConf.audit_log

    def _log(self, op: str, args: dict):
        # WAL discipline: journal BEFORE apply, so an append failure (disk
        # full) never leaves in-memory state ahead of the durable log.
        # Mutations are validated before journaling; if an apply still
        # fails, on_mutation fires anyway so follower seqs stay contiguous
        # (followers fail the same deterministic way and skip the entry).
        #
        # Group commit: with a committer installed, the journal write is
        # buffered (flush=False) and the entry's KV effects are STAGED,
        # not committed — the committer later syncs the journal and lands
        # the whole group as one KV batch. Durability therefore moves to
        # committer.sync(), which the RPC handler awaits before replying;
        # validate→journal→apply is one synchronous stretch on the actor
        # loop, so applied state is visible to later ops immediately.
        grouped = self.committer is not None and self.committer.accepting
        seq = None
        if self.journal is not None:
            seq = self.journal.append(op, args, flush=not grouped)
        try:
            result = self._apply(op, args)
        except BaseException:
            if self._kv:
                self.store.rollback()
                if seq is not None and not grouped:
                    self.store.commit_applied(seq)
            if grouped:
                self.committer.note()
            if seq is not None and self.on_mutation is not None:
                self.on_mutation(seq, op, args, self.journal.last_term)
            raise
        if self._kv:
            if grouped:
                self.store.stage_entry()
            else:
                self.store.commit_applied(
                    seq if seq is not None
                    else self.store.get_counter("applied_seq", 0))
        if grouped:
            self.committer.note()
        if self.audit_log:
            from curvine_tpu.common.logging import audit
            audit.log(op, str(args.get("path", args.get("src", ""))))
        if seq is not None:
            if self.on_mutation is not None:
                self.on_mutation(seq, op, args, self.journal.last_term)
            self._entries_since_snapshot += 1
            if self._entries_since_snapshot >= self.snapshot_interval:
                self.checkpoint()
        return result

    def apply_replicated(self, seq: int, op: str, args: dict,
                         term: int) -> None:
        self.apply_replicated_batch([(seq, op, args, term)])

    def apply_replicated_batch(
            self, entries: list[tuple[int, str, dict, int]]) -> None:
        """Follower-side apply of a leader-streamed batch: journal the
        WHOLE batch with ONE flush (WAL), then apply in order, then land
        the group's KV effects as one atomic batch under the tail seq —
        the follower-side half of group commit. Per-entry failures are
        deterministic (the leader failed identically): roll back that
        entry's pending writes, keep the rest of the batch. CancelledError
        propagates — a cancelled handler must NOT mark entries applied
        (the journal has the batch; restart replays it)."""
        assert self.journal is not None
        if not entries:
            return
        self.journal.append_batch([(op, args, term)
                                   for _seq, op, args, term in entries])
        try:
            for _seq, op, args, _term in entries:
                try:
                    self._apply(op, args)
                    if self._kv:
                        self.store.stage_entry()
                except Exception as e:
                    if self._kv:
                        self.store.rollback()
                    lvl = (log.warning if isinstance(e, err.CurvineError)
                           else log.error)
                    lvl("follower apply %s failed: %s", op, e)
        except BaseException:
            if self._kv:
                self.store.rollback_group()
            raise
        if self._kv:
            self.store.commit_applied(entries[-1][0])

    def install_snapshot(self, state: dict, seq: int, last_term: int) -> None:
        """Replace the whole state machine (HA catch-up / divergence heal)."""
        self._load_snapshot(state)
        if self._kv:
            self.store.commit_applied(seq)
        if self.journal is not None:
            # stale on-disk entries (possibly from a divergent history)
            # must not survive to be replayed after a restart
            self.journal.reset_log()
            self.journal.seq = seq
            self.journal.last_term = last_term
            self.journal.note_term(seq, last_term)
            self.journal.write_snapshot(state)

    def flush_group(self) -> None:
        """Commit any open journal group inline. Snapshot scans, restarts
        and direct-KV reads must not observe staged-but-unflushed state."""
        if self.committer is not None:
            self.committer.flush_sync()

    def checkpoint(self) -> None:
        if self.journal is None:
            return
        self.flush_group()
        if self._kv:
            # KV mode: the store IS the checkpoint. Flush the memtable and
            # drop journal segments fully covered by applied_seq — no full
            # snapshot write, so checkpoint cost is O(memtable) not O(ns).
            self.store.flush()
            self.journal.gc_covered(self.store.get_counter("applied_seq", 0))
        else:
            self.journal.write_snapshot(self._snapshot_state())
        self._entries_since_snapshot = 0

    def _snapshot_state(self) -> dict:
        """Full-state dump (HA snapshot transfer / mem-mode checkpoints)."""
        self.flush_group()
        ch_map: dict[int, dict[str, int]] = {}
        for pid, name, cid in self.store.iter_children_all():
            ch_map.setdefault(pid, {})[name] = cid
        inodes = []
        for node in self.store.iter_inodes():
            inodes.append({
                "id": node.id, "name": node.name, "ft": int(node.file_type),
                "pid": node.parent_id, "mtime": node.mtime, "atime": node.atime,
                "owner": node.owner, "group": node.group, "mode": node.mode,
                "xattr": node.x_attr, "sp": node.storage_policy.to_wire(),
                "nlink": node.nlink, "len": node.len, "bs": node.block_size,
                "rep": node.replicas, "blocks": node.blocks,
                "done": node.is_complete, "target": node.target,
                "dir": node.is_dir,
                # explicit directory entries: a hard-linked inode has a
                # second (parent, name) pair that (pid, name) alone cannot
                # represent — children must be serialized, not derived.
                "ch": ch_map.get(node.id, {}) if node.is_dir else None,
            })
        blocks = [(bid, length, iid, rep)
                  for bid, (length, iid, rep) in self.store.iter_blocks()]
        state = {"next_id": self.store.get_counter("next_id", ROOT_ID + 1),
                 "next_block_id": self.store.get_counter("next_block_id", 1),
                 "inodes": inodes, "blocks": blocks,
                 "jobs": list(self.store.iter_jobs()),
                 "ec": [[bid, stripe] for bid, stripe in self.store.iter_ec()],
                 "deco": sorted(self.workers.deco_ids)}
        if self.mounts is not None:
            state["mounts"] = self.mounts.snapshot_state()
        if self.raft_conf is not None:
            state["raft_conf"] = self.raft_conf
        return state

    def _load_snapshot(self, snap: dict) -> None:
        self.store.clear()
        self.open_files = None       # rebuilt lazily from the new state
        have_entries = any(d.get("ch") is not None for d in snap["inodes"])
        for d in snap["inodes"]:
            is_dir = d["dir"]
            ch = d.get("ch") if have_entries else None
            node = Inode(
                id=d["id"], name=d["name"], file_type=FileType(d["ft"]),
                parent_id=d["pid"], mtime=d["mtime"], atime=d["atime"],
                owner=d["owner"], group=d["group"], mode=d["mode"],
                x_attr=d["xattr"] or {},
                storage_policy=StoragePolicy.from_wire(d["sp"]),
                nlink=d["nlink"], len=d["len"], block_size=d["bs"],
                replicas=d["rep"], blocks=list(d["blocks"]),
                is_complete=d["done"], target=d.get("target"),
                children_num=len(ch) if ch is not None else 0)
            self.store.put(node, new=True)
            if ch is not None:
                for name, cid in ch.items():
                    self.store.child_put(node.id, str(name), cid)
        if not have_entries:
            # legacy snapshot: derive children from (parent_id, name)
            counts: dict[int, int] = {}
            for d in snap["inodes"]:
                if d["pid"]:
                    self.store.child_put(d["pid"], d["name"], d["id"])
                    counts[d["pid"]] = counts.get(d["pid"], 0) + 1
            for pid, n in counts.items():
                parent = self.store.get(pid)
                if parent is not None:
                    parent.children_num = n
                    self.store.put(parent)
        self.store.set_counter("next_id", snap["next_id"])
        self.store.set_counter("next_block_id", snap["next_block_id"])
        for bid, blen, iid, rep in snap["blocks"]:
            self.store.block_put(bid, blen, iid, rep)
        for wire in snap.get("jobs", []):
            self.store.job_put(wire["job_id"], wire)
        for bid, stripe in snap.get("ec", []):
            self.store.ec_put(bid, stripe)
        self._rebuild_ec_index()
        self.workers.deco_ids = set(snap.get("deco", []))
        for wid in self.workers.deco_ids:
            self.store.deco_put(wid)
        if self.mounts is not None and "mounts" in snap:
            self.mounts.load_snapshot_state(snap["mounts"])
        if snap.get("raft_conf") is not None:
            self.raft_conf = snap["raft_conf"]

    def _apply(self, op: str, args: dict):
        fn = getattr(self, f"_apply_{op}", None)
        if fn is None:
            raise err.InvalidArgument(f"unknown journal op {op!r}")
        return fn(**args)

    def _apply_noop(self) -> None:
        """Term-opening no-op (raft leader turnover)."""

    def _apply_raft_conf(self, ver: int = 0, voters: dict | None = None,
                         learners: dict | None = None,
                         action: str | None = None,
                         target: int | None = None) -> None:
        """Raft membership config entry (master/ha.py): the state
        machine only RECORDS the active config (so snapshots and replay
        carry it); RaftLite adopts it via on_mutation / _h_append /
        raft.start()."""
        self.raft_conf = {"ver": ver, "voters": dict(voters or {}),
                          "learners": dict(learners or {}),
                          "action": action, "target": target}

    def decommission_worker(self, worker_id: int, on: bool = True) -> None:
        """Journaled decommission intent: survives restarts/failovers
        (workers re-register from heartbeats, so intents can't live only
        in the runtime worker map). Recommission is allowed for ABSENT
        workers too — a durable intent for a long-gone worker must be
        clearable. Parity: curvine-cli node --add/remove-decommission."""
        if on:
            self.workers.get(worker_id)      # raises WorkerNotFound
        self._log("worker_deco", dict(worker_id=worker_id, on=on))

    def _apply_worker_deco(self, worker_id: int, on: bool) -> None:
        if on:
            self.store.deco_put(worker_id)
            self.workers.decommission(worker_id)
        else:
            self.store.deco_remove(worker_id)
            self.workers.recommission(worker_id)

    def _apply_job_put(self, job: dict) -> None:
        """Durable job record (resume after restart/failover)."""
        self.store.job_put(job["job_id"], job)

    def _apply_job_del(self, job_id: str) -> None:
        self.store.job_remove(job_id)

    # ==================== erasure-coded stripes ====================
    # A striped logical block keeps its durable block record (length,
    # inode linkage) but its bytes live in k+m CELL blocks, each a
    # first-class block with its own checksum and replica location.
    # Protocol: ec_plan durably allocates + registers the cell ids
    # BEFORE any cell byte is written (a cell arriving in a worker
    # block report must never look like an orphan and get GC'd), then
    # the converting worker writes all cells and sends EC_COMMIT_STRIPE,
    # which journals ec_put (state "committed") — the read path switches
    # to the stripe and the 3x replicas retire copy-first-delete-last.

    def ec_plan(self, block_id: int, profile: str, k: int, m: int,
                cell_size: int) -> list[int]:
        durable = self.store.block_get(block_id)
        if durable is None:
            raise err.InvalidArgument(f"ec_plan: unknown block {block_id}")
        stripe = self.ec_stripes.get(block_id)
        if stripe is not None and stripe.get("state") == "committed":
            raise err.InvalidArgument(
                f"ec_plan: block {block_id} already striped")
        return self._log("ec_plan", dict(
            block_id=block_id, profile=profile, n_cells=k + m,
            cell_size=cell_size))

    def _apply_ec_plan(self, block_id: int, profile: str, n_cells: int,
                       cell_size: int) -> list[int]:
        durable = self.store.block_get(block_id)
        if durable is None:
            raise err.InvalidArgument(f"ec_plan: unknown block {block_id}")
        blen, inode_id, _rep = durable
        # re-plan (job retry after a crash): free the previous attempt's
        # cells so abandoned ids never leak in the durable block table
        old = self.ec_stripes.get(block_id)
        if old is not None and old.get("state") != "committed":
            for cid in old.get("cells", []):
                meta = self.blocks.remove_block(cid)
                if meta:
                    for wid in meta.locs:
                        self.pending_deletes.setdefault(wid, set()).add(cid)
        cells = [self.tree.alloc_block_id() for _ in range(n_cells)]
        for cid in cells:
            self.store.block_put(cid, cell_size, inode_id, 1)
        stripe = {"profile": profile, "cell_size": cell_size,
                  "block_len": blen, "cells": cells, "state": "planned"}
        self.store.ec_put(block_id, stripe)
        self._ec_index(block_id, stripe)
        return cells

    def ec_commit(self, block_id: int,
                  cell_locs: list[list[int]]) -> None:
        """EC_COMMIT_STRIPE: all cells written. cell_locs is
        [[cell_id, worker_id, storage_type], ...]."""
        stripe = self.ec_stripes.get(block_id)
        if stripe is None:
            raise err.InvalidArgument(
                f"ec_commit: no planned stripe for block {block_id}")
        known = set(stripe.get("cells", []))
        for cid, _wid, _st in cell_locs:
            if cid not in known:
                raise err.InvalidArgument(
                    f"ec_commit: cell {cid} not in stripe {block_id}")
        if stripe.get("state") != "committed":
            self._log("ec_put", dict(block_id=block_id))
        # replica locations are runtime state (rebuilt by reports)
        for cid, wid, st in cell_locs:
            self.blocks.add_replica(cid, wid, StorageType(st))
        self.retire_stripe_replicas(block_id)

    def _apply_ec_put(self, block_id: int) -> None:
        stripe = self.store.ec_get(block_id)
        if stripe is None:
            raise err.InvalidArgument(
                f"ec_put: no planned stripe for block {block_id}")
        stripe = dict(stripe)
        stripe["state"] = "committed"
        self.store.ec_put(block_id, stripe)
        self._ec_index(block_id, stripe)

    def retire_stripe_replicas(self, block_id: int) -> None:
        """Copy-first-delete-last: drop the replicated copies of a
        committed stripe. Runtime-only (worker deletes ride heartbeat
        pending_deletes); the replication scan re-runs this until the
        locations converge to empty, so a crash between ec_put and the
        deletes cannot strand live replicas."""
        meta = self.blocks.get(block_id)
        if meta is None:
            return
        for wid in list(meta.locs):
            self.blocks.remove_replica(block_id, wid)
            self.pending_deletes.setdefault(wid, set()).add(block_id)

    # ==================== namespace ops ====================

    def mkdir(self, path: str, create_parent: bool = True, mode: int = 0o755,
              owner: str = "root", group: str = "root",
              x_attr: dict | None = None) -> FileStatus:
        self._mount_write_guard(path)
        node = self.tree.resolve(path)
        if node is not None:
            if node.is_dir:
                return node.to_status(path)
            raise err.FileAlreadyExists(f"{path} exists and is a file")
        self.tree.check_parent_dirs(path)
        parent, _ = self.tree.resolve_parent(path)
        if parent is None and not create_parent:
            raise err.FileNotFound(f"parent of {path} not found")
        return self._log("mkdir", dict(path=path, create_parent=create_parent,
                                       mode=mode, owner=owner, group=group,
                                       x_attr=x_attr or {}))

    def _apply_mkdir(self, path: str, create_parent: bool, mode: int,
                     owner: str, group: str, x_attr: dict) -> FileStatus:
        node, _ = self.tree.mkdirs(path, mode=mode, owner=owner, group=group,
                                   create_parent=create_parent, x_attr=x_attr)
        return node.to_status(path)

    def create_file(self, path: str, overwrite: bool = False,
                    create_parent: bool = True, replicas: int = 1,
                    block_size: int = 64 * 1024 * 1024, mode: int = 0o644,
                    owner: str = "root", group: str = "root",
                    client_name: str = "", x_attr: dict | None = None,
                    storage_policy: dict | None = None,
                    file_type: int = int(FileType.FILE),
                    walked: tuple | None = None) -> FileStatus:
        # cache-warming loads mark themselves with the ufs_mtime they
        # observed; those creates are allowed on read-only mounts
        caching = bool((storage_policy or {}).get("ufs_mtime"))
        self._mount_write_guard(path, caching=caching)
        # one walk replaces resolve + check_parent_dirs + resolve_parent;
        # the RPC layer passes its acl/quota walk through (same
        # synchronous actor-loop stretch, so the tree cannot change
        # between the two)
        parent, _name, existing = walked or self.tree.walk_parent(path)
        if existing is not None:
            if existing.is_dir:
                raise err.IsADirectory(path)
            if not overwrite:
                raise err.FileAlreadyExists(path)
        if parent is None and not create_parent:
            raise err.FileNotFound(f"parent of {path} not found")
        # leader fast path: hand the validated walk to _apply_create
        # (nothing runs between here and the apply — same synchronous
        # stretch). NOT journaled: replay and followers re-walk.
        self._walk_hint = (parent, _name, existing)
        try:
            return self._log("create", dict(
                path=path, overwrite=overwrite, create_parent=create_parent,
                replicas=replicas, block_size=block_size, mode=mode,
                owner=owner, group=group, client_name=client_name,
                x_attr=x_attr or {},
                storage_policy=storage_policy or dict(_DEFAULT_POLICY_WIRE),
                file_type=file_type))
        finally:
            self._walk_hint = None

    def _apply_create(self, path: str, overwrite: bool, create_parent: bool,
                      replicas: int, block_size: int, mode: int, owner: str,
                      group: str, client_name: str, x_attr: dict,
                      storage_policy: dict, file_type: int) -> FileStatus:
        hint, self._walk_hint = self._walk_hint, None
        parent, name, existing = hint if hint is not None \
            else self.tree.walk_parent(path)
        if existing is not None:
            self._delete_inode(existing, recursive=False, parent=parent,
                               name=name)
        if parent is None:
            parent, _ = self.tree.mkdirs("/".join(path.split("/")[:-1]) or "/")
        if not parent.is_dir:
            raise err.NotADirectory(self.tree.path_of(parent))
        ts = now_ms()
        # the wire->object parse is hot; the overwhelmingly common case
        # is the default policy, which the default ctor builds cheaper
        sp = StoragePolicy() if storage_policy == _DEFAULT_POLICY_WIRE \
            else StoragePolicy.from_wire(storage_policy)
        node = Inode(id=self.tree._alloc_id(), name=name,
                     file_type=FileType(file_type), parent_id=parent.id,
                     mtime=ts, atime=ts, owner=owner, group=group,
                     mode=mode, x_attr=dict(x_attr), storage_policy=sp,
                     replicas=replicas, block_size=block_size,
                     is_complete=False, client_name=client_name)
        self.tree.add_child(parent, node)
        if self.open_files is not None:
            self.open_files.add(node.id)
        return node.to_status(path)

    def append_file(self, path: str, client_name: str = "") -> FileBlocks:
        self._mount_write_guard(path)
        node = self._file_or_raise(path)
        if not node.is_complete:
            raise err.LeaseConflict(f"{path} is being written")
        self._log("set_incomplete", dict(inode_id=node.id,
                                         client_name=client_name))
        return self._file_blocks(self.tree.get(node.id), path)

    def _apply_set_incomplete(self, inode_id: int, client_name: str) -> None:
        node = self._inode_or_raise(inode_id)
        node.is_complete = False
        node.client_name = client_name
        self.tree.save(node)
        if self.open_files is not None:
            self.open_files.add(node.id)

    def exists(self, path: str) -> bool:
        return self.tree.resolve(path) is not None

    def file_status(self, path: str) -> FileStatus:
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        return node.to_status(path)

    def list_status(self, path: str) -> list[FileStatus]:
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        if not node.is_dir:
            return [node.to_status(path)]
        base = path.rstrip("/")
        return [child.to_status(f"{base}/{name}")
                for name, child in self.tree.children(node)]

    def rename(self, src: str, dst: str) -> bool:
        self._mount_write_guard(src, subtree=True)
        self._mount_write_guard(dst)
        s = self.tree.resolve(src)
        if s is None:
            raise err.FileNotFound(src)
        if src == "/" or dst.startswith(src.rstrip("/") + "/"):
            raise err.InvalidArgument(f"cannot rename {src} into itself")
        d = self.tree.resolve(dst)
        if d is not None:
            if d.is_dir and d.children_num:
                raise err.DirNotEmpty(dst)
            if d.is_dir != s.is_dir:
                raise (err.IsADirectory if d.is_dir else err.NotADirectory)(dst)
        self.tree.check_parent_dirs(dst)
        return self._log("rename", dict(src=src, dst=dst))

    def _apply_rename(self, src: str, dst: str) -> bool:
        s = self.tree.resolve(src)
        if s is None:
            raise err.FileNotFound(src)
        d = self.tree.resolve(dst)
        if d is not None:
            p, n = self.tree.resolve_parent(dst)
            self._delete_inode(d, recursive=False, parent=p, name=n)
        new_parent, new_name = self.tree.resolve_parent(dst)
        if new_parent is None or not new_parent.is_dir:
            raise err.FileNotFound(f"parent of {dst} not found")
        # move the directory ENTRY (src path tail, which for a hard link
        # can differ from s.name): remove old entry, add new, no nlink churn
        old_parent, old_name = self.tree.resolve_parent(src)
        self.store.child_remove(old_parent.id, old_name)
        old_parent.children_num = max(0, old_parent.children_num - 1)
        old_parent.mtime = now_ms()
        self.tree.save(old_parent)
        s.name = new_name
        # refresh: old_parent save may be the same object as new_parent
        new_parent = self.tree.get(new_parent.id)
        s.parent_id = new_parent.id
        self.tree.save(s)
        self.store.child_put(new_parent.id, new_name, s.id)
        new_parent.children_num += 1
        new_parent.mtime = now_ms()
        self.tree.save(new_parent)
        return True

    def delete(self, path: str, recursive: bool = False,
               system: bool = False) -> None:
        # system=True: master-internal reclaim (TTL actions) bypasses the
        # read-only-mount guard — the mount's own policy initiated it
        if not system:
            self._mount_write_guard(path, subtree=recursive)
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        if node.is_dir and node.children_num and not recursive:
            raise err.DirNotEmpty(path)
        if node.id == ROOT_ID:
            raise err.InvalidArgument("cannot delete root")
        self._log("delete", dict(path=path, recursive=recursive))

    def _apply_delete(self, path: str, recursive: bool) -> None:
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        parent, name = self.tree.resolve_parent(path)
        self._delete_inode(node, recursive, parent=parent, name=name)

    def _delete_inode(self, node: Inode, recursive: bool,
                      parent: Inode | None = None,
                      name: str | None = None) -> None:
        """`name` is the directory-entry name being removed — it can
        differ from node.name when the inode has hard links."""
        if node.is_dir and node.children_num:
            if not recursive:
                raise err.DirNotEmpty(self.tree.path_of(node))
            for child_name, child in self.tree.children(node):
                self._delete_inode(child, recursive=True,
                                   parent=node, name=child_name)
        if parent is None:
            parent = self.tree.get(node.parent_id)
        if parent is not None:
            removed = self.tree.remove_child(parent, name or node.name)
            if removed is not None and removed.nlink <= 0:
                self._free_blocks(removed)
                if self.open_files is not None:
                    self.open_files.discard(removed.id)

    def _free_blocks(self, node: Inode) -> None:
        """Drops the node's blocks. Does NOT save the inode: callers on
        the delete path have already removed it from the store (saving
        would resurrect it as an orphan); the free path saves explicitly."""
        for bid in node.blocks:
            stripe = self.ec_stripes.pop(bid, None)
            if stripe is not None:
                # striped block: free its cells too
                for cid in stripe.get("cells", []):
                    self.ec_cells.pop(cid, None)
                    cmeta = self.blocks.remove_block(cid)
                    if cmeta:
                        for wid in cmeta.locs:
                            self.pending_deletes.setdefault(
                                wid, set()).add(cid)
                self.store.ec_remove(bid)
            meta = self.blocks.remove_block(bid)
            if meta:
                for wid in meta.locs:
                    self.pending_deletes.setdefault(wid, set()).add(bid)
        node.blocks = []

    def free(self, path: str, recursive: bool = False) -> int:
        """Drop cached blocks but keep metadata (data remains in UFS)."""
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        return self._log("free", dict(path=path, recursive=recursive))

    def _apply_free(self, path: str, recursive: bool) -> int:
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        return self._free_inode(node, recursive)

    def _free_inode(self, node: Inode, recursive: bool) -> int:
        n = 0
        if node.is_dir:
            if not recursive:
                return 0
            for _name, child in self.tree.children(node):
                n += self._free_inode(child, recursive)
            return n
        if node.blocks:
            self._free_blocks(node)
            node.storage_policy.state = StorageState.UFS
            self.tree.save(node)
            n += 1
        return n

    def set_attr(self, path: str, opts: SetAttrOpts) -> None:
        self._mount_write_guard(path)
        if self.tree.resolve(path) is None:
            raise err.FileNotFound(path)
        if opts.ec:
            from curvine_tpu.common.ec import ECProfile
            ECProfile.parse(opts.ec)       # validate before journaling
        self._log("set_attr", dict(path=path, opts=opts.to_wire()))

    def _apply_set_attr(self, path: str, opts: dict) -> None:
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        o = SetAttrOpts.from_wire(opts)
        if o.replicas is not None:
            node.replicas = o.replicas
        if o.owner is not None:
            node.owner = o.owner
        if o.group is not None:
            node.group = o.group
        if o.mode is not None:
            node.mode = o.mode
        if o.ttl_ms is not None:
            node.storage_policy.ttl_ms = o.ttl_ms
        if o.ttl_action is not None:
            node.storage_policy.ttl_action = TtlAction(o.ttl_action)
        if o.atime is not None:
            node.atime = o.atime
        if o.mtime is not None:
            node.mtime = o.mtime
        if o.ec is not None:
            node.storage_policy.ec = o.ec
        node.x_attr.update(o.add_x_attr)
        for k in o.remove_x_attr:
            node.x_attr.pop(k, None)
        self.tree.save(node)

    def symlink(self, target: str, link: str) -> FileStatus:
        self._mount_write_guard(link)
        if self.tree.resolve(link) is not None:
            raise err.FileAlreadyExists(link)
        parent, _ = self.tree.resolve_parent(link)
        if parent is None or not parent.is_dir:
            raise err.FileNotFound(f"parent of {link} not found")
        return self._log("symlink", dict(target=target, link=link))

    def _apply_symlink(self, target: str, link: str) -> FileStatus:
        parent, name = self.tree.resolve_parent(link)
        if parent is None or not parent.is_dir:
            raise err.FileNotFound(f"parent of {link} not found")
        node = Inode(id=self.tree._alloc_id(), name=name,
                     file_type=FileType.LINK, parent_id=parent.id,
                     mtime=now_ms(), atime=now_ms(), target=target)
        self.tree.add_child(parent, node)
        return node.to_status(link)

    def link(self, src: str, dst: str) -> FileStatus:
        self._mount_write_guard(dst)
        self._file_or_raise(src)
        if self.tree.resolve(dst) is not None:
            raise err.FileAlreadyExists(dst)
        parent, _ = self.tree.resolve_parent(dst)
        if parent is None or not parent.is_dir:
            raise err.FileNotFound(f"parent of {dst} not found")
        return self._log("link", dict(src=src, dst=dst))

    def _apply_link(self, src: str, dst: str) -> FileStatus:
        node = self._file_or_raise(src)
        parent, name = self.tree.resolve_parent(dst)
        if parent is None or not parent.is_dir:
            raise err.FileNotFound(f"parent of {dst} not found")
        self.tree.add_entry(parent, name, node)
        return node.to_status(dst)

    # ============ cross-shard two-phase ops (master/sharding.py) ============
    # Presumed-abort 2PC for renames/links whose src and dst hash to
    # different namespace shards. Each participant journals its vote
    # (tx_prepare) and keeps a durable tx record until the coordinator
    # tells it to commit/abort; the dst side RETAINS its record in state
    # "committed" until the final forget, so a recovery sweep that finds
    # any committed record knows the tx passed the commit point. All
    # methods run on the shard's single-writer actor loop.

    def tx_prepare(self, txid: str, op: str, src: str, dst: str,
                   role: str, rec: dict | None = None) -> dict:
        from curvine_tpu.master.store import _enc_inode
        if op not in ("rename", "link"):
            raise err.InvalidArgument(f"unknown shard tx op {op!r}")
        if role == "src":
            node = self.tree.resolve(src)
            if node is None:
                raise err.FileNotFound(src)
            if node.is_dir:
                raise err.IsADirectory(src)
            if op == "rename":
                self._mount_write_guard(src)
                if node.nlink > 1:
                    raise err.Unsupported(
                        "cross-shard rename of a hard-linked file")
                if not node.is_complete:
                    raise err.InvalidArgument(
                        f"cross-shard rename of open file {src}")
            blocks, locs = [], []
            for bid in node.blocks:
                meta = self.blocks.get(bid)
                if meta is None:
                    blocks.append([bid, 0, 1])
                    continue
                blocks.append([bid, meta.len, meta.replicas])
                for wid, loc in meta.locs.items():
                    locs.append([bid, wid, int(loc.storage_type)])
            rec = {"txid": txid, "role": "src", "op": op, "src": src,
                   "dst": dst, "inode": _enc_inode(node), "blocks": blocks,
                   "locs": locs, "state": "prepared"}
        else:
            if rec is None:
                raise err.InvalidArgument("dst prepare without src payload")
            self._mount_write_guard(dst)
            d = self.tree.resolve(dst)
            if d is not None:
                if op == "link":
                    raise err.FileAlreadyExists(dst)
                if d.is_dir and d.children_num:
                    raise err.DirNotEmpty(dst)
                if d.is_dir:
                    raise err.IsADirectory(dst)
            self.tree.check_parent_dirs(dst)
            rec = dict(rec)
            rec["role"] = "dst"
        self._log("tx_prepare", dict(rec=rec))
        return rec

    def _apply_tx_prepare(self, rec: dict) -> None:
        self.store.tx_put(rec["txid"], rec)

    def tx_commit(self, txid: str) -> None:
        # idempotent: a retried/replayed commit for a forgotten tx no-ops
        if self.store.tx_get(txid) is None:
            return
        self._log("tx_commit", dict(txid=txid))

    def _apply_tx_commit(self, txid: str) -> None:
        rec = self.store.tx_get(txid)
        if rec is None:
            return
        if rec["role"] == "src":
            self._tx_commit_src(rec)
            self.store.tx_remove(txid)
            return
        self._tx_commit_dst(rec)
        # dst keeps the record ("committed") until the coordinator's
        # forget — it is the durable marker that the tx passed the
        # commit point, consulted by the crash-recovery sweep
        rec = dict(rec)
        rec["state"] = "committed"
        self.store.tx_put(txid, rec)

    def _tx_commit_src(self, rec: dict) -> None:
        node = self.tree.resolve(rec["src"])
        if node is None:
            return                     # replay after the entry moved
        if rec["op"] == "link":
            # the dst shard now holds a mirrored entry referencing the
            # same blocks: count it here so a later delete of this copy
            # never frees blocks the mirror still reads
            node.nlink += 1
            self.tree.save(node)
            return
        parent, name = self.tree.resolve_parent(rec["src"])
        if parent is None:
            return
        removed = self.tree.remove_child(parent, name)
        if removed is not None:
            # drop block METAS only — ownership moved to the dst shard,
            # so no worker-side deletes are queued
            for bid in list(removed.blocks):
                self.blocks.remove_block(bid)
            if self.open_files is not None:
                self.open_files.discard(removed.id)

    def _tx_commit_dst(self, rec: dict) -> None:
        from curvine_tpu.master.store import _dec_inode
        node = _dec_inode(rec["inode"])
        dst = rec["dst"]
        parent, name = self.tree.resolve_parent(dst)
        if parent is None or not parent.is_dir:
            raise err.FileNotFound(f"parent of {dst} not found")
        existing = self.tree.resolve(dst)
        if existing is not None:
            if existing.id == node.id:
                return                 # replay: already committed
            if rec["op"] == "rename":
                self._delete_inode(existing, recursive=False,
                                   parent=parent, name=name)
                parent = self.tree.get(parent.id)
            else:
                raise err.FileAlreadyExists(dst)
        node.name = name
        node.parent_id = parent.id
        node.mtime = now_ms()
        if rec["op"] == "link":
            # mirrored hard link: 1 for this entry + 1 phantom for the
            # src shard's copy — neither side ever frees the shared
            # blocks (leak-over-corruption; see docs/metadata-scale.md)
            node.nlink = 2
        self.tree.add_child(parent, node)
        for bid, length, replicas in rec.get("blocks", []):
            self.blocks.put(bid, length, node.id, replicas)
        for bid, wid, st in rec.get("locs", []):
            self.blocks.add_replica(bid, wid, StorageType(st))

    def tx_abort(self, txid: str) -> None:
        if self.store.tx_get(txid) is None:
            return
        self._log("tx_abort", dict(txid=txid))

    def _apply_tx_abort(self, txid: str) -> None:
        self.store.tx_remove(txid)

    def tx_forget(self, txid: str) -> None:
        if self.store.tx_get(txid) is None:
            return
        self._log("tx_forget", dict(txid=txid))

    def _apply_tx_forget(self, txid: str) -> None:
        self.store.tx_remove(txid)

    def list_tx(self) -> list[dict]:
        """In-doubt tx records for the recovery sweep (no inode bytes)."""
        out = []
        for rec in self.store.iter_tx():
            out.append({k: rec[k] for k in
                        ("txid", "role", "op", "src", "dst", "state")})
        return out

    def resize_file(self, path: str, new_len: int) -> None:
        """Shrink OR extend. Extending past the last written block
        creates a HOLE — a region with no backing block — which the
        client read path serves as zeros (parity: reference
        block_reader_hole.rs; sparse-file semantics)."""
        self._mount_write_guard(path)
        node = self._file_or_raise(path)
        if new_len < 0:
            raise err.InvalidArgument(f"resize to negative length {new_len}")
        self._log("resize", dict(path=path, new_len=new_len))

    def _apply_resize(self, path: str, new_len: int) -> None:
        node = self._file_or_raise(path)
        grow = new_len >= node.len
        node.len = new_len
        node.mtime = now_ms()
        if grow:
            # extend: existing blocks keep their data, the tail becomes
            # a hole (no block allocation — readers zero-fill)
            self.tree.save(node)
            return
        # drop whole blocks past the new length
        keep, off = [], 0
        for bid in node.blocks:
            meta = self.blocks.get(bid)
            blen = meta.len if meta else node.block_size
            if off < new_len:
                keep.append(bid)
            else:
                removed = self.blocks.remove_block(bid)
                if removed:
                    for wid in removed.locs:
                        self.pending_deletes.setdefault(wid, set()).add(bid)
            off += blen
        node.blocks = keep
        self.tree.save(node)

    # ==================== block ops ====================

    def add_block(self, path: str, client_host: str = "",
                  exclude_workers: list[int] | None = None,
                  commit_blocks: list[CommitBlock] | None = None,
                  ici_coords: list[int] | None = None,
                  storage_type: StorageType = StorageType.MEM,
                  abandon_block: int | None = None,
                  ) -> LocatedBlock:
        node = self._file_or_raise(path)
        if node.is_complete:
            raise err.LeaseConflict(f"{path} is not open for writing")
        self._commit(node, commit_blocks)
        chosen = self.policy.choose(
            self.workers.live_workers(), max(1, node.replicas),
            client_host=client_host, exclude=set(exclude_workers or []),
            needed=node.block_size, ici_coords=ici_coords, min_count=1)
        args = dict(inode_id=node.id)
        # HDFS abandonBlock semantics: a writer retrying a failed block
        # open discards its previous allocation in the same journal
        # entry, so retries never accumulate zero-length ghost blocks on
        # the inode. Only the trailing, never-committed block qualifies.
        if abandon_block is not None and node.blocks \
                and node.blocks[-1] == abandon_block:
            meta = self.blocks.get(abandon_block)
            if meta is None or meta.len == 0:
                args["abandon"] = abandon_block
        block_id = self._log("alloc_block", args)
        block = ExtendedBlock(id=block_id, len=0, storage_type=storage_type,
                              file_type=node.file_type)
        node = self.tree.get(node.id)
        off = sum(meta.len for b in node.blocks[:-1]
                  if (meta := self.blocks.get(b)) is not None)
        return LocatedBlock(block=block, offset=off,
                            locs=[w.address for w in chosen],
                            storage_types=[storage_type] * len(chosen))

    def _apply_alloc_block(self, inode_id: int,
                           abandon: int | None = None) -> int:
        node = self._inode_or_raise(inode_id)
        if abandon is not None and node.blocks \
                and node.blocks[-1] == abandon:
            node.blocks.pop()
            self.blocks.remove_block(abandon)
        block_id = self.tree.alloc_block_id()
        node.blocks.append(block_id)
        node.mtime = now_ms()      # writer liveness for lease recovery
        self.tree.save(node)
        # placeholder meta: a worker report of this in-flight block must
        # not look like an orphan (it is referenced by the inode)
        if self.store.block_get(block_id) is None:
            self.store.block_put(block_id, 0, inode_id, node.replicas)
        return block_id

    def complete_file(self, path: str, length: int,
                      commit_blocks: list[CommitBlock] | None = None,
                      client_name: str = "", only_flush: bool = False) -> bool:
        node = self._file_or_raise(path)
        self._commit(node, commit_blocks)
        if not only_flush:
            self._log("complete", dict(path=path, length=length))
        return True

    def _apply_complete(self, path: str, length: int) -> None:
        node = self._file_or_raise(path)
        node.len = length
        node.is_complete = True
        node.mtime = now_ms()
        node.client_name = ""
        self.tree.save(node)
        if self.open_files is not None:
            self.open_files.discard(node.id)

    def _commit(self, node: Inode, commit_blocks: list[CommitBlock] | None
                ) -> None:
        """Journal block lens (durable), then register replica locations
        (runtime state, rebuilt from worker reports after a restart)."""
        if not commit_blocks:
            return
        self._log("commit_blocks", dict(
            inode_id=node.id,
            commits=[[cb.block_id, cb.block_len] for cb in commit_blocks]))
        for cb in commit_blocks:
            for wid in cb.worker_ids:
                self.blocks.add_replica(cb.block_id, wid, cb.storage_type)

    def _apply_commit_blocks(self, inode_id: int, commits: list) -> None:
        node = self.tree.get(inode_id)
        replicas = node.replicas if node is not None else 1
        for bid, blen in commits:
            durable = self.store.block_get(bid)
            if durable is None:
                self.store.block_put(bid, blen, inode_id, replicas)
            else:
                old_len, iid, rep = durable
                self.store.block_put(bid, max(old_len, blen),
                                     iid or inode_id, rep)

    def get_block_locations(self, path: str) -> FileBlocks:
        node = self._file_or_raise(path)
        return self._file_blocks(node, path)

    def _file_blocks(self, node: Inode, path: str) -> FileBlocks:
        out = []
        off = 0
        for bid in node.blocks:
            meta = self.blocks.get(bid)
            if meta is None:
                continue
            locs, sts = [], []
            for wid, loc in meta.locs.items():
                try:
                    w = self.workers.get(wid)
                except err.WorkerNotFound:
                    continue
                # LIVE and DECOMMISSIONING replicas both serve reads
                # (draining workers keep their data until re-replicated)
                if w.state.value in (0, 2):
                    locs.append(w.address)
                    sts.append(loc.storage_type)
            out.append(LocatedBlock(
                block=ExtendedBlock(id=bid, len=meta.len,
                                    storage_type=sts[0] if sts else StorageType.MEM,
                                    file_type=node.file_type),
                offset=off, locs=locs, storage_types=sts,
                ec=self._ec_descriptor(bid)))
            off += meta.len
        return FileBlocks(status=node.to_status(path), block_locs=out)

    def _ec_descriptor(self, block_id: int) -> dict | None:
        """Stripe descriptor for a located block: per-cell ids + live
        worker addresses (wire form). None for replicated blocks and
        for stripes still mid-conversion (replicas serve those)."""
        stripe = self.ec_stripes.get(block_id)
        if stripe is None or stripe.get("state") != "committed":
            return None
        cells = []
        for idx, cid in enumerate(stripe["cells"]):
            cmeta = self.blocks.get(cid)
            clocs = []
            if cmeta is not None:
                for wid in cmeta.locs:
                    try:
                        w = self.workers.get(wid)
                    except err.WorkerNotFound:
                        continue
                    if w.state.value in (0, 2):
                        clocs.append(w.address.to_wire())
            cells.append({"index": idx, "block_id": cid, "locs": clocs})
        return {"profile": stripe["profile"],
                "cell_size": stripe["cell_size"],
                "block_len": stripe["block_len"], "cells": cells}

    # ==================== worker plane ====================

    def worker_heartbeat(self, info_wire: dict) -> dict:
        info = WorkerInfo.from_wire(info_wire)
        w = self.workers.heartbeat(info.address, info.storages,
                                   info.ici_coords)
        wid = info.address.worker_id
        deletes = list(self.pending_deletes.pop(wid, set()))
        cmds = {"delete_blocks": deletes}
        if w.state in (WorkerState.LIVE, WorkerState.DECOMMISSIONING) \
                and not self.workers.has_current_report(wid):
            # no full block report since this worker (re)registered — the
            # worker just started, returned from LOST, or THIS MASTER
            # restarted and lost its runtime location map. Ask for a
            # report now: reads need locations, and waiting out the
            # periodic report interval leaves every pre-restart block
            # location-less for up to that long.
            cmds["report_now"] = True
        if w.state == WorkerState.DECOMMISSIONING:
            # drain hint: the worker bounces NEW write streams with a
            # retryable error (in-flight ones finish), so the drain scan
            # never races fresh uploads onto a departing worker
            cmds["draining"] = True
        return cmds

    def worker_block_report(self, worker_id: int, held: dict,
                            storage_types: dict,
                            incremental: bool = False,
                            removed: list | None = None) -> dict:
        w = self.workers.workers.get(worker_id)
        if w is not None and w.state == WorkerState.DECOMMISSIONED:
            # a drained worker's copies are surplus and were purged from
            # the block map at drain completion — a report must not
            # resurrect them as countable locations
            return {"delete_blocks": []}
        held = {int(k): int(v) for k, v in held.items()}
        storage_types = {int(k): int(v) for k, v in storage_types.items()}
        orphans = self.blocks.apply_report(worker_id, held, storage_types,
                                           incremental=incremental)
        # blocks the worker dropped under cache pressure since it last
        # said so: their locations are gone now, not at the next full
        # report
        for bid in removed or ():
            self.blocks.remove_replica(int(bid), worker_id)
        if not incremental:
            self.workers.mark_reported(worker_id)
        # report-driven len bumps are durable but not journaled: persist
        # them now so they don't ride some later entry's atomic batch
        self.store.commit_runtime()
        return {"delete_blocks": orphans}

    def recover_stale_leases(self, lease_timeout_ms: int = 300_000) -> int:
        """Finalize files abandoned mid-write (dead client, no complete).
        Parity: master/fs/fs_dir_watchdog.rs. A stale incomplete file is
        completed at its committed block length (data salvaged) or deleted
        when nothing was ever committed."""
        deadline = now_ms() - lease_timeout_ms
        recovered = 0
        if self.open_files is None:
            # one lazy scan after restart; incremental from then on
            self.open_files = {n.id for n in self.tree.iter_files()
                               if not n.is_complete}
        for inode_id in list(self.open_files):
            node = self.tree.get(inode_id)
            if node is None or node.file_type == FileType.DIR:
                self.open_files.discard(inode_id)
                continue
            if node.is_complete:
                self.open_files.discard(inode_id)
                continue
            if node.mtime >= deadline:
                continue
            path = self.tree.path_of(node)
            committed = sum((self.blocks.get(b).len
                             for b in node.blocks if self.blocks.get(b)),
                            start=0)
            try:
                if committed > 0:
                    self._log("complete", dict(path=path, length=committed))
                    log.warning("lease recovery: completed %s at %d bytes",
                                path, committed)
                else:
                    self._log("delete", dict(path=path, recursive=False))
                    log.warning("lease recovery: removed empty stale %s",
                                path)
                recovered += 1
            except err.CurvineError as e:
                log.warning("lease recovery of %s failed: %s", path, e)
        return recovered

    def check_lost_workers(self, act: bool = True) -> list[WorkerInfo]:
        """LOST-state bookkeeping always runs (reads filter locations on
        worker state, so followers must notice dead workers too);
        `act=False` skips the repair dispatch side effects (HA followers
        must not initiate re-replication)."""
        newly_lost = self.workers.check_lost()
        for w in newly_lost:
            affected = self.blocks.worker_lost(w.address.worker_id)
            if act and affected and self.on_worker_lost:
                self.on_worker_lost(w, affected)
        return newly_lost

    def master_info(self, addr: str = "") -> MasterInfo:
        cap, avail = self.workers.capacity()
        return MasterInfo(
            active_master=addr, inode_num=self.tree.count(),
            block_num=self.blocks.count(), capacity=cap, available=avail,
            fs_used=cap - avail,
            # draining workers still serve and still report in: they
            # belong in the live list (their state field says the rest);
            # fully-drained DECOMMISSIONED workers ride the lost list so
            # `cv node list` keeps showing the safe-to-remove signal
            live_workers=self.workers.serving_workers(),
            lost_workers=(self.workers.lost_workers()
                          + self.workers.retired_workers()))

    # ==================== helpers ====================

    def _mount_write_guard(self, path: str, caching: bool = False,
                           subtree: bool = False) -> None:
        """Reference parity: write RPCs under a read-only mount are
        refused (curvine-client unified_filesystem.rs
        is_mount_write_rpc + AccessMode); enforced master-side here so
        every client/gateway/FUSE path also gets it without carrying the
        mount table. Cache-warming loads are exempt — their creates
        carry the ufs_mtime marker. Like the reference's client-side
        gate, that marker is COOPERATIVE (a raw-RPC client can set it);
        the access mode protects against accidental writes — authz is
        the ACL layer's job. `subtree` ops (recursive delete, rename of
        an ancestor) are refused when a read-only mount lies anywhere
        UNDER the target too."""
        if self.mounts is None or caching:
            return
        m = self.mounts.get_mount(path)
        if m is not None and getattr(m, "access_mode", "rw") == "r":
            raise err.Unsupported(
                f"write on read-only mount {m.cv_path}: {path}")
        if subtree:
            prefix = path.rstrip("/") + "/"
            for info in self.mounts.table():
                if info.access_mode == "r" and \
                        info.cv_path.startswith(prefix):
                    raise err.Unsupported(
                        f"{path} contains read-only mount {info.cv_path}")

    def _file_or_raise(self, path: str) -> Inode:
        node = self.tree.resolve(path)
        if node is None:
            raise err.FileNotFound(path)
        if node.is_dir:
            raise err.IsADirectory(path)
        return node

    def _inode_or_raise(self, inode_id: int) -> Inode:
        node = self.tree.get(inode_id)
        if node is None:
            raise err.FileNotFound(f"inode {inode_id}")
        return node
