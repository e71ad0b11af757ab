"""Sharded, resharding-safe checkpointing with async C2H drains.

Layout per step: ``<dir>/step_<N>/`` holding one ``.npy`` per pytree leaf
(flattened key path as filename) plus ``manifest.json`` (step, tree
structure, shapes/dtypes, integrity digests).  Writes go to ``step_<N>.tmp``
and are atomically renamed, so a crash mid-save never corrupts the latest
checkpoint — the restore path simply picks the newest *complete* manifest.

The device->host snapshot streams through the NMA engine's C2H channels
(``MemoryEngine.read_tree_async``), then a background thread persists it —
training resumes while bytes drain, the paper's C2H pattern (DESIGN.md §3.2).

Arrays are saved *unsharded* (global view), so restore works under any mesh
or world size — this is what makes elastic restarts trivial.

``save_far``/``restore_far`` spill a snapshot to a far-memory node instead
of disk (DESIGN.md §4.4): the C2H drain is unchanged, but leaves land in
NIC-attached DRAM through one-sided verbs — a diskless checkpoint on the
rmem tier, restorable by any host that can reach the node.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.engine import MemoryEngine


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "__".join(parts) or "leaf"


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3,
                 engine: Optional[MemoryEngine] = None, digest: bool = True,
                 path="auto"):
        """``path`` names the access path the C2H snapshot drain rides
        (DESIGN.md §5) — the default stage-only ``auto`` selector rides
        xdma while idle and spills to the qdma queues under occupancy;
        ignored when an ``engine`` is handed in."""
        self.dir = directory
        self.keep = keep
        self.engine = engine or MemoryEngine(n_channels=2, path=path)
        self.digest = digest
        os.makedirs(directory, exist_ok=True)
        self._save_thread: Optional[threading.Thread] = None
        self._save_error: Optional[BaseException] = None

    # -- save ---------------------------------------------------------------
    def save(self, step: int, tree: Any, block: bool = True) -> None:
        self.wait()  # one async save at a time
        leaves_dev, treedef = jax.tree.flatten_with_path(tree)
        paths = [p for p, _ in leaves_dev]
        join = self.engine.read_tree_async([l for _, l in leaves_dev])

        def persist():
            try:
                host_leaves = join()
                self._write(step, paths, host_leaves, treedef)
            except BaseException as e:  # surfaced on next wait()
                self._save_error = e

        self._save_thread = threading.Thread(target=persist, daemon=True)
        self._save_thread.start()
        if block:
            self.wait()

    def wait(self) -> None:
        if self._save_thread is not None:
            self._save_thread.join()
            self._save_thread = None
        if self._save_error is not None:
            e, self._save_error = self._save_error, None
            raise e

    def _write(self, step: int, paths, host_leaves, treedef) -> None:
        final = os.path.join(self.dir, f"step_{step:010d}")
        tmp = final + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        manifest: Dict[str, Any] = {
            "step": step,
            "treedef": str(treedef),
            "leaves": [],
        }
        names = set()
        for path, leaf in zip(paths, host_leaves):
            arr = np.asarray(leaf)
            name = _leaf_name(path)
            assert name not in names, f"duplicate leaf name {name}"
            names.add(name)
            # raw bytes + manifest dtype: np.save cannot round-trip
            # ml_dtypes (bfloat16) through its descr encoding
            np.save(os.path.join(tmp, name + ".npy"),
                    arr.reshape(-1).view(np.uint8))
            entry = {"name": name, "shape": list(arr.shape),
                     "dtype": str(arr.dtype)}
            if self.digest:
                entry["sha256"] = hashlib.sha256(
                    arr.tobytes()).hexdigest()[:16]
            manifest["leaves"].append(entry)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        shutil.rmtree(final, ignore_errors=True)
        os.rename(tmp, final)
        self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                          ignore_errors=True)

    # -- far-memory spill ----------------------------------------------------
    def save_far(self, step: int, tree: Any, node,
                 doorbell_batch: int = 8,
                 reuse: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Spill a snapshot to a ``repro.rmem.MemoryNode``.

        Returns the manifest needed by ``restore_far`` (leaf name ->
        node address); digests guard the far copy exactly like the disk
        path.  Leaves are posted as one-sided writes with doorbell
        batching and fenced once at the end.

        Node memory is bump-allocated, so periodic checkpointing must
        pass the previous ``save_far`` manifest as ``reuse``: leaves
        with matching name/size overwrite their old addresses in place
        instead of growing the node (``MemoryNode.reset()`` is the
        coarse alternative when the node holds nothing else).
        """
        from repro.rmem.verbs import MemoryRegion, QueuePair
        self.wait()
        reuse_addrs = {e["name"]: e for e in reuse["leaves"]} if reuse \
            else {}
        leaves_dev, treedef = jax.tree.flatten_with_path(tree)
        host_leaves = self.engine.read_tree_async(
            [l for _, l in leaves_dev])()
        entries: List[Dict[str, Any]] = []
        keepalive = []                     # MRs must outlive the doorbell
        # context-managed: a per-checkpoint QP must not leak its reactor
        # telemetry source (periodic far checkpoints would accumulate
        # one per save forever)
        with QueuePair(node, doorbell_batch=doorbell_batch) as qp:
            for (path, _), leaf in zip(leaves_dev, host_leaves):
                arr = np.asarray(leaf)
                # ascontiguousarray promotes 0-d to (1,): record shape
                # first
                flat = np.ascontiguousarray(arr).reshape(-1) \
                    .view(np.uint8)
                name = _leaf_name(path)
                prev = reuse_addrs.get(name)
                if prev is not None and prev["nbytes"] == arr.nbytes:
                    addr = prev["addr"]
                else:
                    addr = node.alloc(max(arr.nbytes, 1))
                mr = MemoryRegion(flat if arr.nbytes
                                  else np.zeros(1, np.uint8))
                keepalive.append(mr)
                qp.post_write(mr, 0, addr, max(arr.nbytes, 1))
                entry = {"name": name, "addr": addr,
                         "nbytes": arr.nbytes, "shape": list(arr.shape),
                         "dtype": str(arr.dtype)}
                if self.digest:
                    entry["sha256"] = hashlib.sha256(
                        arr.tobytes()).hexdigest()[:16]
                entries.append(entry)
            qp.flush()
            return {"step": step, "node": node.name, "leaves": entries,
                    "bytes": sum(e["nbytes"] for e in entries),
                    "qp": qp.stats()}

    def restore_far(self, like: Any, manifest: Dict[str, Any],
                    node) -> Tuple[int, Any]:
        """Pull a ``save_far`` snapshot back from the node into ``like``'s
        structure, verifying digests."""
        import jax.numpy as jnp
        from repro.rmem.verbs import MemoryRegion, QueuePair
        by_name = {e["name"]: e for e in manifest["leaves"]}
        leaves_like, treedef = jax.tree.flatten_with_path(like)
        out = []
        with QueuePair(node) as qp:
            for path, leaf in leaves_like:
                name = _leaf_name(path)
                if name not in by_name:
                    raise KeyError(f"leaf {name} missing from far "
                                   f"snapshot")
                e = by_name[name]
                raw = np.zeros(max(e["nbytes"], 1), np.uint8)
                qp.read(MemoryRegion(raw), 0, e["addr"],
                        max(e["nbytes"], 1))
                raw = raw[:e["nbytes"]]
                if self.digest and "sha256" in e:
                    h = hashlib.sha256(raw.tobytes()).hexdigest()[:16]
                    if h != e["sha256"]:
                        raise IOError(f"far digest mismatch for {name}")
                arr = raw.view(jnp.dtype(e["dtype"])).reshape(e["shape"])
                if tuple(arr.shape) != tuple(leaf.shape):
                    raise ValueError(f"shape mismatch {name}: far "
                                     f"{arr.shape} vs model {leaf.shape}")
                out.append(jax.device_put(arr))
        return manifest["step"], jax.tree.unflatten(treedef, out)

    # -- restore --------------------------------------------------------------
    def all_steps(self) -> List[int]:
        out = []
        for n in os.listdir(self.dir):
            if n.startswith("step_") and not n.endswith(".tmp") and \
                    os.path.exists(os.path.join(self.dir, n,
                                                "manifest.json")):
                out.append(int(n[5:]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like: Any, step: Optional[int] = None,
                shardings: Any = None) -> Tuple[int, Any]:
        """Restore into the structure of ``like`` (abstract or concrete).

        Verifies digests; raises on corruption so the caller's fault
        handler can fall back to an older step (runtime/fault.py).
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = os.path.join(self.dir, f"step_{step:010d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        by_name = {e["name"]: e for e in manifest["leaves"]}
        leaves_like, treedef = jax.tree.flatten_with_path(like)
        shard_leaves = (jax.tree.leaves(shardings)
                        if shardings is not None else [None] * len(leaves_like))
        out = []
        for (path, leaf), sh in zip(leaves_like, shard_leaves):
            name = _leaf_name(path)
            if name not in by_name:
                raise KeyError(f"leaf {name} missing from checkpoint {step}")
            e = by_name[name]
            raw = np.load(os.path.join(d, name + ".npy"))
            if self.digest and "sha256" in e:
                h = hashlib.sha256(raw.tobytes()).hexdigest()[:16]
                if h != e["sha256"]:
                    raise IOError(f"digest mismatch for {name} @ step {step}")
            import jax.numpy as jnp
            arr = raw.view(jnp.dtype(e["dtype"])).reshape(e["shape"])
            if tuple(arr.shape) != tuple(leaf.shape):
                raise ValueError(f"shape mismatch {name}: ckpt {arr.shape} "
                                 f"vs model {leaf.shape}")
            if sh is not None:
                out.append(jax.device_put(arr, sh))
            else:
                out.append(jax.device_put(arr))
        return step, jax.tree.unflatten(treedef, out)
