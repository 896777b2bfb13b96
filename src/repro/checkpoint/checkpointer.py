"""Atomic, elastic checkpointing (fault-tolerance core, DESIGN.md §4).

Guarantees:
  * atomicity  — write to tmp dir, fsync, os.replace (a crash mid-save
    never corrupts the latest checkpoint);
  * keep-N     — bounded disk usage with monotonic step dirs;
  * elasticity — arrays are saved LOGICALLY (np arrays + pytree structure
    + step/config metadata). Restore places them onto whatever mesh the
    restarting job runs (2 pods -> 8 pods works: jax.device_put with the
    new sharding reshards), so node-count changes need no conversion;
  * async      — `save_async` hands the host copy to a writer thread so
    the device step resumes immediately;
  * migration  — checkpoints written before the NodeTree unification
    (sketch state as per-group dicts, two fewer leaves) restore through
    `repro.sketches.compat.restore_legacy_state`; new checkpoints tag
    metadata with `sketch_layout` so the provenance is inspectable.

Per-worker residual persistence (DESIGN.md §12): DP runs carry state
that is INTENTIONALLY distinct per worker — the countsketch
error-feedback accumulators, and under ``dp_merge="reduce_scatter"``
the worker's sketch shard. `gather_per_worker` stacks every worker's
device-local copy onto a leading (W, ...) axis so checkpoints keep the
full decomposition (no pmean merge destroys it at save time);
`scatter_per_worker` hands each worker its row back on restore. The
caller tags metadata with ``residual_layout="per_worker_v1"`` +
``dp_workers`` so restore can tell stacked from legacy-merged
checkpoints (`Checkpointer.metadata` reads it without touching the
arrays); train/loop.py owns the W-change and legacy migrations.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time

import jax
import numpy as np

RESIDUAL_LAYOUT = "per_worker_v1"


def gather_per_worker(tree, mesh, axis_name):
    """Stack every DP worker's device-local copy of `tree`'s leaves on
    a NEW leading (W, ...) axis. The per-worker buffers live under a
    replicated spec (check_vma=False), so a plain host copy would
    silently keep worker 0's buffer and drop the rest — this makes the
    decomposition explicit before it leaves the devices."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fn = shard_map(
        lambda t: jax.tree.map(lambda x: x[None], t),
        mesh=mesh, in_specs=P(), out_specs=P(axis_name),
        check_vma=False)
    return jax.jit(fn)(tree)


def scatter_per_worker(stacked, mesh, axis_name):
    """Inverse of `gather_per_worker`: each worker takes its own row of
    the replicated (W, ...) stacked leaves — exact restore of the
    per-worker decomposition (no mass redistribution)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def _take(t):
        i = jax.lax.axis_index(axis_name)
        return jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, 0,
                                                   keepdims=False), t)

    fn = shard_map(_take, mesh=mesh, in_specs=P(), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn)(stacked)


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None

    # -- paths --------------------------------------------------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:010d}")

    def latest_step(self) -> int | None:
        steps = [int(d.split("_")[1]) for d in os.listdir(self.directory)
                 if d.startswith("step_") and not d.endswith(".tmp")]
        return max(steps) if steps else None

    # -- save ---------------------------------------------------------

    def save(self, step: int, state, metadata: dict | None = None):
        leaves, treedef = jax.tree.flatten(state)
        host = [np.asarray(x) for x in leaves]
        self._write(step, host, str(treedef), metadata or {})

    def save_async(self, step: int, state, metadata: dict | None = None):
        self.wait()                       # one in-flight save at a time
        leaves, treedef = jax.tree.flatten(state)
        host = [np.asarray(x) for x in leaves]   # device->host copy now
        self._thread = threading.Thread(
            target=self._write, args=(step, host, str(treedef),
                                      metadata or {}))
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _write(self, step: int, host_leaves, treedef_str: str,
               metadata: dict):
        final = self._step_dir(step)
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        np.savez(os.path.join(tmp, "arrays.npz"),
                 **{f"leaf_{i}": a for i, a in enumerate(host_leaves)})
        meta = dict(metadata)
        meta.setdefault("sketch_layout", "nodetree-v1")
        meta.update({"step": step, "time": time.time(),
                     "num_leaves": len(host_leaves),
                     "treedef": treedef_str})
        with open(os.path.join(tmp, "metadata.json"), "w") as f:
            json.dump(meta, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            # re-save of an existing step (e.g. periodic + final save
            # colliding): replace atomically via a second rename
            stale = final + ".old"
            os.replace(final, stale)
            os.replace(tmp, final)
            shutil.rmtree(stale, ignore_errors=True)
        else:
            os.replace(tmp, final)        # atomic publish
        self._gc()

    def _gc(self):
        steps = sorted(
            int(d.split("_")[1]) for d in os.listdir(self.directory)
            if d.startswith("step_") and not d.endswith(".tmp"))
        for s in steps[: -self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    # -- restore ------------------------------------------------------

    def metadata(self, step: int | None = None) -> dict:
        """The metadata dict of a checkpoint WITHOUT loading its arrays
        — restore callers read `residual_layout`/`dp_workers` here
        first to build the right template (a per_worker_v1 checkpoint's
        stacked leaves have different shapes than live state)."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        with open(os.path.join(self._step_dir(step),
                               "metadata.json")) as f:
            return json.load(f)

    def restore(self, template, step: int | None = None,
                shardings=None):
        """Restore into the structure of `template`.

        `shardings` (optional pytree of NamedSharding matching template)
        reshards onto the CURRENT mesh — the elastic-restart path.

        Pre-NodeTree checkpoints (sketch state saved as per-group dicts)
        are detected by leaf count and migrated in place.
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        d = self._step_dir(step)
        z = np.load(os.path.join(d, "arrays.npz"))
        leaves = [z[f"leaf_{i}"] for i in range(len(z.files))]
        _, treedef = jax.tree.flatten(template)
        if len(leaves) != treedef.num_leaves:
            # load-time migration from the pre-unification sketch layout
            from repro.sketches.compat import restore_legacy_state
            state = restore_legacy_state(template, leaves)
        else:
            state = jax.tree.unflatten(treedef, leaves)
        if shardings is not None:
            state = jax.tree.map(
                lambda x, s: jax.device_put(x, s), state, shardings)
        with open(os.path.join(d, "metadata.json")) as f:
            meta = json.load(f)
        return state, meta
