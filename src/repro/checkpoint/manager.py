"""Fault-tolerant checkpointing.

Design for 1000+ nodes (documented; exercised at container scale):
* **Shard-agnostic format**: leaves are saved as FULL logical arrays
  (device_get gathers shards), so a restore may use a different mesh shape
  or host count — this is what makes resume *elastic*.
* **Atomic**: write to `step_XXXX.tmp/` then rename; a crash mid-write
  never corrupts the newest valid checkpoint; `latest()` scans only
  completed directories.
* **Verified**: every snapshot carries per-array crc32 stamps
  (`checksums.json`, the shared `core.faults.checksum`, same stamp the
  peer-replica tier uses); `latest()` verifies and SKIPS a torn/corrupted
  snapshot to the previous good one instead of restoring garbage.
* **Async**: the device→host copy is synchronous (cheap, avoids donation
  races), the disk write happens on a background thread so the train loop
  isn't stalled on I/O.
* The data-pipeline position is part of the checkpoint, so the token
  stream resumes exactly (no repeated/skipped batches after failover).
"""
from __future__ import annotations

import json
import os
import shutil
import threading

import jax
import numpy as np


def _flatten(tree):
    leaves = jax.tree.flatten_with_path(tree)[0]
    out = {}
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = np.asarray(jax.device_get(leaf))
    return out


def _unflatten_like(template, flat: dict):
    leaves, treedef = jax.tree.flatten_with_path(template)
    vals = []
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        arr = flat[key]
        vals.append(arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr)
    return jax.tree.unflatten(treedef, vals)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_write: bool = True):
        self.dir = directory
        self.keep = keep
        self.async_write = async_write
        self.skipped: list[int] = []    # steps latest() refused to restore
        self._thread: threading.Thread | None = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------- write -------------------------
    def save(self, step: int, params, opt_state=None, extra: dict | None = None):
        self.wait()
        snap = {
            "params": _flatten(params),
            "opt": _flatten(opt_state) if opt_state is not None else {},
        }
        meta = {"step": int(step), "extra": extra or {}}

        from repro.core.faults import checksum
        sums = {fname: {k: checksum(v) for k, v in snap[part].items()}
                for part, fname in (("params", "params.npz"),
                                    ("opt", "opt.npz"))}

        def write():
            tmp = os.path.join(self.dir, f"step_{step:08d}.tmp")
            final = os.path.join(self.dir, f"step_{step:08d}")
            os.makedirs(tmp, exist_ok=True)
            np.savez(os.path.join(tmp, "params.npz"), **snap["params"])
            np.savez(os.path.join(tmp, "opt.npz"), **snap["opt"])
            with open(os.path.join(tmp, "checksums.json"), "w") as f:
                json.dump(sums, f)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)           # atomic commit
            self._gc()

        if self.async_write:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        else:
            write()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = self.steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)

    # ------------------------- read -------------------------
    def steps(self) -> list[int]:
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and not d.endswith(".tmp"):
                out.append(int(d.split("_")[1]))
        return sorted(out)

    def verify(self, step: int) -> bool:
        """Check every array in the snapshot against its crc32 stamp.
        Pre-checksum snapshots (no checksums.json) are accepted as-is —
        the stamp protects against torn/corrupted bytes, and a legacy
        snapshot's absence of stamps is not evidence of either."""
        from repro.core.faults import checksum
        d = os.path.join(self.dir, f"step_{step:08d}")
        cpath = os.path.join(d, "checksums.json")
        if not os.path.exists(cpath):
            return True
        try:
            with open(cpath) as f:
                sums = json.load(f)
            for fname, keys in sums.items():
                zf = np.load(os.path.join(d, fname))
                for k, crc in keys.items():
                    if checksum(zf[k]) != int(crc):
                        return False
        except Exception:               # noqa: BLE001 — torn bytes, any form
            return False
        return True

    def latest(self) -> int | None:
        """Newest snapshot that VERIFIES.  A torn or bit-flipped snapshot
        is skipped (recorded in `self.skipped`) and the previous good one
        is returned instead — restoring garbage is strictly worse than
        restoring slightly older state."""
        for s in reversed(self.steps()):
            if self.verify(s):
                return s
            self.skipped.append(s)
        return None

    def restore(self, step: int, params_template, opt_template=None,
                shardings=None):
        """Returns (step, params, opt_state, extra).  `shardings` (optional
        pytree of NamedSharding for the CURRENT mesh) makes the restore
        elastic: full arrays are re-placed onto whatever mesh is alive."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        pf = np.load(os.path.join(d, "params.npz"))
        params = _unflatten_like(params_template,
                                 {k: pf[k] for k in pf.files})
        opt = None
        if opt_template is not None:
            of = np.load(os.path.join(d, "opt.npz"))
            opt = _unflatten_like(opt_template, {k: of[k] for k in of.files})
        if shardings is not None:
            params = jax.device_put(params, shardings)
        return meta["step"], params, opt, meta["extra"]

    def restore_flat(self, step: int):
        """Template-free read: (step, {path: np.ndarray}, extra).  The
        mid-loop resume path (runtime/ft.LoopRunner) uses this — after a
        crash there is no live pytree to unflatten into; the flat keys
        (``loop<i>/<carry-name>``) are self-describing."""
        d = os.path.join(self.dir, f"step_{step:08d}")
        with open(os.path.join(d, "meta.json")) as f:
            meta = json.load(f)
        pf = np.load(os.path.join(d, "params.npz"))
        return meta["step"], {k: pf[k] for k in pf.files}, meta["extra"]
