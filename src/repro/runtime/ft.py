"""Fault-tolerance / elasticity / straggler runtime around the train loop.

At 1000+ nodes, failures are routine; the runner provides:
* periodic async checkpoints + resume-from-latest (restart-safe);
* **elastic resume**: the checkpoint stores full arrays and the data
  position, so a job restarted with a different host/mesh size re-places
  params onto the new mesh and re-slices the SAME token stream;
* **straggler mitigation**: per-step wall-time watchdog — the SAME
  trailing-median `FaultLedger.note_time` watchdog the core executor and
  the serving layer use (one straggler story across all three layers,
  visible in `explain_faults()`); on a real pod this signal feeds
  preemption/replacement (here: surfaced via `runner.straggler_events`
  and the ledger, tested by injecting a slow step);
* **peer-replicated carry snapshots** (DESIGN.md §13): an in-memory tier
  ABOVE the disk checkpoints — every `peer_every` iterations the loop
  carries are ring-copied to the neighbouring shard (`ppermute` shift) and
  checksummed, so a lost shard restores its carry from the peer without
  touching disk; a torn replica fails its checksum and the previous good
  one is used instead;
* simulated failure injection for tests (`fail_at_step`).
"""
from __future__ import annotations

import time

import numpy as np

from ..checkpoint import CheckpointManager
from ..core.faults import FaultLedger, checksum


class SimulatedFailure(Exception):
    pass


class PeerReplica:
    """In-memory peer-replicated snapshot tier (DESIGN.md §13).

    Disk checkpoints survive a full-job restart but cost serialization +
    I/O per save; losing ONE shard should not need them.  This tier keeps
    the last `depth` carry snapshots in memory, each array ring-copied to
    the neighbouring shard (`jax.lax.ppermute` shift by +1 over the dp
    axis — shard k's block lives on shard k+1, so shard k dying leaves
    every one of its blocks on a survivor) and stamped with the shared
    crc32 `core.faults.checksum`.  `latest_good()` inverse-permutes the
    newest snapshot back and verifies the stamp; a torn replica (a write
    interrupted by the very failure it protects against) fails its
    checksum and the PREVIOUS good snapshot is returned instead.  Without
    a mesh (single-device runs, tests) the "copy" is a host-side mirror —
    same protocol, same stamps, no collective."""

    def __init__(self, mesh=None, dp=("data",), depth: int = 2,
                 ledger: FaultLedger | None = None):
        self.mesh = mesh
        self.dp = tuple(dp)
        self.depth = int(depth)
        self.ledger = ledger
        self.snaps: list[dict] = []     # oldest → newest
        self.torn: list[int] = []       # steps whose replica failed verify
        self._shift = {}                # (shape, dtype) → jitted ring copy
        self.dp_n = 1
        if mesh is not None:
            for a in self.dp:
                self.dp_n *= dict(zip(mesh.axis_names,
                                      mesh.devices.shape))[a]

    # ------------------------- ring copy -------------------------
    def _ring(self, x, inverse: bool):
        """Shift row blocks to the (next/previous) shard.  Arrays that do
        not tile over the mesh (scalars, odd lengths) mirror host-side —
        the protocol and stamps are identical either way."""
        if self.mesh is None or self.dp_n <= 1 or x.ndim == 0 \
                or x.shape[0] % self.dp_n:
            return np.array(x)          # host mirror (defensive copy)
        import jax
        from jax.sharding import PartitionSpec as P
        key = (tuple(x.shape), str(x.dtype), inverse)
        fn = self._shift.get(key)
        if fn is None:
            n = self.dp_n
            perm = [((i + 1) % n, i) if inverse else (i, (i + 1) % n)
                    for i in range(n)]

            def body(b):
                return jax.lax.ppermute(b, self.dp, perm)
            fn = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                       in_specs=(P(self.dp),),
                                       out_specs=P(self.dp)))
            self._shift[key] = fn
        return fn(x)

    # ------------------------- write / read -------------------------
    def mirror(self, li: int, it: int, step: int, carry: dict) -> None:
        import jax.numpy as jnp
        snap = {"li": int(li), "it": int(it), "step": int(step),
                "data": {}, "crc": {}}
        for name, v in carry.items():
            arr = jnp.asarray(v)
            snap["crc"][name] = checksum(arr)
            snap["data"][name] = self._ring(arr, inverse=False)
        self.snaps.append(snap)
        del self.snaps[:-self.depth]

    def latest_good(self):
        """(li, it, step, carry) from the newest snapshot whose every
        array verifies against its stamp; torn snapshots are skipped to
        the previous good one.  None when nothing usable remains."""
        import jax.numpy as jnp
        for snap in reversed(self.snaps):
            carry = {}
            ok = True
            for name, v in snap["data"].items():
                back = jnp.asarray(self._ring(jnp.asarray(v), inverse=True))
                if checksum(back) != snap["crc"][name]:
                    ok = False
                    break
                carry[name] = back
            if ok:
                return snap["li"], snap["it"], snap["step"], carry
            self.torn.append(snap["step"])
            if self.ledger is not None:
                self.ledger.record(
                    "escalate", f"loop{snap['li']}",
                    f"peer replica at iteration {snap['it']} is torn "
                    f"(checksum mismatch) — previous good snapshot used")
        return None


class TrainRunner:
    def __init__(self, step_fn, params, opt_state, data, ckpt_dir: str,
                 ckpt_every: int = 10, straggler_factor: float = 3.0,
                 shardings=None, ledger: FaultLedger | None = None):
        self.step_fn = step_fn
        self.params = params
        self.opt_state = opt_state
        self.data = data
        self.mgr = CheckpointManager(ckpt_dir)
        self.ckpt_every = ckpt_every
        self.shardings = shardings
        self.step = 0
        # ONE straggler watchdog for the whole system: the shared
        # FaultLedger trailing-median idiom (same as core rounds and
        # served batches), not a private list only this class can see
        self.faults = ledger if ledger is not None else \
            FaultLedger(name="train")
        self.faults.straggler_factor = straggler_factor
        self.straggler_events: list[int] = []   # flagged step indices

    def explain_faults(self) -> str:
        return self.faults.explain()

    def maybe_resume(self):
        latest = self.mgr.latest()
        if latest is None:
            return False
        self.step, self.params, self.opt_state, extra = self.mgr.restore(
            latest, self.params, self.opt_state, self.shardings)
        if "data" in extra:
            self.data.restore(extra["data"],
                              host_index=self.data.host,
                              host_count=self.data.global_batch
                              // self.data.local_batch)
        return True

    def run(self, num_steps: int, fail_at_step: int | None = None):
        metrics = None
        while self.step < num_steps:
            if fail_at_step is not None and self.step == fail_at_step:
                raise SimulatedFailure(f"injected failure at {self.step}")
            batch = self.data.next_batch()
            t0 = time.perf_counter()
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            if self.faults.note_time("train.step",
                                     time.perf_counter() - t0):
                self.straggler_events.append(self.step)
            self.step += 1
            if self.step % self.ckpt_every == 0:
                self.mgr.save(self.step, self.params, self.opt_state,
                              extra={"data": self.data.state()})
        self.mgr.wait()
        return metrics


class LoopRunner:
    """Mid-loop checkpoint/resume for ITERATIVE PLANS (DESIGN.md §11) —
    the TrainRunner idiom applied to the core executor's SeqLoops.

    Drives ``CompiledProgram.run_stepwise`` (host-driven loops) and
    snapshots every loop carry through CheckpointManager every ``every``
    iterations, keyed ``loop<i>/<carry-name>`` with the iteration count in
    the checkpoint metadata.  A plan killed at iteration k (crash, or an
    injected ``lower.loop_iter`` fault) restarts with ``resume=True``:
    nodes before the loop re-execute (pure + deterministic), the carry is
    restored from the latest snapshot, and the final outputs are
    BIT-IDENTICAL to an uninterrupted stepwise run — both execute the
    exact same per-iteration body computations on the same carry values
    (npz array round-trips are exact).  Per-iteration wall times feed the
    program's straggler watchdog (`explain_faults()`).

    With ``peer_every`` > 0 the carries ADDITIONALLY mirror to the
    in-memory peer-replica tier (DESIGN.md §13) every ``peer_every``
    iterations: resume prefers the newest GOOD peer snapshot over the disk
    tier when the peer is fresher (memory beats disk on recency AND
    latency; disk survives what memory cannot — a full-job restart still
    restores from npz).  Both tiers verify the shared crc32 stamp and
    skip torn snapshots to the previous good one.

    Out-of-core runs (DESIGN.md §12) ride the same machinery unchanged:
    a ChunkLoop is a top-level SeqLoop to run_stepwise, so its observer
    fires per CHUNK and a killed streamed run resumes from the last chunk
    checkpoint, fast-forwarding past completed tiles."""

    def __init__(self, cp, ckpt_dir: str, every: int = 1, keep: int = 3,
                 async_write: bool = False, peer_every: int = 0,
                 mesh=None, dp=("data",)):
        self.cp = cp
        self.mgr = CheckpointManager(ckpt_dir, keep=keep,
                                     async_write=async_write)
        self.every = int(every)
        self.saves = 0
        self.resumed_from = None       # checkpoint step of the last resume
        self.peer_every = int(peer_every)
        self.peer = PeerReplica(mesh=mesh, dp=dp, ledger=cp.faults) \
            if peer_every else None
        self.peer_restores = 0
        self._step = 0
        self._t_last = 0.0

    def run(self, inputs: dict, resume: bool = True) -> dict:
        loop_state = None
        self.resumed_from = None
        if resume:
            latest = self.mgr.latest()
            if latest is not None:
                step, flat, extra = self.mgr.restore_flat(latest)
                loop_state = {}
                for li_s, it in (extra.get("loops") or {}).items():
                    li = int(li_s)
                    carry = {k.split("/", 1)[1]: v for k, v in flat.items()
                             if k.startswith(f"loop{li}/")}
                    loop_state[li] = (int(it), carry)
                self.resumed_from = step
                self._step = step
            good = self.peer.latest_good() if self.peer is not None \
                else None
            if good is not None:
                li, it, step, carry = good
                disk_it = loop_state.get(li, (-1, None))[0] \
                    if loop_state else -1
                if it > disk_it:
                    loop_state = loop_state or {}
                    loop_state[li] = (it, {c: np.asarray(v)
                                           for c, v in carry.items()})
                    self.resumed_from = step
                    self._step = max(self._step, step)
                    self.peer_restores += 1
                    self.cp.faults.recovered(
                        f"loop{li}",
                        f"carry restored from peer replica (iteration "
                        f"{it}, ring copy verified against checksum; disk "
                        f"tier was at iteration {max(disk_it, 0)})")
        self._t_last = time.perf_counter()
        out = self.cp.run_stepwise(inputs, loop_state=loop_state,
                                   observer=self._observer)
        self.mgr.wait()
        return out

    def _observer(self, li, it, carry):
        self._step += 1
        now = time.perf_counter()
        self.cp.faults.note_time(f"loop{li}.iter", now - self._t_last)
        self._t_last = now
        if self.every and it % self.every == 0:
            self.mgr.save(self._step,
                          {f"loop{li}/{c}": v for c, v in carry.items()},
                          extra={"loops": {str(li): int(it)}})
            self.saves += 1
        if self.peer is not None and it % self.peer_every == 0:
            self.peer.mirror(li, it, self._step, dict(carry))
