"""Faults planted under a run, to show that ``correct`` catches them.

Never used by a measured run. ``run.py --fault <name>`` plants one on the
chip (the controls), and ``benchmark/tests`` plant each on the CPU. Each
breaks the timed path or a guarantee the configuration states; the run's
comparison with the reference must then come out false.

- ``replication_gap`` (the control of the replicated cells): replica b
  unsubscribes from the shared topic for the window and resubscribes
  before the check, so b misses acknowledged writes (the guarantee that
  replicas converge is broken).
- ``lost_write``: a write the node acknowledged is gone from its engine
  (a step that leaves its state unchanged).
- ``altered_answer``: the value of a key the window reads is changed
  under node a's server, so its GETs answer the wrong bytes (an answer
  altered where it is produced).
- ``half_batch``: the device tree applies only the first half of each
  scatter batch (half of the batch left out). The mirrors' integrity scrub
  is turned off for the run: it would find the dropped leaves and rebuild
  the tree from the engine before the check.
- ``no_rejoin`` (the control of the repair cells): replica b's
  ``REPLICATE enable`` and ``SYNC`` do nothing, so b never rejoins and is
  never repaired.
- ``lost_repair``: after each SYNC reply one key the SYNC repaired is
  dropped from the initiator's engine (an answer altered where it is
  produced).
"""

from __future__ import annotations


class ReplicationGap:
    def before_window(self, ctx) -> None:
        self._b = ctx.cluster["b"]
        if self._b.client.replicate("disable") != "OK":
            raise RuntimeError("REPLICATE disable failed")

    def before_check(self, ctx) -> None:
        if self._b.client.replicate("enable") != "OK":
            raise RuntimeError("REPLICATE enable failed")


class LostWrite:
    def before_check(self, ctx) -> None:
        # The last acknowledged SET of the window is undone in a's engine.
        sched, res = ctx.readings["sched"], ctx.readings["results"]
        acked = sched.key[(sched.kind == 1) & (res["status"] == 0)]
        i = int(acked[-1])
        ctx.cluster["a"].served.engine.set(ctx.ds.keys[i], ctx.ds.value(i, 0))


class AlteredAnswer:
    def before_window(self, ctx) -> None:
        # A key the window reads and never writes, so the change stands.
        sched = ctx.readings["sched"]
        written = set(sched.key[sched.kind == 1].tolist())
        reads = sched.key[(sched.kind == 0) & (sched.due >= 0)].tolist()
        i = next(k for k in reads if k not in written)
        ctx.cluster["a"].served.engine.set(ctx.ds.keys[i], b"X" * 32)


class HalfBatch:
    def before_window(self, ctx) -> None:
        from merklekv_tpu.merkle import incremental

        cls = incremental.DeviceMerkleState
        orig = cls._update_in_place

        def half(self, items):
            return orig(self, items[: (len(items) + 1) // 2])

        cls._update_in_place = half
        from merklekv_tpu.cluster.mirror import DeviceTreeMirror

        DeviceTreeMirror._maybe_scrub = lambda self: None


class NoRejoin:
    def before_window(self, ctx) -> None:
        from merklekv_tpu.cluster.node import ClusterNode
        from merklekv_tpu.cluster.sync import SyncManager

        ClusterNode._enable_replication = lambda self: None
        SyncManager.sync_once = lambda self, *a, **kw: None


class LostRepair:
    def before_window(self, ctx) -> None:
        from merklekv_tpu.cluster.sync import SyncManager

        orig = SyncManager.sync_once

        def lossy(manager, *a, **kw):
            report = orig(manager, *a, **kw)
            # The first key of the episode that was diverged.
            manager._engine.delete_quiet(
                ctx.ds.keys[int(ctx.episode["distinct"][0])])
            return report

        SyncManager.sync_once = lossy


FAULTS = {
    "replication_gap": ReplicationGap,
    "lost_write": LostWrite,
    "altered_answer": AlteredAnswer,
    "half_batch": HalfBatch,
    "no_rejoin": NoRejoin,
    "lost_repair": LostRepair,
}
