"""Multi-chip batch sharding.

The reference scales across GPUs by instantiating batch objects per device
and pulling work from a shared index — no inter-GPU communication at all
(src/cuda/cudapolisher.cpp:165-199,228-345; SURVEY.md §2c-5). The TPU
equivalent is simpler and declarative: one `jax.sharding.Mesh` over all
chips with a single 'batch' axis, inputs placed with a batch-sharded
`NamedSharding`, and XLA partitions the jitted kernel across chips over
ICI. The workload needs no collectives (every window/overlap is
independent), so sharding the leading axis is the complete distribution
story; multi-host runs add only file-level scatter/gather (SURVEY.md §5).
"""

from __future__ import annotations

import numpy as np


def mesh_info(worker_lanes: int = 1) -> dict:
    """The mesh posture a perf artifact was measured under — ONE schema
    shared by bench.py / synthbench / servebench, because
    tools/perfgate.py refuses cross-mesh comparisons key-by-key: a
    field added here reaches every artifact at once instead of drifting
    per tool. (windows/s on 1 chip vs 8 is a different machine, not a
    regression.)"""
    import os

    return {"n_devices": BatchRunner().n_devices,
            "worker_lanes": int(worker_lanes),
            "max_devices_env": os.environ.get(
                "RACON_TPU_MAX_DEVICES") or None}


def partition_devices(devices=None, k: int = 1) -> list[list]:
    """Split a device list into `k` contiguous, near-equal sub-lists —
    the serve layer's worker-lane partition (each lane becomes an
    independent sub-mesh with its own BatchRunner). `k` clamps to the
    device count (a lane with zero devices schedules nothing) and the
    first len(devices) % k lanes carry the extra device.

    `devices` may be an explicit list — in particular the GLOBAL
    device list of a `jax.distributed` run, the prep seam for the
    multi-host mesh (ROADMAP item 1): carving lanes from the global
    list instead of the process-local set is what lets one job's
    worker lanes span hosts. `devices=None` auto-discovers via
    `jax.devices()` (which IS the global list once jax.distributed is
    initialized, ordered by process index — so contiguous lanes stay
    host-contiguous), honoring the same RACON_TPU_MAX_DEVICES cap as
    `BatchRunner`."""
    if devices is None:
        import os

        import jax

        devices = jax.devices()
        cap = int(os.environ.get("RACON_TPU_MAX_DEVICES", "0") or 0)
        if cap > 0:
            devices = devices[:cap]
    devices = list(devices)
    k = max(1, min(int(k), len(devices)))
    base, extra = divmod(len(devices), k)
    out, start = [], 0
    for i in range(k):
        n = base + (1 if i < extra else 0)
        out.append(devices[start:start + n])
        start += n
    return out


class BatchRunner:
    """Runs batched kernels with the leading axis sharded over all devices.

    On a single device this degrades to plain dispatch with zero overhead;
    on N devices each chip receives B/N rows of every operand.
    """

    def __init__(self, devices=None):
        import os

        import jax

        if devices is not None:
            self.devices = list(devices)  # explicit list: caller decides
        else:
            self.devices = jax.devices()
            # RACON_TPU_MAX_DEVICES caps the auto-discovered mesh
            # (operators pinning chips; tests that don't exercise
            # sharding keep the 8-virtual-device CPU mesh from
            # multiplying their sequential work)
            cap = int(os.environ.get("RACON_TPU_MAX_DEVICES", "0") or 0)
            if cap > 0:
                self.devices = self.devices[:cap]
        if len(self.devices) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec

            self.mesh = Mesh(np.array(self.devices), ("batch",))
            self.sharding = NamedSharding(self.mesh, PartitionSpec("batch"))
        else:
            self.mesh = None
            self.sharding = None
        self._wrapped: dict = {}
        self._subs: dict[int, "BatchRunner"] = {}

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def round_batch(self, batch: int) -> int:
        """Smallest multiple of n_devices >= batch (so shards are equal)."""
        n = self.n_devices
        return ((batch + n - 1) // n) * n

    def for_batch(self, batch: int) -> "BatchRunner":
        """The runner a batch of `batch` rows should dispatch through:
        this runner when the batch fills the mesh, else a cached
        SUB-MESH over the first `batch` devices — so a tail batch
        smaller than the mesh ships with ZERO padding lanes instead of
        rounding up to the full device count (`round_batch` padding
        waste grows with slice size; a 3-row tail on an 8-chip slice
        would burn 5 whole padded lanes). Per-row results are
        independent of batch composition, so the output is
        byte-identical either way (dryrun-pinned)."""
        n = self.n_devices
        if batch >= n or batch < 1 or n == 1:
            return self
        sub = self._subs.get(batch)
        if sub is None:
            sub = self._subs[batch] = BatchRunner(
                devices=self.devices[:batch])
        return sub

    def run_split(self, fn, *arrays):
        """Manual per-device batch split for kernels whose grid is
        sequential per core (the Pallas resident kernels): each chip
        gets B/N rows dispatched async — the multi-GPU batch-per-device
        loop of cudapolisher.cpp:228-345, shared by BOTH kernel planes
        (DeviceGraphPOA._run_pallas, align.BatchAligner). The leading
        dim must be a multiple of n_devices (round_batch). Returns the
        kernel's output directly on one device, else the list of
        per-shard outputs in device order (caller concatenates).

        ALL shards are placed before the first kernel call: device_put
        is async, so shard k+1's host->device transfer overlaps shard
        k's compute instead of serializing transfer/dispatch per device
        (the old interleaved loop paid the full transfer latency on the
        dispatch path for every device after the first). Concatenating
        the per-shard outputs in device order is identical to the
        single-device result row-for-row (test-pinned)."""
        if len(self.devices) == 1:
            return fn(*arrays)
        import jax

        per = arrays[0].shape[0] // len(self.devices)
        placed = [[jax.device_put(a[i * per:(i + 1) * per], d)
                   for a in arrays]
                  for i, d in enumerate(self.devices)]
        return [fn(*ops) for ops in placed]

    def run(self, fn, *arrays, out_batch_axes=0, donate_argnums=()):
        """Invoke jitted `fn` on operands whose leading dim is the batch.

        All operands must share the same leading dimension, divisible by
        the device count (use round_batch + padding). `out_batch_axes`
        names the batch axis of each output: an int when every output
        carries the batch on the same axis, or a tuple with one entry per
        output of a tuple-returning kernel. `donate_argnums` is applied
        to the outer jit on accelerator backends (state-carrying kernels
        chain calls without duplicating their buffers); ignored on the
        CPU test backend, which cannot donate.

        Multi-device dispatch goes through `shard_map`, so each device
        runs an INDEPENDENT copy of the program on its batch shard — no
        cross-device communication exists in the compiled module. Plain
        sharded-jit would instead let XLA turn batch-wide reductions
        (e.g. a while-loop's `jnp.any` exit test) into all-reduces, and
        with several async batches in flight those collectives can
        interleave across programs and deadlock the per-device rendezvous
        (observed as an abort on the 8-virtual-device CPU test mesh; the
        workload needs no collectives, per SURVEY.md §2c-5, so none
        should be emitted). Per-shard loop exits are semantically
        identical: finished lanes iterate as no-ops either way.
        """
        import jax

        if self.sharding is None:
            return fn(*arrays)
        donate = (tuple(donate_argnums)
                  if jax.default_backend() == "tpu" else ())
        key = (fn, len(arrays), out_batch_axes, donate)
        shard_fn = self._wrapped.get(key)
        if shard_fn is None:
            from jax.sharding import PartitionSpec

            def axis_spec(axis: int) -> PartitionSpec:
                return PartitionSpec(*([None] * axis + ["batch"]))

            spec = PartitionSpec("batch")
            if isinstance(out_batch_axes, int):
                out_specs = axis_spec(out_batch_axes)
            else:
                out_specs = tuple(axis_spec(a) for a in out_batch_axes)
            # check_vma off: the kernels mix literal-initialized and
            # data-derived loop carries, which the varying-axes checker
            # rejects even though every output is plainly batch-sharded
            smapped = jax.shard_map(fn, mesh=self.mesh,
                                    in_specs=(spec,) * len(arrays),
                                    out_specs=out_specs, check_vma=False)
            shard_fn = jax.jit(smapped, donate_argnums=donate)
            self._wrapped[key] = shard_fn
        placed = [jax.device_put(a, self.sharding) for a in arrays]
        return shard_fn(*placed)
