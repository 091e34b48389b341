"""JaxExecutor — device-resident XLA collectives for classified CommPlans.

This is the backend the planner's pattern classification exists for.
Three properties make it fast where the paper's runtime is fast
(§4.2: only the sections that must move, overlap for the rest):

**Residency.**  Shards live as one ``(nproc, *shape)`` jax array per
HDArray, sharded over a 1-D host-device mesh
(``launch.mesh.make_host_mesh``), and STAY on device across steps.
The numpy host mirrors of the Sim layout become a lazy, dirty-tracked
cache: they materialize only on ``read`` / ``write`` / non-traceable
kernels / the reduce local fold (the oracle-parity paths), so a
steady-state step does zero ``np.stack`` / ``device_put`` /
``device_get``.  ``h2d_transfers`` / ``d2h_transfers`` count the
full-buffer crossings — benchmarks and tests assert they stay flat
while a pipeline runs.

**Plan fusion.**  ``execute_plan`` traces ALL arrays' messages of a
CommPlan into ONE jitted ``shard_map`` program (cached by a plan-level
structure signature, inputs donated so updates are in place), so a
plan reused via the §4.2 cache replays a single already-compiled
dispatch instead of one program per array per kind.  (Exception: the
XLA *cpu* host platform serializes multiple in-program collective
rendezvous pathologically, so there a multi-collective plan runs as
one cached dispatch per collective, chained through the donated
resident buffers — see :meth:`JaxExecutor._build_plan_program`.)
Per-kind lowering, inside ``shard_map`` over axis ``p``:

=============  =====================================================
CommKind       lowering (inside ``shard_map`` over axis ``p``)
=============  =====================================================
ALL_GATHER     one ``jax.lax.all_gather`` of each sender's section,
               receivers scatter the gathered slabs into their buffer
HALO           one ``jax.lax.ppermute`` per direction (forward /
               backward neighbor shift), like the paper's ghost-cell
               exchange
ALL_TO_ALL     per-destination chunks stacked and exchanged with one
               ``jax.lax.all_to_all``
P2P            the message list decomposed into shift-bucketed
               partial-permutation rounds, one ``ppermute`` per round
=============  =====================================================

Sections are rectangular boxes at per-rank offsets: each rank
``dynamic_slice``s its send box (start indices gathered from a
per-rank table by ``axis_index``), the collective moves the slabs, and
each receiver ``dynamic_update_slice``s the payload at its recv
offset, masked so ranks without a message keep their buffer
bit-identical.  A mixed-shape message round is padded to ONE common
slab shape (per-rank extent masks carve the real payload back out), so
it costs one ``ppermute`` per permutation round instead of one per
distinct shape.

**On-device kernels.**  A kernel marked with
:func:`~repro.executors.kernels.device_kernel` is traced — once per
(kernel, regions) signature — into a jitted per-device program over
the resident stacked arrays, so a ``run_pipeline`` of Jacobi/GEMM
steps never leaves the device.  Unmarked (in-place numpy) kernels fall
back to the host mirrors, exactly the Sim semantics.

**One-program steps.**  ``execute_step`` goes one step further: the
plan's exchange AND the device kernel are traced into a SINGLE jitted
shard_map program per step signature.  When the plan admits the exact
interior/boundary work split
(:func:`~repro.executors.overlap.halo_split`), the interior kernel
sweep is ordered before the ppermute payloads land — it has no data
dependency on them, so XLA overlaps ghost-cell exchange with interior
compute inside the one program (the device-level analogue of the host
overlap scheduler, bit-identical to it by the same exactness
argument).  The runtime counts these as ``PlannerStats.fused_steps``.

**Captured pipelines.**  ``capture_cycle`` compiles a verified
steady-state cycle (every step's plan a §4.2 cache hit and its commit
a fingerprint replay for two full periods) into ONE jitted
``lax.scan`` over ``reps`` repetitions with donated carries: K more
steps of the pipeline become one dispatch, and the per-step host
dispatch count (``PlannerStats.python_dispatches_per_step``) drops to
zero.  The scan body chains the same step tracers the fused step
programs use, so the result stays bit-identical to the unfused
oracle.

``HDArrayReduce`` keeps the oracle split: the local fold runs on the
host mirrors (one d2h sync when the device copy is newer) and the
global combine is a REAL collective — ``lax.psum`` / ``pmax`` /
``pmin`` (prod via ``all_gather`` + fold; jax has no ``pprod``) over
the per-rank partials, cached per (op, dtype, nproc) and counted in
``collective_counts`` under the logical op name.

``resident=False`` restores the pre-residency behavior — every
``execute_messages`` stages host mirrors up, runs the collective, and
copies results back down — and exists so the residency benchmark can
measure exactly what the round-trip used to cost.

Thread safety: device state (the resident arrays + their dirty flags)
is guarded by one reentrant lock, so the §4.2 overlap scheduler may
run message execution on its comm thread while kernels dispatch from
the host thread.  The overlap safety conditions guarantee those touch
disjoint arrays, so serialized *dispatch* under the lock keeps results
bit-identical while XLA still overlaps the actual compute.
"""
from __future__ import annotations

import threading
from typing import (TYPE_CHECKING, Any, Callable, Dict, List, Optional,
                    Sequence, Tuple)

import numpy as np

from .base import register_executor
from .kernels import resolve_kernel
from .sim import SimExecutor

if TYPE_CHECKING:
    from repro.core.hdarray import HDArray
    from repro.core.planner import CommKind, CommPlan
    from repro.core.sections import SectionSet

# one flattened message: (src rank, dst rank, Box)
Msg = Tuple[int, int, Any]


def _reduce_identity(op: str, dtype: np.dtype):
    """The op's identity element — the fill for ranks with no partial."""
    if op == "sum":
        return dtype.type(0)
    if op == "prod":
        return dtype.type(1)
    if np.issubdtype(dtype, np.floating):
        return dtype.type(-np.inf) if op == "max" else dtype.type(np.inf)
    info = np.iinfo(dtype)
    return dtype.type(info.min) if op == "max" else dtype.type(info.max)


def cpu_host_platform() -> bool:
    """True on the XLA *cpu* host platform, where multi-collective plans
    run staged (one dispatch per collective) and kernel-only steps run
    per shard — see :meth:`JaxExecutor._build_plan_program` and
    :meth:`JaxExecutor._build_pershard_kernel`.  Elsewhere a plan or a
    step is ONE shard_map program and kernels sweep by ``lax.switch``.
    Every such branch asks this one predicate, so a CPU test can
    patch it to run the accelerator program shapes."""
    import jax

    return jax.default_backend() == "cpu"


def _decompose_rounds(msgs: Sequence[Msg], nproc: int) -> List[List[Msg]]:
    """Decompose a message list into rounds in which every rank sends
    and receives at most once — each round a valid ``ppermute``
    permutation.

    Messages are bucketed by rank shift ``(dst - src) mod nproc`` (plus
    an occurrence index for multi-box pairs): two messages with one
    shift and distinct sources necessarily have distinct destinations,
    so every bucket is a partial permutation.  O(msgs), replacing the
    old greedy O(msgs²) packing; a halo plan still lands in exactly one
    round per direction.
    """
    buckets: Dict[Tuple[int, int], List[Msg]] = {}
    occ: Dict[Tuple[int, int], int] = {}
    for m in msgs:
        s, d, _b = m
        k = occ.get((s, d), 0)
        occ[(s, d)] = k + 1
        buckets.setdefault(((d - s) % nproc, k), []).append(m)
    return [buckets[k] for k in sorted(buckets)]


@register_executor("jax")
class JaxExecutor(SimExecutor):
    """Backend lowering planner messages to XLA collectives over
    device-resident shards."""

    def __init__(self, nproc: Optional[int] = None, axis: str = "p",
                 resident: bool = True) -> None:
        super().__init__(nproc=nproc)
        # jax must be FULLY imported here, on the constructing thread:
        # under overlap=True the comm thread and the host thread would
        # otherwise race each other through jax's lazy circular imports
        # on the first step and deadlock.  Importing the modules does
        # NOT initialize backends or lock the device count.
        import jax  # noqa: F401
        import jax.sharding  # noqa: F401
        self.axis = axis
        self.resident = resident
        # how many of each collective this executor has ISSUED (per
        # traced collective op); the psum family counts reduce combines
        # by their logical op
        self.collective_counts: Dict[str, int] = {
            "all_gather": 0, "all_to_all": 0, "ppermute": 0,
            "psum": 0, "pprod": 0, "pmax": 0, "pmin": 0}
        # full-buffer host<->device crossings (the residency meters:
        # steady-state resident steps move NOTHING; reduce combines and
        # other scalar traffic are not full buffers and do not count)
        self.h2d_transfers: int = 0
        self.d2h_transfers: int = 0
        self.device_kernel_launches: int = 0
        self._mesh = None
        self._sharding = None
        # structure signature -> (jitted program, counts delta)
        self._programs: Dict[tuple, Tuple[Callable, Dict[str, int]]] = {}
        # step signature -> halo_split result (pure section algebra
        # over a steady plan — identical every hit, costly to redo)
        self._splits: Dict[tuple, Any] = {}
        # (fn, input avals, meta) of the most recent fused step / scan
        # program — the roofline report hook (last_program_lowered)
        self._last_program: Optional[tuple] = None
        # name -> resident (nproc, *shape) sharded array + dirty flags
        self._device: Dict[str, Any] = {}
        self._device_ok: Dict[str, bool] = {}
        self._host_ok: Dict[str, bool] = {}
        self._device_class: Optional[str] = None
        self._lock = threading.RLock()

    @property
    def device_class(self) -> str:  # type: ignore[override]
        """Kernel-variant resolution key: the jax platform name
        ("cpu"/"gpu"/"tpu").  Resolved lazily — ``default_backend()``
        initializes the backend, which must come after
        ``ensure_host_devices`` — and only at execute/trace time, the
        same moment the device paths first touch the backend anyway."""
        if self._device_class is None:
            import jax

            self._device_class = jax.default_backend()
        return self._device_class

    # -- mesh -----------------------------------------------------------
    def _ensure_mesh(self, nproc: int):
        if self._mesh is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from repro.launch.mesh import make_host_mesh

            self._mesh = make_host_mesh(nproc, axis=self.axis)
            self._sharding = NamedSharding(self._mesh, P(self.axis))
        return self._mesh

    # -- residency hooks (Executor protocol) ----------------------------
    def sync_host(self, arr: "HDArray") -> None:
        """Materialize the host mirrors from the resident device copy
        (one d2h when the device side is newer; no-op otherwise)."""
        with self._lock:
            self._to_host(arr.name)

    def sync_device(self, arr: "HDArray") -> None:
        """Stage the host mirrors up into the resident device copy
        (one h2d when the host side is newer; no-op otherwise)."""
        with self._lock:
            self._to_device(arr)

    def shard_devices(self, arr: "HDArray") -> list:
        """The devices holding ``arr``'s resident shards, in rank order
        (the host copy is staged up first when it is newer)."""
        with self._lock:
            self._to_device(arr)
            shards = self._device[arr.name].addressable_shards
            return [s.device for s in
                    sorted(shards, key=lambda s: s.index[0].start or 0)]

    def _to_host(self, name: str) -> None:
        if self._host_ok.get(name, True):
            return
        import jax

        stacked = np.array(jax.device_get(self._device[name]))
        self.buffers[name] = list(stacked)   # per-rank writable views
        self._host_ok[name] = True
        self.d2h_transfers += 1

    def _to_device(self, arr: "HDArray") -> None:
        name = arr.name
        if self._device_ok.get(name, False):
            return
        import jax

        self._ensure_mesh(arr.nproc)
        stacked = np.stack(self.buffers[name])
        self._device[name] = jax.device_put(stacked, self._sharding)
        self._device_ok[name] = True
        self.h2d_transfers += 1

    @staticmethod
    def _donate(n: int) -> tuple:
        # buffer donation lets XLA alias the resident input allocations
        # to the outputs (in-place updates: a section write stops
        # costing a full-buffer copy).  Donated inputs are invalidated,
        # which is exactly right — every caller immediately replaces
        # its self._device entries with the program outputs.
        return tuple(range(n))

    # -- lifecycle ------------------------------------------------------
    def allocate(self, arr: "HDArray") -> None:
        super().allocate(arr)
        with self._lock:
            self._device.pop(arr.name, None)
            self._host_ok[arr.name] = True
            self._device_ok[arr.name] = False

    def free(self, arr: "HDArray") -> None:
        super().free(arr)
        with self._lock:
            self._device.pop(arr.name, None)
            self._host_ok.pop(arr.name, None)
            self._device_ok.pop(arr.name, None)

    def drop_rank(self, arr: "HDArray", rank: int) -> None:
        """Simulated device loss: pull the survivors' state down to the
        host mirrors, poison the dead rank's mirror (Sim semantics), and
        invalidate the resident copy — the recovery path re-stages the
        array with sync_device after the restore write."""
        with self._lock:
            self.sync_host(arr)
            super().drop_rank(arr, rank)
            self._device_ok[arr.name] = False

    def add_rank(self, arr: "HDArray", rank: int) -> None:
        """Simulated device (re)join: pull current state down to the
        host mirrors, zero the joining rank's mirror (its old resident
        bytes are untrusted), and invalidate the resident copy — the
        grow repartition's planned messages hand it real sections and
        the next sync_device re-stages the stacked array.  The jax mesh
        itself is fixed at nproc, so a join within the original
        allocation is purely a buffer/residency event."""
        with self._lock:
            self.sync_host(arr)
            super().add_rank(arr, rank)
            self._device_ok[arr.name] = False

    # -- controller I/O (host-mirror paths) -----------------------------
    def write(self, arr: "HDArray", data: np.ndarray,
              per_device: Sequence["SectionSet"]) -> None:
        with self._lock:
            self.sync_host(arr)
            super().write(arr, data, per_device)
            self._device_ok[arr.name] = False

    def read(self, arr: "HDArray",
             per_device: Sequence["SectionSet"]) -> np.ndarray:
        with self._lock:
            self.sync_host(arr)
            return super().read(arr, per_device)

    # -- protocol: message execution ------------------------------------
    def execute_messages(self, arr: "HDArray",
                         messages: Dict[Tuple[int, int], "SectionSet"],
                         kind: Optional["CommKind"] = None) -> None:
        msgs: List[Msg] = [
            (src, dst, box)
            for (src, dst), secs in sorted(messages.items())
            for box in secs  # canonical SectionSets hold no empty boxes
        ]
        if not msgs:
            return
        if self.resident:
            self._execute_fused([(arr, msgs, kind)])
        else:
            self._execute_legacy(arr, msgs, kind)

    @staticmethod
    def _plan_groups(plan: "CommPlan",
                     arrays_by_name: Dict[str, "HDArray"]
                     ) -> List[Tuple["HDArray", List[Msg], Any]]:
        """Flatten a CommPlan into per-array (array, messages, kind)
        groups — the unit every fused program is lowered from."""
        groups: List[Tuple["HDArray", List[Msg], Any]] = []
        for ap in plan.arrays:
            if not ap.messages:
                continue
            arr = arrays_by_name[ap.array]
            msgs = [(src, dst, box)
                    for (src, dst), secs in sorted(ap.messages.items())
                    for box in secs]
            if msgs:
                groups.append((arr, msgs, ap.kind))
        return groups

    def execute_plan(self, plan: "CommPlan",
                     arrays_by_name: Dict[str, "HDArray"]) -> None:
        """One fused jitted dispatch for ALL arrays with traffic."""
        groups = self._plan_groups(plan, arrays_by_name)
        if not groups:
            return
        if self.resident:
            self._execute_fused(groups)
        else:
            for arr, msgs, kind in groups:
                self._execute_legacy(arr, msgs, kind)

    def _execute_fused(self, groups) -> None:
        import jax  # noqa: F401  (device backend must be importable)

        with self._lock:
            self._ensure_mesh(groups[0][0].nproc)
            for arr, _msgs, _kind in groups:
                self.sync_device(arr)
            sig = tuple(
                (arr.shape, arr.dtype.str, arr.nproc, kind,
                 tuple((s, d, b.bounds) for s, d, b in msgs))
                for arr, msgs, kind in groups)
            prog = self._programs.get(sig)
            if prog is None:
                prog = self._build_plan_program(groups)
                self._programs[sig] = prog
            stages, counts = prog
            devs = [self._device[arr.name] for arr, _m, _k in groups]
            for gi, fn in stages:
                if gi is None:              # one fused program, all arrays
                    devs = list(fn(*devs))
                else:                        # staged dispatch, one array
                    devs[gi] = fn(devs[gi])
            for (arr, msgs, _kind), out in zip(groups, devs):
                self._device[arr.name] = out
                self._host_ok[arr.name] = False
                itemsize = arr.itemsize
                for _s, _d, box in msgs:
                    self.bytes_moved += box.volume() * itemsize
                    self.messages_executed += 1
            for k, v in counts.items():
                self.collective_counts[k] += v

    def _execute_legacy(self, arr: "HDArray", msgs: List[Msg],
                        kind: Optional["CommKind"]) -> None:
        """Pre-residency round trip: stack the host mirrors, one
        device_put, run the collective program, one device_get, copy
        the received sections back into the mirrors."""
        import jax

        with self._lock:
            self._ensure_mesh(arr.nproc)
            sig = ("legacy", arr.shape, arr.dtype.str, arr.nproc, kind,
                   tuple((s, d, b.bounds) for s, d, b in msgs))
            prog = self._programs.get(sig)
            if prog is None:
                prog = self._build_plan_program([(arr, msgs, kind)])
                self._programs[sig] = prog
            stages, counts = prog
            stacked = np.stack(self.buffers[arr.name])
            self.h2d_transfers += 1
            val = jax.device_put(stacked, self._sharding)
            for _gi, fn in stages:           # single array: gi is 0/None
                val = fn(val) if _gi is not None else fn(val)[0]
            out = np.asarray(jax.device_get(val))
            self.d2h_transfers += 1
            bufs = self.buffers[arr.name]
            # write back ONLY the received sections: everything else is
            # untouched by the program, and the overlap scheduler may be
            # running the interior kernel sweep on those regions now
            for _s, d, box in msgs:
                sl = box.to_slices()
                bufs[d][sl] = out[d][sl]
                self.bytes_moved += box.volume() * arr.itemsize
                self.messages_executed += 1
            for k, v in counts.items():
                self.collective_counts[k] += v

    # -- lowering -------------------------------------------------------
    def _build_plan_program(self, groups):
        """Trace + jit the collective program(s) for a whole plan.

        Each array's message set lowers to (collect, apply) pairs —
        ``collect`` slices the send payload from the PRE-exchange state
        and runs the collective, ``apply`` scatters the received
        payload.  Issuing every collect before any apply keeps the
        collectives dependency-free, which is sound because the planner
        guarantees a device's send boxes are disjoint from its recv
        boxes (at most one device holds the pending coherent copy of
        any element — `HDArray._supersede`).

        On real accelerators the whole plan is ONE shard_map program (a
        single cached dispatch with buffer donation).  The XLA *cpu*
        host-platform backend serializes multiple in-program collective
        rendezvous pathologically (~10x each), so there the plan runs
        as one jitted dispatch PER collective, chained through the
        donated device buffers — still resident, still one cache entry
        per plan signature, zero host round-trips between stages.
        Either way the cache value is a stage list ``[(group_index or
        None, fn)]``.
        """
        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        per_group, counts = self._lower_groups(groups)
        n_coll = sum(len(s) for s in per_group)
        if n_coll > 1 and cpu_host_platform():
            stages = []
            for gi, steps in enumerate(per_group):
                for collect, apply in steps:
                    def body1(xb, _c=collect, _a=apply):
                        idx = jax.lax.axis_index(axis)
                        x = xb[0]
                        return _a(x, _c(x, idx), idx)[None]
                    stages.append((gi, jax.jit(jax.shard_map(
                        body1, mesh=self._mesh, in_specs=P(axis),
                        out_specs=P(axis), check_vma=False),
                        donate_argnums=(0,))))
            return stages, counts

        def body(*xbs):
            # xbs: each array's (1, *shape) block of its stacked buffer
            idx = jax.lax.axis_index(axis)
            xs = [xb[0] for xb in xbs]
            # every collective reads the pre-exchange state ...
            payloads = [[collect(x, idx) for collect, _a in steps]
                        for x, steps in zip(xs, per_group)]
            # ... then every payload lands
            outs = []
            for x, steps, pls in zip(xs, per_group, payloads):
                for (_c, apply), pl in zip(steps, pls):
                    x = apply(x, pl, idx)
                outs.append(x[None])
            return tuple(outs)

        k = len(groups)
        fn = jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=tuple(P(axis) for _ in range(k)),
            out_specs=tuple(P(axis) for _ in range(k)),
            check_vma=False), donate_argnums=self._donate(k))
        return [(None, fn)], counts

    def _lower_groups(self, groups):
        """Lower each (array, msgs, kind) group to its (collect, apply)
        closure pairs — shared by the plan, fused-step and captured-scan
        program builders.  Returns ``(per_group, counts)``."""
        from repro.core.planner import CommKind as CK

        counts = {"all_gather": 0, "all_to_all": 0, "ppermute": 0}
        per_group: List[List[Tuple[Callable, Callable]]] = []
        for arr, msgs, kind in groups:
            steps: List[Tuple[Callable, Callable]] = []
            if kind == CK.ALL_GATHER and self._gather_structure(msgs, arr.nproc):
                steps.append(self._lower_all_gather(arr, msgs))
                counts["all_gather"] += 1
            elif kind == CK.ALL_TO_ALL and self._a2a_structure(msgs, arr.nproc):
                steps.append(self._lower_all_to_all(arr, msgs))
                counts["all_to_all"] += 1
            else:
                # HALO lands here naturally: its two directional sweeps
                # are the two shift buckets, one ppermute per direction.
                for rnd in _decompose_rounds(msgs, arr.nproc):
                    steps.append(self._lower_ppermute_round(arr, rnd))
                    counts["ppermute"] += 1
            per_group.append(steps)
        return per_group, counts

    # -- structure checks ----------------------------------------------
    @staticmethod
    def _gather_structure(msgs: List[Msg], nproc: int) -> bool:
        """True iff each sender ships ONE box, identical for all of its
        receivers, and all senders' boxes share a shape — the layout
        ``lax.all_gather`` moves in one op."""
        per_src: Dict[int, Any] = {}
        for s, _d, b in msgs:
            if s in per_src and per_src[s] != b:
                return False
            per_src[s] = b
        shapes = {b.shape() for b in per_src.values()}
        return len(shapes) == 1

    @staticmethod
    def _a2a_structure(msgs: List[Msg], nproc: int) -> bool:
        """True iff every ordered pair carries at most one box and all
        boxes share a shape — the layout ``lax.all_to_all`` moves."""
        seen = set()
        shapes = set()
        for s, d, b in msgs:
            if (s, d) in seen:
                return False
            seen.add((s, d))
            shapes.add(b.shape())
        return len(shapes) == 1

    # -- per-kind lowerings ---------------------------------------------
    def _lower_all_gather(self, arr: "HDArray", msgs: List[Msg]) -> Callable:
        import jax
        import jax.numpy as jnp

        nproc, nd, axis = arr.nproc, arr.ndim, self.axis
        per_src = {s: b for s, _d, b in msgs}
        slab_shape = next(iter(per_src.values())).shape()
        send_starts = np.zeros((nproc, nd), np.int32)
        for s, b in per_src.items():
            send_starts[s] = [lo for lo, _hi in b.bounds]
        recv_mask = np.zeros((nproc, nproc), bool)      # [src, dst]
        for s, d, _b in msgs:
            recv_mask[s, d] = True
        starts_c = jnp.asarray(send_starts)
        mask_c = jnp.asarray(recv_mask)

        def collect(x, idx):
            slab = jax.lax.dynamic_slice(
                x, tuple(starts_c[idx, d] for d in range(nd)), slab_shape)
            return jax.lax.all_gather(slab, axis, axis=0, tiled=False)

        def apply(x, g, idx):
            for s, b in sorted(per_src.items()):
                pos = tuple(int(lo) for lo, _hi in b.bounds)
                # mask at SLAB granularity: non-receivers write their
                # own bits back, so the program never materializes a
                # full-buffer select per sender
                cur = jax.lax.dynamic_slice(x, pos, slab_shape)
                payload = jnp.where(mask_c[s, idx], g[s], cur)
                x = jax.lax.dynamic_update_slice(x, payload, pos)
            return x

        return collect, apply

    def _lower_all_to_all(self, arr: "HDArray", msgs: List[Msg]) -> Callable:
        import jax
        import jax.numpy as jnp

        nproc, nd, axis = arr.nproc, arr.ndim, self.axis
        slab_shape = msgs[0][2].shape()
        # starts[s, d]: where the (s -> d) box lives; the section is the
        # same global region on both ends (full-size device buffers)
        starts = np.zeros((nproc, nproc, nd), np.int32)
        mask = np.zeros((nproc, nproc), bool)
        for s, d, b in msgs:
            starts[s, d] = [lo for lo, _hi in b.bounds]
            mask[s, d] = True
        starts_c = jnp.asarray(starts)
        mask_c = jnp.asarray(mask)

        def collect(x, idx):
            chunks = [jax.lax.dynamic_slice(
                x, tuple(starts_c[idx, q, d] for d in range(nd)), slab_shape)
                for q in range(nproc)]
            st = jnp.stack(chunks)                       # (P, *slab)
            return jax.lax.all_to_all(st, axis, split_axis=0, concat_axis=0,
                                      tiled=False)

        def apply(x, rt, idx):
            # rt[s] = the chunk rank s addressed to me; slab-level mask
            # (see _lower_all_gather) keeps non-receivers copy-free
            for s in range(nproc):
                pos = tuple(starts_c[s, idx, d] for d in range(nd))
                cur = jax.lax.dynamic_slice(x, pos, slab_shape)
                payload = jnp.where(mask_c[s, idx], rt[s], cur)
                x = jax.lax.dynamic_update_slice(x, payload, pos)
            return x

        return collect, apply

    def _lower_ppermute_round(self, arr: "HDArray", rnd: List[Msg]) -> Callable:
        """One ppermute moving every message of a partial permutation.

        Mixed-shape rounds are padded to one common slab shape: each
        sender slices a max-shape slab positioned over its box (start
        clamped to stay in bounds — the payload keeps the SAME offset
        inside the slab on both ends, because a message box is one
        global section), and each receiver blends the payload back out
        with a per-rank extent mask before updating its buffer.
        Uniform-shape rounds (halos) skip the mask entirely.
        """
        import jax
        import jax.numpy as jnp

        nproc, nd, axis = arr.nproc, arr.ndim, self.axis
        shapes = {b.shape() for _s, _d, b in rnd}
        slab = tuple(max(sh[d] for sh in shapes) for d in range(nd))
        uniform = len(shapes) == 1
        perm = [(s, d) for s, d, _b in rnd]
        send_starts = np.zeros((nproc, nd), np.int32)
        recv_starts = np.zeros((nproc, nd), np.int32)
        recv_off = np.zeros((nproc, nd), np.int32)
        recv_ext = np.zeros((nproc, nd), np.int32)
        recv_mask = np.zeros((nproc,), bool)
        for s, d, b in rnd:
            lows = [lo for lo, _hi in b.bounds]
            # clamp so the padded slab stays inside the buffer; the box
            # then sits at offset (low - start) within the slab — the
            # same value on the send and recv side
            start = [min(l, arr.shape[dd] - slab[dd])
                     for dd, l in enumerate(lows)]
            send_starts[s] = start
            recv_starts[d] = start
            recv_off[d] = [l - st for l, st in zip(lows, start)]
            recv_ext[d] = b.shape()
            recv_mask[d] = True
        ss_c = jnp.asarray(send_starts)
        rs_c = jnp.asarray(recv_starts)
        off_c = jnp.asarray(recv_off)
        ext_c = jnp.asarray(recv_ext)
        rm_c = jnp.asarray(recv_mask)

        def collect(x, idx):
            sent = jax.lax.dynamic_slice(
                x, tuple(ss_c[idx, d] for d in range(nd)), slab)
            return jax.lax.ppermute(sent, axis, perm)

        def apply(x, recv, idx):
            # masking happens at SLAB granularity (non-receivers blend
            # their own bits back and write them in place), never as a
            # full-buffer select
            cur = jax.lax.dynamic_slice(
                x, tuple(rs_c[idx, d] for d in range(nd)), slab)
            if uniform:
                payload = jnp.where(rm_c[idx], recv, cur)
            else:
                m = None
                for d in range(nd):
                    io = jax.lax.broadcasted_iota(jnp.int32, slab, d)
                    md = ((io >= off_c[idx, d])
                          & (io < off_c[idx, d] + ext_c[idx, d]))
                    m = md if m is None else m & md
                # ext is zero for non-receivers: m masks them out too
                payload = jnp.where(m, recv, cur)
            return jax.lax.dynamic_update_slice(
                x, payload, tuple(rs_c[idx, d] for d in range(nd)))

        return collect, apply

    # -- kernels --------------------------------------------------------
    def run_kernel(self, kernel: Callable, part_regions, arrays,
                   defs=None, **kw) -> None:
        """Device-marked kernels run as a jitted per-device program over
        the resident shards; anything else falls back to the host
        mirrors (one d2h per stale array), exactly the Sim semantics.
        ``defs`` (the def-clause array names) bounds the invalidation:
        only arrays the kernel may write lose their device copy —
        read-only inputs stay resident.  Without it every touched array
        is conservatively invalidated."""
        kernel = resolve_kernel(kernel, self.device_class)
        if self.resident and getattr(kernel, "__hdarray_device__", False):
            self._run_kernel_device(kernel, part_regions, arrays, **kw)
            return
        with self._lock:
            for a in arrays:
                self.sync_host(a)
        # the kernel itself runs outside the lock: in the overlap
        # halo-split schedule it touches arrays disjoint from the
        # in-flight message set, so mirror mutation is race-free
        super().run_kernel(kernel, part_regions, arrays, **kw)
        stale = set(defs) if defs is not None else {a.name for a in arrays}
        with self._lock:
            for a in arrays:
                if a.name in stale:
                    self._device_ok[a.name] = False

    def _run_kernel_device(self, kernel, part_regions, arrays, **kw) -> None:
        import jax

        # fused device sweeps have no per-rank host timing
        self.last_rank_times = None
        with self._lock:
            self._ensure_mesh(arrays[0].nproc)
            for a in arrays:
                self.sync_device(a)
            try:
                kw_key: Any = tuple(sorted(kw.items()))
                hash((kernel, kw_key))
            except TypeError:
                kw_key = None      # unhashable kw: trace fresh each call
            pershard = cpu_host_platform()
            key = ("kernelps" if pershard else "kernel", kernel, kw_key,
                   tuple(r.bounds for r in part_regions),
                   tuple((a.name, a.shape, a.dtype.str) for a in arrays))
            prog = self._programs.get(key) if kw_key is not None else None
            if prog is None:
                prog = (self._build_pershard_kernel(kernel, part_regions,
                                                    arrays, kw)
                        if pershard else
                        self._build_kernel_program(kernel, part_regions,
                                                   arrays, kw))
                if kw_key is not None:
                    self._programs[key] = prog
            if pershard:
                _tag, rank_fns, out_names = prog
                if not out_names:
                    return
                self._dispatch_pershard(rank_fns, out_names, arrays)
            else:
                fn, out_names = prog
                if not out_names:
                    return                # kernel defines nothing
                outs = fn(*[self._device[a.name] for a in arrays])
                for name, out in zip(out_names, outs):
                    self._device[name] = out
                    self._host_ok[name] = False
            self.device_kernel_launches += 1

    def _build_kernel_program(self, kernel, part_regions, arrays, kw):
        """Jit the kernel across devices INSIDE shard_map: one
        ``lax.switch`` branch per rank, each closing over that rank's
        static work region and transforming its local slabs only.  The
        shard_map boundary is what keeps the program device-local —
        tracing the same update as a plain jit over the stacked arrays
        makes GSPMD materialize cross-device traffic on every call,
        which is exactly the round trip residency exists to delete.
        Devices are isolated (each branch reads its own PRE-kernel
        slabs), as in the OpenCL model.

        The program outputs ONLY the arrays the kernel defines
        (discovered with one abstract pre-trace per rank), so pure
        inputs never pay a copy through the jit boundary.
        """
        import jax
        from jax.sharding import PartitionSpec as P

        names = [a.name for a in arrays]
        regions = list(part_regions)
        axis = self.axis
        nproc = arrays[0].nproc
        assert len(regions) == nproc, (len(regions), nproc)

        defined = self._kernel_defined(kernel, regions, arrays, kw)
        out_names = [n for n in names if n in defined]
        if not out_names:
            return None, out_names

        def make_branch(region):
            def branch(ops):
                bufs = dict(zip(names, ops))
                if region.is_empty():
                    return tuple(bufs[n] for n in out_names)
                res = kernel(region, bufs, **kw) or {}
                return tuple(res.get(n, bufs[n]) for n in out_names)
            return branch

        branches = [make_branch(r) for r in regions]

        def body(*xbs):
            idx = jax.lax.axis_index(axis)
            out = jax.lax.switch(idx, branches,
                                 tuple(xb[0] for xb in xbs))
            return tuple(o[None] for o in out)

        donate = tuple(i for i, n in enumerate(names) if n in defined)
        fn = jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=tuple(P(axis) for _ in names),
            out_specs=tuple(P(axis) for _ in out_names),
            check_vma=False), donate_argnums=donate)
        return fn, out_names

    def _build_pershard_kernel(self, kernel, part_regions, arrays, kw):
        """Per-device jitted kernel calls instead of the one-program
        ``lax.switch`` sweep — the XLA cpu fast path for kernel-only
        dispatch.  The outputs of a ``lax.switch`` cannot alias its
        donated inputs through the branch boundary, so the one-program
        sweep pays a full-buffer copy per defined array per device on
        every step; a per-shard jit keeps the kernel's dynamic-update-
        slice in place on the donated shard (~8x on the n=1024 Jacobi
        band sweep).  Shards are read zero-copy
        (``addressable_shards``) and reassembled with
        ``make_array_from_single_device_arrays``, so the step still
        never crosses the host boundary; each rank's trace is the same
        closure a switch branch would run, on its own pre-kernel slabs.
        """
        import jax

        names = [a.name for a in arrays]
        regions = list(part_regions)
        defined = self._kernel_defined(kernel, regions, arrays, kw)
        out_names = [n for n in names if n in defined]
        if not out_names:
            return ("pershard", [], out_names)
        donate = tuple(i for i, n in enumerate(names) if n in defined)

        def make_fn(region):
            def body(*ops):
                # ops are (1, *shape) shard views; kernel sees slabs
                bufs = {n: o[0] for n, o in zip(names, ops)}
                res = kernel(region, bufs, **kw) or {}
                return tuple(res.get(n, bufs[n])[None] for n in out_names)
            return jax.jit(body, donate_argnums=donate)

        rank_fns = [None if r.is_empty() else make_fn(r) for r in regions]
        return ("pershard", rank_fns, out_names)

    def _dispatch_pershard(self, rank_fns, out_names, arrays) -> None:
        """Run per-shard kernel fns device-by-device (dispatch is
        async, so the devices still compute concurrently) and rebuild
        the resident stacked arrays from the output shards.  Caller
        holds the lock and has synced arrays to device."""
        import jax

        names = [a.name for a in arrays]
        nproc = arrays[0].nproc
        shards: Dict[str, list] = {}
        for a in arrays:
            per = [None] * nproc
            for s in self._device[a.name].addressable_shards:
                per[s.index[0].start or 0] = s.data
            shards[a.name] = per
        # drop the stacked parents of the defined arrays so the donated
        # shard buffers are single-referenced — otherwise the runtime
        # declines the donation and copies (the rebuild below restores
        # the entries before anyone can observe the gap)
        for n in out_names:
            del self._device[n]
        outs = {n: list(shards[n]) for n in out_names}
        for i, fn in enumerate(rank_fns):
            if fn is None:
                continue                    # empty region: pass-through
            res = fn(*[shards[n][i] for n in names])
            for n, o in zip(out_names, res):
                outs[n][i] = o
        by_name = {a.name: a for a in arrays}
        for n in out_names:
            shape = (nproc,) + by_name[n].shape
            self._device[n] = jax.make_array_from_single_device_arrays(
                shape, self._sharding, outs[n])
            self._host_ok[n] = False

    @staticmethod
    def _kernel_defined(kernel, regions, arrays, kw) -> set:
        """Names of the arrays the kernel defines — discovered with one
        abstract pre-trace (``jax.eval_shape``) per non-empty region."""
        import jax

        slabs = {a.name: jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in arrays}
        defined: set = set()
        for region in regions:
            if region.is_empty():
                continue
            res = jax.eval_shape(
                lambda bufs, _r=region: kernel(_r, bufs, **kw) or {}, slabs)
            defined.update(res.keys())
        return defined

    # -- fused steps & captured pipelines (one-program execution) -------
    def execute_step(self, plan, arrays_by_name, kernel, part_regions,
                     arrays, uses=None, defs=None, kw=None) -> bool:
        """One apply_kernel step as ONE device program.

        When the backend is resident and the kernel is ``device_kernel``
        -marked, the plan's exchange and the kernel sweep are traced
        into a single jitted shard_map program (cached per step
        signature).  When the step's plan admits the exact halo split
        (:func:`~repro.executors.overlap.halo_split`), the interior
        kernel sweep is ordered BEFORE the ppermute payload applies —
        it has no data dependency on them, so XLA overlaps ghost-cell
        exchange with interior compute inside the one program, the
        device-level analogue of the host overlap scheduler.  Returns
        True iff the step ran fused (the runtime counts
        ``PlannerStats.fused_steps``); everything else falls back to
        the classic two-phase path and returns False.
        """
        kw = kw or {}
        kernel = resolve_kernel(kernel, self.device_class)
        if (not self.resident or kernel is None
                or not getattr(kernel, "__hdarray_device__", False)):
            return super().execute_step(
                plan, arrays_by_name, kernel, part_regions, arrays,
                uses=uses, defs=defs, kw=kw)
        groups = self._plan_groups(plan, arrays_by_name)
        try:
            kw_key: Any = tuple(sorted(kw.items()))
            hash((kernel, kw_key))
        except TypeError:
            return super().execute_step(
                plan, arrays_by_name, kernel, part_regions, arrays,
                uses=uses, defs=defs, kw=kw)
        import jax

        from .overlap import halo_split

        if not groups and cpu_host_platform():
            # no traffic (e.g. GEMM after the gather): the kernel alone
            # is the step, and per-shard dispatch beats the one-program
            # switch on the cpu backend (see _build_pershard_kernel)
            self._run_kernel_device(kernel, part_regions, arrays, **kw)
            return True

        gsig = tuple((arr.name, kind,
                      tuple((s, d, b.bounds) for s, d, b in msgs))
                     for arr, msgs, kind in groups)
        rsig = tuple(r.bounds for r in part_regions)
        # a step without traffic (e.g. GEMM after the gather) still runs
        # as ONE program — the kernel-only case of the same builder, one
        # dispatch instead of a per-device launch loop.  The halo split
        # is pure section algebra over the (steady, identical) plan, so
        # memoize it per step signature — computed fresh it rivals the
        # device time of the whole step.
        split = None
        if groups and uses is not None and defs is not None:
            try:
                skey = (gsig, rsig, tuple(sorted(uses.items())),
                        tuple(sorted(defs.items())))
                split = self._splits[skey]
            except KeyError:
                split = halo_split(plan, part_regions, uses, defs)
                self._splits[skey] = split
            except TypeError:               # unhashable Access values
                split = halo_split(plan, part_regions, uses, defs)
        with self._lock:
            self._ensure_mesh(arrays[0].nproc)
            for a in arrays:
                self.sync_device(a)
            key = ("step", kernel, kw_key, rsig,
                   tuple((a.name, a.shape, a.dtype.str) for a in arrays),
                   gsig, self._split_key(split))
            prog = self._programs.get(key)
            if prog is None:
                prog = self._build_step_program(groups, kernel,
                                                part_regions, arrays, kw,
                                                split)
                self._programs[key] = prog
            self._dispatch_step(prog, groups, arrays)
        return True

    @staticmethod
    def _split_key(split):
        # the halo split is an input of the traced program, so it must
        # be part of the cache key (bounds are hashable ints)
        if split is None:
            return None
        return tuple(tuple(tuple(b.bounds for b in boxes) for boxes in half)
                     for half in split)

    def _dispatch_step(self, prog, groups, arrays) -> None:
        """Run a built step program and account its counters (caller
        holds the lock and has synced every array to device)."""
        self.last_rank_times = None   # one program, no per-rank timing
        mode = prog[0]
        if mode == "fused":
            _m, fn, out_names, counts, launches = prog
            outs = fn(*[self._device[a.name] for a in arrays])
            for name, out in zip(out_names, outs):
                self._device[name] = out
                self._host_ok[name] = False
        else:                                   # "staged" (cpu backend)
            _m, stages, kprog, counts, launches = prog
            devs = [self._device[a.name] for a in arrays]
            names = [a.name for a in arrays]
            for i, fn1 in stages:
                devs[i] = fn1(devs[i])
                self._device[names[i]] = devs[i]
                self._host_ok[names[i]] = False
            if kprog is not None:
                rank_fns, k_out = kprog
                self._dispatch_pershard(rank_fns, k_out, arrays)
        for arr, msgs, _kind in groups:
            itemsize = arr.itemsize
            for _s, _d, box in msgs:
                self.bytes_moved += box.volume() * itemsize
                self.messages_executed += 1
        for k, v in counts.items():
            self.collective_counts[k] += v
        self.device_kernel_launches += launches

    def _kernel_switch(self, names, kernel, kw, out_kernel, boxes_per_rank):
        """A per-rank ``lax.switch`` sweeping the given boxes: each
        branch chains the kernel over its rank's boxes (device-kernel
        convention: each call returns full updated buffers, threaded
        into the next box's view).  Returns an ``xs -> xs`` tracer."""
        import jax

        def make_branch(boxes):
            def branch(ops):
                bufs = dict(zip(names, ops))
                for box in boxes:
                    if box.is_empty():
                        continue
                    res = kernel(box, bufs, **kw) or {}
                    for n in out_kernel:
                        if n in res:
                            bufs[n] = res[n]
                return tuple(bufs[n] for n in out_kernel)
            return branch

        branches = [make_branch(b) for b in boxes_per_rank]
        out_idx = [names.index(n) for n in out_kernel]

        def run(xs, idx):
            outs = jax.lax.switch(idx, branches, tuple(xs))
            xs = list(xs)
            for i, o in zip(out_idx, outs):
                xs[i] = o
            return xs

        return run

    def _make_step_fn(self, names, lowered_idx, kernel, kw, out_kernel,
                      regions, split):
        """Trace ONE whole step over the per-rank local blocks:
        collects on the pre-exchange state, the interior kernel sweep
        (when the halo split applies — no data dependency on the
        in-flight payloads, so XLA overlaps them), the payload applies,
        then the boundary (or full-region) sweep.  Shared by the fused
        step program and the captured-scan body.  ``lowered_idx`` maps
        each group's (collect, apply) pairs to its index in ``names``.
        """
        def step_fn(xs, idx):
            xs = list(xs)
            payloads = [[collect(xs[gi], idx) for collect, _a in steps]
                        for gi, steps in lowered_idx]
            if kernel is not None and out_kernel and split is not None:
                xs = self._kernel_switch(names, kernel, kw, out_kernel,
                                         split[0])(xs, idx)
            for (gi, steps), pls in zip(lowered_idx, payloads):
                x = xs[gi]
                for (_c, apply), pl in zip(steps, pls):
                    x = apply(x, pl, idx)
                xs[gi] = x
            if kernel is not None and out_kernel:
                boxes = (split[1] if split is not None
                         else [(r,) for r in regions])
                xs = self._kernel_switch(names, kernel, kw, out_kernel,
                                         boxes)(xs, idx)
            return xs

        return step_fn

    def _build_step_program(self, groups, kernel, part_regions, arrays,
                            kw, split):
        """Trace + jit one WHOLE step (exchange + kernel).  Cache value
        is ``("fused", fn, out_names, counts, launches)`` or — on the
        XLA cpu host platform when the exchange needs more than one
        collective (the in-program rendezvous pathology, see
        :meth:`_build_plan_program`; at n=1024 the fused two-ppermute
        halo step measured ~10x slower than staged on XLA cpu) —
        ``("staged", stages, kernel_fn,
        kernel_out, counts, launches)``: one dispatch per collective
        chained through the donated resident buffers, then the kernel
        program.  Either way ONE executor call runs the step."""
        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        names = [a.name for a in arrays]
        regions = list(part_regions)
        per_group, counts = self._lower_groups(groups)
        gidx = [names.index(arr.name) for arr, _m, _k in groups]
        n_coll = sum(len(s) for s in per_group)

        defined = self._kernel_defined(kernel, regions, arrays, kw)
        out_kernel = [n for n in names if n in defined]
        traffic = {arr.name for arr, _m, _k in groups}
        out_names = [n for n in names if n in defined or n in traffic]
        launches = 1 if out_kernel else 0

        if n_coll > 1 and cpu_host_platform():
            stages = []
            for gi, steps in zip(gidx, per_group):
                for collect, apply in steps:
                    def body1(xb, _c=collect, _a=apply):
                        idx = jax.lax.axis_index(axis)
                        x = xb[0]
                        return _a(x, _c(x, idx), idx)[None]
                    stages.append((gi, jax.jit(jax.shard_map(
                        body1, mesh=self._mesh, in_specs=P(axis),
                        out_specs=P(axis), check_vma=False),
                        donate_argnums=(0,))))
            kprog = None
            if out_kernel:
                _tag, rank_fns, k_out = self._build_pershard_kernel(
                    kernel, regions, arrays, kw)
                kprog = (rank_fns, k_out)
            return ("staged", stages, kprog, counts, launches)

        step_fn = self._make_step_fn(names, list(zip(gidx, per_group)),
                                     kernel, kw, out_kernel, regions,
                                     split)

        def body(*xbs):
            idx = jax.lax.axis_index(axis)
            xs = step_fn([xb[0] for xb in xbs], idx)
            return tuple(xs[names.index(n)][None] for n in out_names)

        donate = tuple(i for i, n in enumerate(names) if n in out_names)
        fn = jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=tuple(P(axis) for _ in names),
            out_specs=tuple(P(axis) for _ in out_names),
            check_vma=False), donate_argnums=donate)
        self._last_program = (fn, tuple(
            jax.ShapeDtypeStruct((a.nproc,) + a.shape, a.dtype)
            for a in arrays), {"kind": "step", "steps": 1})
        return ("fused", fn, out_names, counts, launches)

    def capture_cycle(self, cycle, reps: int) -> Optional[Callable]:
        """Capture a steady-state pipeline cycle as ONE jitted
        ``lax.scan`` over ``reps`` repetitions, carries donated.

        Each cycle step is a dict with keys ``plan`` / ``kernel`` /
        ``regions`` / ``arrays`` / ``uses`` / ``defs`` / ``kw`` (see
        ``HDArrayRuntime._run_pipeline_serial``).  The scan body chains
        the same step tracers the fused step program uses, over the
        union of all steps' arrays, so the captured program is
        bit-identical to ``reps`` unfused steps — the per-step host
        dispatch count drops to ZERO.  Returns the runner (executes the
        scan and accounts counters) or None when any step is not
        device-traceable.
        """
        if not self.resident or reps < 1 or not cycle:
            return None
        from .overlap import halo_split

        resolved = [resolve_kernel(st["kernel"], self.device_class)
                    for st in cycle]
        for k in resolved:
            if k is not None and not getattr(k, "__hdarray_device__",
                                             False):
                return None
        axis = self.axis

        # union of every step's arrays, first-seen order: the scan carry
        union: List = []
        seen = set()
        for st in cycle:
            for a in st["arrays"]:
                if a.name not in seen:
                    seen.add(a.name)
                    union.append(a)
        names = [a.name for a in union]
        by_name = {a.name: a for a in union}

        try:
            step_meta = []
            sub_keys = []
            for st, kernel in zip(cycle, resolved):
                kw = st.get("kw") or {}
                kw_key: Any = tuple(sorted(kw.items()))
                hash((kernel, kw_key))
                groups = self._plan_groups(st["plan"], by_name)
                regions = list(st["regions"])
                split = (halo_split(st["plan"], regions, st["uses"],
                                    st["defs"])
                         if kernel is not None else None)
                step_meta.append((groups, kernel, kw, regions, split))
                sub_keys.append(
                    (kernel, kw_key, tuple(r.bounds for r in regions),
                     tuple((arr.name, kind,
                            tuple((s, d, b.bounds) for s, d, b in msgs))
                           for arr, msgs, kind in groups),
                     self._split_key(split)))
        except TypeError:
            return None

        with self._lock:
            self._ensure_mesh(union[0].nproc)
            key = ("scan", reps, tuple(sub_keys),
                   tuple((a.name, a.shape, a.dtype.str) for a in union))
            prog = self._programs.get(key)
            if prog is None:
                prog = self._build_cycle_program(step_meta, union, reps)
                self._programs[key] = prog
            fn, counts, launches, bytes_c, msgs_c = prog

        def run() -> None:
            with self._lock:
                self._ensure_mesh(union[0].nproc)
                for a in union:
                    self.sync_device(a)
                outs = fn(*[self._device[a.name] for a in union])
                for name, out in zip(names, outs):
                    self._device[name] = out
                    self._host_ok[name] = False
                self.bytes_moved += bytes_c * reps
                self.messages_executed += msgs_c * reps
                for k, v in counts.items():
                    self.collective_counts[k] += v * reps
                self.device_kernel_launches += launches * reps

        return run

    def _build_cycle_program(self, step_meta, union, reps: int):
        """Jit the scan: carry = every union array's local block, body =
        the cycle's chained step tracers, length = ``reps``."""
        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        names = [a.name for a in union]
        counts = {"all_gather": 0, "all_to_all": 0, "ppermute": 0}
        launches = 0
        bytes_c = 0
        msgs_c = 0
        step_fns = []
        for groups, kernel, kw, regions, split in step_meta:
            per_group, c = self._lower_groups(groups)
            for k, v in c.items():
                counts[k] += v
            for arr, msgs, _kind in groups:
                for _s, _d, box in msgs:
                    bytes_c += box.volume() * arr.itemsize
                    msgs_c += 1
            lowered_idx = list(zip(
                [names.index(arr.name) for arr, _m, _k in groups],
                per_group))
            out_kernel: List[str] = []
            if kernel is not None:
                defined = self._kernel_defined(kernel, regions, union, kw)
                out_kernel = [n for n in names if n in defined]
                if out_kernel:
                    launches += 1
            step_fns.append(self._make_step_fn(
                names, lowered_idx, kernel, kw, out_kernel, regions,
                split))

        def body(*xbs):
            idx = jax.lax.axis_index(axis)

            def one(carry, _):
                cs = list(carry)
                for f in step_fns:
                    cs = f(cs, idx)
                return tuple(cs), None

            out, _ = jax.lax.scan(one, tuple(xb[0] for xb in xbs), None,
                                  length=reps)
            return tuple(o[None] for o in out)

        fn = jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=tuple(P(axis) for _ in names),
            out_specs=tuple(P(axis) for _ in names),
            check_vma=False), donate_argnums=self._donate(len(names)))
        self._last_program = (fn, tuple(
            jax.ShapeDtypeStruct((a.nproc,) + a.shape, a.dtype)
            for a in union), {"kind": "scan", "reps": reps,
                              "steps": len(step_meta)})
        return fn, counts, launches, bytes_c, msgs_c

    def last_program_lowered(self):
        """Compile the most recent fused step / captured scan program
        from its stored avals and return ``(compiled, meta)`` — the
        input of the roofline report in benchmarks/executor_residency
        and of the chip smoke's ``tpu_custom_call`` check.  Raises
        RuntimeError when no such program was built yet; a failing
        compile raises what the compiler raised."""
        if self._last_program is None:
            raise RuntimeError("no fused step or captured scan program "
                               "has been built on this executor")
        fn, avals, meta = self._last_program
        return fn.lower(*avals).compile(), meta

    # -- reductions -----------------------------------------------------
    def reduce_local(self, arr: "HDArray", per_device, op: str):
        """The local fold runs on the host mirrors, exactly like the
        Sim oracle — one d2h sync when the resident copy is newer."""
        with self._lock:
            self.sync_host(arr)
        return super().reduce_local(arr, per_device, op)

    def reduce_combine(self, partials, op: str, dtype):
        if all(v is None for v in partials):
            return None
        import jax

        nproc = len(partials)
        dtype = np.dtype(dtype)
        with self._lock:
            self._ensure_mesh(nproc)
            # ranks without a live partial contribute the op's identity
            # (±inf / int extremes for max/min), masked by the combine
            vals = np.full((nproc,), _reduce_identity(op, dtype), dtype=dtype)
            for i, v in enumerate(partials):
                if v is not None:
                    vals[i] = v
            key = ("__reduce__", op, dtype.str, nproc)
            prog = self._programs.get(key)
            if prog is None:
                prog = self._build_reduce_program(op)
                self._programs[key] = prog
            fn, counts = prog
            out = np.asarray(jax.device_get(
                fn(jax.device_put(vals, self._sharding))))
            for k, v in counts.items():
                self.collective_counts[k] += v
        return dtype.type(out[0])

    def _build_reduce_program(self, op: str):
        """One shard_map program: each rank holds its (1,) partial; the
        psum-family collective replicates the combined value."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        # the op -> collective-name table is shared with the symbolic
        # lowering (function-level import: core.comm imports executors)
        from repro.core.comm import REDUCE_COLLECTIVES

        axis = self.axis
        prims = {"sum": jax.lax.psum, "max": jax.lax.pmax,
                 "min": jax.lax.pmin}

        def body(xb):
            v = xb[0]
            if op == "prod":
                # no lax.pprod primitive: all_gather + local fold is the
                # standard lowering of the product combine tree
                r = jnp.prod(jax.lax.all_gather(v, axis))
            else:
                r = prims[op](v, axis)
            return r[None]

        fn = jax.jit(jax.shard_map(
            body, mesh=self._mesh, in_specs=P(axis), out_specs=P(axis),
            check_vma=False))
        return fn, {REDUCE_COLLECTIVES[op]: 1}
