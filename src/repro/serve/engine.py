"""Serving engine: batched prefill + decode over ModelBundle caches.

The `decode_32k` / `long_500k` dry-run cells lower `decode_step` (one
new token against a seq_len cache) — NOT train_step.  This module
provides those steps plus a slot-based continuous-batching engine used
by `examples/serve_lm.py`:

  * each cache slot holds one active sequence; per-slot positions are
    ragged (`pos: (B,)`), so new requests join mid-flight without
    flushing the batch (the decode step is shape-stable => one compiled
    executable),
  * prefill writes a new request's KV into its slot at pos 0 with a
    snapshot + scatter, so every OTHER live slot's cache is untouched
    (prefill traces the whole pool batch; only the admitted slot's
    rows are kept),
  * sampling: greedy / temperature / top-k, all in fp32 logits,
  * backpressure: with every slot busy, requests queue up to
    ``queue_depth`` (priority-ordered, FIFO within a priority level,
    drained on ``finish``/``cancel``) and beyond that raise the typed
    :class:`SlotsExhausted`,
  * cancellation: ``cancel(ticket)`` removes a queued request;
    ``cancel(slot)`` aborts a live decode, frees the slot, and
    backfills it from the admission queue,
  * chunked prefill (with prefix reuse on, or ``prefill_chunk`` set):
    a prompt is prefilled in calls over the fixed position blocks
    [0, C), [C, 2C), ..., so the programs that compute a token's KV
    and logits never depend on how much of the prompt was already
    cached.  On TPU a token's numerics depend on the shape of the
    call it runs in, so this is what keeps prefix reuse bit-identical
    there.  Otherwise a prompt is prefilled in one call,
  * prefix reuse (``ServeConfig(prefix_reuse=True)``): when another
    slot's cache rows start with a prefix of the new prompt, the
    matched rows that a whole C-token chunk call wrote are copied (KV
    at position i is a pure function of tokens[0..i] under causal
    attention, and those rows came from the same chunk programs, so
    the copy is bit-identical to recomputing) and only the remaining
    chunks are prefilled — the router-visible "prefill work" drops by
    the copied length.  Rows of a ragged last chunk or of decode steps
    came from other call shapes and are never copied.  Only cache
    families with a per-position seq axis support this (full KV, MLA
    latent); ring/recurrent families auto-disable,
  * failover: :class:`RecoveryEngine` backs the slot KV caches with
    HDArrays partitioned over serving instances (ranks), so an
    instance loss mid-request is the ft layer's planned shrink — KV
    migrates to survivors via ``repartition``, the checkpointed window
    replays, and in-flight requests stream bit-identical tokens; a
    later rejoin is the planned grow.

Cache family is dictated by the arch (full KV / MLA latent / ring
window / recurrent state) — `bundle.init_cache` hides that behind one
pytree, and `repro.train.sharding.cache_shardings` shards it.
"""
from __future__ import annotations

import collections
import dataclasses
import tempfile
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SlotsExhausted(RuntimeError):
    """``add_request`` with every slot busy AND the admission queue
    full (or disabled, the ``queue_depth=0`` default): real
    backpressure, distinct from a transient queue wait.  Subclasses
    RuntimeError so seed-era callers that caught the bare error keep
    working."""


# prompt tokens per prefill call when prefix reuse is on and
# ServeConfig.prefill_chunk is not set
REUSE_PREFILL_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_seq: int = 2048         # cache capacity per slot
    slots: int = 8              # concurrent sequences
    temperature: float = 0.0    # 0 => greedy
    top_k: int = 0              # 0 => full softmax
    queue_depth: int = 0        # admission queue size (0 => reject)
    prefix_reuse: bool = False  # copy matching cached prefix rows on admit
    # prompt tokens per prefill call; None: the whole prompt in one
    # call, or REUSE_PREFILL_CHUNK-token chunks with prefix_reuse on
    prefill_chunk: Optional[int] = None

    def __post_init__(self):
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{self.prefill_chunk}")

    @property
    def chunk(self) -> Optional[int]:
        """The prefill chunk in effect (None: one call per prompt)."""
        if self.prefill_chunk is None and self.prefix_reuse:
            return REUSE_PREFILL_CHUNK
        return self.prefill_chunk


def sample_tokens(logits, key, temperature: float = 0.0, top_k: int = 0):
    """logits (B, 1, V) -> tokens (B, 1)."""
    logits = logits[:, -1, :].astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    tok = jax.random.categorical(key, logits, axis=-1)
    return tok[:, None].astype(jnp.int32)


def make_prefill_step(bundle) -> Callable:
    def prefill_step(params, batch, cache):
        return bundle.prefill(params, batch, cache)
    return prefill_step


def make_decode_step(bundle) -> Callable:
    def decode_step(params, batch, cache):
        return bundle.decode(params, batch, cache)
    return decode_step


class Engine:
    """Slot-based continuous batching on top of the jitted steps.

    Host-side request management; device-side state is one cache pytree
    whose batch dim is the slot pool.  Designed for the CPU examples and
    integration tests — on a real pod the same steps run under pjit with
    the shardings from launch/serve.py.
    """

    def __init__(self, bundle, params, scfg: ServeConfig, seed: int = 0):
        self.bundle = bundle
        self.cfg = bundle.cfg
        self.scfg = scfg
        self.params = params
        self.cache = bundle.init_cache(scfg.slots, scfg.max_seq)
        self._prefill = jax.jit(make_prefill_step(bundle))
        self._decode = jax.jit(make_decode_step(bundle))
        self._key = jax.random.PRNGKey(seed)
        # host-side slot table
        self.slot_pos = np.zeros(scfg.slots, np.int32)      # next write pos
        self.slot_live = np.zeros(scfg.slots, bool)
        self.slot_tokens: List[List[int]] = [[] for _ in range(scfg.slots)]
        # admission queue (backpressure): deferred requests drained
        # into freed slots on finish()/cancel() in (priority desc,
        # arrival asc) order; `admitted` maps each drained ticket
        # (negative id) to the slot it landed in
        self.queue: collections.deque = collections.deque()
        self.admitted: Dict[int, int] = {}
        self._next_ticket = -1
        # which axis of each cache leaf is the slot (batch) dim: probed
        # by shaping the cache with one extra slot (jax.eval_shape: no
        # allocation) and diffing shapes (family-agnostic — full KV,
        # MLA latent, recurrent all place B differently); -1 marks a
        # slot-invariant leaf
        def first_diff(c, p):
            return next((d for d, (s0, s1) in enumerate(zip(c.shape,
                                                            p.shape))
                         if s0 != s1), -1)

        self._slot_axis = jax.tree.map(first_diff, self.cache, jax.eval_shape(
            lambda: bundle.init_cache(scfg.slots + 1, scfg.max_seq)))
        # which axis is the per-position (seq) dim, probed the same way
        # with one extra cache row — prefix reuse copies rows along it.
        # Leaves without one (ring slabs, recurrent state, `pos`) get
        # -1; a slot-carrying non-`pos` leaf with no seq axis means the
        # family folds history into running state, so reuse is off.
        self._seq_axis = jax.tree.map(first_diff, self.cache, jax.eval_shape(
            lambda: bundle.init_cache(scfg.slots, scfg.max_seq + 1)))
        paths = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_flatten_with_path(self.cache)[0]]
        self.supports_prefix_reuse = all(
            tax >= 0 or sax < 0 or "pos" in name
            for name, sax, tax in zip(
                paths, jax.tree_util.tree_leaves(self._slot_axis),
                jax.tree_util.tree_leaves(self._seq_axis)))
        # the token sequence whose KV currently occupies each slot's
        # cache rows (positions 0..len-1) — retained after finish()
        # until the slot is reused, so finished sequences act as a
        # prefix cache; len(kv_tokens[s]) == slot_pos[s] while live
        self.kv_tokens: List[List[int]] = [[] for _ in range(scfg.slots)]
        # how many of each slot's leading rows whole C-token prefill
        # chunks wrote: the only rows prefix reuse may copy
        self.chunk_rows = np.zeros(scfg.slots, np.int64)
        # the last admitted prompt's last-position logits (V,), on device
        self.prefill_logits = None
        # prefill-work accounting for the router/benchmark layer
        self.prefill_tokens_computed = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0

    # ------------------------------------------------------------------
    def add_request(self, prompt_tokens: np.ndarray,
                    extra_inputs: Optional[Dict[str, Any]] = None,
                    priority: int = 0) -> int:
        """Prefill `prompt_tokens` into a free slot; returns the slot
        id (>= 0).  With every slot busy the request queues (up to
        ``queue_depth``) and a NEGATIVE ticket id returns instead —
        ``finish``/``cancel`` drain the queue into freed slots in
        (priority desc, arrival asc) order and record ticket -> slot
        in :attr:`admitted`.  Queue full (or disabled) raises
        :class:`SlotsExhausted`."""
        free = np.flatnonzero(~self.slot_live)
        if free.size == 0:
            if len(self.queue) < self.scfg.queue_depth:
                ticket = self._next_ticket
                self._next_ticket -= 1
                self.queue.append((ticket, np.asarray(prompt_tokens),
                                   extra_inputs, int(priority)))
                return ticket
            raise SlotsExhausted(
                f"no free slots ({self.scfg.slots} busy) and the "
                f"admission queue is full "
                f"({len(self.queue)}/{self.scfg.queue_depth})")
        return self._admit(int(free[0]), np.asarray(prompt_tokens),
                           extra_inputs)

    def cancel(self, tid: int) -> Optional[List[int]]:
        """Abort a request.  ``tid`` < 0 (a queue ticket): the queued
        request is removed before it ever touches a slot (a drained
        ticket resolves through :attr:`admitted` to its slot first).
        ``tid`` >= 0 (a live slot): the slot is freed mid-decode and
        backfilled from the admission queue, and the tokens produced
        so far return.  Raises KeyError for an unknown/idle id."""
        if tid < 0:
            if tid in self.admitted:
                return self.cancel(self.admitted.pop(tid))
            for i, entry in enumerate(self.queue):
                if entry[0] == tid:
                    del self.queue[i]
                    return None
            raise KeyError(f"ticket {tid} is not queued")
        if not (0 <= tid < self.scfg.slots) or not self.slot_live[tid]:
            raise KeyError(f"slot {tid} is not live")
        self.slot_live[tid] = False
        toks, self.slot_tokens[tid] = self.slot_tokens[tid], []
        self.slot_pos[tid] = 0
        self._drain_queue()
        return toks

    def _drain_queue(self) -> None:
        """Admit the best queued request (priority desc, then arrival
        order — earlier tickets are numerically GREATER) into a free
        slot, recording ticket -> slot in :attr:`admitted`."""
        if not self.queue:
            return
        best = max(range(len(self.queue)),
                   key=lambda i: (self.queue[i][3], self.queue[i][0]))
        ticket, prompt, extra, _prio = self.queue[best]
        del self.queue[best]
        slot = int(np.flatnonzero(~self.slot_live)[0])
        self.admitted[ticket] = self._admit(slot, prompt, extra)

    def _admit(self, sid: int, prompt_tokens: np.ndarray,
               extra_inputs: Optional[Dict[str, Any]]) -> int:
        T = len(prompt_tokens)
        B = self.scfg.slots
        # chunks need per-position cache rows (the families that support
        # prefix reuse) and no per-request inputs; otherwise one call
        chunked = (self.scfg.chunk is not None and self.supports_prefix_reuse
                   and not extra_inputs)
        C = self.scfg.chunk if chunked else T
        # prefix reuse: find the slot whose whole-chunk rows share the
        # longest prefix with this prompt, copy them, and only prefill
        # the rest (L is capped at T-1: the last prompt token always
        # runs so prefill has logits to return)
        L, src = 0, sid
        if self.scfg.prefix_reuse and chunked:
            src, L = self._best_prefix(prompt_tokens)
            L -= L % C
        snapshot = jax.tree.map(lambda x: x, self.cache)
        if L > 0 and src != sid:
            self._copy_prefix_rows(src, sid, L)
        # snapshot + scatter: prefill traces the WHOLE pool batch, so
        # it rewrites every slot's cache at the prompt positions (and
        # advances every slot's pos).  Keep only the admitted slot's
        # rows; every other live slot's cache is bit-identical to its
        # pre-prefill snapshot.
        for lo in range(L, T, C):
            hi = min(lo + C, T)
            toks = np.zeros((B, hi - lo), np.int32)
            toks[sid] = prompt_tokens[lo:hi]
            batch = {"tokens": jnp.asarray(toks)}
            if extra_inputs:
                batch.update(extra_inputs)
            for g in self._cache_groups():
                g["pos"] = jnp.where(jnp.arange(B) == sid, lo, g["pos"])
            logits, cache = self._prefill(self.params, batch, self.cache)
            self.cache = self._scatter_slot(snapshot, cache, sid)
        self.slot_pos[sid] = T
        self.slot_live[sid] = True
        self.slot_tokens[sid] = list(map(int, prompt_tokens))
        self.kv_tokens[sid] = list(map(int, prompt_tokens))
        self.chunk_rows[sid] = T - T % C if chunked else 0
        self.prefill_logits = logits[sid, -1]
        self.prefill_tokens_computed += T - L
        if L > 0:
            self.prefix_hits += 1
            self.prefix_tokens_reused += L
        # first generated token
        tok = self._sample(logits)
        self.slot_tokens[sid].append(int(tok[sid, 0]))
        return sid

    def _best_prefix(self, prompt: np.ndarray) -> Tuple[int, int]:
        """(slot, match length): the slot whose whole-chunk rows share
        the longest common prefix with `prompt` (live or retained),
        capped at len(prompt)-1.  Ties break to the lowest slot id."""
        best_s, best_l = 0, 0
        cap = len(prompt) - 1
        for s in range(self.scfg.slots):
            cached = self.kv_tokens[s]
            n = min(cap, int(self.chunk_rows[s]))
            m = 0
            while m < n and cached[m] == int(prompt[m]):
                m += 1
            if m > best_l:
                best_s, best_l = s, m
        return best_s, best_l

    def _copy_prefix_rows(self, src: int, dst: int, L: int) -> None:
        """Copy cache rows [0, L) (along each leaf's seq axis) from
        slot `src` to slot `dst`.  Bit-identical to recomputing them:
        under causal attention KV at position i depends only on
        tokens[0..i], which match by construction."""
        def copy(leaf, sax, tax):
            if sax < 0 or tax < 0:
                return leaf
            src_ix = [slice(None)] * leaf.ndim
            dst_ix = [slice(None)] * leaf.ndim
            src_ix[sax], dst_ix[sax] = src, dst
            src_ix[tax] = dst_ix[tax] = slice(0, L)
            return leaf.at[tuple(dst_ix)].set(leaf[tuple(src_ix)])

        self.cache = jax.tree.map(copy, self.cache, self._slot_axis,
                                  self._seq_axis)

    def _scatter_slot(self, old, new, sid: int):
        """Merge two cache pytrees: slot `sid`'s rows from `new`,
        every other slot's from `old` (slot-invariant leaves keep the
        snapshot)."""
        B = self.scfg.slots

        def pick(o, n, ax):
            if ax < 0:
                return o
            shape = [1] * n.ndim
            shape[ax] = B
            mask = jnp.arange(B).reshape(shape) == sid
            return jnp.where(mask, n, o)

        return jax.tree.map(pick, old, new, self._slot_axis)

    def step(self) -> Dict[int, int]:
        """One decode step for all live slots; returns {slot: token}."""
        B = self.scfg.slots
        last = np.array([self.slot_tokens[s][-1] if self.slot_live[s] else 0
                         for s in range(B)], np.int32)[:, None]
        batch = {"token": jnp.asarray(last),
                 "pos": jnp.asarray(self.slot_pos)}
        logits, self.cache = self._decode(self.params, batch, self.cache)
        toks = self._sample(logits)
        out = {}
        for s in range(B):
            if self.slot_live[s]:
                # the fed token's KV was just written at slot_pos[s]
                self.kv_tokens[s].append(int(last[s, 0]))
                t = int(toks[s, 0])
                self.slot_tokens[s].append(t)
                self.slot_pos[s] += 1
                out[s] = t
        return out

    def finish(self, sid: int) -> List[int]:
        self.slot_live[sid] = False
        toks, self.slot_tokens[sid] = self.slot_tokens[sid], []
        self.slot_pos[sid] = 0
        # kv_tokens[sid] is deliberately retained: the finished
        # sequence's cache rows stay valid until the slot is reused,
        # so they keep serving as a prefix cache
        self._drain_queue()
        return toks

    def generate(self, prompt_tokens: np.ndarray, n_tokens: int,
                 extra_inputs: Optional[Dict[str, Any]] = None) -> List[int]:
        sid = self.add_request(np.asarray(prompt_tokens), extra_inputs)
        for _ in range(n_tokens - 1):
            self.step()
        return self.finish(sid)

    # ------------------------------------------------------------------
    def _sample(self, logits):
        self._key, k = jax.random.split(self._key)
        return np.asarray(sample_tokens(logits, k, self.scfg.temperature,
                                        self.scfg.top_k))

    def _cache_groups(self):
        if isinstance(self.cache, dict) and "pos" in self.cache:
            return [self.cache]
        return [g for g in self.cache.values()
                if isinstance(g, dict) and "pos" in g]


# ----------------------------------------------------------------------
class RecoveryEngine:
    """Failure-aware serving: an :class:`Engine` whose slot KV caches
    are backed by HDArrays partitioned over serving ``instances``
    (ranks of an :class:`~repro.core.runtime.HDArrayRuntime`) — rank p
    owns the cache sections of its share of the slot pool, the way a
    production stack spreads requests over replicas.

    Every cache leaf mirrors into one HDArray (slot axis moved to
    dim 0, non-native dtypes bit-viewed); a ``CheckpointManager``
    snapshots the HDArrays + the host slot table after each admit and
    every ``checkpoint_interval`` decode steps.  The mirror is written
    only when a checkpoint is taken or a grow migrates it, not on every
    decode step: between checkpoints the engine's device cache is the
    state, and a loss restores from the checkpoint anyway.
    ``fail_instance(rank)``
    is the ft layer's planned shrink applied to serving: mark the rank
    lost, restore the checkpoint onto the survivors' staging layout,
    ``repartition`` the live slots' caches onto the shrunken layout
    (migration bytes in ``rt.comm_log``), then silently replay the
    decode steps since the snapshot — greedy decoding makes the replay,
    and therefore every in-flight token stream, bit-identical to an
    uninterrupted run.  ``rejoin_instance(rank)`` is the planned grow:
    ``Executor.add_rank`` + ``grow_partition`` + a migrating
    ``repartition``, no replay needed (the survivors hold every
    coherent byte).  The audit records land in ``rt.recovery_log`` as
    ``kind="instance_loss"`` / ``"instance_join"``.
    """

    def __init__(self, bundle, params, scfg: ServeConfig,
                 instances: int = 2, seed: int = 0,
                 checkpoint_interval: int = 2,
                 ckpt_dir: Optional[str] = None, backend: str = "sim"):
        from repro.ckpt.checkpoint import CheckpointManager
        from repro.core import HDArrayRuntime

        self.engine = Engine(bundle, params, scfg, seed)
        self.scfg = scfg
        self.instances = instances
        self.rt = HDArrayRuntime(instances, backend=backend)
        self.live: List[int] = list(range(instances))
        self._tmp = (tempfile.TemporaryDirectory()
                     if ckpt_dir is None else None)
        self.cm = CheckpointManager(ckpt_dir or self._tmp.name)
        self.checkpoint_interval = max(1, int(checkpoint_interval))
        self.recovery_log = self.rt.recovery_log
        # one HDArray per slot-carrying cache leaf, row-partitioned
        # (slot dim 0) over the instances
        leaves, self._treedef = jax.tree_util.tree_flatten_with_path(
            self.engine.cache)
        axes = jax.tree_util.tree_leaves(self.engine._slot_axis)
        self._leaves: List[Tuple[str, int, Any]] = []
        self._parts: Dict[str, int] = {}
        for (path, leaf), ax in zip(leaves, axes):
            name = "kv" + jax.tree_util.keystr(path)
            if ax < 0:
                self._leaves.append((name, -1, None))
                continue
            host = np.asarray(leaf)
            shape = (host.shape[ax],) + tuple(
                s for d, s in enumerate(host.shape) if d != ax)
            view = host.dtype if _is_native(host.dtype) else _bit_view(host)
            self.rt.create(name, shape, dtype=view)
            self._parts[name] = self.rt.partition_row(shape)
            self._leaves.append((name, int(ax), host.dtype))
        self._decode_count = 0
        self._ckpt_step = 0
        self._ckpt_decode = 0
        self._host_snap = None
        # injected per-instance slowdown (seconds added to that
        # instance's reported step latency) — deterministic straggler
        # modeling for tests and the serving benchmark
        self.step_cost: Dict[int, float] = {}
        self.last_step_time = 0.0
        self._checkpoint()

    # -- engine API (checkpointed) -------------------------------------
    def add_request(self, prompt_tokens, extra_inputs=None,
                    priority: int = 0) -> int:
        sid = self.engine.add_request(np.asarray(prompt_tokens),
                                      extra_inputs, priority=priority)
        # checkpoint right after the admit so the replay window after
        # a failure only ever contains decode steps
        self._checkpoint()
        return sid

    def step(self) -> Dict[int, int]:
        import time as _time
        t0 = _time.perf_counter()
        out = self.engine.step()
        dt = _time.perf_counter() - t0
        # per-instance step latency: the decode is one synchronous
        # program over the slot pool, so each live instance's share of
        # the step is the measured wall time plus its injected
        # `step_cost` (tests/benchmarks model a slow instance with it);
        # dead instances report 0.0 (skipped by the monitor).  Lands in
        # PlannerStats.rank_step_times so the Rebalancer /
        # StragglerMonitor machinery — and through them the load-aware
        # router — can flag a slow replica.
        times = [dt + self.step_cost.get(r, 0.0) if r in self.live else 0.0
                 for r in range(self.instances)]
        self.rt.planner.stats.note_rank_times(self._decode_count, times)
        self.last_step_time = max(times)
        self._decode_count += 1
        if self._decode_count - self._ckpt_decode >= self.checkpoint_interval:
            self._checkpoint()
        return out

    def finish(self, sid: int) -> List[int]:
        out = self.engine.finish(sid)
        self._checkpoint()
        return out

    def cancel(self, tid: int) -> Optional[List[int]]:
        out = self.engine.cancel(tid)
        self._checkpoint()
        return out

    def generate(self, prompt_tokens, n_tokens: int,
                 extra_inputs=None) -> List[int]:
        sid = self.add_request(np.asarray(prompt_tokens), extra_inputs)
        for _ in range(n_tokens - 1):
            self.step()
        return self.finish(sid)

    # -- elasticity ----------------------------------------------------
    def fail_instance(self, rank: int) -> None:
        """Instance `rank` died mid-serving.  Planned shrink + replay:
        caller-visible token streams continue bit-identically."""
        from repro.ft.faults import (ElasticPlan, inherit_partition,
                                     shrink_partition, survivor_partition)

        if rank not in self.live:
            raise ValueError(f"instance {rank} is not live ({self.live})")
        self.live.remove(rank)
        if not self.live:
            raise RuntimeError(f"instance {rank} lost and no survivors "
                               f"remain")
        for arr in self.rt.arrays.values():
            arr.mark_rank_lost(rank)
            self.rt.executor.drop_rank(arr, rank)
        staging: Dict[str, int] = {}
        targets: Dict[str, int] = {}
        for name, arr in self.rt.arrays.items():
            pid = inherit_partition(self.rt, self._parts[name], self.live)
            if pid is None:
                pid = survivor_partition(self.rt, arr.shape, self.live)
            staging[name] = pid
            targets[name] = shrink_partition(self.rt, self._parts[name],
                                             self.live)
        self.cm.restore_runtime(self.rt, parts=staging, live=self.live)
        migration = 0
        for name, arr in self.rt.arrays.items():
            if targets[name] != staging[name]:
                plan = self.rt.repartition(arr, staging[name],
                                           targets[name])
                migration += plan.bytes_total
        self._parts.update(targets)
        # rebuild the engine at the checkpoint, then silently replay —
        # greedy decode regenerates the exact in-flight tokens
        replay = self._decode_count - self._ckpt_decode
        slots_live = int(self.engine.slot_live.sum())
        self._restore_host(self._host_snap)
        self.engine.cache = self._cache_from_hdarrays()
        self._decode_count = self._ckpt_decode
        for _ in range(replay):
            self.engine.step()
            self._decode_count += 1
        self.rt.planner.stats.elastic_shrinks += 1
        self.rt.recovery_log.append({
            "kind": "instance_loss", "rank": rank, "live": list(self.live),
            "migration_bytes": migration, "steps_replayed": replay,
            "slots_live": slots_live,
            "plan": ElasticPlan(len(self.live) + 1, len(self.live),
                                (len(self.live),), migration)})

    def rejoin_instance(self, rank: int) -> None:
        """Instance `rank` (re)joined: planned grow — add_rank +
        grow_partition + a migrating repartition.  No replay needed;
        the survivors hold every coherent byte."""
        from repro.ft.faults import ElasticPlan, grow_partition

        if rank in self.live:
            self.rt.recovery_log.append({
                "kind": "instance_join", "rank": rank,
                "live": list(self.live), "migration_bytes": 0,
                "noop": True, "plan": None})
            return
        # the grow migrates the cache as it stands, not as of the last
        # checkpoint
        self._mirror()
        self.live.append(rank)
        self.live.sort()
        for arr in self.rt.arrays.values():
            arr.mark_rank_joined(rank)
            self.rt.executor.add_rank(arr, rank)
        migration = 0
        for name, arr in self.rt.arrays.items():
            tgt = grow_partition(self.rt, self._parts[name], self.live,
                                 rank)
            plan = self.rt.repartition(arr, self._parts[name], tgt)
            migration += plan.bytes_total
            self._parts[name] = tgt
        self.rt.planner.stats.elastic_grows += 1
        self.rt.recovery_log.append({
            "kind": "instance_join", "rank": rank, "live": list(self.live),
            "migration_bytes": migration,
            "plan": ElasticPlan(len(self.live) - 1, len(self.live),
                                (len(self.live),), migration)})

    # -- cache <-> HDArray mirroring ------------------------------------
    def _mirror(self) -> None:
        """Write the engine's current cache leaves into their backing
        HDArrays (slot axis first, bit-preserving views for non-native
        dtypes) under the current data layout."""
        flat = jax.tree_util.tree_leaves(self.engine.cache)
        for (name, ax, dtype), leaf in zip(self._leaves, flat):
            if ax < 0:
                continue
            host = np.asarray(leaf)
            if not _is_native(host.dtype):
                host = host.view(_bit_view(host))
            if ax != 0:
                host = np.moveaxis(host, ax, 0)
            self.rt.write(self.rt.arrays[name],
                          np.ascontiguousarray(host), self._parts[name])

    def _cache_from_hdarrays(self):
        """Rebuild the engine's cache pytree from the (restored +
        repartitioned) HDArrays — the inverse of :meth:`_mirror`.
        Slot-invariant leaves come from the host snapshot."""
        snap_static = self._host_snap["static_leaves"]
        out = []
        for name, ax, dtype in self._leaves:
            if ax < 0:
                out.append(snap_static[name])
                continue
            host = self.rt.read_coherent(self.rt.arrays[name])
            if ax != 0:
                host = np.moveaxis(host, 0, ax)
            if not _is_native(np.dtype(dtype)):
                host = np.ascontiguousarray(host).view(dtype)
            out.append(jnp.asarray(host))
        return jax.tree_util.tree_unflatten(self._treedef, out)

    # -- host-state snapshots -------------------------------------------
    def _checkpoint(self) -> None:
        self._mirror()
        self.cm.save_runtime(self._ckpt_step, self.rt)
        self._ckpt_step += 1
        self._ckpt_decode = self._decode_count
        eng = self.engine
        flat = jax.tree_util.tree_leaves(eng.cache)
        self._host_snap = {
            "slot_pos": eng.slot_pos.copy(),
            "slot_live": eng.slot_live.copy(),
            "slot_tokens": [list(t) for t in eng.slot_tokens],
            "kv_tokens": [list(t) for t in eng.kv_tokens],
            "chunk_rows": eng.chunk_rows.copy(),
            "key": eng._key,
            "queue": list(eng.queue),
            "admitted": dict(eng.admitted),
            "next_ticket": eng._next_ticket,
            "static_leaves": {name: leaf
                              for (name, ax, _d), leaf
                              in zip(self._leaves, flat) if ax < 0},
        }

    def _restore_host(self, snap: Dict[str, Any]) -> None:
        eng = self.engine
        eng.slot_pos = snap["slot_pos"].copy()
        eng.slot_live = snap["slot_live"].copy()
        eng.slot_tokens = [list(t) for t in snap["slot_tokens"]]
        eng.kv_tokens = [list(t) for t in snap["kv_tokens"]]
        eng.chunk_rows = snap["chunk_rows"].copy()
        eng._key = snap["key"]
        eng.queue = collections.deque(snap["queue"])
        eng.admitted = dict(snap["admitted"])
        eng._next_ticket = snap["next_ticket"]


_BIT_VIEWS = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _is_native(dtype) -> bool:
    """True for dtypes numpy can serialize losslessly (npz round-trip).
    Extension dtypes like ml_dtypes' bfloat16 report ``isbuiltin == 2``
    and kind ``V`` — savez would degrade them to raw void — so the test
    is the numeric kind set, not ``isbuiltin``."""
    return np.dtype(dtype).kind in "biufc"


def _bit_view(host: np.ndarray):
    """A same-itemsize native integer dtype for bit-preserving storage
    of extension dtypes (bfloat16 & co) in numpy-backed HDArrays."""
    return _BIT_VIEWS[host.dtype.itemsize]
