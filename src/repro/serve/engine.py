"""Continuous-batching inference engine over slot caches (DESIGN.md §12).

The production serving loop for search winners and the LM zoo: requests are
admitted into per-slot cache rows the moment a slot frees (no wave
barrier), prefill runs in padding-bucketed batches (serve/buckets.py), and
decode is ONE jitted step over all slots per iteration — every batch row is
a slot at its own sequence position (``cache["lens"]``), so mixed prompt
and output lengths coexist in flight.

Greedy decode through the engine is bit-identical per request to a scalar
one-request reference (:func:`greedy_reference`): every model op on the
batch axis is row-local, prefill buckets right-pad (masked contributions
are exact zeros), and the slotted decode step shares the scalar path's
arithmetic (models/attention.py).

Wall-clock behaviour: ``run(requests)`` honours each request's
``arrival_s`` (open-loop load — the Poisson generator in serve/loadgen.py);
``realtime=False`` collapses arrivals to "already queued" for deterministic
tests.

Failure semantics (DESIGN.md §13) — an always-on edge deployment needs
explicit answers to "what if it never finishes / keeps arriving / must
shut down":

* **deadlines** — a request carrying ``deadline_s`` (latency budget from
  arrival) is expired the moment the budget runs out: its slot is
  reclaimed for the next waiting request and the partial output is
  returned flagged ``expired`` (on the virtual clock one decode step is
  one second, so budgets are deterministic step counts in tests);
* **backpressure** — ``EngineConfig.max_queue`` bounds the admission
  queue; a submit over the bound is *rejected explicitly* (flagged
  ``rejected``, returned unserved) instead of growing the queue without
  limit;
* **graceful drain** — :meth:`ServeEngine.drain` completes the in-flight
  requests without admitting more work, the shutdown path that never
  abandons a sequence mid-decode.

Replication hooks (DESIGN.md §14): the engine is also the unit a
:class:`~repro.serve.router.ReplicaRouter` replicates, so it exposes the
health/metrics surface the router dispatches on — :meth:`tick` (one
scheduling round on the *caller's* clock: expire → admit → decode),
:meth:`cancel` (withdraw a request without recording a result — the
hedge-loser / failover path), :meth:`take_finished` (drain completions
incrementally), and the :attr:`in_flight` / :attr:`queue_depth` /
:attr:`has_work` load metrics.  ``decode_steps`` doubles as the heartbeat
counter: a replica with work whose ``decode_steps`` stops advancing is
stalled.

Phase spans: each host phase of a round (:data:`PHASES` — the tick, the
admitting round, each prefill bucket's dispatch and its wait, the decode
step, its wait and the emit loop after it) opens a
``jax.profiler.TraceAnnotation`` named ``engine.<phase>``, so a profiler
trace lays every device idle gap against what the host was doing, and adds
its host seconds and count to ``stats()["phase_s"]`` /
``stats()["phase_n"]``.  The profiler being on or off is the only switch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import FaultPlan
from repro.serve.buckets import build_buckets
from repro.serve.paged import BlockPool

# host phases of one scheduling round, each a ``TraceAnnotation`` named
# ``engine.<phase>`` and a pair of counters in ``stats()``
PHASES = ("tick", "admit", "prefill", "prefill_wait", "decode",
          "decode_wait", "emit")


@dataclasses.dataclass
class ServeRequest:
    """One inference request and its measured lifecycle."""

    rid: int
    prompt: np.ndarray             # (len,) int32
    max_new: int
    arrival_s: float = 0.0         # offset from the run's t0 (open loop)
    deadline_s: Optional[float] = None  # latency budget from arrival; the
    #   engine reclaims the slot and returns partial output on expiry
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    expired: bool = False          # deadline ran out (out = partial tokens)
    rejected: bool = False         # bounced off a full admission queue
    oom: bool = False              # shed by the paged engine when the block
    #   pool ran dry mid-decode (out = partial tokens, prefix of reference)
    blocks_held: int = 0           # peak cache blocks held (paged engine)
    # measured lifecycle (seconds from the run's t0)
    t_arrival: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0


@dataclasses.dataclass
class EngineConfig:
    slots: int = 8                 # concurrent sequences in flight
    cache_len: int = 256           # per-slot KV/state capacity
    pad_to: int = 8                # prompt-length bucket granularity
    max_prefill_batch: int = 8     # rows per prefill dispatch
    max_wait: int = 0              # admission rounds a ready request may be
    #   held to fill a denser bucket (0 = admit immediately; latency knob)
    max_queue: Optional[int] = None  # admission-queue bound: a submit over
    #   it is rejected explicitly (backpressure).  None = unbounded
    # paged KV cache (DESIGN.md §15): admit on free *blocks* instead of
    # worst-case dense slots.  ``n_blocks=None`` sizes the pool for the
    # worst case (slots * cache_len / block_size — never OOMs); a smaller
    # pool trades capacity for memory, with explicit OOM shedding.
    paged: bool = False
    block_size: int = 16           # tokens per cache block
    n_blocks: Optional[int] = None  # pool size; None = worst case


class ServeEngine:
    """Slot-cache continuous batching over a ModelBundle's slotted path.

    The dense engine donates ``self.cache`` to each decode step and each
    splice, which update it in place and hand back the buffer as the new
    ``self.cache``: the arrays a caller read from ``engine.cache`` before a
    ``tick``/``step`` are deleted by it, so callers must not keep a
    reference to ``engine.cache`` across one."""

    def __init__(self, bundle, params, config: Optional[EngineConfig] = None,
                 faults: Optional[FaultPlan] = None):
        cfg = config or EngineConfig()
        self.faults = faults  # "serve.decode" inject point (DESIGN.md §13)
        if bundle.decode_slotted is None or bundle.prefill_slotted is None:
            raise ValueError(
                f"family {bundle.cfg.family!r} has no slotted serving path "
                f"(supported: decoder-only LM and SSM/hybrid families)")
        if cfg.pad_to > 1 and not bundle.prefill_pads:
            raise ValueError(
                f"family {bundle.cfg.family!r} folds every prompt token "
                f"into running state — right-padded prefill buckets would "
                f"corrupt it; use pad_to=1 (exact-length buckets)")
        self.bundle = bundle
        self.params = params
        self.cfg = cfg
        self._specs = {k: v for k, v in bundle.cache_specs().items()
                       if k != "len"}
        self.paged = cfg.paged
        self.pool: Optional[BlockPool] = None
        if cfg.paged:
            if (bundle.decode_paged is None or bundle.prefill_paged is None
                    or bundle.make_paged_cache is None):
                raise ValueError(
                    f"family {bundle.cfg.family!r} has no paged serving "
                    f"path (supported: decoder-only LM and SSM/hybrid "
                    f"families)")
            if cfg.cache_len % cfg.block_size:
                raise ValueError(
                    f"cache_len {cfg.cache_len} is not a multiple of "
                    f"block_size {cfg.block_size}")
            max_blocks = cfg.cache_len // cfg.block_size
            n_blocks = cfg.n_blocks or cfg.slots * max_blocks
            self.pool = BlockPool(n_blocks, cfg.block_size, cfg.slots,
                                  max_blocks)
            # pool-resident leaves are spliced block/offset-wise; per-slot
            # leaves (hybrid conv/SSM state) splice at their batch axis
            pspecs = bundle.paged_cache_specs()
            self._pool_specs = {k: v for k, v in pspecs.items()
                                if k not in ("lens", "tables")
                                and "blocks" in v}
            self._row_specs = {k: v for k, v in pspecs.items()
                               if k not in ("lens", "tables")
                               and "blocks" not in v}
            self._tables_dirty = False

        def _greedy(logits):
            # greedy next token per row, and whether the row's logits were
            # all finite (the nonfinite_rows health counter); the logits
            # themselves never leave the device
            return jnp.argmax(logits, axis=-1), \
                jnp.isfinite(logits).all(axis=-1)

        def _prefill(params, tokens, lens):
            logits, cache1 = bundle.prefill_slotted(
                params, {"tokens": tokens, "lens": lens,
                         "cache_len": cfg.cache_len})
            return _greedy(logits), cache1

        def _decode(params, cache, tokens, active):
            logits, cache = bundle.decode_slotted(
                params, cache, {"tokens": tokens, "active": active})
            return _greedy(logits), cache

        def _splice(cache, cache1, slot_idx):
            # copy each prefill row's cache into its slot with row-sized
            # updates, so the donated cache is written in place; the pad
            # rows of a bucket come last, with an out-of-range slot index,
            # and are skipped
            def put_row(r, out):
                out = dict(out)
                for key, spec in self._specs.items():
                    ax = spec.index("batch")
                    row = jax.lax.dynamic_slice_in_dim(cache1[key], r, 1,
                                                       axis=ax)
                    out[key] = jax.lax.dynamic_update_slice_in_dim(
                        out[key], row, slot_idx[r], axis=ax)
                return out
            out = jax.lax.fori_loop(0, jnp.sum(slot_idx < cfg.slots),
                                    put_row, dict(cache))
            out["lens"] = cache["lens"].at[slot_idx].set(
                cache1["lens"], mode="drop")
            return out

        def _prefill_paged(params, tokens, lens):
            logits, rows = bundle.prefill_paged(
                params, {"tokens": tokens, "lens": lens})
            return _greedy(logits), rows

        def _decode_paged(params, cache, tokens, active):
            logits, cache = bundle.decode_paged(
                params, cache, {"tokens": tokens, "active": active})
            return _greedy(logits), cache

        def _splice_paged(cache, rows, slot_idx, blk, off):
            # scatter prefill rows into the block pool: (B, L) block /
            # offset index arrays computed host-side from the allocator;
            # sentinel block indices (pad rows, pad tail) are dropped
            out = dict(cache)
            for key, spec in self._pool_specs.items():
                ax = spec.index("blocks")
                idx = (slice(None),) * ax + (blk, off)
                out[key] = cache[key].at[idx].set(rows[key], mode="drop")
            for key, spec in self._row_specs.items():
                ax = spec.index("batch")
                idx = (slice(None),) * ax + (slot_idx,)
                out[key] = cache[key].at[idx].set(rows[key], mode="drop")
            out["lens"] = cache["lens"].at[slot_idx].set(
                rows["lens"], mode="drop")
            return out

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._splice = jax.jit(_splice, donate_argnums=(0,))
        if cfg.paged:
            self._prefill_paged = jax.jit(_prefill_paged)
            self._decode_paged = jax.jit(_decode_paged)
            self._splice_paged = jax.jit(_splice_paged)
        self.reset()

    # ------------------------------------------------------------ lifecycle
    def reset(self) -> None:
        """Fresh slot state (cache arrays are reallocated; the jitted
        executables persist, so a warmed engine stays warm)."""
        cfg = self.cfg
        if self.paged:
            self.pool.reset()
            self.cache = self.bundle.make_paged_cache(
                cfg.slots, cfg.cache_len, self.pool.n_blocks, cfg.block_size)
            self._tables_dirty = False
        else:
            self.cache = self.bundle.make_slot_cache(cfg.slots,
                                                     cfg.cache_len)
        self.active: List[Optional[ServeRequest]] = [None] * cfg.slots
        self.last_tok = np.zeros((cfg.slots,), np.int32)
        self.waiting: List[ServeRequest] = []   # arrived, not yet admitted
        self.finished: List[ServeRequest] = []
        self.rejected: List[ServeRequest] = []  # bounced at admission
        self.decode_steps = 0
        self.prefill_calls = 0
        self.shed_blocks = 0        # paged OOM sheds (explicit, counted)
        self.nonfinite_rows = 0     # live rows whose logits held NaN/inf
        self.peak_concurrency = 0   # max sequences simultaneously in flight
        self.prefill_tokens = 0     # real prompt tokens prefilled
        self.prefill_padded_tokens = 0  # rows x length of every prefill
        #   bucket dispatched, pad rows included
        self.cache_updates = 0      # dense decode and splice dispatches
        self.cache_inplace = 0      # of those, the ones that consumed the
        #   donated cache (every leaf passed in deleted)
        self.phase_s: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.phase_n: Dict[str, int] = dict.fromkeys(PHASES, 0)

    def submit(self, req: ServeRequest) -> bool:
        """Queue a request.  Returns ``False`` (and flags the request
        ``rejected``) when the bounded admission queue is full — explicit
        backpressure the caller can act on, instead of unbounded queue
        growth.  Malformed requests still raise."""
        if len(req.prompt) > self.cfg.cache_len:
            raise ValueError(f"request {req.rid}: prompt length "
                             f"{len(req.prompt)} exceeds cache_len "
                             f"{self.cfg.cache_len}")
        if self.paged:
            need = self.pool.blocks_for(len(req.prompt))
            if need > self.pool.n_blocks:
                # would never fit even an empty pool: reject explicitly
                # (truncating the prompt would silently change the output)
                raise ValueError(
                    f"request {req.rid}: prompt needs {need} cache blocks "
                    f"but the pool only has {self.pool.n_blocks}")
        if self.cfg.max_queue is not None \
                and len(self.waiting) >= self.cfg.max_queue:
            req.rejected = True
            req.t_done = req.t_arrival
            self.rejected.append(req)
            return False
        self.waiting.append(req)
        return True

    def cancel(self, rid: int) -> Optional[ServeRequest]:
        """Withdraw a request without recording a result: an in-flight
        request's slot is reclaimed, a queued one leaves the queue.  The
        router's hedge-loser and failover path — the caller owns the
        request's fate.  Returns the withdrawn request, or ``None`` when
        ``rid`` is not held here (already finished, or never submitted)."""
        for s, r in enumerate(self.active):
            if r is not None and r.rid == rid:
                if self.paged:
                    self._release_blocks(s, r)
                self.active[s] = None
                return r
        for i, r in enumerate(self.waiting):
            if r.rid == rid:
                return self.waiting.pop(i)
        return None

    def take_finished(self) -> List[ServeRequest]:
        """Drain the finished list (completed + expired since the last
        take).  The router's per-tick completion collector; :meth:`run`
        keeps its own accounting and never calls this."""
        out = self.finished
        self.finished = []
        return out

    # ----------------------------------------------------- health / metrics
    @property
    def in_flight(self) -> List[ServeRequest]:
        """Requests currently occupying slots."""
        return [r for r in self.active if r is not None]

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or any(r is not None for r in self.active)

    @property
    def free_blocks(self) -> Optional[int]:
        """Free cache blocks in the pool (``None`` for a dense engine) —
        the memory-depth signal the router's placement prefers."""
        return self.pool.free_count if self.paged else None

    def stats(self) -> Dict[str, Any]:
        """Counters for loadgen reports: throughput-side (decode steps,
        prefill dispatches and their real and padded tokens), concurrency
        (peak sequences in flight), host seconds and count of each engine
        phase (``phase_s``/``phase_n``, keyed by :data:`PHASES`), dense
        cache updates and how many of them were in place
        (``cache_updates``/``cache_inplace``) and — for the paged engine —
        block-pool residency."""
        d: Dict[str, Any] = {
            "decode_steps": self.decode_steps,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens": self.prefill_tokens,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "peak_concurrency": self.peak_concurrency,
            "shed_blocks": self.shed_blocks,
            "nonfinite_rows": self.nonfinite_rows,
            "cache_updates": self.cache_updates,
            "cache_inplace": self.cache_inplace,
            "phase_s": dict(self.phase_s),
            "phase_n": dict(self.phase_n),
        }
        if self.paged:
            d.update({
                "n_blocks": self.pool.n_blocks,
                "block_size": self.cfg.block_size,
                "free_blocks": self.pool.free_count,
                "peak_blocks_used": self.pool.peak_used,
            })
        return d

    @contextlib.contextmanager
    def _phase(self, name: str, **args: int):
        """One engine phase: a ``jax.profiler.TraceAnnotation`` named
        ``engine.<name>`` (with ``args`` as its stats), which lands in the
        profiler's host trace on the device planes' clock and costs next
        to nothing while no profiler runs, and the phase's host seconds
        and count added to ``phase_s``/``phase_n``."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"engine.{name}", **args):
                yield
        finally:
            self.phase_s[name] += time.perf_counter() - t0
            self.phase_n[name] += 1

    def _count_update(self, old_cache) -> None:
        """Count one dense cache update, and whether it consumed the
        donated cache: JAX deletes a donated buffer only when XLA aliased
        it to an output, so this counts real hand-overs."""
        self.cache_updates += 1
        self.cache_inplace += all(
            a.is_deleted() for a in jax.tree_util.tree_leaves(old_cache))

    # ------------------------------------------------------------ block pool
    def _release_blocks(self, slot: int, req: ServeRequest) -> None:
        """Return a leaving request's blocks to the pool (records its peak
        residency first; held counts are monotone until release)."""
        req.blocks_held = max(req.blocks_held, self.pool.held(slot))
        if self.pool.free_slot(slot):
            self._tables_dirty = True

    def _refresh_tables(self) -> None:
        """Push the allocator's block tables to the device cache whenever
        allocation changed since the last dispatch."""
        if self._tables_dirty:
            self.cache["tables"] = jnp.asarray(self.pool.table_array())
            self._tables_dirty = False

    def _grow_blocks(self, now: float) -> int:
        """Pre-decode growth: every active slot needs the block covering
        its next write position.  On pool exhaustion, sheds the
        youngest-admitted starved request (explicit OOM: ``oom`` flag,
        partial output kept — a prefix of the reference — and the
        ``shed_blocks`` counter bumped; zero silent drops), then retries
        the remaining starved slots with the freed blocks.  Returns the
        number shed."""
        need = []
        for s, req in enumerate(self.active):
            if req is None:
                continue
            pos = len(req.prompt) + len(req.out) - 1  # next write position
            need.append((req.t_admit, req.rid, s, pos))
        need.sort()
        before = self.pool.allocs
        pending = need
        shed = 0
        while True:
            failed = []
            for item in pending:
                _, _, s, pos = item
                if not self.pool.ensure(s, pos):
                    failed.append(item)
            if not failed:
                break
            _, _, s, _ = failed[-1]   # youngest admission among the starved
            req = self.active[s]
            req.oom = True
            req.done = True
            req.t_done = now
            self._release_blocks(s, req)
            self.finished.append(req)
            self.active[s] = None
            self.shed_blocks += 1
            shed += 1
            pending = failed[:-1]
        if self.pool.allocs != before:
            self._tables_dirty = True
        return shed

    # ------------------------------------------------------------ admission
    def _admit(self, now: float) -> int:
        """Fill free slots from the waiting queue (FCFS), one bucketed
        prefill dispatch per padded prompt length.  Returns the number of
        requests admitted."""
        free = [s for s, r in enumerate(self.active) if r is None]
        if not free or not self.waiting:
            return 0
        if self.paged:
            # admit while *blocks* are available, not worst-case slots:
            # strict FCFS — the first waiting request whose prompt doesn't
            # fit blocks the line (no length-based overtaking, so paged
            # admission order matches dense admission order exactly)
            reqs: List[ServeRequest] = []
            slots: List[int] = []
            for req in self.waiting:
                if len(reqs) >= len(free):
                    break
                need = self.pool.blocks_for(len(req.prompt))
                if not self.pool.can_alloc(need):
                    break
                slot = free[len(reqs)]
                self.pool.alloc(slot, need)
                reqs.append(req)
                slots.append(slot)
            if not reqs:
                return 0
            del self.waiting[:len(reqs)]
            self._tables_dirty = True
        else:
            take = min(len(free), len(self.waiting))
            reqs = self.waiting[:take]
            del self.waiting[:take]
            slots = free[:take]
        with self._phase("admit", admitted=len(reqs)):
            buckets = build_buckets([r.prompt for r in reqs], slots,
                                    self.cfg.slots, pad_to=self.cfg.pad_to,
                                    max_batch=self.cfg.max_prefill_batch)
            for b in buckets:
                # the splice is dispatched here and not waited on: its
                # device time lands in the next wait of this tick
                with self._phase("prefill", rows=len(b.rows),
                                 padded_len=b.tokens.shape[1]):
                    if self.paged:
                        self._refresh_tables()
                        greedy, rows_cache = self._prefill_paged(
                            self.params, jnp.asarray(b.tokens),
                            jnp.asarray(b.lens))
                        blk, off = self._block_offsets(b)
                        self.cache = self._splice_paged(
                            self.cache, rows_cache, jnp.asarray(b.slot_idx),
                            jnp.asarray(blk), jnp.asarray(off))
                    else:
                        greedy, cache1 = self._prefill(
                            self.params, jnp.asarray(b.tokens),
                            jnp.asarray(b.lens))
                        old = self.cache
                        self.cache = self._splice(old, cache1,
                                                  jnp.asarray(b.slot_idx))
                        self._count_update(old)
                self.prefill_calls += 1
                self.prefill_tokens += int(b.lens[:len(b.rows)].sum())
                self.prefill_padded_tokens += int(b.tokens.size)
                with self._phase("prefill_wait"):
                    first, finite = jax.device_get(greedy)
                self.nonfinite_rows += int((~finite[:len(b.rows)]).sum())
                for row, i in enumerate(b.rows):
                    req, slot = reqs[i], slots[i]
                    req.out.append(int(first[row]))
                    req.t_admit = now
                    self.active[slot] = req
                    self.last_tok[slot] = first[row]
                    self._maybe_finish(slot, now)
        return len(reqs)

    def _block_offsets(self, b):
        """(B, L) block / offset index arrays for a prefill bucket: row r,
        position p lands in ``table[slot_r][p // bs]`` at offset
        ``p % bs``; pad rows and pad-tail positions get the sentinel block
        (scatter-dropped)."""
        bp, L = b.tokens.shape
        bs = self.cfg.block_size
        pos = np.arange(L)
        blk = np.full((bp, L), self.pool.n_blocks, np.int32)
        off = np.tile((pos % bs).astype(np.int32), (bp, 1))
        for row in range(len(b.rows)):
            slot = int(b.slot_idx[row])
            ln = int(b.lens[row])
            table = np.asarray(self.pool.slot_blocks(slot), np.int32)
            blk[row, :ln] = table[pos[:ln] // bs]
        return blk, off

    def _maybe_finish(self, slot: int, now: float) -> None:
        req = self.active[slot]
        seq_len = len(req.prompt) + len(req.out)
        if len(req.out) >= req.max_new or seq_len >= self.cfg.cache_len:
            req.done = True
            req.t_done = now
            if self.paged:
                self._release_blocks(slot, req)
            self.finished.append(req)
            self.active[slot] = None

    def _expire(self, now: float) -> int:
        """Reclaim slots (and drop queued requests) whose deadline passed.
        An expired in-flight request keeps its partial output; the freed
        slot is immediately admittable.  Returns the number expired."""
        n = 0
        for s, req in enumerate(self.active):
            if req is None or req.deadline_s is None:
                continue
            if now - req.t_arrival >= req.deadline_s:
                req.expired = True
                req.done = True
                req.t_done = now
                if self.paged:
                    self._release_blocks(s, req)  # deadline block reclaim
                self.finished.append(req)
                self.active[s] = None   # slot reclaimed
                n += 1
        still = []
        for req in self.waiting:
            if req.deadline_s is not None \
                    and now - req.t_arrival >= req.deadline_s:
                req.expired = True
                req.done = True
                req.t_done = now
                self.finished.append(req)
                n += 1
            else:
                still.append(req)
        self.waiting = still
        return n

    # --------------------------------------------------------------- decode
    def step(self, now: float) -> int:
        """One jitted decode step over every slot.  Returns the number of
        live tokens produced."""
        active_mask = np.array([r is not None for r in self.active])
        if not active_mask.any():
            return 0
        with self._phase("decode", rows=int(active_mask.sum())):
            if self.paged:
                # grow each active slot's table to cover this step's write
                # position; pool exhaustion sheds explicitly (OOM), so the
                # mask may shrink before the dispatch
                self._grow_blocks(now)
                active_mask = np.array([r is not None for r in self.active])
                if not active_mask.any():
                    return 0
                self._refresh_tables()
                decode = self._decode_paged
            else:
                decode = self._decode
            old = self.cache
            greedy, self.cache = decode(
                self.params, old,
                jnp.asarray(self.last_tok[:, None]), jnp.asarray(active_mask))
            if not self.paged:
                self._count_update(old)
            self.decode_steps += 1
            with self._phase("decode_wait"):
                nxt, finite = jax.device_get(greedy)
            self.nonfinite_rows += int((~finite & active_mask).sum())
            produced = 0
            with self._phase("emit"):
                for s, req in enumerate(self.active):
                    if req is None:
                        continue
                    req.out.append(int(nxt[s]))
                    self.last_tok[s] = nxt[s]
                    produced += 1
                    self._maybe_finish(s, now)
            return produced

    # ----------------------------------------------------------------- tick
    def tick(self, now: float, *, realtime: bool = False
             ) -> Dict[str, float]:
        """One scheduling round on the caller's clock: expire deadlines,
        admit waiting requests (bucketed prefill), one jitted decode step.
        The router drives its replicas through this — each replica advances
        exactly one round per router tick, so a shared virtual clock stays
        meaningful across replicas.

        Returns ``{"produced", "admitted", "expired", "stall_s"}`` counts;
        ``stall_s`` is the injected ``serve.decode`` stall the caller must
        add to its virtual clock (``realtime=True`` sleeps it here)."""
        with self._phase("tick"):
            expired = self._expire(now)
            admitted = self._admit(now)
            self.peak_concurrency = max(
                self.peak_concurrency,
                sum(r is not None for r in self.active))
            stall_s = 0.0
            if self.faults is not None:
                # injected decode stall: the engine owns no clock of its
                # own, so the plan is consulted (check), never slept inside
                # (fire) — the caller's virtual clock advances
                # deterministically instead
                spec = self.faults.check("serve.decode",
                                         step=self.decode_steps)
                if spec is not None and spec.kind in ("hang", "stall"):
                    if realtime:
                        time.sleep(spec.hang_s)
                    else:
                        stall_s = spec.hang_s
            produced = self.step(now + stall_s)
        return {"produced": produced, "admitted": admitted,
                "expired": expired, "stall_s": stall_s}

    # ------------------------------------------------------------------ run
    def run(self, requests: Sequence[ServeRequest], *,
            realtime: bool = False,
            log: Optional[Callable[[str], None]] = None
            ) -> List[ServeRequest]:
        """Serve a workload to completion.

        ``realtime=True`` honours each request's ``arrival_s`` against the
        wall clock (open-loop load; the loop sleeps when idle before the
        next arrival).  ``realtime=False`` runs on a virtual clock that
        ticks once per decode step — ``arrival_s`` (and ``deadline_s``)
        are then counted in decode steps, which makes mid-flight
        admission and deadline expiry deterministic for tests.

        Every submitted request comes back exactly once: completed,
        ``expired`` (deadline hit; partial output), or ``rejected``
        (bounced off a full admission queue, never served).
        """
        self.reset()
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        t0 = time.monotonic()
        clock = (lambda: time.monotonic() - t0) if realtime else None
        vnow = 0.0

        while pending or self.waiting or any(self.active):
            now = clock() if realtime else vnow
            while pending and pending[0].arrival_s <= now:
                req = pending.pop(0)
                req.t_arrival = req.arrival_s
                self.submit(req)
            if not realtime and not self.waiting and not any(self.active) \
                    and pending:
                vnow = pending[0].arrival_s  # idle jump to the next arrival
                continue
            t = self.tick(clock() if realtime else vnow, realtime=realtime)
            produced, admitted, expired = (t["produced"], t["admitted"],
                                           t["expired"])
            if not realtime:
                vnow += 1.0 + t["stall_s"]
            if produced == 0 and not admitted and not expired:
                if realtime and pending and not self.waiting \
                        and not any(self.active):
                    # idle gap in the open-loop schedule
                    gap = pending[0].arrival_s - (time.monotonic() - t0)
                    if gap > 0:
                        time.sleep(min(gap, 0.05))
            if log and (admitted or expired):
                log(f"[serve] t={now:7.3f}s active="
                    f"{sum(r is not None for r in self.active)} "
                    f"waiting={len(self.waiting)} pending={len(pending)} "
                    f"finished={len(self.finished)}")
        return sorted(self.finished + self.rejected, key=lambda r: r.rid)

    # ---------------------------------------------------------------- drain
    def drain(self, *, realtime: bool = False,
              log: Optional[Callable[[str], None]] = None
              ) -> List[ServeRequest]:
        """Graceful shutdown: decode the in-flight requests to completion
        WITHOUT admitting any more work.  Requests still waiting in the
        admission queue are left there untouched — the caller reroutes or
        fails them explicitly.  Returns the requests that finished during
        the drain (deadlines stay live, measured on the drain's own
        clock)."""
        t0 = time.monotonic()
        vnow = 0.0
        before = len(self.finished)
        while any(r is not None for r in self.active):
            now = (time.monotonic() - t0) if realtime else vnow
            # expire only in-flight work: queued requests are not ours to
            # time out here — we are shutting down, not serving
            for s, req in enumerate(self.active):
                if req is not None and req.deadline_s is not None \
                        and now - req.t_arrival >= req.deadline_s:
                    req.expired = True
                    req.done = True
                    req.t_done = now
                    if self.paged:
                        self._release_blocks(s, req)
                    self.finished.append(req)
                    self.active[s] = None
            self.step(now)
            if not realtime:
                vnow += 1.0
            if log:
                log(f"[serve] drain t={now:7.3f}s active="
                    f"{sum(r is not None for r in self.active)} "
                    f"waiting={len(self.waiting)} (held)")
        return self.finished[before:]


# ---------------------------------------------------------------------------
# Scalar reference
# ---------------------------------------------------------------------------


def greedy_reference(bundle, params, prompt: np.ndarray, max_new: int,
                     cache_len: int,
                     decode_jit: Optional[Callable] = None) -> List[int]:
    """One-request greedy decode through the *scalar* serving path
    (``bundle.prefill`` + ``bundle.decode_step`` with the shared scalar
    cache length) — the bit-parity oracle for the engine."""
    toks = jnp.asarray(prompt, jnp.int32)[None]
    logits, cache = bundle.prefill(params,
                                   {"tokens": toks, "cache_len": cache_len})
    out = [int(jnp.argmax(logits[0]))]
    dec = decode_jit or jax.jit(bundle.decode_step)
    while len(out) < max_new and len(prompt) + len(out) < cache_len:
        logits, cache = dec(params, cache,
                            {"tokens": jnp.asarray([[out[-1]]], jnp.int32)})
        out.append(int(jnp.argmax(logits[0])))
    return out
