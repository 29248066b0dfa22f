"""Serving engines: the continuous-batching :class:`ServeEngine` (slot
cache + scheduler + in-jit sampling) and the legacy static-batch
:class:`DecodeEngine` (used by tests and ``examples/quickstart.py``),
plus the prefill/serve step factories it jits.

ServeEngine contract (the decode hot path):
  * ONE jitted call per emitted token, for the whole slot batch, with
    the cache and token buffers DONATED (keys are read-only per decode
    step and donated only on admit, which rewrites them) — the
    persistent KV/SSM state never double-buffers and never visits the
    host;
  * sampling (greedy/temperature/top-k/top-p, per-slot RNG) is fused
    into that call, so only (slots, 1) int32 tokens are shipped back;
  * admission is a second jitted call (``prefill_at``) that scatters a
    batch of new requests into free slot rows while resident slots keep
    their state — the NEXT decode step serves old and new together;
  * under a mesh, params take the serve (pure-TP when they fit) specs
    and the cache takes ``cache_pspecs`` (sequence sharded over
    ``model`` = flash-decoding split-KV), with explicit in/out
    shardings so donation aliases buffers exactly.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import sampling
from repro.serve.cache import SlotCache
from repro.serve.prefix import PrefixPool
from repro.serve.sampling import SamplerConfig
from repro.serve.scheduler import (FinishedRequest, PriorityScheduler,
                                   Request, RequestScheduler)

Pytree = Any

SERVE_FAMILIES = ("dense", "moe", "ssm", "hybrid")


@dataclasses.dataclass
class _PendingRow:
    """A slot mid-prefill on the chunked admission path."""

    slot: int
    req: Request
    start: int                    # next prompt position to fill
    hold: Optional[int]           # pinned prefix-store entry (refcount)
    key: np.ndarray               # (2,) uint32 per-request RNG key data


def make_prefill_step(model, cfg=None) -> Callable:
    """(params, batch) -> (last-token logits (B, V), cache).

    batch: {"tokens"} (+"frames" encdec, +"image_embeddings" vlm).
    ``cache_len`` fixes the decode-cache capacity (defaults to prompt len).
    """
    cfg = cfg if cfg is not None else model.cfg

    def step(params, batch, *, cache_len: Optional[int] = None):
        kw = {}
        if cfg.family == "encdec":
            kw["frames"] = batch["frames"]
        if cfg.family == "vlm":
            kw["image_embeddings"] = batch["image_embeddings"]
        return model.prefill(params, batch["tokens"], cache_len=cache_len,
                             **kw)

    return step


def make_serve_step(model, cfg=None) -> Callable:
    """(params, cache, tokens (B,1)) -> (logits (B,1,V), new cache).

    ONE new token against the standing cache.
    """
    cfg = cfg if cfg is not None else model.cfg

    def step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)

    return step


class DecodeEngine:
    """Static-batch greedy decoding (the pre-continuous-batching path).

    Prefills one fixed batch, then steps the jitted single-token decode
    for a fixed number of tokens. Kept as the serving benchmark's
    baseline: every sequence occupies its lane until the LONGEST one
    finishes, which is exactly the throughput loss continuous batching
    removes.
    """

    def __init__(self, model, params, cfg=None):
        self.model = model
        self.cfg = cfg if cfg is not None else model.cfg
        self.params = params
        self._prefill = jax.jit(make_prefill_step(model, self.cfg),
                                static_argnames=("cache_len",))
        self._step = jax.jit(make_serve_step(model, self.cfg),
                             donate_argnums=(1,))

    def generate(self, batch, *, max_new_tokens: int,
                 cache_len: Optional[int] = None) -> jnp.ndarray:
        """Returns generated tokens (B, max_new_tokens)."""
        prompt = batch["tokens"]
        B, S = prompt.shape
        cap = cache_len or (S + max_new_tokens)
        logits, cache = self._prefill(self.params, batch, cache_len=cap)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out = [tok]
        for _ in range(max_new_tokens - 1):
            logits, cache = self._step(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
        return jnp.concatenate(out, axis=1)


# --------------------------------------------------------------- continuous

class ServeEngine:
    """Continuous-batching decode over a slot-paged persistent cache.

    Drive it either with :meth:`run` (drain a request list) or manually
    — ``submit()`` between ``step()`` calls injects traffic mid-flight;
    each ``step()`` admits whatever fits into free slots and decodes
    ONE token for every resident sequence.

    Passing ``slos`` ({tier: TierSLO}) swaps the FIFO scheduler for the
    :class:`PriorityScheduler`: admission orders by (aged) tier, and an
    SLO-driven preemption pass runs before admission each tick — when a
    queued high-tier request has burned ``preempt_at`` of its TTFT
    budget and no slot is free, the worst over-budget lower-tier decode
    is evicted. Its resident state (prompt + emitted[:-1]) is
    snapshotted into the prefix store and PINNED, so re-admission
    replays the emitted tokens as a one-suffix-token prefill and the
    token stream resumes byte-identically (position-folded sampling).
    ``min_slots`` bounds slot autoscaling: the admission target starts
    there and ramps one slot per tick while the queue is non-empty
    (decaying back when it drains), so light load runs small stable
    batches and bursts still reach ``slots``. ``reserve_slots`` keeps
    that many slots off-limits to tier > 0 admissions, so a tier-0
    arrival never waits behind a wall of un-preemptable mid-prefill
    batch rows.
    """

    def __init__(self, model, params, cfg=None, *, slots: int = 4,
                 capacity: int = 256, sampler: Optional[SamplerConfig] = None,
                 mesh=None, use_flash: Optional[bool] = None,
                 prefill_bucket: int = 1, max_queue: int = 1024,
                 prefill_chunk: Optional[int] = None,
                 prefix_entries: int = 0, prefix_min_tokens: int = 4,
                 admit_limit: Optional[int] = None, seed: int = 0,
                 slos=None, min_slots: Optional[int] = None,
                 aging_s: Optional[float] = None, preempt_at: float = 0.5,
                 over_budget_only: bool = False, preempt: bool = True,
                 reserve_slots: int = 0):
        self.model = model
        self.cfg = cfg if cfg is not None else model.cfg
        if self.cfg.family not in SERVE_FAMILIES:
            raise ValueError(
                f"ServeEngine covers {SERVE_FAMILIES}, got "
                f"{self.cfg.family!r}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.sampler = sampler if sampler is not None else SamplerConfig()
        self.mesh = mesh
        # compile the flash-decode megakernel on single-device TPU; the
        # CPU interpreter is correctness-only, and under a mesh the KV
        # sequence axis is sharded over `model` — pallas_call has no
        # partitioning rule for it, so the jnp online-softmax core (the
        # GSPMD split-KV path) must carry sharded decode
        self.use_flash = (jax.default_backend() == "tpu" and mesh is None
                          if use_flash is None else use_flash)
        self.seed = seed
        self.cache = SlotCache(model, slots, capacity, mesh=mesh)
        if slos is not None:
            self.scheduler: RequestScheduler = PriorityScheduler(
                self.cache, slos=slos, max_queue=max_queue,
                prefill_bucket=prefill_bucket, aging_s=aging_s,
                preempt_at=preempt_at, over_budget_only=over_budget_only,
                reserve_slots=reserve_slots)
        else:
            self.scheduler = RequestScheduler(
                self.cache, max_queue=max_queue,
                prefill_bucket=prefill_bucket)
        self.preempt_enabled = preempt and slos is not None
        if min_slots is not None and not 1 <= min_slots <= slots:
            raise ValueError(f"min_slots must be in [1, {slots}]")
        self.min_slots = min_slots
        self._slot_target = min_slots if min_slots is not None else slots
        self._preempt_holds: dict[int, int] = {}   # rid -> pinned entry
        self._next_rid = 0
        self.traces = {"decode": 0, "admit": 0, "admit_chunk": 0,
                       "restore": 0, "snap": 0}
        self.stats = {"decode_steps": 0, "admit_calls": 0,
                      "chunk_calls": 0, "restore_calls": 0,
                      "snap_calls": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0,
                      "tokens_out": 0, "occupancy_sum": 0.0,
                      "ticks": 0, "preemptions": 0,
                      "replayed_tokens": 0, "slot_target_sum": 0.0}
        # chunked admission path: active when either knob is set. With
        # `prefill_chunk` each engine tick advances every mid-prefill
        # slot by ONE C-token chunk and still decodes the resident
        # slots (masked decode protects mid-prefill rows); with only
        # `prefix_entries` the suffix past the matched prefix is filled
        # in one shot (legacy-latency admission, prefix savings only).
        self.prefill_chunk = prefill_chunk
        self.admit_limit = admit_limit
        self._chunked = prefill_chunk is not None or prefix_entries > 0
        self.pool: Optional[PrefixPool] = None
        self.store: Optional[SlotCache] = None
        if prefix_entries > 0:
            self.pool = PrefixPool(prefix_entries,
                                   min_tokens=prefix_min_tokens)
            self.store = SlotCache(model, prefix_entries, capacity,
                                   mesh=mesh)
        self._pending: list[_PendingRow] = []
        self._prefilling: set[int] = set()
        self._snap_q: list[tuple[int, int]] = []    # (entry, src slot)

        toks = jnp.zeros((slots, 1), jnp.int32)
        keys = jnp.zeros((slots, 2), jnp.uint32)
        if mesh is None:
            self.params = params
            self._shard = {}
        else:
            from repro.distributed.sharding import (serve_param_pspecs,
                                                    tree_named)
            from jax.sharding import NamedSharding, PartitionSpec as P
            pspecs = serve_param_pspecs(
                self.cfg, jax.eval_shape(lambda: params), mesh)
            pshard = tree_named(mesh, pspecs)
            self.params = jax.device_put(params, pshard)
            b_ax = self.cache.pspecs["pos"]          # P(batch axes)
            row = NamedSharding(mesh, P(*b_ax, None))
            toks = jax.device_put(toks, row)
            keys = jax.device_put(keys, row)
            self._shard = {"params": pshard, "cache": self.cache.shardings,
                           "row": row, "vec": NamedSharding(mesh, P(*b_ax)),
                           "repl": NamedSharding(mesh, P())}
        self._toks = toks
        self._keys = keys
        self._decode = self._build_decode()
        self._admit = self._build_admit()
        if self._chunked:
            self._admit_chunk = self._build_admit_chunk()
            self._decode_live = self._build_decode_live()
        if self.store is not None:
            self._restore = self._build_restore()
            self._snap = self._build_snap()

    # ------------------------------------------------------------- jits

    def _build_decode(self) -> Callable:
        model, scfg, use_flash = self.model, self.sampler, self.use_flash

        def step(params, cache, toks, keys):
            self.traces["decode"] += 1        # trace-time side effect
            logits, cache = model.decode_step(params, cache, toks,
                                              use_flash=use_flash)
            # token at absolute position p <- fold(slot key, p): pos was
            # just incremented to where the sampled token will be written
            step_keys = sampling.fold_positions(keys, cache["pos"])
            nxt = sampling.sample(scfg, logits[:, -1], step_keys)
            return nxt[:, None], cache

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(1, 2))
        s = self._shard
        return jax.jit(
            step,
            in_shardings=(s["params"], s["cache"], s["row"], s["row"]),
            out_shardings=(s["row"], s["cache"]),
            donate_argnums=(1, 2))

    def _build_admit(self) -> Callable:
        model, scfg = self.model, self.sampler

        def admit(params, cache, toks, keys, prompt, lengths, slot_ids,
                  req_keys):
            self.traces["admit"] += 1
            logits, cache = model.prefill_at(params, cache, prompt,
                                             slot_ids, lengths=lengths)
            keys = keys.at[slot_ids].set(req_keys)
            first = sampling.sample(
                scfg, logits, sampling.fold_positions(req_keys, lengths))
            toks = toks.at[slot_ids, 0].set(first)
            return first, cache, toks, keys

        if self.mesh is None:
            return jax.jit(admit, donate_argnums=(1, 2, 3))
        s = self._shard
        r = s["repl"]
        return jax.jit(
            admit,
            in_shardings=(s["params"], s["cache"], s["row"], s["row"],
                          r, r, r, r),
            out_shardings=(r, s["cache"], s["row"], s["row"]),
            donate_argnums=(1, 2, 3))

    def _build_admit_chunk(self) -> Callable:
        """Gathered (n, C) resume-prefill call: only the mid-prefill
        rows are gathered, advanced by one chunk, and scattered back —
        compute per tick scales with the rows actually prefilling, not
        the slot count. Shapes are (n, C) with n = pending rows, so the
        path compiles at most ``slots`` times (once per distinct n)
        regardless of per-row prefix offsets."""
        model, scfg = self.model, self.sampler

        def admit_chunk(params, cache, toks, keys, slot_ids, chunk,
                        start, cl, full_lengths, req_keys, done_now):
            self.traces["admit_chunk"] += 1
            logits, cache = model.prefill_chunk_at(
                params, cache, chunk, slot_ids, start=start,
                chunk_lengths=cl)
            write = cl > 0
            # key scatter masked by `write`: an inactive row may be a
            # freshly reacquired slot whose resident keys must survive
            keys = keys.at[slot_ids].set(
                jnp.where(write[:, None], req_keys, keys[slot_ids]))
            # rows completing their prompt this chunk sample their first
            # token from the chunk's last-valid logits, folded at the
            # prompt length — same stream as a monolithic admission
            first = sampling.sample(
                scfg, logits, sampling.fold_positions(req_keys,
                                                      full_lengths))
            sel = done_now & write
            toks = toks.at[slot_ids, 0].set(
                jnp.where(sel, first, toks[slot_ids, 0]))
            return first, cache, toks, keys

        if self.mesh is None:
            return jax.jit(admit_chunk, donate_argnums=(1, 2, 3))
        s = self._shard
        r = s["repl"]
        return jax.jit(
            admit_chunk,
            in_shardings=(s["params"], s["cache"], s["row"], s["row"],
                          r, r, r, r, r, r, r),
            out_shardings=(r, s["cache"], s["row"], s["row"]),
            donate_argnums=(1, 2, 3))

    def _build_decode_live(self) -> Callable:
        """Decode step with a ``live`` row mask: cache/token writes for
        masked-off rows are dropped, so slots mid-chunked-prefill (whose
        SSM state and KV rows a blind decode would irreversibly
        corrupt) pass through untouched. Still ONE traced call per
        emitted token for every live row."""
        model, scfg, use_flash = self.model, self.sampler, self.use_flash

        def step(params, cache, toks, keys, live):
            self.traces["decode"] += 1        # trace-time side effect
            logits, new_cache = model.decode_step(params, cache, toks,
                                                  use_flash=use_flash)
            step_keys = sampling.fold_positions(keys, new_cache["pos"])
            nxt = sampling.sample(scfg, logits[:, -1], step_keys)
            toks = jnp.where(live[:, None], nxt[:, None], toks)
            out_cache = {}
            for name, new in new_cache.items():
                m = (live if name == "pos"
                     else live.reshape((1, -1) + (1,) * (new.ndim - 2)))
                out_cache[name] = jnp.where(m, new, cache[name])
            return toks, out_cache

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(1, 2))
        s = self._shard
        return jax.jit(
            step,
            in_shardings=(s["params"], s["cache"], s["row"], s["row"],
                          s["vec"]),
            out_shardings=(s["row"], s["cache"]),
            donate_argnums=(1, 2))

    def _build_restore(self) -> Callable:
        """cache[slot] <- store[entries[slot]] where mask — the on-device
        prefix copy that replaces recomputing the matched prefix."""

        def restore(cache, store, entries, mask):
            self.traces["restore"] += 1
            out = {}
            for name, big in cache.items():
                src = store[name]
                if name == "pos":
                    out[name] = jnp.where(mask, src[entries], big)
                else:
                    m = mask.reshape((1, -1) + (1,) * (big.ndim - 2))
                    out[name] = jnp.where(m, src[:, entries], big)
            return out

        if self.mesh is None:
            return jax.jit(restore, donate_argnums=(0,))
        s = self._shard
        store_shard = self.store.shardings
        return jax.jit(
            restore,
            in_shardings=(s["cache"], store_shard, s["repl"], s["repl"]),
            out_shardings=s["cache"],
            donate_argnums=(0,))

    def _build_snap(self) -> Callable:
        """store[entry] <- cache[src_slots[entry]] where mask — snapshot
        a slot's complete decode state into the prefix store."""

        def snap(cache, store, src_slots, mask):
            self.traces["snap"] += 1
            out = {}
            for name, st in store.items():
                src = cache[name]
                if name == "pos":
                    out[name] = jnp.where(mask, src[src_slots], st)
                else:
                    m = mask.reshape((1, -1) + (1,) * (st.ndim - 2))
                    out[name] = jnp.where(m, src[:, src_slots], st)
            return out

        if self.mesh is None:
            return jax.jit(snap, donate_argnums=(1,))
        s = self._shard
        store_shard = self.store.shardings
        return jax.jit(
            snap,
            in_shardings=(s["cache"], store_shard, s["repl"], s["repl"]),
            out_shardings=store_shard,
            donate_argnums=(1,))

    # ------------------------------------------------------------- host

    def submit(self, tokens, max_new_tokens: int, *,
               eos_id: Optional[int] = None,
               rid: Optional[int] = None, tier: int = 0) -> int:
        """Enqueue one request (bounded queue); returns its rid."""
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        req = Request(rid=rid, tokens=np.asarray(tokens),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      tier=tier)
        self.scheduler.submit(req, now=time.perf_counter())
        return rid

    def _admit_budget(self) -> Optional[int]:
        """Admissions allowed this tick: the static ``admit_limit`` cap
        combined with the autoscaled slot target."""
        lim = self.admit_limit
        if self.min_slots is not None:
            budget = max(0, self._slot_target - len(self.scheduler.active))
            lim = budget if lim is None else min(lim, budget)
        return lim

    def _autoscale(self) -> None:
        if self.min_slots is None:
            return
        if self.scheduler.queued > 0:
            self._slot_target = min(self.cache.slots, self._slot_target + 1)
        else:
            self._slot_target = max(self.min_slots, self._slot_target - 1)

    def _admit_pending(self) -> list[FinishedRequest]:
        finished = []
        for pad_len, group in sorted(
                self.scheduler.pop_admissions(self._admit_budget()).items()):
            group = [(s, req, t0) for s, req, t0 in group
                     if self.scheduler.claim_popped(s, req.rid)]
            if not group:
                continue
            n = len(group)
            prompt = np.zeros((n, pad_len), np.int32)
            lengths = np.zeros((n,), np.int32)
            for i, (_, req, _) in enumerate(group):
                prompt[i, :req.prompt_len] = req.tokens
                lengths[i] = req.prompt_len
            slot_ids = np.asarray([s for s, _, _ in group], np.int32)
            req_keys = sampling.make_keys(
                self.seed, [req.rid for _, req, _ in group])
            first, self.cache.data, self._toks, self._keys = self._admit(
                self.params, self.cache.data, self._toks, self._keys,
                jnp.asarray(prompt), jnp.asarray(lengths),
                jnp.asarray(slot_ids), req_keys)
            self.stats["admit_calls"] += 1
            now = time.perf_counter()
            for (slot, _, _), tok in zip(group, np.asarray(first)):
                self.stats["tokens_out"] += 1
                fin = self.scheduler.record(slot, int(tok), now)
                if fin is not None:
                    finished.append(fin)
        return finished

    # --------------------------------------------------- chunked admission

    def _record(self, slot: int, token: int, now: float,
                finished: list) -> None:
        """Record one emitted token; on retirement queue a prefix-store
        snapshot of prompt + emitted[:-1] (exactly the tokens whose
        state is resident — the last sampled token was never fed back),
        which is what a follow-up session turn will prefix-match."""
        st = self.scheduler.active[slot]
        self.stats["tokens_out"] += 1
        fin = self.scheduler.record(slot, token, now)
        if fin is None:
            return
        if self.pool is not None:
            # this slot's resident tokens: the CONTINUATION prompt (which
            # already contains any pre-preemption output) plus the tokens
            # emitted by this attempt, minus the never-fed last one
            self._queue_snapshot(
                np.concatenate([st.request.tokens,
                                np.asarray(st.emitted[:-1], np.int32)]),
                slot)
        finished.append(fin)

    def _queue_snapshot(self, tokens: np.ndarray, slot: int) -> None:
        e = self.pool.insert(tokens)
        if e is not None:
            self._snap_q.append((e, slot))

    def _flush_snaps(self) -> None:
        """One jitted copy for every snapshot queued since the last
        flush. Must run BEFORE anything rewrites the source slots (the
        next decode/chunk for live rows, the next admission for freed
        ones) so each stored state matches its token key."""
        if not self._snap_q:
            return
        src = np.zeros((self.store.slots,), np.int32)
        mask = np.zeros((self.store.slots,), bool)
        for e, slot in self._snap_q:
            src[e] = slot
            mask[e] = True
        self._snap_q.clear()
        self.stats["snap_calls"] += 1
        self.store.data = self._snap(self.cache.data, self.store.data,
                                     jnp.asarray(src), jnp.asarray(mask))

    def _admit_chunked(self) -> None:
        """Move queued requests into slots on the chunk path: consult
        the prefix pool, batch-restore matched prefix states on device
        (pinning their entries), and leave each row mid-prefill."""
        groups = self.scheduler.pop_admissions(self._admit_budget())
        rows = [rt for g in sorted(groups) for rt in groups[g]]
        rows = [(slot, req, t0) for slot, req, t0 in rows
                if self.scheduler.claim_popped(slot, req.rid)]
        if not rows:
            return
        restores = []
        for slot, req, _t0 in rows:
            start, hold = 0, None
            if self.pool is not None and req.prompt_len >= 2:
                # match capped at prompt_len - 1: at least one suffix
                # token must run to produce the first-token logits
                m = self.pool.acquire(req.tokens[:req.prompt_len - 1])
                if m is not None:
                    hold, start = m
                    restores.append((slot, hold))
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += start
            # a preempted request's snapshot hold is released only now,
            # AFTER acquire pinned it again — the entry stays live from
            # preemption through replay with no eviction window
            prev = self._preempt_holds.pop(req.rid, None)
            if prev is not None:
                self.pool.release(prev)
            key = np.asarray(sampling.make_keys(self.seed, [req.rid]))[0]
            self._pending.append(_PendingRow(slot, req, start, hold, key))
            self._prefilling.add(slot)
        if restores:
            entries = np.zeros((self.cache.slots,), np.int32)
            mask = np.zeros((self.cache.slots,), bool)
            for slot, e in restores:
                entries[slot] = e
                mask[slot] = True
            self.stats["restore_calls"] += 1
            self.cache.data = self._restore(
                self.cache.data, self.store.data,
                jnp.asarray(entries), jnp.asarray(mask))

    def _advance_chunks(self, finished: list) -> None:
        """Advance every mid-prefill slot by one chunk (one gathered
        jit call over the pending rows). Rows completing their prompt
        emit their first token and join the decode batch this tick."""
        if not self._pending:
            return
        if self.prefill_chunk is not None:
            C = self.prefill_chunk
        else:   # prefix-only mode: drain each suffix in one shot
            C = self.scheduler.padded_len(
                max(r.req.prompt_len - r.start for r in self._pending))
        # pad the row count to the next power of two (capped at the slot
        # count) so the gathered call compiles O(log slots) shapes, not
        # one per pending-row count; pad rows point at DISTINCT unused
        # slots with cl == 0, so they pass through untouched
        S = self.cache.slots
        n_real = len(self._pending)
        n_rows = n_real
        if n_rows & (n_rows - 1):
            n_rows = 1 << n_rows.bit_length()
        n_rows = min(n_rows, S)
        used = {r.slot for r in self._pending}
        spare = iter(s for s in range(S) if s not in used)
        slot_ids = np.zeros((n_rows,), np.int32)
        chunk = np.zeros((n_rows, C), np.int32)
        start = np.zeros((n_rows,), np.int32)
        cl = np.zeros((n_rows,), np.int32)
        full = np.ones((n_rows,), np.int32)
        rkeys = np.zeros((n_rows, 2), np.uint32)
        done = np.zeros((n_rows,), bool)
        for i in range(n_real, n_rows):
            slot_ids[i] = next(spare)
        for i, r in enumerate(self._pending):
            li = r.req.prompt_len
            n = min(C, li - r.start)
            slot_ids[i] = r.slot
            chunk[i, :n] = r.req.tokens[r.start:r.start + n]
            start[i] = r.start
            cl[i] = n
            full[i] = li
            rkeys[i] = r.key
            done[i] = r.start + n == li
        self.stats["chunk_calls"] += 1
        first, self.cache.data, self._toks, self._keys = self._admit_chunk(
            self.params, self.cache.data, self._toks, self._keys,
            jnp.asarray(slot_ids), jnp.asarray(chunk), jnp.asarray(start),
            jnp.asarray(cl), jnp.asarray(full), jnp.asarray(rkeys),
            jnp.asarray(done))
        first = np.asarray(first)
        now = time.perf_counter()
        still = []
        for i, r in enumerate(self._pending):
            r.start += int(cl[i])
            if done[i]:
                self._prefilling.discard(r.slot)
                if r.hold is not None:
                    self.pool.release(r.hold)
                    r.hold = None
                if self.pool is not None:
                    self._queue_snapshot(r.req.tokens, r.slot)
                self._record(r.slot, int(first[i]), now, finished)
            else:
                if self.pool is not None \
                        and r.req.prompt_len - r.start <= C:
                    # LAST chunk-boundary snapshot only: it still lets a
                    # concurrent request sharing only PART of this
                    # prompt (system prompt) hit before this one
                    # finishes prefilling, but distinct-suffix traffic
                    # stops inserting one never-reused entry per chunk
                    # (each a device row copy + an LRU eviction under
                    # small pools). Prompt-completion and retirement
                    # snapshots above/in _record are unchanged.
                    self._queue_snapshot(r.req.tokens[:r.start], r.slot)
                still.append(r)
        self._pending = still

    def _preempt_pass(self) -> None:
        """Evict SLO-selected victims so deadline-risk queued requests
        admit this same tick. Each victim's resident state is inserted
        into the prefix store and PINNED under its rid before the slot
        is surrendered; the snapshot copy flushes before admission can
        rewrite the freed rows."""
        victims = self.scheduler.select_preemptions(
            prefilling=frozenset(self._prefilling))
        if not victims:
            return
        for slot in victims:
            st = self.scheduler.active[slot]
            if self.pool is not None:
                resident = np.concatenate(
                    [st.request.tokens,
                     np.asarray(st.emitted[:-1], np.int32)])
                self._hold_preempt_snapshot(st.request.rid, resident, slot)
            self.stats["preemptions"] += 1
            self.stats["replayed_tokens"] += len(st.emitted)
            self.scheduler.preempt(slot, time.perf_counter())
        self._flush_snaps()     # before admission reuses the freed slots

    def _hold_preempt_snapshot(self, rid: int, tokens: np.ndarray,
                               slot: int) -> None:
        if len(tokens) < self.pool.min_tokens:
            return
        e = self.pool.insert(tokens)
        if e is not None:
            self._snap_q.append((e, slot))
        else:
            # exact prefix already stored (e.g. a second preemption at
            # the same position): its state is byte-identical, reuse it
            e = self.pool.index.get(tokens)
        if e is None:
            return          # pool fully pinned: re-admission recomputes
        self.pool.pin(e)
        prev = self._preempt_holds.pop(rid, None)
        if prev is not None:
            self.pool.release(prev)
        self._preempt_holds[rid] = e

    def cancel(self, rid: int) -> bool:
        """Abort a request: drop it from the queue (tombstoning it if
        its admission group was already popped), or retire its slot
        mid-prefill/mid-decode (releasing any pinned prefix entry). The
        survivor slots are untouched — a cancelled row's cache writes
        are masked off from the next decode on."""
        kind, slot = self.scheduler.cancel(rid)
        if kind is None:
            return False
        hold = self._preempt_holds.pop(rid, None)
        if hold is not None:
            self.pool.release(hold)
        if kind == "active":
            self._prefilling.discard(slot)
            for r in list(self._pending):
                if r.slot == slot:
                    if r.hold is not None:
                        self.pool.release(r.hold)
                    self._pending.remove(r)
        return True

    # -------------------------------------------------------------- tick

    def step(self) -> list[FinishedRequest]:
        """One engine tick: preempt SLO victims (priority mode), admit
        into free slots (chunk path: restore matched prefixes + advance
        one chunk), then decode ONE token for every live resident
        sequence (a single donated jit call)."""
        finished: list[FinishedRequest] = []
        self.stats["ticks"] += 1
        self._autoscale()
        self.stats["slot_target_sum"] += self._slot_target
        if self.preempt_enabled:
            self._preempt_pass()
        if self._chunked:
            self._admit_chunked()
            self._advance_chunks(finished)
            self._flush_snaps()     # before decode rewrites source rows
        else:
            finished.extend(self._admit_pending())
        live = [s for s in self.scheduler.active
                if s not in self._prefilling]
        if live:
            self.stats["decode_steps"] += 1
            self.stats["occupancy_sum"] += len(live) / self.cache.slots
            if self._chunked:
                mask = np.zeros((self.cache.slots,), bool)
                mask[live] = True
                self._toks, self.cache.data = self._decode_live(
                    self.params, self.cache.data, self._toks, self._keys,
                    jnp.asarray(mask))
            else:
                self._toks, self.cache.data = self._decode(
                    self.params, self.cache.data, self._toks, self._keys)
            emitted = np.asarray(self._toks)[:, 0]   # the ONLY host copy
            now = time.perf_counter()
            for slot in live:
                self._record(slot, int(emitted[slot]), now, finished)
        self._flush_snaps()         # retirement snapshots from this tick
        return finished

    def run(self, requests: Optional[Iterable] = None
            ) -> list[FinishedRequest]:
        """Submit ``requests`` (Request objects or (tokens, max_new)
        pairs), then step until queue and slots drain."""
        for r in requests or ():
            if isinstance(r, Request):
                self.submit(r.tokens, r.max_new_tokens, eos_id=r.eos_id,
                            rid=r.rid, tier=r.tier)
            else:
                tokens, max_new = r
                self.submit(tokens, max_new)
        finished = []
        while self.scheduler.has_work():
            finished.extend(self.step())
        return finished

    def generate(self, prompts: Sequence, max_new_tokens: int
                 ) -> list[np.ndarray]:
        """Convenience: decode ``max_new_tokens`` for each prompt; output
        ordered like ``prompts`` regardless of scheduling."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        by_rid = {f.request.rid: f.tokens for f in self.run()}
        return [by_rid[r] for r in rids]

    @property
    def occupancy(self) -> float:
        """Mean fraction of slots doing useful work per decode step."""
        steps = self.stats["decode_steps"]
        return self.stats["occupancy_sum"] / steps if steps else 0.0

    def reset_stats(self) -> None:
        """Zero the step/occupancy counters (e.g. after a compile
        warmup); trace counters are kept — they pin the contract."""
        self.stats = {k: 0.0 if k in ("occupancy_sum", "slot_target_sum")
                      else 0 for k in self.stats}
        if self.pool is not None:
            self.pool.stats = {k: 0 for k in self.pool.stats}
