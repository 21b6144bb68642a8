"""Slot-based continuous-batching inference engine.

`ServeEngine` holds a fixed-capacity decode batch — ``slots`` lanes of the
existing ring-buffer KV / O(1) SSM decode cache (`models.api`) — and drives
it with a small, pinned set of compiled programs:

  * one **decode step** for all slots at once: per-slot token and position,
    vmapped over the slot axis of the batched cache, greedy argmax on
    device.  The slot count is static and free slots simply compute garbage
    lanes (the same static-shape discipline as `BatchCtx.active_budget`),
    so admitting and evicting requests never recompiles — one compile
    serves the server's whole lifetime, pinned by tests/test_serve.py.
  * one **fused decode chunk** per ``decode_chunk`` size used: ``step(now,
    decode_chunk=d)`` folds d decode steps into a single ``lax.scan`` —
    prompt-tail tokens are fed as a precomputed forced-token matrix,
    EOS/max-token finishers freeze their token/position inside the scan
    (finished lanes keep computing garbage, exactly the lane the host loop
    would have left behind), and the chunk pays **one host sync** instead
    of d.  Token-identical to d single steps; mid-chunk finishers are
    accounted at their true virtual sub-step time (``now + j * step_dt``)
    so latency percentiles are unchanged.  Each d is keyed separately in
    the jit cache, so toggling chunk sizes never recompiles.
  * one **prefill-insert** per prompt-length bucket: prefill the largest
    bucket-length *prefix* of the prompt in a single full-sequence shot,
    write the resulting one-request cache into the claimed slot
    (``dynamic_update_slice`` along the slot axis, slot index traced), and
    feed the short prompt tail through the normal decode step as forced
    tokens.  No prompt padding ever enters the model, so a request decodes
    **token-identically** to serving it alone; the bucket set only bounds
    how many prefill programs get compiled.  Bucket 1 is always a member,
    so prompts shorter than every configured bucket prefill their first
    token through the shared length-1 program instead of compiling one
    program per distinct short length (the compile set IS the bucket set).
  * one **batched prefill-insert** per (bucket, batch-size-class):
    ``insert_batch`` admits up to ``slots`` same-bucket requests in one
    compiled shot — the (m, n) token block prefills as one batch and the
    resulting per-request caches land via a traced slot-index *vector*
    (a vectorized ``dynamic_update_slice`` over the slot axis).  m is
    padded up to a power-of-two class (pad rows duplicate row 0 and write
    row 0's lane the identical values, so padding is order-free and
    token-exact), bounding compiles to one per (bucket, class).

Per-slot bookkeeping (prompt tail, generated tokens, timestamps) is plain
host Python: the device work per step is one dispatch returning the (N,)
argmax tokens — or, chunked, one dispatch returning the (d, N) token
matrix the host replays — the sync serving must pay anyway to emit
tokens, now amortized over d steps.

Weights are swapped live via ``swap_weights`` (see `repro.serve.swap` for
the `FedEngine` hook): treedefs/shapes must match the current serving
params (checked, mismatches named), the old buffers are donated to the
swap jit so the new weights land in their storage, and a version counter
is stamped onto every `Response` so callers can tell which federated
round's distilled model produced their tokens.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import assert_tree_compatible
from ..models.api import model_decode_step, model_init_cache, model_prefill
from ..models.base import ModelConfig
from ..obs import trace as obs
from ..obs.jit_watch import jit_cache_size  # canonical impl; re-exported
from .queue import Request, Response, bucket_of

DEFAULT_BUCKETS = (16, 32, 64, 128)


@dataclass
class _SlotTask:
    """Host-side state of one occupied slot."""
    req: Request
    pending: list                       # prompt-tail tokens not yet fed
    generated: list = field(default_factory=list)
    admitted_at: float = 0.0
    first_token_at: Optional[float] = None


class ServeEngine:
    """Continuous-batching greedy decoder over a fixed slot budget.

    ``seq_budget`` caps prompt + generation per request (it sizes the
    ring-buffer KV cache, so staying under it keeps full-context exactness).
    ``buckets`` are the compiled prefill lengths (see module docstring);
    bucket 1 is always added, so prompts shorter than every configured
    bucket prefill their first token through the shared length-1 program
    and force the rest through the decode step — the prefill compile set
    never grows beyond the bucket set.

    Token-only architectures (dense / moe / ssm / hybrid); the audio and
    vlm stubs need modality inputs a prompt doesn't carry.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 seq_budget: int = 128,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 eos_id: Optional[int] = None, version: int = 0):
        if cfg.arch_type in ("vlm", "audio"):
            raise NotImplementedError(
                f"ServeEngine serves token-only archs; {cfg.arch_type!r} "
                "needs modality inputs per request")
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        self.cfg = cfg
        self.params = params
        self.slots = int(slots)
        self.seq_budget = int(seq_budget)
        # bucket 1 is always a member: the short-prompt fallback compiles
        # the one shared length-1 prefill instead of one program per
        # distinct short length (the compile set == the bucket set)
        self.buckets = tuple(sorted({1} | {int(b) for b in buckets
                                           if b <= self.seq_budget}))
        self.eos_id = eos_id
        self.version = int(version)

        self.cache = model_init_cache(cfg, params, self.slots, self.seq_budget)
        self.tok = np.zeros((self.slots,), np.int32)
        self.pos = np.zeros((self.slots,), np.int32)
        self.tasks: list = [None] * self.slots
        self.completed: list = []       # drained by pop_completed()
        self.n_steps = 0                # decode sub-steps accounted
        self.n_dispatches = 0           # device round-trips those steps cost
        self.n_inserts = 0              # requests admitted
        self.n_prefill_shots = 0        # compiled prefill dispatches
        self.n_swaps = 0

        self._step_fn = self._build_step()
        self._chunk_fns: dict = {}      # decode_chunk d -> jitted fused scan
        self._prefill_fns: dict = {}    # prefill length -> jitted insert
        self._prefill_batch_fns: dict = {}   # (bucket, class) -> jitted

    # -------------------------------------------------------- compiled fns ---
    def _build_step(self):
        cfg = self.cfg

        def one(params, cache_i, tok_i, pos_i):
            cache_i = jax.tree.map(lambda a: jnp.expand_dims(a, 1), cache_i)
            logits, nc = model_decode_step(cfg, params, cache_i,
                                           tok_i[None], pos_i)
            return (jnp.argmax(logits[0]).astype(jnp.int32),
                    jax.tree.map(lambda a: jnp.squeeze(a, axis=1), nc))

        def step(params, cache, tok, pos):
            # vmap over the slot axis (axis 1 of every cache leaf: leaves are
            # (n_blocks, slots, ...)); each lane sees its own position, so
            # slots at different depths decode in the same dispatch
            return jax.vmap(one, in_axes=(None, 1, 0, 0),
                            out_axes=(0, 1))(params, cache, tok, pos)

        return jax.jit(step, donate_argnums=(1,))

    def _build_chunk(self, d: int):
        """d decode steps fused into one compiled ``lax.scan``.

        Carry: (cache, tok, pos, remaining, forced_len).  ``forced`` is the
        (d, N) prompt-tail matrix — sub-step j feeds ``forced[j, i]`` to
        lanes still consuming their tail; ``remaining`` counts tokens each
        lane still owes (0 == free or finished).  A lane that hits its
        max-token count (or EOS) mid-chunk freezes its token/position —
        bitwise the lane the per-step host loop leaves behind after
        eviction — and keeps computing garbage nothing reads, so the chunk
        shape never depends on who finishes when.  Output is the (d, N)
        argmax-token matrix: the chunk's single host sync."""
        cfg, eos = self.cfg, self.eos_id

        def one(params, cache_i, tok_i, pos_i):
            cache_i = jax.tree.map(lambda a: jnp.expand_dims(a, 1), cache_i)
            logits, nc = model_decode_step(cfg, params, cache_i,
                                           tok_i[None], pos_i)
            return (jnp.argmax(logits[0]).astype(jnp.int32),
                    jax.tree.map(lambda a: jnp.squeeze(a, axis=1), nc))

        def chunk(params, cache, tok, pos, forced, forced_len, remaining):
            def body(carry, forced_j):
                cache, tok, pos, rem, fl = carry
                nxt, cache = jax.vmap(one, in_axes=(None, 1, 0, 0),
                                      out_axes=(0, 1))(params, cache, tok,
                                                       pos)
                done = rem <= 0             # finished before this sub-step
                is_forced = (~done) & (fl > 0)
                emitting = (~done) & (fl <= 0)
                rem = jnp.where(emitting, rem - 1, rem)
                if eos is not None:
                    rem = jnp.where(emitting & (nxt == jnp.int32(eos)),
                                    0, rem)
                finishing = emitting & (rem <= 0)
                tok = jnp.where(is_forced, forced_j,
                                jnp.where(emitting & ~finishing, nxt, tok))
                pos = jnp.where(done, pos, pos + 1)
                fl = jnp.where(is_forced, fl - 1, fl)
                return (cache, tok, pos, rem, fl), nxt

            (cache, tok, pos, _, _), mat = jax.lax.scan(
                body, (cache, tok, pos, remaining, forced_len), forced)
            return mat, cache, tok, pos

        return jax.jit(chunk, donate_argnums=(1,))

    def _build_prefill(self, n: int):
        cfg, budget = self.cfg, self.seq_budget

        def prefill_insert(params, cache, toks, slot):
            logits, one = model_prefill(cfg, params, {"tokens": toks}, budget)
            cache = jax.tree.map(
                lambda full, c1: jax.lax.dynamic_update_slice_in_dim(
                    full, c1.astype(full.dtype), slot, axis=1), cache, one)
            return jnp.argmax(logits[0]).astype(jnp.int32), cache

        del n   # the compile is keyed by toks.shape; n only names the cache
        return jax.jit(prefill_insert, donate_argnums=(1,))

    def _build_prefill_batch(self):
        """Batched prefill-insert: (c, n) same-bucket token rows prefill as
        one batch and land in the cache through a traced slot-index vector
        (``full.at[:, idx].set`` — the vectorized form of the single-insert
        ``dynamic_update_slice`` over the slot axis).  Pad rows duplicate
        row 0 and write row 0's lane the identical values, so duplicate
        scatter indices are order-free."""
        cfg, budget = self.cfg, self.seq_budget

        def prefill_insert_many(params, cache, toks, idx):
            logits, many = model_prefill(cfg, params, {"tokens": toks},
                                         budget)
            cache = jax.tree.map(
                lambda full, cc: full.at[:, idx].set(cc.astype(full.dtype)),
                cache, many)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        return jax.jit(prefill_insert_many, donate_argnums=(1,))

    def reset(self) -> None:
        """Drop all in-flight requests and re-zero the cache/positions while
        keeping every compiled program (shapes are unchanged, so the jit
        caches stay warm — a server restart without the recompile)."""
        self.cache = model_init_cache(self.cfg, self.params, self.slots,
                                      self.seq_budget)
        self.tok[:] = 0
        self.pos[:] = 0
        self.tasks = [None] * self.slots
        self.completed = []

    # ----------------------------------------------------------- occupancy ---
    def free_slots(self) -> list:
        return [i for i, t in enumerate(self.tasks) if t is None]

    @property
    def n_active(self) -> int:
        return self.slots - len(self.free_slots())

    def pop_completed(self) -> list:
        out, self.completed = self.completed, []
        return out

    def prefill_len(self, prompt_len: int) -> int:
        return bucket_of(prompt_len, self.buckets)

    # -------------------------------------------------------------- insert ---
    def _check_request(self, req: Request) -> None:
        S = req.prompt_len
        if S < 1:
            raise ValueError(f"request {req.id}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.id}: max_new_tokens must be >= 1")
        if S + req.max_new_tokens > self.seq_budget:
            raise ValueError(
                f"request {req.id}: prompt ({S}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds seq_budget="
                f"{self.seq_budget}; the ring buffer would wrap and drop "
                "context")

    def _admit_task(self, req: Request, slot: int, n: int, first: int,
                    now: float) -> None:
        task = _SlotTask(req=req, pending=list(req.tokens[n:]),
                         admitted_at=float(now))
        self.tasks[slot] = task
        self.pos[slot] = n
        if task.pending:
            # the prefix's next-token prediction is a known prompt token:
            # discard the argmax, force the tail through the decode step
            self.tok[slot] = task.pending.pop(0)
        else:
            self._emit(slot, int(first), now)   # first generated token

    def insert(self, req: Request, now: float = 0.0) -> int:
        """Claim a free slot for ``req``: one compiled prefill of the bucket
        prefix, cache written into the slot, prompt tail queued as forced
        tokens for the shared decode step.  Returns the slot index."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot; admit at most free_slots()")
        self._check_request(req)
        slot = free[0]
        n = self.prefill_len(req.prompt_len)
        fn = self._prefill_fns.get(n)
        if fn is None:
            fn = self._prefill_fns[n] = self._build_prefill(n)
        with obs.span("serve.prefill", "serve", req=req.id, bucket=n,
                      slot=slot):
            toks = jnp.asarray(np.asarray(req.tokens[:n], np.int32)[None])
            first, self.cache = fn(self.params, self.cache, toks, slot)
        self.n_inserts += 1
        self.n_prefill_shots += 1
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("serve.inserts").inc()
            reg.histogram("serve.prefill_batch_size").observe(1)
        self._admit_task(req, slot, n, int(first), now)
        return slot

    def batch_class(self, m: int) -> int:
        """The padded row count batched prefill compiles for ``m`` requests:
        the smallest power of two >= m, capped at ``slots`` — so the jit
        cache holds one program per (bucket, class), not per exact m."""
        c = 1
        while c < m:
            c *= 2
        return min(c, self.slots)

    def insert_batch(self, reqs: Sequence[Request],
                     now: float = 0.0) -> list:
        """Admit up to ``slots`` same-bucket requests in **one** compiled
        shot: their bucket prefixes prefill as a single (m, n) batch and
        the per-request caches land through a traced slot-index vector, so
        admission cost is one dispatch per group instead of one per
        request.  Token-identical to inserting each request alone.
        Returns the claimed slot indices, one per request, in order."""
        reqs = list(reqs)
        if not reqs:
            return []
        free = self.free_slots()
        if len(reqs) > len(free):
            raise RuntimeError(
                f"{len(reqs)} requests for {len(free)} free slots; "
                "admit at most free_slots()")
        ns = set()
        for req in reqs:
            self._check_request(req)
            ns.add(self.prefill_len(req.prompt_len))
        if len(ns) != 1:
            raise ValueError(
                "insert_batch needs same-bucket requests (one compiled "
                f"prefill length per shot); got buckets {sorted(ns)} — "
                "group with AdmissionQueue.admit(..., group=True)")
        n = ns.pop()
        m = len(reqs)
        c = self.batch_class(m)
        claimed = free[:m]
        toks = np.zeros((c, n), np.int32)
        idx = np.zeros((c,), np.int32)
        for row, (req, slot) in enumerate(zip(reqs, claimed)):
            toks[row] = np.asarray(req.tokens[:n], np.int32)
            idx[row] = slot
        toks[m:] = toks[0]          # pad rows duplicate row 0: they write
        idx[m:] = idx[0]            # row 0's lane the identical values
        fn = self._prefill_batch_fns.get((n, c))
        if fn is None:
            fn = self._prefill_batch_fns[(n, c)] = self._build_prefill_batch()
        with obs.span("serve.prefill", "serve", bucket=n, batch=m,
                      cls=c, slots=list(map(int, claimed))):
            firsts, self.cache = fn(self.params, self.cache,
                                    jnp.asarray(toks), jnp.asarray(idx))
            firsts = np.asarray(firsts)
        self.n_inserts += m
        self.n_prefill_shots += 1
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("serve.inserts").inc(m)
            reg.histogram("serve.prefill_batch_size").observe(m)
        for row, (req, slot) in enumerate(zip(reqs, claimed)):
            self._admit_task(req, slot, n, int(firsts[row]), now)
        return claimed

    # ---------------------------------------------------------------- step ---
    def step(self, now: float = 0.0, decode_chunk: int = 1,
             step_dt: float = 0.0) -> list:
        """Decode for every slot (free lanes compute garbage that nothing
        reads).  ``decode_chunk=d`` folds d steps into one compiled scan
        with a single host sync; mid-chunk finishers are stamped at their
        true virtual sub-step time ``now + j * step_dt``.  Each d keys its
        own jit entry, so toggling chunk sizes never recompiles.  Returns
        the requests that finished."""
        if self.n_active == 0:
            return []
        d = int(decode_chunk)
        if d < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if d > 1:
            return self._step_chunk(now, d, float(step_dt))
        with obs.span("serve.decode", "serve", active=self.n_active, chunk=1):
            nxt, self.cache = self._step_fn(self.params, self.cache,
                                            self.tok, self.pos)
            nxt = np.asarray(nxt)       # the per-step host sync: (N,) tokens
        self.n_steps += 1
        self.n_dispatches += 1
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("serve.decode_steps").inc()
            reg.gauge("serve.active_slots").set(self.n_active)
        done_before = len(self.completed)
        for i, task in enumerate(self.tasks):
            if task is None:
                continue
            self.pos[i] += 1
            if task.pending:
                # still consuming the prompt tail: the model's prediction is
                # superseded by the known next prompt token
                self.tok[i] = task.pending.pop(0)
            else:
                self._emit(i, int(nxt[i]), now)
        return self.completed[done_before:]

    def _step_chunk(self, now: float, d: int, step_dt: float) -> list:
        """d fused decode steps: one dispatch, one (d, N) token sync, then
        a host replay of the per-step bookkeeping the d=1 loop would have
        done — same emissions, same finish order, timestamps at the true
        virtual sub-step.  ``n_steps`` advances by the number of sub-steps
        that still had an active lane (exactly the steps the per-token loop
        would have executed); trailing garbage sub-steps cost only device
        time, already amortized into the chunk's single dispatch."""
        N = self.slots
        forced = np.zeros((d, N), np.int32)
        forced_len = np.zeros((N,), np.int32)
        remaining = np.zeros((N,), np.int32)
        for i, task in enumerate(self.tasks):
            if task is None:
                continue
            tail = task.pending[:d]
            forced[:len(tail), i] = tail
            forced_len[i] = len(tail)
            remaining[i] = task.req.max_new_tokens - len(task.generated)
        fn = self._chunk_fns.get(d)
        if fn is None:
            fn = self._chunk_fns[d] = self._build_chunk(d)
        with obs.span("serve.decode", "serve", active=self.n_active, chunk=d):
            mat, self.cache, tok, pos = fn(
                self.params, self.cache, self.tok, self.pos,
                jnp.asarray(forced), jnp.asarray(forced_len),
                jnp.asarray(remaining))
            mat = np.asarray(mat)       # the chunk's one host sync
            # host copies: later bookkeeping mutates these in place
            tok, pos = np.array(tok, np.int32), np.array(pos, np.int32)
        self.n_dispatches += 1
        done_before = len(self.completed)
        used = 0
        with obs.span("serve.replay", "serve", chunk=d):
            for j in range(d):
                if all(t is None for t in self.tasks):
                    break               # the d=1 loop would have stopped
                used += 1
                t_j = now + j * step_dt     # true virtual time of sub-step j
                for i, task in enumerate(self.tasks):
                    if task is None:
                        continue
                    if task.pending:
                        task.pending.pop(0)  # forced: prediction superseded
                    else:
                        self._emit(i, int(mat[j, i]), t_j)
        # the device chained tok/pos through the same masking the replay
        # just applied (finished lanes frozen), so these ARE the d=1 state
        self.tok, self.pos = tok, pos
        self.n_steps += used
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("serve.decode_steps").inc(used)
            reg.counter("serve.decode_chunks").inc()
            reg.gauge("serve.active_slots").set(self.n_active)
        return self.completed[done_before:]

    def _emit(self, slot: int, token: int, now: float) -> None:
        """Record one generated token for ``slot``; evict on completion
        (host bookkeeping only — no device work, no recompile)."""
        task = self.tasks[slot]
        if task.first_token_at is None:
            task.first_token_at = float(now)
            reg = obs.current_registry()
            if reg is not None:
                # admit -> first token, in the caller's clock (virtual or
                # wall) — the serving-latency histogram the bench reports
                reg.histogram("serve.admit_to_first_token_s").observe(
                    task.first_token_at - task.admitted_at)
        task.generated.append(token)
        done = (len(task.generated) >= task.req.max_new_tokens
                or (self.eos_id is not None and token == self.eos_id))
        if done:
            self.completed.append(Response(
                id=task.req.id, prompt_len=task.req.prompt_len,
                tokens=tuple(task.generated), weights_version=self.version,
                arrival=task.req.arrival, admitted_at=task.admitted_at,
                first_token_at=task.first_token_at, finished_at=float(now)))
            self.tasks[slot] = None
        else:
            self.tok[slot] = token

    # ---------------------------------------------------------------- swap ---
    def swap_weights(self, new_params, version: Optional[int] = None) -> None:
        """Hot-swap the serving weights.  The pytree must match the current
        params exactly (structure, shapes, dtypes — mismatches are named);
        the old buffers are donated, so the swap neither recompiles the
        decode/prefill programs nor doubles resident weight memory beyond
        the unavoidable old+incoming overlap.  ``step`` syncs before it
        returns, so a swap always lands at a decode-chunk boundary: every
        token inside one fused chunk comes from a single weights version,
        and the version stamped on a Response is exactly the version its
        chunks decoded under."""
        assert_tree_compatible(self.params, new_params,
                               what="hot-swapped serving weights")
        if not hasattr(self, "_swap_fn"):
            # old (donated) -> freed or aliased as the landing buffers for
            # the incoming values; `new` is NOT donated, so a trainer handing
            # us views into its live state keeps its buffers intact
            self._swap_fn = jax.jit(
                lambda old, new: jax.tree.map(
                    lambda o, n: n.astype(o.dtype), old, new),
                donate_argnums=(0,))
        with obs.span("serve.swap", "swap",
                      version=version if version is not None
                      else self.version + 1):
            self.params = self._swap_fn(self.params, new_params)
        self.version = int(version) if version is not None \
            else self.version + 1
        self.n_swaps += 1
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("serve.swaps").inc()

    # ----------------------------------------------------------- telemetry ---
    def compile_counts(self) -> dict:
        """Compiled-program counts per entry point — the no-recompile pin:
        after warmup ``step`` stays at 1, each ``decode_chunk`` size at 1,
        ``prefill`` at one per bucket used (the bucket-1 fallback keeps the
        set inside the bucket set), and ``prefill_batch`` at one per
        (bucket, batch-size-class), no matter how many requests churn
        through."""
        return {"step": jit_cache_size(self._step_fn),
                "decode_chunk": {d: jit_cache_size(fn)
                                 for d, fn in sorted(self._chunk_fns.items())},
                "prefill": {n: jit_cache_size(fn)
                            for n, fn in sorted(self._prefill_fns.items())},
                "prefill_batch": {
                    f"{n}x{c}": jit_cache_size(fn)
                    for (n, c), fn in sorted(self._prefill_batch_fns.items())}}

    def stats(self) -> dict:
        return {"slots": self.slots, "active": self.n_active,
                "steps": self.n_steps, "dispatches": self.n_dispatches,
                "inserts": self.n_inserts,
                "prefill_shots": self.n_prefill_shots,
                "swaps": self.n_swaps, "version": self.version,
                "compiles": self.compile_counts()}
