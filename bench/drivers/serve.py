"""Open-loop request traffic on the real clock: the general generator of
serving traffic.

The traffic file fixes the mix: arrivals (``poisson`` at ``rate_per_s``),
prompt and output lengths (each ``lognormal`` with a ``median`` and
``sigma``, cut to ``[min, max]``), the prefill ``buckets``.  Sizes and
inter-arrival gaps are drawn once from ``shape_seed``, so every run serves
the same multiset of work; ``--seed`` only orders them and draws the
prompts' token ids.

Set-up builds the server (the family's) and warms every prefill bucket and
the decode chunk.  The window then admits each request when it is due
(``AdmissionQueue``, oldest first, ``prefill_group`` requests per prefill
shot), runs fused decode chunks while any slot is busy, and stamps each
request's tokens with the host time its chunk handed them back.  Arrivals
stop when ``--seconds`` have passed; the requests due in the window are
then served to their end (at most ``drain_s`` more), so each latency
counts its whole wait.  A request that does not finish has failed.

End-to-end: ``ttft_p90_ms`` (due time to first token), ``tpot_p90_ms``
((last - first token) / (tokens - 1) per request), ``serve_tok_s``
(output tokens delivered inside the window over its length).
"""
from __future__ import annotations

import math
import time

import numpy as np


def percentile(values, q: float):
    """Exact percentile over raw samples, by linear interpolation between
    order statistics (numpy's default rule); a failed request's infinite
    latency makes every percentile that reaches it infinite.  None for no
    samples."""
    v = sorted(float(x) for x in values)
    if not v:
        return None
    rank = q / 100.0 * (len(v) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(v) - 1)
    frac = rank - lo
    if frac == 0.0 or v[hi] == v[lo]:
        return v[lo]
    if math.isinf(v[hi]):
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * frac


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic: dict, seconds: float, seed: int, vocab: int) -> list:
    """[(due offset s, prompt tokens, max_new_tokens)] for one window:
    ``round(rate * seconds)`` requests, Poisson arrivals conditioned on
    that count (exponential gaps scaled to fill the window).  The sizes
    and the gaps come from ``shape_seed``, so every seed serves the same
    work; the seed orders them and draws the token ids."""
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    gaps = shape.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    plen = _lengths(shape, traffic["prompt_len"], n)
    olen = _lengths(shape, traffic["output_len"], n)
    order = np.random.default_rng(seed)
    gaps = gaps[order.permutation(n)]
    pick = order.permutation(n)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [(float(t), order.integers(0, vocab, int(plen[k])).tolist(),
             int(olen[k])) for t, k in zip(due, pick)]


class Book:
    """Per-request host timestamps: due, admitted, each token's arrival."""

    def __init__(self, end: float):
        self.due, self.admitted, self.first, self.last = {}, {}, {}, {}
        self.ntok, self.submitted = {}, {}
        self.end = end              # the window's close
        self.in_window = 0          # tokens handed back before it

    def tokens(self, rid: int, n: int, t: float) -> None:
        """``n`` is the request's generated-token count as of time t."""
        had = self.ntok.get(rid, 0)
        if n <= had:
            return
        self.first.setdefault(rid, t)
        self.last[rid] = t
        self.ntok[rid] = n
        if t <= self.end:
            self.in_window += n - had


def _progress(engine, book: Book, done: list, t: float) -> None:
    for task in engine.tasks:
        if task is not None:
            book.tokens(task.req.id, len(task.generated), t)
    for resp in done:
        book.tokens(resp.id, len(resp.tokens), t)


def serve_window(engine, reqs: list, seconds: float, decode_chunk: int,
                 group: int, drain_s: float, t0: float, prof=None) -> dict:
    """Serve ``reqs`` (``schedule``'s list) open-loop from ``t0`` on the
    real clock: submit each when due, admit oldest first into free slots
    (``group`` per prefill shot), run decode chunks while a slot is busy.
    Arrivals end at ``t0 + seconds``; the loop then drains what is left,
    for at most ``drain_s`` more.  ``prof`` (a `harness.Profiler`) is
    polled between calls; the device counts as of its stop are kept as
    ``traced``.  Returns the bookkeeping."""
    import jax
    from repro.serve.queue import AdmissionQueue
    d = int(decode_chunk)
    queue = AdmissionQueue(buckets=engine.buckets, timeout=None,
                           max_queue=None)
    end = t0 + seconds
    book = Book(end)
    finished, prompts = {}, {}
    n_prefill = n_chunks = lane_steps = 0
    ctx_steps = prefill_s = decode_s = 0.0
    nxt = 0
    traced = None

    def counts():
        return {"prefill_shots": n_prefill, "decode_chunks": n_chunks,
                "decode_sub_steps": n_chunks * d,
                "decode_lane_steps": lane_steps,
                "decode_ctx_steps": ctx_steps, "prefill_host_s": prefill_s,
                "decode_host_s": decode_s}

    while True:
        if prof is not None and prof.poll():
            traced = counts()
        now = time.perf_counter()
        while nxt < len(reqs) and t0 + reqs[nxt][0] <= now:
            due, toks, o = reqs[nxt]
            r = queue.submit(toks, o, now=t0 + due)
            book.due[r.id] = t0 + due
            book.submitted[r.id] = now
            prompts[r.id] = toks
            nxt += 1
        free = len(engine.free_slots())
        while free and len(queue):
            adm = queue.admit(time.perf_counter(), min(free, group),
                              group=True)
            ta = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                engine.insert_batch(adm, now=ta)
            tb = time.perf_counter()
            n_prefill += 1
            prefill_s += tb - ta
            for r in adm:
                book.admitted[r.id] = ta
            done = engine.pop_completed()
            _progress(engine, book, done, tb)
            finished.update((resp.id, resp) for resp in done)
            free = len(engine.free_slots())
        if engine.n_active:
            # required work of the chunk's d sub-steps: the active lanes
            # and the cached positions they attend over
            act = [i for i, t in enumerate(engine.tasks) if t is not None]
            lane_steps += d * len(act)
            ctx_steps += d * float(sum(engine.pos[i] for i in act)) \
                + len(act) * d * (d - 1) / 2
            ta = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.decode"):
                done = engine.step(ta, decode_chunk=d)
            tb = time.perf_counter()
            n_chunks += 1
            decode_s += tb - ta
            engine.pop_completed()
            _progress(engine, book, done, tb)
            finished.update((resp.id, resp) for resp in done)
            continue
        if nxt >= len(reqs) and not len(queue):
            break
        if time.perf_counter() > end + drain_s:
            break
        if nxt < len(reqs):
            wait = t0 + reqs[nxt][0] - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.idle"):
                    time.sleep(wait)
    if prof is not None and prof.running:
        prof.stop()
        traced = counts()
    return {"book": book, "finished": finished, "prompts": prompts,
            "close": time.perf_counter(), "counts": counts(),
            "traced": traced, "decode_chunk": d,
            "traced_until": None if prof is None else prof.stopped_at}


def summary(w: dict, seconds: float) -> dict:
    """End-to-end numbers and counters of one served window."""
    book, finished = w["book"], w["finished"]
    ids = sorted(book.due)
    ttft = [(book.first[i] - book.due[i]) if i in finished else math.inf
            for i in ids]
    tpot = [(book.last[i] - book.first[i]) / (book.ntok[i] - 1)
            for i in ids if i in finished and book.ntok[i] > 1]
    # host-clock layer numbers: in a traced run, over the requests
    # submitted (admitted) before the profiler stopped, since stopping it
    # stalls the loop once
    cut = w["traced_until"] or math.inf
    qwait = [book.admitted[i] - book.due[i] for i in ids
             if book.admitted.get(i, math.inf) < cut]
    late = [book.submitted[i] - book.due[i] for i in ids
            if book.submitted[i] < cut]
    ms = (lambda v, q: 1e3 * percentile(v, q) if v else None)
    return {
        "attempted": len(ids),
        "failed": sum(1 for i in ids if i not in finished),
        "e2e": {"ttft_p90_ms": ms(ttft, 90), "tpot_p90_ms": ms(tpot, 90),
                "serve_tok_s": book.in_window / seconds},
        "counters": {
            "requests": len(ids), "finished": len(finished),
            "window_s": seconds, "close_s": w["close"] - book.end + seconds,
            "decode_chunk": w["decode_chunk"], **w["counts"],
            **{"traced_" + k: v for k, v in (w["traced"] or {}).items()},
            "queue_wait_p90_ms": ms(qwait, 90),
            "late_p99_ms": ms(late, 99),
            "ttft_p50_ms": ms(ttft, 50), "tpot_p50_ms": ms(tpot, 50),
            "output_tokens": sum(len(r.tokens) for r in finished.values())}}


def run(cell) -> dict:
    import jax
    fam, tr = cell.family, cell.traffic
    obj = fam.setup(cell.cfg, tr, cell.seed31, cell.devices)
    reqs = schedule(tr, cell.seconds, cell.seed31, cell.cfg["vocab_size"])
    setup_s = cell.since_start()
    c0 = cell.counter.snapshot()
    prof = cell.profiler()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        prof.start()
        w = serve_window(obj.engine, reqs, cell.seconds,
                         cell.cfg["decode_chunk"], tr["prefill_group"],
                         float(tr["drain_s"]), t0, prof)
    c1 = cell.counter.snapshot()
    from harness import memory_peak
    mem = memory_peak(cell.devices)
    res = summary(w, cell.seconds)
    fin, prompts = w["finished"], w["prompts"]
    served = [(prompts[i], fin[i].tokens) for i in sorted(fin)]
    checks = fam.check(obj, cell.cfg, tr, cell.seed31, served,
                       control=cell.control)
    res["e2e"]["setup_s"] = setup_s
    res["counters"].update(compiles_in_window=c1[0] - c0[0],
                           traces_in_window=c1[1] - c0[1],
                           compile_s_total=cell.counter.compile_s,
                           setup_s=setup_s)
    return dict(res, memory_peak_bytes=mem, checks=checks, trace=prof.result)
