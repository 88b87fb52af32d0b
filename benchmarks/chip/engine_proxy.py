"""The benchmark's instrument: a pass-through `CacheEngine`.

`scheduler.run_schedule` drives the proxy exactly as it drives the engine it
wraps; every hook is delegated unchanged.  Around each call the proxy
records, on the host clock and without ever waiting on a device value:

  * the entry time and kind of every engine call (admit, grow_write,
    decode, release), which partitions the window into host spans;
  * which request holds which slot, and so each slot's live length (the
    prompt at admission, plus one per decode call);
  * the token array each decode call receives: row ``s`` is the token the
    request in slot ``s`` was served last, so reading the arrays after the
    window recovers every served token but each request's last;
  * the pool's live blocks at each decode call;
  * the program's counters, where the engine has a ``counters(cache)``
    method: the program's cumulative counts on the device (a ``Dict[str,
    jax.Array | int]`` the engine keeps in its own state, valid after its
    next call: copies, where that call donates the state), taken at the
    call that opens the window and at the one that closes it, and read
    from the device only after the window (:meth:`window_counters`).

The window opens at the first decode call after ``warmup`` (every slot is
filled by then) and closes at the first engine call ``seconds`` later, where
the proxy raises :class:`WindowClosed` before delegating.  With
``mark_after``, the first engine call that many seconds into the window
calls ``on_mark`` once and records its time as ``t_mark``: a traced run
stops its profiler there and serves on to the window's end.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
from repro.launch.engines import base


class WindowClosed(Exception):
    """Raised by the proxy at the first engine call after the window."""


class QueueDrained(Exception):
    """The request queue ran out before the window closed."""


@dataclasses.dataclass
class Request:
    rid: int
    slot: int
    prompt_len: int
    t_admit: float
    gen: int
    decodes: List[int] = dataclasses.field(default_factory=list)  # call idx
    t_release: Optional[float] = None


class EngineProxy(base.CacheEngine):
    """Delegates every `CacheEngine` hook to ``engine`` and records the run.

    The proxy's :meth:`warmup` compiles a per-slot prefill for each prompt
    length of the queue (and the calibrating prefill for the first
    request's length), besides decode, grow and release.  ``on_open`` runs
    just before the window's clock starts (the traced run starts the
    profiler there), ``on_mark`` ``mark_after`` seconds into the window
    (the traced run stops it there); ``annotate`` wraps each engine call
    in a named host span (the traced run passes
    ``jax.profiler.TraceAnnotation``).
    """

    def __init__(self, engine, prompts, gens, *, seconds: float,
                 on_open: Optional[Callable[[], None]] = None,
                 mark_after: Optional[float] = None,
                 on_mark: Optional[Callable[[], None]] = None):
        self.engine = engine
        self.prompts = prompts
        self.gens = gens
        self.seconds = seconds
        self.on_open = on_open
        self.mark_after = mark_after
        self.on_mark = on_mark
        self.annotate = None
        self.t_open: Optional[float] = None
        self.t_mark: Optional[float] = None
        self.t_close: Optional[float] = None
        self.calls: List[tuple] = []          # (kind, t_entry)
        self.decode_calls: List[dict] = []
        self.token_arrays: List[jax.Array] = []
        self.requests: Dict[int, Request] = {}
        self.slot_req: List[Optional[Request]] = [None] * engine.slots
        self.live_lens = np.zeros((engine.slots,), np.int64)
        self.admitted = 0
        self._counters = getattr(engine, "counters", None)
        self.counts_open: Optional[Dict] = None
        self.counts_close: Optional[Dict] = None

    # ---- attributes the scheduler reads from its engine ----------------
    slots = property(lambda self: self.engine.slots)
    cfg = property(lambda self: self.engine.cfg)
    family = property(lambda self: self.engine.family)
    pool_tag = property(lambda self: self.engine.pool_tag)
    alloc = property(lambda self: self.engine.alloc)
    pager = property(lambda self: self.engine.pager)

    # ---- window --------------------------------------------------------
    @property
    def is_open(self) -> bool:
        return self.t_open is not None

    def _enter(self, kind: str, cache) -> float:
        now = time.perf_counter()
        if self.t_open is not None and now - self.t_open >= self.seconds:
            self.t_close = now
            if self._counters is not None:
                self.counts_close = self._counters(cache)
            raise WindowClosed()
        if (self.mark_after is not None and self.t_open is not None
                and self.t_mark is None
                and now - self.t_open >= self.mark_after):
            self.t_mark = now
            if self.on_mark is not None:
                self.on_mark()
            now = time.perf_counter()
        if kind == "decode" and self.t_open is None:
            if self._counters is not None:
                self.counts_open = self._counters(cache)
            if self.on_open is not None:
                self.on_open()
            now = self.t_open = time.perf_counter()
        self.calls.append((kind, now))
        return now

    def _span(self, kind):
        return (self.annotate(f"bench.{kind}") if self.annotate
                else contextlib.nullcontext())

    # ---- protocol ------------------------------------------------------
    def warmup(self):
        """Compile every shape the window uses.

        The engine's own warmup compiles the first prompt's length (its
        calibrating and plain prefill), grow, decode and release.  Every
        other prompt length is then admitted and released once on a
        scratch run, through the protocol's hooks alone: the first
        request goes first, so that it takes the calibrating prefill as it
        does in the window, and the rest take the plain one.
        """
        warm = self.engine.warmup()
        first = len(self.prompts[0])
        rids = {}
        for rid, p in enumerate(self.prompts):
            if len(p) != first:
                rids.setdefault(len(p), rid)
        if rids:
            e = self.engine
            cache = e.start_run()
            for rid in [0, *rids.values()]:
                logits, cache = e.admit(cache, 0, rid)
                cache = e.release(cache, 0)
            jax.block_until_ready((logits, cache))
        return warm

    def start_run(self):
        return self.engine.start_run()

    def admission_need(self, rid: int) -> int:
        return self.engine.admission_need(rid)

    def admit(self, cache, slot: int, rid: int):
        now = self._enter("admit", cache)
        with self._span("admit"):
            out = self.engine.admit(cache, slot, rid)
        req = Request(rid, slot, len(self.prompts[rid]), now, self.gens[rid])
        self.requests[rid] = req
        self.slot_req[slot] = req
        self.live_lens[slot] = req.prompt_len
        if self.is_open:
            self.admitted += 1
        if rid == len(self.prompts) - 1:
            raise QueueDrained(f"request queue of {len(self.prompts)} "
                               "drained before the window closed")
        return out

    def short(self, slot: int, upto: int) -> int:
        return self.engine.short(slot, upto)

    def grow_blocks(self, slot: int, n: int):
        return self.engine.grow_blocks(slot, n)

    def grow_write(self, cache, slot: int, idx: int, block: int):
        self._enter("grow", cache)
        with self._span("grow"):
            return self.engine.grow_write(cache, slot, idx, block)

    def decode(self, tokens, cache):
        now = self._enter("decode", cache)
        idx = len(self.decode_calls)
        occupied = [s for s, r in enumerate(self.slot_req) if r is not None]
        self.live_lens[occupied] += 1          # this call writes one more
        alloc = self.engine.alloc
        self.decode_calls.append({
            "t": now, "slots": len(occupied),
            "live_tokens": int(self.live_lens[occupied].sum()),
            "live_blocks": alloc.live_count if alloc is not None else 0,
            "pool_blocks": getattr(self.engine, "pool_size", 0),
        })
        self.token_arrays.append(tokens)
        for s in occupied:
            self.slot_req[s].decodes.append(idx)
        with self._span("decode"):
            return self.engine.decode(tokens, cache)

    def release(self, cache, slot: int):
        now = self._enter("release", cache)
        req = self.slot_req[slot]
        if req is not None:
            req.t_release = now
            self.slot_req[slot] = None
            self.live_lens[slot] = 0
        with self._span("release"):
            return self.engine.release(cache, slot)

    def finalize(self, health, inj) -> None:
        self.engine.finalize(health, inj)

    def leaked(self) -> int:
        return self.engine.leaked()

    def kv_bytes_per_step(self, gens) -> int:
        return self.engine.kv_bytes_per_step(gens)

    # ---- after the window ----------------------------------------------
    def served_tokens(self) -> Dict[int, np.ndarray]:
        """rid -> the tokens its decode calls received (all it was served
        but the last), read from the device once the window has closed."""
        if not self.token_arrays:
            return {}
        rows = np.stack(jax.device_get(self.token_arrays))
        return {rid: rows[req.decodes, req.slot]
                for rid, req in self.requests.items()}

    def window_counters(self) -> Dict[str, float]:
        """name -> what the program counted from the window's opening to
        its close, read from the device once the window has closed; empty
        for an engine that keeps no counters."""
        if self.counts_open is None or self.counts_close is None:
            return {}
        start, end = jax.device_get((self.counts_open, self.counts_close))
        return {name: float(end[name]) - float(start[name]) for name in end}
