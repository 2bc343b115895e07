"""Continuous-batching decode engine with a paged KV cache.

Batching whole requests admits them only at batch boundaries: one long
sequence stalls every short one, and the device idles between batches.
This module is the iteration-level scheduler used instead (the
vLLM/Orca recipe, per the Gemma-on-TPU serving comparison in
PAPERS.md): a fixed-shape compiled step program runs over a batch of
**slots**; sequences join at prefill and leave at EOS/max-tokens, at
*every* decode step, so the step program never recompiles as traffic
comes and goes.

A request joins through a **prefill program** (models/gpt.py
paged_prefill): the prompt's own tokens — whatever the
prefix table did not find in shared pages — padded to a multiple of
`prefill_bucket`, go through the layers in ONE pass: per layer one
chunk-wide QKV projection, one scatter of the chunk's K and V rows into
the sequence's pages, and the same masked attention the decode step
runs (a decode step is its one-row case).  The program is a function of
the padded length alone; where the chunk starts, which row yields the
logits and the page-table row are operands.  The engine thread launches
it inside `_admit` and the step right behind it, so every streaming slot
waits for it on the chip, between the step in flight and the next ("The
order of an iteration", below).

A prompt longer than `prefill_chunk` (0: off) is prefilled in chunks of
that size through one program, the tail padded to `prefill_bucket`: one
chunk an iteration, between two decode steps, while its slot rides the
step as empty; nothing is admitted behind it until it decodes (first
come, first served).  Prompts no longer than a chunk keep the schedule
above.

The model interface
-------------------
The engine serves whatever module has these members (`_check_interface`
holds it to the list when an engine is made;
tests/test_serve_model_interface.py asks every served module the same
questions) and whose config has `max_seq`, `vocab_size`, `dtype`, `pos`:

  `cache_kinds(cfg)` -> {kind: None | window | "state"}
  `init_paged_cache(cfg, {kind: pages}, page_size)` -> the cache
  `paged_decode_step(params, cache, tokens [B], {kind: tables [B, W]},
      pos [B], cfg)` -> (logits [B, V], cache[, stats])
  `paged_prefill(params, cache, toks [T], {kind: table row [W]}, start,
      last_idx, cfg)` -> (logits [V], cache[, stats])
  `serve_view(params, cfg)` -> the tree the two programs are handed (a
      view's view is that view)
  where a kind is "state": `state_leaves(cache)` -> its arena's leaves
  where every kind keeps every position (pages are shared only then):
      `copy_page(cache, dst, src)` -> the cache
  optional: `STEP_STATS`, the names of the f32 vector the two programs
      return third; `step_kv_read(cfg, pos, page_size, max_pages)` ->
      (key positions a step reads, the tables' span), on the host;
      `PREFILL_KNOWS_LAST` (true): `paged_prefill` takes one operand more
      behind `last_idx`, `is_last` (a bool scalar): whether the chunk is
      its prompt's last, the one whose logits the engine keeps — a model
      whose later layers write no cache (phi4flash's cross-decoder) runs
      them there alone; a module without the name gets the operands
      above and nothing else

A model's layers may keep several **kinds of KV state**: `cache_kinds`
names them with their window, and the engine keeps a pool of pages, an
allocator and a page table per kind.  A full kind's pages are taken at
admission, in sequence order.  What a page HOLDS is the model's: keys and
values a head wide (gpt, cohere2_moe), or one **latent row** a position
with no K side, no V side and no head axis (deepseek_v3: 576 values, from
which its programs form every head's keys and values, or which they score
as it lies); the engine counts pages and hands tables over, and never
looks inside the arena `init_paged_cache` gave it — a latent kind is a
full kind, shared and copied on write like any other.  A kind's layer may
hold MORE THAN ONE LEAF under the kind's one table (dots3: a full layer's
latent rows and its indexer's key rows, arenas of different widths that a
page number indexes alike), and two paged kinds may both be latent with
rows of different widths (dots3: 576 and 1,088 values a position): a page
of a kind is whatever the cache grows by when the kind's pool has one page
more (`_page_bytes`; an iteration's record carries `bytes_<kind>` beside
`pages_<kind>` where a model has several kinds).  A windowed kind's table is a ring as wide
as the window plus the longest prefill program: pages are taken as the
sequence grows, out of a reservation made at admission, and returned once
every position in them is a window or more behind the next query.  A kind
may also be a **state**: what its layers keep of a sequence does not grow
with it (a recurrent or linear-attention layer's state matrix), so the
pool holds one entry of fixed size a sequence — taken at admission,
emptied by the sequence's first prefill chunk, carried from chunk to chunk
and step to step, returned at eviction — and the kind's table is that one
entry's index.  A model all of whose kinds are states has no page to run
out of: it is admitted while a slot and an entry are free, and `max_total`
bounds its positions only.  A model may MIX a state kind with paged kinds
(ling3: delta-rule layers beside latent attention): an admission then takes
a slot, an entry AND its pages, waits while any of the three is missing and
returns all of them at eviction; phi4flash: a full kind, a windowed kind and
a state — pages, a window reservation and an entry together); which leaves of the cache are the state
arena the model says (`state_leaves(cache)`), so the entries' bytes are
counted apart from the pages'.

Memory is a **paged arena** (models/gpt.py init_paged_cache): fixed-size
pages in one preallocated device array, per-slot page tables gathered
inside the decode step.  The arena and the logits carried between steps
are the engine's device state, and every program that returns a new
version of it **consumes** the one it was given (`_fn` donates them): a
step writes its rows, a prefill its chunk's, `serve.copy_page` one page
and `serve.setrow` one row into the buffer that came in, and the array
the engine held before the call is gone after it.  Should a program
raise, the state may be gone with it: the loop drops it and the next
admission makes it anew (every request in flight has failed by then).
Pages are refcounted through a free list;
full prompt pages register in a prefix table so live sequences with a
common prompt prefix share pages (not for a model with a windowed kind:
a shared prefix is not prefilled, and its window pages may be gone; nor
for one with a state kind, whose state after a prefix is in no page),
with copy-on-write when a new
sequence must write into a shared page (the exact-duplicate-prompt
case: everything is shared but the last prompt position must be
recomputed to produce logits).  Page 0 is the reserved null page —
inactive slots write there and their sampled tokens are discarded
host-side, which is what lets the step program keep one static shape.

Everything device-facing runs on one daemon thread (the engine loop);
`submit` is thread-safe and hands back a `_Sequence` whose results are
consumed either as a blocking token iterator (streaming) or a
concurrent Future (request/response).  The loop hands every program to
the chip through one helper and waits through one other (`_launch`,
`_wait`), each boundary one clock read — call entered, call returned,
result ready — that feeds the iteration's ring record, the running
totals, the `serve.engine.*` profiler annotations and the phase
histogram alike.

The order of an iteration
-------------------------
One step stays in flight.  Within an iteration the thread enqueues, then
waits — and what it waits for first is the step launched an iteration AGO:

    admit    launch this iteration's prefill program(s) and the row
    step     launch this iteration's step, behind them and behind the step
             before it, whose tokens the host has not seen
    emit     fetch THAT step's tokens, hand them out, evict what ended
    stamp    block on what each prefill returned, for the clock read that
             says when it was done
    account  the ring record

so the chip has its next program queued whenever one ends, and a launch's
way to the chip, the tokens' way back, emit, account and the consumers'
turn at the interpreter all pass while a step runs.  Nothing has to wait
because the step program samples its own input: it draws its tokens from
the logits the step before it left ON THE DEVICE, and nothing the host
learns from a token is an operand of the next step — positions advance by
one, a window's next page is the host's own bookkeeping, a sampling
request's keys are indexed by a count the host knows, and a sequence that
ends by `max_new_tokens` ends at a count the host knows at the launch.  So
all of that is counted by steps LAUNCHED for a sequence (`_step`), and a
sequence whose last token by count is in the step just launched gives its
slot, pages and entry back right there, for the very next admission:
whatever is launched later runs later on the chip.  Only `eos_id` is
learned from a token.  It is applied one step late: the step launched
since has computed one more token for the sequence, inside the pages its
admission reserved, and that token is dropped (`_emit`).

The first step after an idle stretch is launched with nothing to fetch and
fetched by the next iteration, which follows at once; an iteration with
nothing to launch (no slot decodes) fetches the step in flight, so an idle
engine holds no unfetched step, and `drain`, `stop`, the health check and
whoever drives the programs by hand on an idle engine's `_cache` /
`_logits` see what they would have seen with no step in flight.  A step's
host operands are copies: the engine's own change before it is waited
for.  The depth is one and is no setting.
"""

from __future__ import annotations

import gc
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .._private import common
from ..util import tracing

__all__ = ["AdmissionRejected", "ContinuousEngine", "PageAllocator"]


class AdmissionRejected(Exception):
    """Raised by submit() when the waiting queue is at capacity — the
    proxy maps this to HTTP 503 + Retry-After instead of letting the
    queue collapse under load."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


# the stood-still ticker beside the engine thread (`_tick`): it sleeps this
# long, and a wake later than this is a stall.  Constants, not fields: the
# counter means the same in every engine.  A wake costs the thread that
# holds the interpreter: at 50 ms six untraced pairs of
# `serve-large-chat-loaded` (3.5 ms steps) read TTFT -0.55% and ITL -0.27%
# with mixed signs (PR 59; at 20 ms an earlier builder read TTFT +1.6%),
# and a stall of 70 ms or more is still always seen.
_TICK_S = 0.05
_TICK_LATE_S = 0.02


# ---------------------------------------------------------------------------
# metrics (lazy, module-cached: strong refs keep them alive across the
# weakref registry's flush epochs — same pattern as telemetry/recorder)

_metric_lock = threading.Lock()
_metric_cache: Dict[str, Any] = {}

_PHASE_BOUNDARIES = [0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1, 2.5]
_TTFT_BOUNDARIES = [0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5,
                    5, 10, 30]


def _metric(key: str, factory):
    with _metric_lock:
        m = _metric_cache.get(key)
        if m is None:
            try:
                m = _metric_cache[key] = factory()
            except Exception:
                return None
        return m


def _m_phase():
    from ..util import metrics as mm
    return _metric("phase", lambda: mm.Histogram(
        "ray_tpu_serve_step_phase_seconds",
        description="Engine loop phase durations "
                    "(swap/prefill/decode/dispatch)",
        boundaries=_PHASE_BOUNDARIES, tag_keys=("phase",)))


def _m_ttft():
    from ..util import metrics as mm
    return _metric("ttft", lambda: mm.Histogram(
        "ray_tpu_serve_ttft_seconds",
        description="Time from submit to first streamed token",
        boundaries=_TTFT_BOUNDARIES))


def _m_tokens():
    from ..util import metrics as mm
    return _metric("tokens", lambda: mm.Counter(
        "ray_tpu_serve_tokens_total",
        description="Generated tokens"))


def _m_requests():
    from ..util import metrics as mm
    return _metric("requests", lambda: mm.Counter(
        "ray_tpu_serve_requests_total",
        description="Engine request outcomes", tag_keys=("outcome",)))


def _m_gauge(which: str):
    from ..util import metrics as mm
    names = {
        "active": ("ray_tpu_serve_active_slots", "Occupied decode slots"),
        "queue": ("ray_tpu_serve_queue_depth", "Waiting (unadmitted) requests"),
        "free_pages": ("ray_tpu_serve_free_pages", "Free KV-cache pages"),
    }
    name, desc = names[which]
    return _metric(which, lambda: mm.Gauge(name, description=desc))


# ---------------------------------------------------------------------------
# paged allocator (host-side bookkeeping; the arena itself is on device)


class PageAllocator:
    """Free-list page allocator with refcounts and a prompt-prefix
    registry.

    The registry maps *full, page-aligned token prefixes* — the tuple of
    a prompt's first (i+1)*page_size token ids — to the page holding
    those positions' K/V.  Sharing is live-sequence only: when a page's
    refcount drops to zero it returns to the free list and its registry
    keys are purged, so a registered page always holds exactly the K/V
    its key promises.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the null page)")
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: deque = deque(range(1, num_pages))
        self._refs: Dict[int, int] = {}
        self._prefix: Dict[Tuple[int, ...], int] = {}
        self._page_keys: Dict[int, List[Tuple[int, ...]]] = {}
        # pages promised to admitted sequences that take them one at a
        # time (a windowed pool): admission counts them as gone
        self.reserved = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available(self) -> int:
        return len(self._free) - self.reserved

    @property
    def used_pages(self) -> int:
        return len(self._refs)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("page arena exhausted")
        p = self._free.popleft()
        self._refs[p] = 1
        return p

    def ref(self, page: int) -> None:
        self._refs[page] += 1

    def unref(self, page: int) -> None:
        if page == 0:
            return
        n = self._refs[page] - 1
        if n > 0:
            self._refs[page] = n
            return
        del self._refs[page]
        for key in self._page_keys.pop(page, ()):
            if self._prefix.get(key) == page:
                del self._prefix[key]
        self._free.append(page)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def register_prefix(self, tokens: Tuple[int, ...], page: int) -> None:
        """Publish `page` as holding the K/V of this full-page prefix
        (first writer wins; a concurrent identical prefix is already
        byte-identical, so keeping the incumbent is free)."""
        if tokens in self._prefix:
            return
        self._prefix[tokens] = page
        self._page_keys.setdefault(page, []).append(tokens)

    def lookup_prefix(self, tokens: Tuple[int, ...]) -> Optional[int]:
        return self._prefix.get(tokens)

    def plan(self, tokens: List[int], n_pages_needed: int,
             share: bool = True) -> Optional[Dict[str, Any]]:
        """Plan the page set for a prompt: walk the registry for fully
        shared leading pages (clamped so the LAST prompt position is
        always recomputed — it must produce logits), then check the free
        list covers the rest.  Returns None when the arena can't fit
        the request right now (caller keeps it queued); on success
        returns {pages, shared_len, copies} with all refcounts taken —
        `copies` lists (src, dst) device page copies the caller must
        apply before prefill (copy-on-write out of a shared page).
        `share=False` skips the registry (every page fresh).
        """
        ps = self.page_size
        plen = len(tokens)
        shared: List[int] = []
        i = 0
        while share and (i + 1) * ps <= plen:
            page = self._prefix.get(tuple(tokens[:(i + 1) * ps]))
            if page is None:
                break
            shared.append(page)
            i += 1
        full_shared = len(shared) * ps
        shared_len = min(full_shared, plen - 1)
        cow = shared_len < full_shared   # exact full-page match: the last
        if cow:                          # shared page must be re-written
            cow_src = shared.pop()
        n_fresh = n_pages_needed - len(shared)
        if n_fresh > self.available:
            return None
        for p in shared:
            self.ref(p)
        pages = list(shared)
        copies: List[Tuple[int, int]] = []
        if cow:
            dst = self.alloc()
            copies.append((cow_src, dst))
            pages.append(dst)
        while len(pages) < n_pages_needed:
            pages.append(self.alloc())
        return {"pages": pages, "shared_len": shared_len,
                "copies": copies, "n_shared": len(shared)}

    def release(self, pages: List[int]) -> None:
        for p in pages:
            self.unref(p)

    def occupancy(self) -> Dict[str, int]:
        """Arena occupancy for the device-memory census (page 0, the
        reserved null page, is in neither free nor used):
        ``live_shared`` counts pages currently referenced by more than
        one sequence (live prefix sharing, distinct from the engine's
        cumulative ``shared_pages`` total)."""
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "free": len(self._free),
            "used": len(self._refs),
            "live_shared": sum(1 for n in self._refs.values() if n > 1),
            "prefix_keys": len(self._prefix),
        }


# ---------------------------------------------------------------------------


class _Sequence:
    """Host-side state of one in-flight request."""

    __slots__ = ("rid", "tokens", "max_new", "temperature", "top_k",
                 "seed", "eos_id", "out_q", "result", "slot", "pages",
                 "pos", "generated", "keys", "t_submit", "t_admit",
                 "t_prefill", "t_ready", "t_first", "t_last", "shared",
                 "scanned", "trace_ctx", "peak", "stream", "request_id",
                 "key_offset", "tabs", "win", "reserved", "states",
                 "next_start",
                 "chunks", "prefill_s", "prefilling", "launched", "done")

    def __init__(self, rid, tokens, max_new, temperature, top_k, seed,
                 eos_id, stream, request_id=None, key_offset=0):
        import concurrent.futures

        self.rid = rid
        self.request_id = request_id
        self.key_offset = int(key_offset)
        self.tokens = list(tokens)
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.seed = int(seed)
        self.eos_id = eos_id
        self.stream = bool(stream)
        self.out_q: "queue.Queue" = queue.Queue()
        self.result = concurrent.futures.Future()
        self.slot = -1
        self.pages: List[int] = []
        self.pos = 0
        self.generated: List[int] = []
        self.keys = None            # np [max_new, 2] uint32 if it samples
        # clock reads at the request's phase boundaries (perf_counter):
        # submit -> popped from _waiting -> prefill dispatched -> prefill
        # ready -> first token out -> last token out.  They feed the
        # ring's `requests` entry and the retro engine.* spans.
        self.t_submit = time.perf_counter()
        self.t_admit = self.t_prefill = self.t_ready = 0.0
        self.t_first: Optional[float] = None
        self.t_last = 0.0
        self.shared = 0             # prompt tokens found in shared pages
        self.scanned = 0            # tokens the prefill scanned (bucket)
        self.trace_ctx = None       # submitter's sampled span context
        self.peak = 0               # max co-resident active slots seen
        # paged state by kind of pool (cache_kinds): this sequence's page
        # table rows; a windowed pool's live pages {logical page: page}
        # and how many more it may still take (its reservation)
        self.tabs: Dict[str, Any] = {}
        self.win: Dict[str, Dict[int, int]] = {}
        self.reserved: Dict[str, int] = {}
        self.states: Dict[str, int] = {}    # a state kind's one entry
        self.next_start = 0         # first prompt position not prefilled
        self.chunks = 0             # prefill programs run for it
        self.prefill_s = 0.0        # their seconds, dispatch -> ready
        self.prefilling = False     # holds a slot, its rows not the step's
        self.launched = 0           # steps launched for it: tokens computed
        self.done = False           # _finish has run: its caller has heard


class _Flight:
    """One launched step whose tokens the host has not fetched: the
    (slot, sequence) pairs it decodes — a sequence may have left its slot
    since (its last token by count is this step's) or ended (on an EOS an
    earlier step drew: this step's token for it is dropped) — its tokens
    and counters on the device, and what the host counted at its launch
    for the record of the iteration that emits it."""

    __slots__ = ("active", "out", "counted")

    def __init__(self, active, out, counted):
        self.active: List[Tuple[int, _Sequence]] = active
        self.out: Any = out                 # (tokens, counters); None once fetched
        self.counted: Dict[str, float] = counted


def _check_interface(mod, cfg) -> None:
    """Hold a model module to the module docstring's "model interface":
    a TypeError that names the module and the first member it lacks."""
    kinds = getattr(mod, "cache_kinds", lambda cfg: {})(cfg).values()
    for name in ("cache_kinds", "init_paged_cache", "paged_decode_step",
                 "paged_prefill", "serve_view",
                 *(["state_leaves"] if "state" in kinds else []),
                 *(["copy_page"] if all(w is None for w in kinds) else [])):
        if not callable(getattr(mod, name, None)):
            raise TypeError(
                f"{getattr(mod, '__name__', mod)} does not implement the "
                f"serving engine's model interface: it has no `{name}` "
                "(ray_tpu/serve/_engine.py, \"The model interface\")")


class ContinuousEngine:
    """Per-replica continuous-batching scheduler (one per model)."""

    _END = object()

    def __init__(self, gpt_mod, cfg, params, *, max_slots: int = 8, page_size: int = 16,
                 num_pages=0, max_total: int = 0,
                 queue_cap: int = 32, shed_queue_depth: int = 16,
                 retry_after_s: float = 1.0, prefill_bucket: int = 32,
                 prefill_chunk: int = 0,
                 ring_size: int = 256, stall_s: float = 10.0):
        import jax
        import numpy as np

        _check_interface(gpt_mod, cfg)
        self._jax, self._np, self._gpt = jax, np, gpt_mod
        # the programs read the model's serve view of the caller's tree,
        # made here once (gpt_mod.serve_view: the leaves they would cast
        # at every use, cast; every other leaf the caller's own array)
        self._cfg, self._params = cfg, gpt_mod.serve_view(params, cfg)
        leaves = jax.tree_util.tree_leaves(self._params)
        self._param_stats = {
            "param_count": sum(int(w.size) for w in leaves),
            "param_bytes": sum(int(w.size) * w.dtype.itemsize for w in leaves)}
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.max_total = int(max_total) or cfg.max_seq
        self.max_pages_per_seq = -(-self.max_total // self.page_size)
        self.max_total = self.max_pages_per_seq * self.page_size
        self.queue_cap = int(queue_cap)
        self.shed_queue_depth = int(shed_queue_depth)
        self.retry_after_s = float(retry_after_s)
        self.prefill_bucket = int(prefill_bucket)
        # a prompt longer than this is prefilled in chunks of this size
        # (the tail padded to prefill_bucket), one chunk an iteration;
        # 0: every prompt is one program of its padded length
        self.prefill_chunk = int(prefill_chunk)

        # one pool per kind of state the model's layers keep
        # (gpt_mod.cache_kinds: name -> window, None = every position,
        # "state" = one entry of fixed size a sequence).
        # A full kind's table is the sequence's pages in order, taken at
        # admission; a windowed kind's is a ring as wide as the window
        # plus the longest prefill program, filled as the sequence grows
        # and emptied as the window passes; a state kind's is its entry.
        self._kinds: Dict[str, Any] = dict(gpt_mod.cache_kinds(cfg))
        self._state_kinds = [k for k, w in self._kinds.items()
                             if w == "state"]
        longest = self.prefill_chunk or self.max_total
        self._widths = {
            k: 1 if w == "state" else self.max_pages_per_seq if w is None
            else min(self.max_pages_per_seq,
                     -(-(w + longest) // self.page_size) + 1)
            for k, w in self._kinds.items()}
        self._pool_pages = {
            k: int(num_pages[k] if isinstance(num_pages, dict)
                   else num_pages) or 1 + self.max_slots * self._widths[k]
            for k in self._kinds}
        # the pool the scheduler's public numbers describe: the first
        # kind that keeps every position (the only one, for most models)
        self._main = next((k for k, w in self._kinds.items() if w is None),
                          next(iter(self._kinds)))
        self.num_pages = self._pool_pages[self._main]
        # live prefix sharing skips a shared prefix's prefill, so it needs
        # every layer's K/V of that prefix to be kept: with a windowed
        # kind nothing is shared (the window's pages may be gone), nor
        # with a state kind (the state after a prefix is in no page)
        self._windowed = [k for k, w in self._kinds.items()
                          if w is not None and w != "state"]
        self._share = not self._windowed and not self._state_kinds

        self._lock = threading.Lock()
        self._waiting: "deque[_Sequence]" = deque()   # guarded-by: _lock
        self._slots: List[Optional[_Sequence]] = [None] * self.max_slots
        self._allocs = {k: PageAllocator(n, self.page_size)
                        for k, n in self._pool_pages.items()}
        self._alloc = self._allocs[self._main]
        self._prefilling: Optional[_Sequence] = None  # mid-prompt, FCFS
        # a model's serve programs may return counters of their own (its
        # module's STEP_STATS names them): a step's go into the
        # iteration's record under these names, a prefill chunk's under
        # `chunk_<name>`
        self._stat_names = tuple(getattr(gpt_mod, "STEP_STATS", ()))
        self._stat_keys = self._stat_names + tuple(
            "chunk_" + n for n in self._stat_names)
        # a model whose step reads its page tables only as far as the
        # contexts are live says how far, from the positions the engine
        # thread already holds (`step_kv_read` of its module: no fetch):
        # `kv_read` / `kv_span` of an iteration's record
        self._kv_read = getattr(gpt_mod, "step_kv_read", None)
        # a model whose prefill runs part of itself on a prompt's last
        # chunk alone is told which chunk that is (`PREFILL_KNOWS_LAST`)
        self._tell_last = bool(getattr(gpt_mod, "PREFILL_KNOWS_LAST", False))
        if self._kv_read is not None:
            self._stat_keys += ("kv_read", "kv_span")
        # steps launched with a temperature in some slot, the ones whose
        # sampler draws (the others take the argmax and nothing else):
        # read off the host's `_temps`, no operand and no fetch
        self._stat_keys += ("sampled_steps",)
        self._fns: Dict[Any, Any] = {}   # bounded by construction: one
        # step program + one prefill per padded-length bucket + setrow +
        # copy_page
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._stopped = False
        self._draining = False        # guarded-by: _lock
        self._rid = 0
        self.stall_s = float(stall_s)
        # (steps, the clock, what the ticker had counted) at the last probe
        # that saw a step taken
        self._health_snap: Optional[Tuple[int, float, float]] = None

        # device state (built lazily on the engine thread)
        self._cache = None
        self._logits = None          # [B, V] carried across steps
        self._state_bytes = 0        # the arena of a model's state kinds
        self._entry_bytes = 0        # of it, one entry's
        self._page_bytes: Dict[str, int] = {}   # one page's, a paged kind

        # host mirrors of the per-slot step operands
        B = self.max_slots
        self._pos = np.zeros(B, np.int32)
        self._ptabs = {k: np.zeros((B, w), np.int32)
                       for k, w in self._widths.items()}
        self._toks_keys = np.zeros((B, 2), np.uint32)
        self._temps = np.zeros(B, np.float32)
        self._topks = np.zeros(B, np.int32)

        # telemetry: per-iteration phase ring + running totals
        self._ring: "deque[Dict[str, float]]" = deque(maxlen=ring_size)  # guarded-by: _lock
        self._ttfts: "deque[float]" = deque(maxlen=256)        # guarded-by: _lock
        self._t_window: "deque[Tuple[float, int]]" = deque(maxlen=512)  # guarded-by: _lock
        self._totals = {"requests": 0, "rejected": 0, "tokens": 0,
                        # steps launched; of them, those launched with
                        # the step before them still unfetched
                        "steps": 0, "steps_ahead": 0,
                        "prefills": 0, "cow_copies": 0,
                        "shared_pages": 0, "chunks": 0,
                        "window_pages_returned": 0,
                        # cumulative sums of the ring's records, so two
                        # engine_stats() snapshots give shares over any
                        # interval whatever ring_size forgot
                        "queue_wait_s": 0.0, "prefill_s": 0.0,
                        "decode_s": 0.0, "host_s": 0.0,
                        "device_wait_s": 0.0, "blocked_slot_s": 0.0,
                        "dispatch_s": 0.0, "ready_wait_s": 0.0,
                        "launches": 0,
                        "prefill_tokens": 0, "prefill_scanned_tokens": 0,
                        # garbage collections of this process while the
                        # engine thread lived (_on_gc alone writes them)
                        "gc_s": 0.0, "gc_collections": 0, "gc_max_s": 0.0,
                        # wakes of the ticker beside the engine thread that
                        # came late, and by how much: nothing of this
                        # process ran meanwhile (`_tick` alone writes them)
                        "stalls": 0, "stall_s": 0.0, "stall_max_s": 0.0,
                        # the model's own counters (its STEP_STATS names)
                        **dict.fromkeys(self._stat_keys, 0.0)}
        self._gc_t0: Optional[float] = None   # a collection under way
        # `stall_s` at the last ring record (or where the loop last idled)
        self._stall_seen = 0.0
        self._ticker: Optional[threading.Thread] = None
        # the ticker's clock and when its sleep is due (None: no ticker)
        self._tick_due: Optional[Tuple[Any, float]] = None
        # when the engine thread started and each program was first
        # launched, on the wall clock (`engine_stats()["ready"]`: a
        # replica's warm-up, between its boot and the window).  A launch's
        # stamp is its `_t_call` moved onto the wall clock: no clock read
        self._thread_start_wall: Optional[float] = None
        self._first_launch_wall: Dict[str, float] = {}
        self._wall_of_perf = time.time() - time.perf_counter()
        self._iter = 0               # iterations since the engine started
        self._t_call = 0.0           # the last launch was entered
        self._t_free = 0.0           # the last launch returned, or wait ended
        # per-iteration scratch of the engine thread (_iteration resets)
        self._last_prefill_s = 0.0
        # prefill programs launched and not yet stamped ready: (sequence,
        # call entered, its logits row, its counters, the prompt's last?)
        self._pending: List[Tuple[_Sequence, float, Any, tuple, bool]] = []
        # steps launched whose tokens are not on the host yet, oldest
        # first: one between two iterations, two between an iteration's
        # launch and its fetch, none while the engine idles
        self._in_flight: "deque[_Flight]" = deque()
        self._launched: Dict[str, float] = {}   # _launch / _wait's sums
        self._blocked = 0            # streaming slots an admission held up
        self._first: List[_Sequence] = []   # first token this iteration
        self._chunks = 0             # prefill programs run
        self._chunk_tokens = 0       # prompt tokens they computed
        self._returned = 0           # window pages returned
        self._stats: Dict[str, float] = {}  # the programs' own counters

        # device-memory census: report this engine's page-arena
        # occupancy under a per-instance tag (unregistered in stop())
        self._census_tag = f"serve.engine.{id(self):x}"
        try:
            from ..telemetry import device as _devtel

            _devtel.get_census().register_owner(self._census_tag,
                                                self._census_report)
        except Exception:
            pass

    # -- public api ---------------------------------------------------------

    def submit(self, tokens: List[int], max_new_tokens: int = 16,
               temperature: float = 0.0, seed: int = 0,
               top_k: Optional[int] = None, eos_id: Optional[int] = None,
               stream: bool = False, request_id: Optional[str] = None,
               key_offset: int = 0) -> _Sequence:
        """Thread-safe request entry: validates capacity, sheds when the
        waiting queue is full, wakes the engine loop."""
        if not tokens:
            raise ValueError("empty prompt")
        plen, max_new = len(tokens), int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if plen + max_new > self.max_total:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({max_new}) exceeds "
                f"engine capacity ({self.max_total})")
        need = -(-min(plen + max_new, self.max_total) // self.page_size)
        for k, n in self._pool_pages.items():
            if min(need, self._widths[k]) > n - 1:
                # can never fit even with the arena idle — reject now
                # rather than park it at the head of the queue forever
                raise ValueError(
                    f"request needs {min(need, self._widths[k])} pages but "
                    f"the arena only has {n - 1}")
        if (self._cfg.pos == "learned"
                and plen + max_new > self._cfg.max_seq):
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({max_new}) exceeds "
                f"the model's learned-position capacity "
                f"({self._cfg.max_seq})")
        with self._lock:
            if self._stopped:
                raise RuntimeError("engine stopped")
            if self._draining:
                self._totals["rejected"] += 1
                m = _m_requests()
                if m:
                    m.inc(tags={"outcome": "rejected"})
                raise AdmissionRejected(
                    "engine draining (replica shutting down)",
                    retry_after_s=self.retry_after_s)
            if len(self._waiting) >= self.queue_cap:
                self._totals["rejected"] += 1
                m = _m_requests()
                if m:
                    m.inc(tags={"outcome": "rejected"})
                raise AdmissionRejected(
                    f"waiting queue at capacity ({self.queue_cap})",
                    retry_after_s=self.retry_after_s)
            self._rid += 1
            seq = _Sequence(self._rid, tokens, max_new, temperature,
                            top_k, seed, eos_id, stream,
                            request_id=request_id, key_offset=key_offset)
            # the submitter's span context (None unless tracing is on
            # and its trace sampled): _finish parents the request's
            # engine.* spans to it, the worker.queue_wait pattern
            seq.trace_ctx = tracing.sampled_context()
            self._waiting.append(seq)
            self._totals["requests"] += 1
            self._ensure_thread()
        self._wake.set()
        return seq

    def stream(self, seq: _Sequence):
        """Blocking token iterator over one sequence's output queue
        (call from a worker thread, not the event loop)."""
        while True:
            item = seq.out_q.get()
            if item is self._END:
                # surface a terminal error (if any) to the consumer
                exc = seq.result.exception()
                if exc is not None:
                    raise exc
                return
            yield item

    def collect(self, seq: _Sequence, timeout: Optional[float] = None
                ) -> Dict[str, Any]:
        return seq.result.result(timeout=timeout)

    def engine_stats(self) -> Dict[str, Any]:
        """Scheduler snapshot for admission control and autoscaling:
        what the router (`accepting`, `retry_after_s`), the controller
        and the autoscaler (`queue_depth`, `active`, `free_pages`,
        `ttft_p99_s`, `tokens_per_s`) read, the size of the tree the
        programs read (`param_count`, `param_bytes`: 2 bytes a parameter
        where bf16 is served, whatever the caller's tree is kept in),
        plus the running totals — counters and the ring's cumulative
        sums, so two snapshots give rates and phase shares over any
        interval — and `ready`: when the engine thread started and each
        program was first launched, on the wall clock."""
        now = time.perf_counter()
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
            qd = len(self._waiting)
            ttfts = sorted(self._ttfts)
            window = [(t, n) for t, n in self._t_window if now - t <= 10.0]
            draining = self._draining
            totals = dict(self._totals)
            ready = {"thread_start_wall": self._thread_start_wall,
                     "first_launch_wall": dict(self._first_launch_wall)}
        toks = sum(n for _, n in window)
        span = (now - window[0][0]) if window else 0.0
        return {
            "active": active,
            "queue_depth": qd,
            "free_pages": self._alloc.free_pages,
            "accepting": (not draining) and qd < self.shed_queue_depth,
            "retry_after_s": self.retry_after_s,
            "ttft_p99_s": ttfts[min(len(ttfts) - 1,
                                    int(0.99 * len(ttfts)))]
            if ttfts else 0.0,
            "tokens_per_s": (toks / span) if span > 0 else 0.0,
            **self._param_stats,
            **self._state_stats(),
            **totals,
            "ready": ready,
        }

    def _state_stats(self) -> Dict[str, int]:
        """A model with a state kind: its entries in use and free, and
        the bytes of the arena that holds them (once it is made)."""
        if not self._state_kinds:
            return {}
        return {"states_live": self._states_live(),
                "states_free": sum(self._allocs[k].free_pages
                                   for k in self._state_kinds),
                "state_arena_bytes": self._state_bytes,
                **self._live_bytes()}

    def _live_bytes(self) -> Dict[str, int]:
        """Of a model with a state kind: the bytes its live entries hold
        (`state_bytes`) and those with the bytes of its used pages
        (`cache_bytes`; the null entry and the null pages are no one's).
        The engine does not look inside a page: a kind's page costs what
        the cache grows by when its pool has one page more."""
        held = self._states_live() * self._entry_bytes
        used = sum(self._allocs[k].used_pages * n
                   for k, n in self._page_bytes.items())
        return {"state_bytes": held, "cache_bytes": held + used}

    def _states_live(self) -> int:
        return sum(self._allocs[k].used_pages for k in self._state_kinds)

    def _census_report(self) -> Dict[str, Any]:
        """Owner callback for telemetry/device.DeviceMemoryCensus: the
        ``pages`` sub-dict feeds ``ray_tpu_kv_pages{state=…}`` — free /
        used are live arena occupancy, shared / cow are the engine's
        cumulative prefix-sharing totals (``engine_stats()``'s
        ``shared_pages`` / ``cow_copies``)."""
        with self._lock:
            totals = dict(self._totals)
        occ = self._alloc.occupancy()
        return {"cache": "paged",
                "num_pages": self.num_pages,
                "max_slots": self.max_slots,
                "pages": {
                    "free": occ["free"],
                    "used": occ["used"],
                    "shared": totals["shared_pages"],
                    "cow": totals["cow_copies"],
                    "live_shared": occ["live_shared"],
                },
                "prefix_keys": occ["prefix_keys"],
                "pools": {k: a.occupancy()
                          for k, a in self._allocs.items()},
                **self._state_stats()}

    def phase_ring(self) -> List[Dict[str, float]]:
        with self._lock:
            return list(self._ring)

    def drain(self, timeout_s: float = 5.0) -> bool:
        """Graceful shutdown, phase 1: stop admitting (submit sheds,
        engine_stats advertises accepting=False so the router steers
        around this replica) and give in-flight sequences a
        deadline-bounded chance to finish.  Returns True when everything
        drained; leftovers are failed by the eventual stop()/kill and
        the router replays them elsewhere."""
        with self._lock:
            self._draining = True
        self._wake.set()
        deadline = time.monotonic() + max(0.0, timeout_s)
        while True:
            if not self._busy():
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.02)

    def check_health(self) -> bool:
        """Engine liveness probe (controller health loop): raises when
        the scheduler thread died with work pending, the step counter
        stalls while slots are active (hung jit step), or the page
        free-list went inconsistent — any of which means every future
        request would hang, so the replica must be restarted."""
        with self._lock:
            if self._stopped:
                raise RuntimeError("engine stopped")
            # (a step nobody has fetched is work too: its sequences may
            # all have left their slots by count)
            active = (sum(1 for s in self._slots if s is not None)
                      or len(self._in_flight))
            queued = len(self._waiting)
            steps = self._totals["steps"]
        thread = self._thread
        if thread is not None and not thread.is_alive() \
                and (active or queued):
            raise RuntimeError(
                f"engine scheduler thread died with work pending "
                f"({active} active, {queued} queued)")
        now = time.monotonic()
        snap = self._health_snap
        # a program's first compile is not a stall: the step counter
        # stands still for as long as the compiler takes, and restarting
        # the replica would only compile again from nothing.  The clock
        # counts from where the last such compile ENDED: a probe may not
        # have run while it lasted (the compiler's host work kept this
        # process from answering one for 9 s of a cold `serve.prefill:512`
        # of 32 layers, and the next probe read 11.3 s without a step: PR
        # 51), so it cannot be the probes that remember it
        # Nor is the engine's bring-up: its first admission takes a slot,
        # THEN builds the device state (the arena's fills are programs of
        # jax's own that compile too) and the first operands, and on an
        # empty cache a replica's other threads trace beside it (a
        # benchmark's reference: 11.4 s with a slot taken and nothing
        # launched in `serve-ling3flash-reasoning`, PR 59) — until the
        # first launch there is no step to miss
        fns = list(self._fns.values())
        compiling = not self._first_launch_wall or any(
            getattr(fn, "first_compile_in_flight", False) for fn in fns)
        stood = self._stood_still_s()
        if active == 0 or snap is None or snap[0] != steps or compiling:
            self._health_snap = snap = (steps, now, stood)
        since = max([snap[1]] + [getattr(fn, "first_compile_ended", 0.0)
                                 for fn in fns])
        # ... nor is a stretch in which nothing of this process ran (the
        # machine frozen 4-9 s while another process reaches the chip, the
        # process stopped or starved): the engine could not have stepped,
        # the ticker beside it saw as much, and that time is taken off —
        # as the control plane credits its own late wakes to every node
        # (`control._credit_stall`).  An engine that is wedged while the
        # process runs gives the ticker nothing to count
        silent_s = now - since - (stood - snap[2])
        if silent_s > self.stall_s:
            raise RuntimeError(
                f"engine stalled: {active} active slots but no decode "
                f"step for {silent_s:.1f}s (> {self.stall_s:g}s)")
        for a in self._allocs.values():
            in_use = len(a._refs)
            if len(a._free) + in_use != a.num_pages - 1:
                raise RuntimeError(
                    f"page free-list inconsistent: {len(a._free)} free "
                    f"+ {in_use} referenced != {a.num_pages - 1}")
            if any(n <= 0 for n in a._refs.values()):
                raise RuntimeError("page refcount <= 0 in allocator")
        return True

    def _stood_still_s(self) -> float:
        """What the ticker has counted, and the wake it is still owed: a
        probe that runs first after a freeze must not read it as the
        engine's before the ticker has said so."""
        owed = 0.0
        due = self._tick_due
        if due is not None:
            clock, t_due = due
            owed = clock() - t_due
        return self._totals["stall_s"] + (owed if owed > _TICK_LATE_S
                                          else 0.0)

    def stop(self):
        try:
            from ..telemetry import device as _devtel

            _devtel.get_census().unregister_owner(self._census_tag)
        except Exception:
            pass
        with self._lock:
            self._stopped = True
            waiting = list(self._waiting)
            self._waiting.clear()
        self._wake.set()
        # let the loop finish its current iteration before touching the
        # slots — clearing them mid-_step would double-release pages
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        err = RuntimeError("engine stopped")
        # in-slot sequences must resolve too: a stream consumer blocked
        # on out_q and a request/response caller blocked on the future
        # would otherwise hang forever — and one whose last token was in
        # a step nobody will fetch
        for s in waiting + self._drop_everything():
            self._finish(s, error=err)

    # -- engine loop --------------------------------------------------------

    def _ensure_thread(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="serve-engine", daemon=True)
            self._thread.start()

    def _loop(self):
        # the interpreter tells this hook of every collection, whichever
        # thread triggers it, for as long as the engine thread lives
        gc.callbacks.append(self._on_gc)
        # ... and the ticker beside it tells "this process was not run"
        # from "the program took longer": both lengthen an iteration
        done = threading.Event()
        self._ticker = threading.Thread(
            target=self._tick, args=(done,), name="serve-engine-tick",
            daemon=True)
        self._ticker.start()
        self._thread_start_wall = time.time()
        try:
            self._run()
        finally:
            gc.callbacks.remove(self._on_gc)
            done.set()
            self._ticker.join(timeout=1.0)

    def _on_gc(self, phase: str, info: Dict[str, int]):
        """`gc.callbacks` hook: two clock reads a collection.  It runs on
        the collecting thread with the interpreter held and collections
        do not nest, so nothing else writes these three sums — and it
        takes no lock (the thread it interrupts may hold `_lock`)."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            dt, self._gc_t0 = time.perf_counter() - self._gc_t0, None
            tot = self._totals
            tot["gc_s"] += dt
            tot["gc_collections"] += 1
            tot["gc_max_s"] = max(tot["gc_max_s"], dt)

    def _tick(self, done: threading.Event, clock=time.perf_counter,
              sleep=time.sleep):
        """The stood-still counter: sleep `_TICK_S`, note every wake that
        comes more than `_TICK_LATE_S` late.  An independent witness: it
        waits for nothing but its own sleep, so a late wake means nothing
        of this process ran (stopped, starved of its core or of the
        interpreter, the machine frozen) — where the engine thread's own
        clock reads cannot tell that from a program that took longer.  Two
        clock reads a wake, none on the engine thread; like `_on_gc` it
        alone writes its sums and takes no lock."""
        tot = self._totals
        t = clock()
        try:
            while not done.is_set():
                self._tick_due = (clock, t + _TICK_S)
                sleep(_TICK_S)
                now = clock()
                late_s = now - t - _TICK_S
                t = now
                if late_s > _TICK_LATE_S:
                    tot["stalls"] += 1
                    tot["stall_s"] += late_s
                    tot["stall_max_s"] = max(tot["stall_max_s"], late_s)
                    common.note_stall(late_s, "serve-engine-tick")
        finally:
            self._tick_due = None

    def _run(self):
        while True:
            with self._lock:
                if self._stopped:
                    return
            if not self._busy():
                # nothing to run and nothing unfetched: the device's idle
                # time here is nobody's
                with self._jax.profiler.TraceAnnotation("serve.engine.idle"):
                    self._wake.wait(timeout=0.2)
                self._wake.clear()
                # a stall of the idle stretch is no iteration's
                self._stall_seen = self._totals["stall_s"]
                continue
            try:
                self._iteration()
            except Exception as e:          # fail every in-flight request
                with self._lock:            # rather than wedge the loop
                    waiting = list(self._waiting)
                    self._waiting.clear()
                for s in self._drop_everything() + waiting:
                    self._finish(s, error=e)

    def _busy(self) -> bool:
        """Something waits, holds a slot, or has a token in a step the
        host has not fetched."""
        with self._lock:
            return (bool(self._waiting) or bool(self._in_flight)
                    or any(s is not None for s in self._slots))

    def _drop_everything(self) -> List[_Sequence]:
        """Empty the slots and drop whatever is launched and unfetched,
        with the device state it consumed (a program that raised may have
        taken it along; `_ensure_device_state` makes it anew) -> the
        sequences that held a slot or had a token in flight, each once,
        holding nothing; their callers have yet to hear."""
        with self._lock:
            seqs = [s for s in self._slots if s is not None]
            self._slots = [None] * self.max_slots
        seqs += [s for fl in self._in_flight for _, s in fl.active
                 if not s.done and s not in seqs]
        self._in_flight.clear()
        self._pending = []
        self._prefilling = None
        self._cache = self._logits = None
        for operand in (self._pos, self._temps, self._topks,
                        self._toks_keys, *self._ptabs.values()):
            operand[:] = 0
        for s in seqs:
            self._release(s)
        return seqs

    def _iteration(self):
        """One scheduler iteration, with one step kept in flight: admit
        (launch this iteration's prefill programs), launch THIS
        iteration's step behind them and behind the step before it, and
        only then fetch and emit THAT step's tokens; stamp the prefills
        done; account.  So the chip has its next program queued when one
        ends, and a launch's way to the chip, the tokens' way back, emit,
        account and the consumers' turn at the interpreter all pass while
        a step runs.  The first step after an idle stretch is launched
        with nothing to fetch (`ahead` 0) and fetched by the next
        iteration; an iteration that launches none (nothing decodes)
        fetches the one in flight, so an idle engine holds no step.  A
        finished step's tokens never wait for a chunk: they are emitted
        before the prefill is stamped.

        The record describes the step whose tokens it EMITTED (`active`,
        the model's counters, `requests`); `stepped` / `ahead` the one it
        launched.  Every phase boundary is one `perf_counter` read that
        feeds every output: the ring record (and `_totals`), the
        `serve.engine.*` annotations on the profiler's clock — a flag
        test each while no profiler session is open — the phase histogram
        and, per request, the retro `engine.*` spans emitted by
        `_finish`."""
        ann = self._jax.profiler.TraceAnnotation
        # (an iteration that launches nothing waits from its own start)
        t0 = self._t_free = time.perf_counter()
        self._iter += 1
        gc0 = self._totals["gc_s"]
        it = self._launched = {"dispatch_s": 0.0, "ready_wait_s": 0.0,
                               "launches": 0, "waits": 0,
                               "step_dispatch_s": 0.0, "step_wait_s": 0.0}
        self._blocked = 0
        self._first = []
        self._chunks = self._chunk_tokens = self._returned = 0
        self._stats = dict.fromkeys(self._stat_keys, 0.0)
        # enqueue: the admission's programs, this iteration's step ...
        with ann("serve.engine.admit", iter=self._iter):
            admitted = self._admit()
        t1 = time.perf_counter()
        in_flight = bool(self._in_flight)
        stepped = self._step()
        # ... then wait: for the tokens of the step launched an iteration
        # ago, which every stream is waiting for, and after them for the
        # admission's work (this iteration's step runs behind it)
        active = self._emit() if in_flight else 0
        t2 = time.perf_counter()
        t3 = self._stamp_prefills() if self._pending else t2
        with ann("serve.engine.account"):
            # a chunk of a prompt already admitted is admission work too
            worked = admitted or self._chunks
            # launch + fetch + emit: from one emit to the next, but for
            # the account.  An admission's share of its iteration is the
            # rest: its launches before, its prefill's end after
            decode_s = (t2 - t1) if active else 0.0
            rec = {"swap_s": (t3 - t0 - decode_s) if worked else 0.0,
                   "prefill_s": self._last_prefill_s if worked else 0.0,
                   "decode_s": decode_s,
                   "active": active, "admitted": admitted, "ts": t3,
                   "t0": t0, "iter": self._iter,
                   "stepped": int(stepped),
                   "ahead": int(stepped and in_flight),
                   # blocked on the device: inside a launch, or waiting
                   # for what the last one returns
                   "device_wait_s": it["dispatch_s"] + it["ready_wait_s"],
                   **it,
                   "blocked_slots": self._blocked,
                   "chunks": self._chunks,
                   "chunk_tokens": self._chunk_tokens,
                   "pages_returned": self._returned,
                   **{"pages_" + k: a.used_pages
                      for k, a in self._allocs.items()},
                   # a model with several kinds of pages: a kind's used
                   # pages as bytes (its pages' layers and widths differ)
                   **({"bytes_" + k: self._allocs[k].used_pages * n
                       for k, n in self._page_bytes.items()}
                      if len(self._page_bytes) > 1 else {}),
                   **({"states_live": self._states_live(),
                       **self._live_bytes()}
                      if self._state_kinds else {}),
                   **self._stats,
                   "requests": [self._request_record(s)
                                for s in self._first]}
            m = _m_phase()
            if m:
                if worked:
                    m.observe(max(0.0, rec["swap_s"] - rec["prefill_s"]),
                              tags={"phase": "swap"})
                    m.observe(rec["prefill_s"], tags={"phase": "prefill"})
                if active:
                    m.observe(rec["decode_s"], tags={"phase": "decode"})
                if it["launches"]:
                    m.observe(it["dispatch_s"], tags={"phase": "dispatch"})
            with self._lock:
                qd = len(self._waiting)
            for which, val in (("active", active), ("queue", qd),
                               ("free_pages", self._alloc.free_pages)):
                g = _m_gauge(which)
                if g:
                    g.set(val)
            # the record closes here: only its append comes after
            rec["gc_s"] = self._totals["gc_s"] - gc0
            # what the ticker counted since the record before this one,
            # or since the idle stretch before this iteration ended
            stall_s = self._totals["stall_s"]
            rec["stall_s"], self._stall_seen = (stall_s - self._stall_seen,
                                                stall_s)
            rec["iter_s"] = time.perf_counter() - t0
            rec["host_s"] = rec["iter_s"] - rec["device_wait_s"]
            tot = self._totals
            with self._lock:
                self._ring.append(rec)
                for k in ("prefill_s", "decode_s", "host_s",
                          "device_wait_s", "dispatch_s", "ready_wait_s",
                          "launches") + self._stat_keys:
                    tot[k] += rec[k]
                tot["steps"] += rec["stepped"]
                tot["steps_ahead"] += rec["ahead"]
                tot["blocked_slot_s"] += rec["swap_s"] * rec["blocked_slots"]
                for r in rec["requests"]:
                    tot["queue_wait_s"] += r["queue_wait_s"]
                    tot["prefill_tokens"] += (r["prompt_tokens"]
                                              - r["shared_tokens"])
                    tot["prefill_scanned_tokens"] += r["scanned_tokens"]

    # -- launch and wait ----------------------------------------------------

    def _launch(self, program: str, fn, *args):
        """Hand one program to the chip -> what `fn(*args)` returns, which
        may not be ready and may be handed straight to the next launch.
        Two clock reads, kept for the caller: call entered (`_t_call`),
        call returned (`_t_free`) — operands converted and put on the
        device, the program enqueued; the device may or may not have
        started, the host can do nothing else.  `program` is the
        compilation ledger's name (`serve.keys`: the one expression that
        seeds and splits a request's keys, three small programs of jax's
        own).  A program that fails may say so only where its result is
        waited for."""
        with self._jax.profiler.TraceAnnotation("serve.engine.dispatch",
                                                program=program):
            self._t_call = time.perf_counter()
            out = fn(*args)
            self._t_free = time.perf_counter()
        self._launched["dispatch_s"] += self._t_free - self._t_call
        self._launched["launches"] += 1
        if program not in self._first_launch_wall:
            self._first_launch_wall[program] = (self._wall_of_perf
                                                + self._t_call)
        return out

    def _wait(self, name: str, *fetch, ready=None) -> Tuple[float, list]:
        """Block, under annotation `name`, until `ready` is done on the
        device and each of `fetch` is on the host -> (the clock then, the
        fetched arrays).  One clock read; the wait counts from where the
        thread was last let go: the last launch's return, the wait before
        this one, or the iteration's start."""
        with self._jax.profiler.TraceAnnotation(name):
            if ready is not None:
                self._jax.block_until_ready(ready)
            out = [self._np.asarray(x) for x in fetch]
            t = time.perf_counter()
        self._launched["ready_wait_s"] += t - self._t_free
        self._launched["waits"] += 1
        self._t_free = t
        return t, out

    @staticmethod
    def _request_record(s: _Sequence) -> Dict[str, Any]:
        """The TTFT of one request by parts, as the ring keeps it: the
        four parts sum to `ttft_s` up to the bookkeeping between
        admission and the prefill's dispatch (page table, operands; a
        sampling request's key launch)."""
        return {"rid": s.rid, "request_id": s.request_id,
                "queue_wait_s": s.t_admit - s.t_submit,
                # its own prefill programs; what lay between a chunked
                # prompt's chunks (the others' decode steps) is apart
                "prefill_s": s.prefill_s,
                "chunk_wait_s": s.t_ready - s.t_prefill - s.prefill_s,
                "first_step_wait_s": s.t_first - s.t_ready,
                "ttft_s": s.t_first - s.t_submit,
                "prompt_tokens": len(s.tokens),
                "shared_tokens": s.shared, "scanned_tokens": s.scanned}

    # -- admission ----------------------------------------------------------

    def _pages_needed(self, seq: _Sequence) -> int:
        total = min(len(seq.tokens) + seq.max_new, self.max_total)
        return -(-total // self.page_size)

    def _admit(self) -> int:
        """Admit waiting sequences into free slots while pages last —
        FIFO (a too-big head request waits for evictions rather than
        being overtaken; admission-order fairness beats packing here).
        A prompt longer than `prefill_chunk` takes one chunk now and one
        an iteration from then on, and nothing is admitted behind it
        until it decodes (first come, first served).
        """
        self._last_prefill_s = 0.0
        if self._prefilling is not None:
            self._blocked = self._streaming()
            self._prefill_next(self._prefilling)
            return 0
        admitted = 0
        while self._prefilling is None:
            with self._lock:
                if not self._waiting:
                    break
                try:
                    slot = self._slots.index(None)
                except ValueError:
                    break
                seq = self._waiting[0]
                plan = self._plan_pages(seq)
                if plan is None:
                    break                   # page-starved: wait for evicts
                if not admitted:
                    # streams this round's prefills stall: slots whose
                    # sequence has already put a token out
                    self._blocked = self._streaming()
                self._waiting.popleft()
                self._slots[slot] = seq
            seq.t_admit = time.perf_counter()
            self._admit_one(seq, slot, plan)
            admitted += 1
        if admitted:
            n = sum(1 for s in self._slots if s is not None)
            for s in self._slots:
                if s is not None:
                    s.peak = max(s.peak, n)
        return admitted

    def _streaming(self) -> int:
        return sum(1 for s in self._slots
                   if s is not None and s.t_first is not None)

    def _plan_pages(self, seq: _Sequence) -> Optional[Dict[str, Any]]:
        """Pages of every pool for `seq`, or None while some pool cannot
        take it.  A full kind's pages are taken here (allocator.plan, with
        its prefix sharing where the model allows it); a windowed kind
        only reserves the most it will hold at once; a state kind gives
        its one entry."""
        need = self._pages_needed(seq)
        windowed = {k: min(need, self._widths[k]) for k in self._windowed}
        if any(self._allocs[k].available < n for k, n in windowed.items()):
            return None
        if any(self._allocs[k].available < 1 for k in self._state_kinds):
            return None
        plan = {"pages": [], "shared_len": 0, "copies": [], "n_shared": 0}
        if self._kinds[self._main] is None:
            plan = self._alloc.plan(seq.tokens, need, share=self._share)
            if plan is None:
                return None
        for k, n in windowed.items():
            self._allocs[k].reserved += n
            seq.reserved[k] = n
        for k in self._state_kinds:
            seq.states[k] = self._allocs[k].alloc()
        return plan

    def _admit_one(self, seq: _Sequence, slot: int, plan):
        np = self._np
        self._ensure_device_state()
        plen = len(seq.tokens)
        seq.pages = plan["pages"]
        shared_len = plan["shared_len"]
        for k, w in self._widths.items():
            seq.tabs[k] = np.zeros(w, np.int32)
            seq.win[k] = {}
        seq.tabs[self._main][:len(seq.pages)] = seq.pages
        for k, entry in seq.states.items():
            seq.tabs[k][0] = entry
        self._totals["cow_copies"] += len(plan["copies"])
        self._totals["shared_pages"] += plan["n_shared"]
        for src, dst in plan["copies"]:
            self._cache = self._launch(
                "serve.copy_page", self._fn("copy_page"), self._cache,
                np.int32(dst), np.int32(src))
        seq.slot = slot
        seq.pos = plen
        seq.shared = seq.next_start = shared_len
        seq.prefilling = True
        self._prefill_next(seq)

    def _prefill_next(self, seq: _Sequence):
        """Launch the next prefill program of `seq`: the rest of its
        prompt padded to a multiple of `prefill_bucket` (pad rows cost
        matmul rows, not steps), or one `prefill_chunk` of it.  After the
        last one the sequence's tables and position enter the step's
        operands and it decodes; until then its slot rides the step as
        empty.  Nothing is waited for here: the step is launched behind
        what this returns, and `_stamp_prefills` reads the clock where
        the program is done."""
        jax, np = self._jax, self._np
        ann = jax.profiler.TraceAnnotation
        plen, start, slot = len(seq.tokens), seq.next_start, seq.slot
        n = plen - start
        if self.prefill_chunk and n > self.prefill_chunk:
            n = self.prefill_chunk
        T = -(-n // self.prefill_bucket) * self.prefill_bucket
        last = start + n == plen
        # a request that samples draws its keys on the chip, ahead of
        # its last prefill program: they are done before it starts
        keys = self._launch_keys(seq) if last and seq.temperature > 0 \
            else None
        self._grow_windows(seq, start, start + n)
        chunk = np.zeros(T, np.int32)
        chunk[:n] = seq.tokens[start:start + n]
        # the program may read a host operand after its launch returns,
        # and the sequence's tables change before it is waited for (a
        # window's pages go back, the step takes the next): it gets its
        # own copy of them
        tabs = {k: tab.copy() for k, tab in seq.tabs.items()}
        with ann("serve.engine.prefill", request_id=seq.request_id or "",
                 tokens=n, bucket=T):
            logits, self._cache, stats = self._launch(
                f"serve.prefill:{T}", self._fn(("prefill", T)),
                self._params, self._cache, chunk, tabs,
                np.int32(start), np.int32(n - 1),
                *([np.bool_(last)] if self._tell_last else []))
            if not seq.chunks:
                seq.t_prefill = self._t_call
            # what no later program consumes: the row and the counters
            self._pending.append((seq, self._t_call, logits, stats, last))
            if last:
                with ann("serve.engine.setrow"):
                    self._logits = self._launch(
                        "serve.setrow", self._fn("setrow"), self._logits,
                        logits, np.int32(slot))
        seq.scanned += T
        seq.chunks += 1
        seq.next_start = start + n
        self._chunks += 1
        self._chunk_tokens += n
        self._totals["chunks"] += 1
        self._shrink_windows(seq, seq.next_start)
        if not last:
            self._prefilling = seq
            return
        # the hand-over: from here the sequence's rows are the step's
        seq.prefilling, self._prefilling = False, None
        for k, tab in self._ptabs.items():
            tab[slot] = seq.tabs[k]
        self._pos[slot] = plen                  # first decode write pos
        # (a slot still prefilling asks the step's sampler for no draw)
        self._temps[slot] = seq.temperature
        self._topks[slot] = int(seq.top_k or 0)
        if keys is not None:
            with ann("serve.engine.keys"):
                _, (keys,) = self._wait("serve.engine.wait", keys)
            # key_offset: a resumed continuation (router replay)
            # re-derives the ORIGINAL request's key schedule and skips
            # the keys its already-delivered tokens consumed — sampled
            # decode stays bitwise-identical across the resume, same as
            # greedy
            seq.keys = keys[seq.key_offset:]
        self._totals["prefills"] += 1

        # register this prompt's full pages for live prefix sharing
        if self._share:
            for i in range(plen // self.page_size):
                self._alloc.register_prefix(
                    tuple(seq.tokens[:(i + 1) * self.page_size]),
                    seq.pages[i])

    def _launch_keys(self, seq: _Sequence):
        """A sampling request's keys, launched and on their way to the
        host: the expression `gpt.generate` splits its keys with, one
        program of jax's a length.  A request with no temperature draws
        none — `sample` takes the argmax wherever `temps <= 0`, and a step
        in which no slot has a temperature reads no key at all (it runs
        the argmax branch of the sampler's one `cond`)."""
        jax = self._jax
        with jax.profiler.TraceAnnotation("serve.engine.keys"):
            keys = self._launch(
                "serve.keys", lambda: jax.random.split(
                    jax.random.PRNGKey(seq.seed),
                    seq.key_offset + seq.max_new))
        keys.copy_to_host_async()
        return keys

    def _stamp_prefills(self) -> float:
        """The iteration's blocking stretch, second part (the tokens of
        the step in flight went out first): one clock read
        where each launched prefill program is done -> the last read.  A
        program's `prefill_s` is dispatch -> ready; behind another of the
        same iteration it counts from that one's read, so the sums count
        no stretch twice."""
        t = 0.0
        for seq, t0, logits, stats, last in self._pending:
            since = max(t0, t)
            t, stats = self._wait("serve.engine.wait", *stats, ready=logits)
            self._note_stats(stats, "chunk_")
            seq.prefill_s += t - since
            self._last_prefill_s += t - since
            if last:
                seq.t_ready = t
        self._pending = []          # let go before any consumer is woken
        return t

    def _note_stats(self, stats, prefix: str = ""):
        """The model's own counters of one program (`STEP_STATS` of its
        module: names of the f32 vector its serve programs return, here
        as `_wait` fetched it), summed into this iteration's record."""
        if stats:
            for k, v in zip(self._stat_names, stats[0]):
                self._stats[prefix + k] += float(v)

    # -- windowed pools -----------------------------------------------------

    def _set_entry(self, seq: _Sequence, kind: str, entry: int, page: int):
        seq.tabs[kind][entry] = page
        if not seq.prefilling:      # decoding: its rows are the step's
            self._ptabs[kind][seq.slot, entry] = page

    def _grow_windows(self, seq: _Sequence, lo: int, hi: int):
        """Pages for positions lo..hi-1 in every windowed pool, out of
        the sequence's reservation: logical page lp sits in ring entry
        lp % width."""
        ps = self.page_size
        for k in self._windowed:
            a, live = self._allocs[k], seq.win[k]
            for lp in range(lo // ps, (hi - 1) // ps + 1):
                if lp in live:
                    continue
                a.reserved -= 1
                seq.reserved[k] -= 1
                live[lp] = a.alloc()
                self._set_entry(seq, k, lp % self._widths[k], live[lp])

    def _shrink_windows(self, seq: _Sequence, next_pos: int):
        """Return the pages no query at or after `next_pos` can see: those
        whose every position is a window or more behind it."""
        ps = self.page_size
        for k in self._windowed:
            a, live, w = self._allocs[k], seq.win[k], self._kinds[k]
            for lp in [lp for lp in live if (lp + 1) * ps - 1 <= next_pos - w]:
                page = live.pop(lp)
                a.unref(page)
                a.reserved += 1
                seq.reserved[k] += 1
                if seq.tabs[k][lp % self._widths[k]] == page:
                    self._set_entry(seq, k, lp % self._widths[k], 0)
                self._returned += 1
                self._totals["window_pages_returned"] += 1

    def _release(self, seq: _Sequence):
        """Everything `seq` holds or has reserved goes back to its pools
        (once: the sequence is left holding nothing)."""
        self._alloc.release(seq.pages)
        seq.pages = []
        for k, live in seq.win.items():
            a = self._allocs[k]
            a.release(list(live.values()))
            live.clear()
            a.reserved -= seq.reserved.pop(k, 0)
        for k, entry in seq.states.items():
            self._allocs[k].unref(entry)
        seq.states = {}
        if self._prefilling is seq:
            self._prefilling = None

    # -- decode -------------------------------------------------------------

    def _step(self) -> bool:
        """Launch one fused sample+decode step over every slot, behind
        whatever this iteration's admission launched and behind the step
        before it, whose tokens the host has not seen -> whether there
        was one to launch.  Nothing a step is launched with is learned
        from a token: a sequence is in it while fewer steps were launched
        for it than it may answer tokens (`launched`, not `generated`,
        indexes its keys, and its position and window advance here, at
        the launch), and one whose last token by count this step computes
        gives its slot, pages and entry back right behind the launch —
        whatever is launched later runs later on the chip.  The step gets
        its own copy of the host operands: they change before it is
        waited for.  Its tokens start for the host as it ends and wait,
        with the slots it decodes, at the end of `_in_flight` for the next
        iteration's `_emit`.  Inactive slots ride along at pos 0 against
        the null page; `_emit` discards their tokens on the host."""
        ann = self._jax.profiler.TraceAnnotation
        active = []
        for i, s in enumerate(self._slots):
            if s is None or s.prefilling:
                continue
            # `max_new` may have been cut from outside (a driver closing
            # its window): a sequence with a token in flight ends with
            # that one, any other with the one launched here
            if s.launched < max(s.max_new, len(s.generated) + 1):
                active.append((i, s))
            else:
                self._vacate(i, s)
        if not active:
            return False
        with ann("serve.engine.step", iter=self._iter):
            for i, s in active:
                if s.keys is not None:
                    self._toks_keys[i] = s.keys[s.launched]
                self._grow_windows(s, int(self._pos[i]),
                                   int(self._pos[i]) + 1)
            counted = {"sampled_steps": float((self._temps > 0).any())}
            if self._kv_read is not None:
                counted["kv_read"], counted["kv_span"] = self._kv_read(
                    self._cfg, self._pos, self.page_size,
                    self.max_pages_per_seq)
            toks, self._logits, self._cache, stats = self._launch(
                "serve.step", self._fn("step"),
                self._params, self._cache, self._logits,
                self._toks_keys.copy(), self._temps.copy(),
                self._topks.copy(),
                {k: tab.copy() for k, tab in self._ptabs.items()},
                self._pos.copy())
            self._launched["step_dispatch_s"] = self._t_free - self._t_call
            for out in (toks, *stats):
                out.copy_to_host_async()
            self._in_flight.append(_Flight(active, (toks, stats), counted))
            for i, s in active:
                s.launched += 1
                self._pos[i] += 1
                self._shrink_windows(s, int(self._pos[i]))
                if s.launched >= s.max_new:
                    self._vacate(i, s)
        return True

    def _emit(self) -> int:
        """The iteration's blocking stretch, first part: the tokens of
        the oldest step in flight on the host, each to its sequence;
        sequences that are done hear of it -> how many slots that step
        decoded.  What needs the token happens here: `generated`, the
        stream, first and last token's clock reads, `eos_id`.  A sequence
        that ends on its EOS is found here one step late: the step
        launched since has computed one token more for it, inside the
        pages its admission reserved, and that token is dropped when its
        turn comes."""
        ann = self._jax.profiler.TraceAnnotation
        fl = self._in_flight[0]
        t_free = self._t_free
        # the device arrays are let go here, before any consumer is woken:
        # freeing one gives up the interpreter, and a consumer that takes
        # it then holds the engine thread up with nothing launched (seen
        # on the chip as 0.3-0.8 ms between one iteration and the next)
        (toks, stats), fl.out = fl.out, None
        now, (toks, *stats) = self._wait("serve.engine.fetch", toks, *stats)
        self._note_stats(stats)
        for k, v in fl.counted.items():
            self._stats[k] += v
        # how long the thread was blocked for these tokens: from where it
        # was last let go, which is this iteration's step's launch
        self._launched["step_wait_s"] = now - t_free
        with ann("serve.engine.emit"):
            emitted = 0
            finished = []
            for i, s in fl.active:
                if s.done:      # ended a step ago, or failed: no token
                    continue
                tok = int(toks[i])
                s.generated.append(tok)
                emitted += 1
                s.t_last = now
                if s.t_first is None:
                    s.t_first = now
                    self._first.append(s)
                    ttft = now - s.t_submit
                    with self._lock:
                        self._ttfts.append(ttft)
                    m = _m_ttft()
                    if m:
                        m.observe(ttft)
                s.out_q.put(tok)
                if (len(s.generated) >= s.max_new
                        or (s.eos_id is not None and tok == s.eos_id)):
                    finished.append((i, s))
            self._totals["tokens"] += emitted
            with self._lock:
                self._t_window.append((now, emitted))
            m = _m_tokens()
            if m and emitted:
                m.inc(emitted)
            for i, s in finished:
                if self._slots[i] is s:     # not by count: still there
                    self._vacate(i, s)
                self._finish(s)
        self._in_flight.popleft()
        return len(fl.active)

    def _vacate(self, slot: int, seq: _Sequence):
        """`seq` leaves its slot, and everything it holds goes back: no
        later step decodes it.  (Its last token may still be on its way:
        `_emit` tells its caller.)"""
        with self._lock:
            self._slots[slot] = None
        self._pos[slot] = 0
        for tab in self._ptabs.values():
            tab[slot] = 0
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._toks_keys[slot] = 0
        self._release(seq)
        self._wake.set()          # page/slot freed: retry page-starved head

    def _finish(self, seq: _Sequence, error: Optional[Exception] = None):
        if seq.done:
            return
        seq.done = True
        if seq.trace_ctx is not None and seq.t_first is not None:
            self._record_spans(seq)     # before the caller hears of the end
        if error is not None:
            if not seq.result.done():
                seq.result.set_exception(error)
            m = _m_requests()
            if m:
                m.inc(tags={"outcome": "error"})
        elif not seq.result.done():
            seq.result.set_result({
                "tokens": seq.tokens + seq.generated,
                "completion": list(seq.generated),
                "batch_size": seq.peak,
                "ttft_s": (seq.t_first - seq.t_submit)
                if seq.t_first else None,
            })
            m = _m_requests()
            if m:
                m.inc(tags={"outcome": "ok"})
        seq.out_q.put(self._END)

    @staticmethod
    def _record_spans(seq: _Sequence):
        """The request's phases as children of the span that submitted
        it, from the clock reads the engine thread took anyway (only
        reached for a request whose trace is sampled)."""
        wall = time.time_ns() - int(time.perf_counter() * 1e9)
        for name, a, b in (
                ("engine.queue_wait", seq.t_submit, seq.t_admit),
                ("engine.prefill", seq.t_prefill, seq.t_ready),
                ("engine.first_step", seq.t_ready, seq.t_first),
                ("engine.decode", seq.t_first, seq.t_last)):
            tracing.record_span(
                name, "INTERNAL", wall + int(a * 1e9), wall + int(b * 1e9),
                seq.trace_ctx, request_id=seq.request_id, rid=seq.rid,
                prompt_tokens=len(seq.tokens),
                generated_tokens=len(seq.generated))

    # -- compiled programs --------------------------------------------------

    def _ensure_device_state(self):
        if self._cache is not None:
            return
        jnp = self._jax.numpy
        self._cache = self._gpt.init_paged_cache(
            self._cfg, self._pool_pages, self.page_size)
        self._logits = jnp.zeros(
            (self.max_slots, self._cfg.vocab_size), jnp.float32)
        nbytes = lambda leaves: sum(
            int(a.size) * a.dtype.itemsize for a in leaves)
        # a model with a state kind says which leaves of its cache are
        # that kind's arena; the others hold its pages
        if self._state_kinds:
            self._state_bytes = nbytes(self._gpt.state_leaves(self._cache))
            self._entry_bytes = self._state_bytes // max(1, sum(
                self._pool_pages[k] for k in self._state_kinds))
        if len(self._kinds) > 1:
            def with_pages(pools):      # the cache's bytes, as shapes
                return nbytes(self._jax.tree_util.tree_leaves(
                    self._jax.eval_shape(lambda: self._gpt.init_paged_cache(
                        self._cfg, pools, self.page_size))))

            # kinds of pages may differ in layers, in a row's width and in
            # the leaves a layer keeps under the kind's table (dots3: a
            # latent row and an indexer row), so in a page's bytes: a
            # kind's page costs what the cache grows by when its pool has
            # one page more
            whole = with_pages(self._pool_pages)
            self._page_bytes = {
                k: with_pages({**self._pool_pages, k: n + 1}) - whole
                for k, n in self._pool_pages.items()
                if k not in self._state_kinds}

    def _fn(self, key):
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        jax, gpt, cfg = self._jax, self._gpt, self._cfg
        jnp = jax.numpy
        # every engine program routes through the compilation ledger:
        # "the step program never recompiles" (module docstring) is now
        # a measured claim — a benchmark cell is `correct` only if
        # nothing compiled inside its window
        from ..telemetry import device as devtel

        if key == "step":
            from ..ops.sampling import sample

            # named apart from the train step: `jit_serve_step(...)` on
            # the device trace's `XLA Modules` line
            def serve_step(params, cache, logits, keys, temps, topks,
                           ptab, pos):
                # one token a slot at the cost the operands ask for
                # (ops/sampling.py): where no slot has a temperature the
                # argmax and nothing else, decided on the chip from
                # `temps`; where one draws, gpt.sample_logits a row in
                # cfg.dtype, its top-k threshold selected, not sorted
                toks = sample(logits, keys, temps, topks, cfg.dtype)
                # a model may return its own counters third (the
                # vector its module's STEP_STATS names)
                new_logits, cache, *stats = gpt.paged_decode_step(
                    params, cache, toks, ptab, pos, cfg)
                return (toks, new_logits.astype(jnp.float32), cache,
                        tuple(stats))

            fn = self._fns[key] = devtel.instrument(
                jax.jit(serve_step, donate_argnums=(1, 2)),
                name="serve.step")
        elif key == "setrow":
            def serve_setrow(L, row, slot):
                return L.at[slot].set(row.astype(jnp.float32))

            fn = self._fns[key] = devtel.instrument(
                jax.jit(serve_setrow, donate_argnums=(0,)),
                name="serve.setrow")
        elif key == "copy_page":
            def serve_copy_page(cache, dst, src):
                return gpt.copy_page(cache, dst, src)

            fn = self._fns[key] = devtel.instrument(
                jax.jit(serve_copy_page, donate_argnums=(0,)),
                name="serve.copy_page")
        elif isinstance(key, tuple) and key[0] == "prefill":
            # per-bucket ledger name: a healthy engine compiles each
            # padded-length bucket once; the SAME bucket recompiling is
            # the storm signal, a new bucket is not
            def serve_prefill(params, cache, toks, *operands):
                logits, cache, *stats = gpt.paged_prefill(
                    params, cache, toks, *operands, cfg=cfg)
                return logits, cache, tuple(stats)

            fn = self._fns[key] = devtel.instrument(
                jax.jit(serve_prefill, donate_argnums=(1,)),
                name=f"serve.prefill:{key[1]}")
        else:
            raise KeyError(key)
        return fn
