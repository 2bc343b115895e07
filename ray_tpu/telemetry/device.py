"""Device runtime observability: compilation ledger + HBM memory census.

Everything above the device runtime is instrumented — PR 4 times steps,
PR 12 times RPCs, PR 17 traces the task hot path — but nothing watched
XLA itself, even though the serve engine stakes its design on "the step
program never recompiles" (``serve/_engine.py``) and the KV page arena
is the HBM budget that decides admission.  Pod-scale TPU runs live or
die on compile time and per-replica memory headroom (arXiv:1909.09756),
and HBM occupancy is *the* capacity signal for TPU serving
(PAPERS.md 2605.25645).  This module makes both visible:

``CompilationLedger``
    An instrumented jit/pjit entry point (``device.jit`` /
    ``device.instrument``) plus ``jax.monitoring`` duration hooks.
    Every compile is detected per call via the executable-cache size
    delta (``_cache_size()`` grows exactly when a new input signature
    compiles, and is stable on a cache hit), stamped with where the
    compiling call was entered on the wall clock (``t_call_wall``) and
    how long it took (``call_s``) by exclusive parts — trace / lower /
    backend-compile from the monitoring events, a nested ``jit``'s trace
    counted once, inside its caller's; ``run_s`` the rest (the
    executable's load, the first execution); ``cache_load_s`` the part
    of the backend's that read the persistent cache, and ``cache_hit``
    whether it served the program —, a
    fingerprint of the triggering signature, optional executable
    cost/memory analysis (``argument_bytes`` / ``output_bytes`` /
    ``alias_bytes``: what of the output lives in a donated argument's
    buffer / ``temp_bytes``), and — on a *re*compile — a **cause diff**
    against the previous compile of the same program: which argument
    changed shape, dtype, weak-type, static value or tree structure.
    A sliding window per program detects **recompile storms** (the
    compiles-per-iteration bug class the new ``jit-per-call`` lint
    flags statically) and publishes an advisory on the "train" pubsub
    topic exactly once per episode.

``DeviceMemoryCensus``
    Samples live device buffers (``jax.live_arrays``) by dtype/shape,
    plus registered owner reports — the serve engine registers its
    ``PageAllocator`` arena occupancy (free / used / shared / COW
    pages) and emergency-vault footprint.  Crossing a configured
    watermark publishes a ``memory_watermark`` advisory with the same
    episode semantics.

Snapshots flush to control-plane KV namespace ``_device`` (keyed
``device:<worker_id>``) over the same rate-limited, never-raises path
as PR-4 telemetry, and surface through ``ray-tpu device-stats``,
``GET /api/device/stats``, Prometheus series (``ray_tpu_compile_seconds``,
``ray_tpu_recompiles_total``, ``ray_tpu_hbm_live_bytes``,
``ray_tpu_kv_pages{state=…}``) and Chrome-trace compile slices.  The
snapshot also carries how the process came up (``boot``) and when it
stood still (``stalls``; both kept in ``_private/common``), drawn on the
same worker row in wall order.
"""

from __future__ import annotations

import functools
import inspect
import os
import pickle
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..util import metrics as metrics_mod

#: control-plane KV namespace for device snapshots.  Deliberately NOT
#: ``_metrics``: collect_cluster_metrics json-merges every key there.
DEVICE_NS = "_device"
DEVICE_KEY_PREFIX = "device:"

#: jax.monitoring duration events -> the parts of a compiling call.  jax
#: announces each (a scalar event, on entry) and times it (a duration
#: event, on exit); `jaxpr_trace_duration` NESTS — every `jit` and every
#: jitted `jnp` function traced inside a program fires its own, inside its
#: caller's — so a part counts only where no other part is open around it
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
#: fired inside `backend_compile_duration` where the persistent cache
#: served the executable: the read + deserialise
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_PARTS = tuple(_DURATION_EVENTS.values())

_COMPILE_BOUNDARIES = [0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5,
                       5, 10, 30, 60, 120, 300]

_tls = threading.local()

# Strong refs so the weakref metric registry keeps these alive.
_metric_lock = threading.Lock()
_metric_cache: Dict[str, Any] = {}


def _get_metric(key: str, factory: Callable[[], Any]) -> Any:
    with _metric_lock:
        m = _metric_cache.get(key)
        if m is None:
            m = _metric_cache[key] = factory()
        return m


def _compile_histogram():
    return _get_metric("compile_hist", lambda: metrics_mod.Histogram(
        "ray_tpu_compile_seconds",
        description="XLA trace+lower+compile wall time per program",
        boundaries=_COMPILE_BOUNDARIES,
        tag_keys=("program",)))


def _recompile_counter():
    return _get_metric("recompile_ctr", lambda: metrics_mod.Counter(
        "ray_tpu_recompiles_total",
        description="Recompiles (2nd+ compile of the same program)",
        tag_keys=("program",)))


def _hbm_gauge():
    return _get_metric("hbm_gauge", lambda: metrics_mod.Gauge(
        "ray_tpu_hbm_live_bytes",
        description="Live device-buffer bytes (jax.live_arrays sample)"))


def _kv_pages_gauge():
    return _get_metric("kv_pages", lambda: metrics_mod.Gauge(
        "ray_tpu_kv_pages",
        description="KV page arena occupancy by state "
                    "(free/used live; shared/cow cumulative)",
        tag_keys=("state",)))


def _default_publish(payload: Dict[str, Any]) -> None:
    """Advisories ride the existing "train" pubsub topic (the same one
    StepAggregator straggler advisories use) so RemediationEngine and
    dashboards need no new subscription."""
    from ray_tpu._private import core as core_mod

    core = core_mod._current_core
    if core is None or getattr(core, "_shutdown", False):
        return
    core.control.call("publish", {"topic": "train", "payload": payload},
                      timeout=5.0)


# ---------------------------------------------------------------------------
# Signature fingerprints + cause diffs
# ---------------------------------------------------------------------------


def _is_arraylike(x: Any) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype") \
        and not inspect.isclass(x)


def _leaf_desc(x: Any) -> Dict[str, Any]:
    if _is_arraylike(x):
        return {"kind": "array",
                "shape": tuple(int(s) for s in x.shape),
                "dtype": str(x.dtype),
                "weak_type": bool(getattr(x, "weak_type", False))}
    return {"kind": "static", "value": repr(x)[:80]}


def _describe(x: Any) -> Dict[str, Any]:
    """Bounded structural descriptor of one call argument."""
    if _is_arraylike(x) or x is None or isinstance(
            x, (int, float, bool, complex, str, bytes)):
        return _leaf_desc(x)
    try:
        import jax

        flat, treedef = jax.tree_util.tree_flatten_with_path(x)
        leaves = [{"path": jax.tree_util.keystr(path), **_leaf_desc(leaf)}
                  for path, leaf in flat[:32]]
        return {"kind": "pytree", "num_leaves": len(flat),
                "treedef": str(treedef)[:120], "leaves": leaves}
    except Exception:
        return {"kind": "static", "value": repr(x)[:80]}


def _fmt_desc(d: Dict[str, Any]) -> str:
    if d.get("kind") == "array":
        shape = ",".join(str(s) for s in d.get("shape", ()))
        weak = "~" if d.get("weak_type") else ""
        return f"{weak}{d.get('dtype')}[{shape}]"
    if d.get("kind") == "pytree":
        return f"pytree({d.get('num_leaves')} leaves)"
    return str(d.get("value"))


def _fingerprint(sig: Optional[inspect.Signature], args: Tuple,
                 kwargs: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Per-arg descriptors, named from the wrapped fn's signature when
    it binds (fallback: positional ``argN``)."""
    named: List[Tuple[str, Any]] = []
    if sig is not None:
        try:
            bound = sig.bind_partial(*args, **kwargs)
            named = list(bound.arguments.items())
        except TypeError:
            named = []
    if not named:
        named = [(f"arg{i}", a) for i, a in enumerate(args)]
        named += sorted(kwargs.items())
    return [{"arg": name, **_describe(val)} for name, val in named]


def _diff_entry(name: str, old: Dict[str, Any],
                new: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Field-level diff of one argument's descriptor (old vs new)."""
    if old.get("kind") != new.get("kind"):
        return [{"arg": name, "kind": "type",
                 "old": _fmt_desc(old), "new": _fmt_desc(new)}]
    kind = new.get("kind")
    out: List[Dict[str, Any]] = []
    if kind == "array":
        for field, label in (("shape", "shape"), ("dtype", "dtype"),
                             ("weak_type", "weak_type")):
            if old.get(field) != new.get(field):
                out.append({"arg": name, "kind": label,
                            "old": _fmt_desc(old), "new": _fmt_desc(new)})
        return out
    if kind == "pytree":
        if (old.get("num_leaves") != new.get("num_leaves")
                or old.get("treedef") != new.get("treedef")):
            return [{"arg": name, "kind": "structure",
                     "old": _fmt_desc(old), "new": _fmt_desc(new)}]
        for o_leaf, n_leaf in zip(old.get("leaves", []),
                                  new.get("leaves", [])):
            if o_leaf != n_leaf:
                leaf_name = f"{name}{n_leaf.get('path', '')}"
                out.extend(_diff_entry(leaf_name,
                                       {k: v for k, v in o_leaf.items()
                                        if k != "path"},
                                       {k: v for k, v in n_leaf.items()
                                        if k != "path"}))
        return out
    if old.get("value") != new.get("value"):
        return [{"arg": name, "kind": "static",
                 "old": str(old.get("value")), "new": str(new.get("value"))}]
    return out


def diff_signatures(old: List[Dict[str, Any]],
                    new: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """All per-arg changes between two compile fingerprints."""
    changes: List[Dict[str, Any]] = []
    old_map = {e["arg"]: e for e in old}
    new_names = set()
    for e in new:
        name = e["arg"]
        new_names.add(name)
        o = old_map.get(name)
        if o is None:
            changes.append({"arg": name, "kind": "added",
                            "old": None, "new": _fmt_desc(e)})
        else:
            changes.extend(_diff_entry(name, o, e))
    for e in old:
        if e["arg"] not in new_names:
            changes.append({"arg": e["arg"], "kind": "removed",
                            "old": _fmt_desc(e), "new": None})
    return changes


# ---------------------------------------------------------------------------
# jax.monitoring hookup
# ---------------------------------------------------------------------------

_monitoring_lock = threading.Lock()
_monitoring_installed = False
# persistent compile cache (JAX_COMPILATION_CACHE_DIR) traffic, counted
# on the process ledger: whether a restarted process found its programs
# or paid for them again
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def _frame_stack() -> List[Dict[str, Any]]:
    st = getattr(_tls, "frames", None)
    if st is None:
        st = _tls.frames = []
    return st


def _new_frame() -> Dict[str, Any]:
    """What the listeners note of one instrumented call: the parts'
    seconds, how many parts are open (entered, not yet timed), and the
    persistent cache's answers."""
    return {"durations": {}, "open": 0, "hits": 0, "misses": 0}


def _on_scalar(event: str, value: float, **kw) -> None:
    """jax entered a part (`log_elapsed_time.__enter__`)."""
    if event in _DURATION_EVENTS:
        st = _frame_stack()
        if st:
            st[-1]["open"] += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    """jax timed a part, or a read of the persistent cache.  A part that
    ends inside another (a nested `jit`'s trace inside its caller's) is
    its caller's time already and is dropped: the parts of a record are
    exclusive and sum to no more than the call."""
    st = _frame_stack()
    frame = st[-1] if st else None
    if event == _CACHE_LOAD_EVENT:
        key = "cache_load_s"
        led = get_ledger()         # the process's sum: every program's
        with led._lock:
            led._persistent_cache["load_s"] += float(duration)
    else:
        key = _DURATION_EVENTS.get(event)
        if key is None:
            return
        if frame is not None:
            frame["open"] = max(0, frame["open"] - 1)
            if frame["open"]:
                return
    if frame is not None:
        d = frame["durations"]
        d[key] = d.get(key, 0.0) + float(duration)


def _on_event(event: str, **kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is None:
        return
    st = _frame_stack()
    if st:
        st[-1][key] += 1
    led = get_ledger()
    with led._lock:
        led._persistent_cache[key] += 1


def _install_monitoring() -> None:
    """Attach the listeners once per process.  They fire *during* the
    instrumented call, on the calling thread, while the compile happens,
    so what they hear attaches to the innermost open call frame."""
    global _monitoring_installed
    with _monitoring_lock:
        if _monitoring_installed:
            return
        _monitoring_installed = True
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            # (a jax that announces no part: every duration counts, a
            # nested trace twice, as before PR 58)
            monitoring.register_scalar_listener(_on_scalar)
        except Exception:
            # jax absent or too old: cache-size deltas still detect
            # compiles, records just carry no phase durations
            pass


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------


def _no_cache_traffic() -> Dict[str, float]:
    """The persistent cache's counts of a process: entries found and
    written, and the seconds spent loading the found ones."""
    return {"hits": 0, "misses": 0, "load_s": 0.0}


class _ProgramState:
    """Per-program compile history (owned by the ledger, all access
    under the ledger's lock)."""

    __slots__ = ("name", "compiles", "recompiles", "last_signature",
                 "last_cause", "last_compile_wall", "last_compile_mono",
                 "compile_times", "storm_open", "storm_episodes",
                 "durations_total", "t_first_call_wall")

    def __init__(self, name: str):
        self.name = name
        self.compiles = 0
        self.recompiles = 0
        self.last_signature: Optional[List[Dict[str, Any]]] = None
        self.last_cause: Optional[Dict[str, Any]] = None
        self.last_compile_wall = 0.0
        self.last_compile_mono = 0.0
        self.compile_times: deque = deque(maxlen=64)  # mono stamps
        self.storm_open = False
        self.storm_episodes = 0
        self.durations_total: Dict[str, float] = {}
        self.t_first_call_wall = 0.0   # its first compiling call entered


class CompilationLedger:
    """Per-process XLA compilation ledger.

    Thread-safe; the per-call fast path (cache hit) costs one
    ``_cache_size()`` C call and no lock.  Records, program state and
    advisories are guarded by ``_lock``.
    """

    def __init__(self, max_records: int = 256,
                 storm_threshold: Optional[int] = None,
                 storm_window_s: Optional[float] = None,
                 analysis: Optional[bool] = None,
                 publish: Optional[Callable[[Dict[str, Any]], None]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 wall: Callable[[], float] = time.time):
        if storm_threshold is None:
            storm_threshold = int(os.environ.get(
                "RAY_TPU_DEVICE_STORM_THRESHOLD", "4"))
        if storm_window_s is None:
            storm_window_s = float(os.environ.get(
                "RAY_TPU_DEVICE_STORM_WINDOW_S", "30"))
        if analysis is None:
            analysis = os.environ.get(
                "RAY_TPU_DEVICE_ANALYSIS", "0") not in ("0", "", "false")
        self.storm_threshold = max(2, int(storm_threshold))
        self.storm_window_s = float(storm_window_s)
        self.analysis = bool(analysis)
        self._publish = publish or _default_publish
        self._clock = clock
        self._wall = wall
        self._lock = threading.Lock()
        self._programs: Dict[str, _ProgramState] = {}  # guarded-by: _lock
        self._records: deque = deque(maxlen=max(1, int(max_records)))  # guarded-by: _lock
        self._advisories: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._total_compiles = 0   # guarded-by: _lock
        self._total_recompiles = 0  # guarded-by: _lock
        self._persistent_cache = _no_cache_traffic()  # guarded-by: _lock
        self._drain_idx = 0  # guarded-by: _lock
        self._last_flush = 0.0  # rate limiter state (monotonic)

    # -- instrumentation entry points ---------------------------------

    def instrument(self, jitted: Any, name: Optional[str] = None,
                   analysis: Optional[bool] = None) -> "InstrumentedProgram":
        """Wrap an already-jitted callable so its compiles are recorded
        under ``name``.  Idempotent on already-instrumented programs."""
        if isinstance(jitted, InstrumentedProgram):
            return jitted
        return InstrumentedProgram(jitted, name=name, ledger=self,
                                   analysis=analysis)

    def jit(self, fun: Optional[Callable] = None, *,
            name: Optional[str] = None, analysis: Optional[bool] = None,
            **jit_kwargs) -> Any:
        """Instrumented drop-in for ``jax.jit`` (usable as a decorator
        or a wrap call): the returned program records every compile in
        this ledger."""
        if fun is None:
            return functools.partial(self.jit, name=name,
                                     analysis=analysis, **jit_kwargs)
        import jax

        return self.instrument(jax.jit(fun, **jit_kwargs), name=name,
                               analysis=analysis)

    # -- record path (called from InstrumentedProgram) ----------------

    def _record_compile(self, prog: "InstrumentedProgram", args: Tuple,
                        kwargs: Dict[str, Any], call_s: float,
                        frame: Dict[str, Any], t_call_wall: float) -> None:
        """One detected compile: the call took `call_s` from
        `t_call_wall`, and `frame` is what the listeners heard inside it.
        Never raises (observability must not take down the workload)."""
        try:
            self._record_compile_inner(prog, args, kwargs, call_s,
                                       frame, t_call_wall)
        except Exception:
            pass

    def _record_compile_inner(self, prog: "InstrumentedProgram",
                              args: Tuple, kwargs: Dict[str, Any],
                              call_s: float, frame: Dict[str, Any],
                              t_call_wall: float) -> None:
        signature = _fingerprint(prog._sig, args, kwargs)
        heard = frame["durations"]
        durations = {k: heard[k] for k in _PARTS if k in heard}
        compile_s = sum(durations.values()) if durations else call_s
        # what of the call no part covers: the executable's load and the
        # first execution's dispatch (and jax's own Python between parts)
        run_s = max(0.0, call_s - compile_s) if durations else 0.0
        cache_load_s = heard.get("cache_load_s", 0.0)
        # served by the persistent cache / compiled and written to it /
        # neither (no cache, or an entry under jax's size and time floors)
        cache_hit = (True if frame["hits"] and not frame["misses"]
                     else False if frame["misses"] else None)
        analysis = None
        if prog._analysis if prog._analysis is not None else self.analysis:
            analysis = _analyze_executable(prog._fn, args, kwargs)
        now_wall, now_mono = self._wall(), self._clock()

        advisory = None
        with self._lock:
            st = self._programs.get(prog.name)
            if st is None:
                st = self._programs[prog.name] = _ProgramState(prog.name)
            st.compiles += 1
            self._total_compiles += 1
            cause: Optional[Dict[str, Any]] = None
            is_recompile = st.compiles > 1
            if is_recompile:
                st.recompiles += 1
                self._total_recompiles += 1
                changes = diff_signatures(st.last_signature or [],
                                          signature)
                cause = {"changes": changes}
                if changes:
                    cause.update({"arg": changes[0]["arg"],
                                  "kind": changes[0]["kind"],
                                  "old": changes[0]["old"],
                                  "new": changes[0]["new"]})
                else:
                    cause["note"] = ("signature-equivalent recompile "
                                     "(sharding/backend or untracked "
                                     "static)")
            st.last_signature = signature
            st.last_cause = cause
            st.last_compile_wall = now_wall
            st.last_compile_mono = now_mono
            if not st.t_first_call_wall:
                st.t_first_call_wall = t_call_wall
            for k, v in (*durations.items(), ("call_s", call_s),
                         ("cache_load_s", cache_load_s), ("run_s", run_s)):
                st.durations_total[k] = st.durations_total.get(k, 0.0) + v
            rec = {
                "program": prog.name,
                "ts": now_wall,
                # the call: entered at `t_call_wall`, `call_s` long, of
                # which `durations` are jax's parts (exclusive), `run_s`
                # the rest, and `cache_load_s` the part of `backend_s`
                # that read the persistent cache
                "t_call_wall": t_call_wall,
                "nth_compile": st.compiles,
                "call_s": round(call_s, 6),
                "compile_s": round(compile_s, 6),
                "durations": {k: round(v, 6)
                              for k, v in durations.items()},
                "run_s": round(run_s, 6),
                "cache_hit": cache_hit,
                "cache_load_s": round(cache_load_s, 6),
                "signature": signature,
                "cause": cause,
            }
            if analysis:
                rec["analysis"] = analysis
            self._records.append(rec)

            # storm detection: threshold compiles inside the sliding
            # window opens an episode; one advisory per episode, re-armed
            # only after the window drains.
            st.compile_times.append(now_mono)
            cutoff = now_mono - self.storm_window_s
            while st.compile_times and st.compile_times[0] < cutoff:
                st.compile_times.popleft()
            if st.storm_open and not st.compile_times:
                st.storm_open = False
            if (not st.storm_open
                    and len(st.compile_times) >= self.storm_threshold):
                st.storm_open = True
                st.storm_episodes += 1
                advisory = {
                    "event": "device_advisory",
                    "kind": "recompile_storm",
                    "program": prog.name,
                    "compiles_in_window": len(st.compile_times),
                    "window_s": self.storm_window_s,
                    "threshold": self.storm_threshold,
                    "cause": cause,
                    "ts": now_wall,
                }
                self._advisories.append(advisory)

        try:
            _compile_histogram().observe(compile_s,
                                         tags={"program": prog.name})
            if is_recompile:
                _recompile_counter().inc(1.0, tags={"program": prog.name})
        except Exception:
            pass
        if advisory is not None:
            try:
                self._publish(advisory)
            except Exception:
                pass
        # piggyback the KV flush on the compile path (rate-limited):
        # a storm flushes itself visible without any cooperating loop
        flush_device_snapshot()

    def push_advisory(self, payload: Dict[str, Any],
                      publish: bool = True) -> None:
        """Record (and optionally publish) an externally-raised device
        advisory — the memory census uses this for watermark events."""
        with self._lock:
            self._advisories.append(payload)
        if publish:
            try:
                self._publish(payload)
            except Exception:
                pass

    # -- read side -----------------------------------------------------

    def counts(self) -> Dict[str, int]:
        """Per-program compile counts — the bench zero-recompile gate
        diffs two of these around the timed region."""
        with self._lock:
            return {name: st.compiles
                    for name, st in self._programs.items()}

    def compiles_since(self, mark: Dict[str, int]) -> Dict[str, int]:
        """Programs that compiled since ``mark = ledger.counts()``."""
        now = self.counts()
        out = {}
        for name, n in now.items():
            delta = n - mark.get(name, 0)
            if delta > 0:
                out[name] = delta
        return out

    def storm_advisories(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [a for a in self._advisories
                    if a.get("kind") == "recompile_storm"]

    def drain_advisories(self) -> List[Dict[str, Any]]:
        """Advisories raised since the last drain — driver loops feed
        these to ``RemediationEngine.observe_advisory`` once per round."""
        with self._lock:
            new = list(self._advisories[self._drain_idx:])
            self._drain_idx = len(self._advisories)
            if len(self._advisories) > 512:  # bound the log
                drop = len(self._advisories) - 256
                del self._advisories[:drop]
                self._drain_idx = max(0, self._drain_idx - drop)
            return new

    def snapshot(self) -> Dict[str, Any]:
        """Picklable ledger state for the ``_device`` KV flush."""
        now_mono = self._clock()
        with self._lock:
            programs = {}
            for name, st in self._programs.items():
                if (st.storm_open and st.compile_times
                        and now_mono - st.compile_times[-1]
                        > self.storm_window_s):
                    st.storm_open = False  # episode drained
                programs[name] = {
                    "compiles": st.compiles,
                    "recompiles": st.recompiles,
                    "last_compile_ts": st.last_compile_wall,
                    "last_cause": st.last_cause,
                    "storm_open": st.storm_open,
                    "storm_episodes": st.storm_episodes,
                    # sums over its compiles — and, a stamp among them,
                    # when its first compiling call was entered (this is
                    # the one dict of a program the benchmark's drivers
                    # print)
                    "durations_total_s": {
                        **{k: round(v, 6)
                           for k, v in st.durations_total.items()},
                        "t_first_call_wall": st.t_first_call_wall},
                }
            return {
                "total_compiles": self._total_compiles,
                "total_recompiles": self._total_recompiles,
                "persistent_cache": dict(self._persistent_cache),
                "programs": programs,
                "records": list(self._records),
                "advisories": list(self._advisories),
                "storm_threshold": self.storm_threshold,
                "storm_window_s": self.storm_window_s,
            }

    def reset(self) -> None:
        """Forget all state (tests)."""
        with self._lock:
            self._programs.clear()
            self._records.clear()
            self._advisories.clear()
            self._drain_idx = 0
            self._total_compiles = 0
            self._total_recompiles = 0
            self._persistent_cache = _no_cache_traffic()


def _analyze_executable(jitted: Any, args: Tuple,
                        kwargs: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Opt-in executable cost/memory analysis via the AOT path.  The
    AOT lower→compile does NOT share the jit dispatch cache, so this
    roughly doubles compile cost — off by default, on in tests/bench."""
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
    except Exception:
        return None
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):   # list-of-dicts on jax 0.4.x
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            out["cost"] = {k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float))
                           and k in ("flops", "bytes accessed",
                                     "utilization operand 0",
                                     "optimal_seconds")}
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out["memory"] = {
                "argument_bytes": int(getattr(
                    ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(
                    ma, "output_size_in_bytes", 0)),
                # bytes of output written into a donated argument's
                # buffer: 0 where a program returns its state as a copy
                "alias_bytes": int(getattr(
                    ma, "alias_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "code_bytes": int(getattr(
                    ma, "generated_code_size_in_bytes", 0)),
            }
    except Exception:
        pass
    return out or None


class InstrumentedProgram:
    """A jitted callable routed through the ledger.

    Transparent: attribute access (``lower``, ``clear_cache``, …)
    proxies to the underlying jitted object, so instrumented programs
    drop into existing call sites unchanged.
    """

    def __init__(self, jitted: Any, name: Optional[str] = None,
                 ledger: Optional["CompilationLedger"] = None,
                 analysis: Optional[bool] = None):
        self._fn = jitted
        wrapped = getattr(jitted, "__wrapped__", None)
        self.name = name or getattr(wrapped, "__qualname__", None) \
            or getattr(jitted, "__name__", None) or repr(jitted)
        self._ledger = ledger
        self._analysis = analysis
        self._cold_calls = 0   # calls in flight that found no executable
        self._cold_lock = threading.Lock()
        # `time.monotonic()` where the last such call returned (0.0: none
        # yet): a liveness probe that did not run WHILE the program
        # compiled still learns that it did
        self.first_compile_ended = 0.0
        try:
            self._sig: Optional[inspect.Signature] = \
                inspect.signature(wrapped if wrapped is not None else jitted)
        except (TypeError, ValueError):
            self._sig = None
        functools.update_wrapper(self, wrapped or jitted, updated=())
        _install_monitoring()

    def __call__(self, *args, **kwargs):
        led = self._ledger if self._ledger is not None else get_ledger()
        try:
            before = self._fn._cache_size()
        except Exception:
            before = None
        frame = _new_frame()
        stack = _frame_stack()
        stack.append(frame)
        cold = before == 0
        if cold:
            with self._cold_lock:
                self._cold_calls += 1
        t_wall = time.time()
        t0 = time.perf_counter()
        try:
            out = self._fn(*args, **kwargs)
        finally:
            stack.pop()
            if cold:
                with self._cold_lock:
                    self._cold_calls -= 1
                    self.first_compile_ended = time.monotonic()
        if before is not None:
            try:
                compiled_new = self._fn._cache_size() > before
            except Exception:
                compiled_new = False
            if compiled_new:
                led._record_compile(self, args, kwargs,
                                    time.perf_counter() - t0, frame, t_wall)
        return out

    @property
    def first_compile_in_flight(self) -> bool:
        """A call is running that found this program never compiled:
        it is in trace/lower/compile (seconds to minutes on a cold
        cache), not hung — liveness probes read this."""
        return self._cold_calls > 0

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):
        return f"<InstrumentedProgram {self.name!r} of {self._fn!r}>"


# ---------------------------------------------------------------------------
# Memory census
# ---------------------------------------------------------------------------


class DeviceMemoryCensus:
    """Samples live device memory and registered owner reports.

    Owners (e.g. the serve engine) register a zero-arg callback
    returning a small dict; a callback reporting a ``pages`` sub-dict
    (``free/used/shared/cow``) feeds the ``ray_tpu_kv_pages`` gauge.
    """

    def __init__(self, watermark_bytes: Optional[int] = None,
                 ledger: Optional[CompilationLedger] = None,
                 wall: Callable[[], float] = time.time):
        if watermark_bytes is None:
            watermark_bytes = int(float(os.environ.get(
                "RAY_TPU_DEVICE_WATERMARK_BYTES", "0")))
        self.watermark_bytes = int(watermark_bytes)
        self._ledger = ledger
        self._wall = wall
        self._lock = threading.Lock()
        self._owners: Dict[str, Callable[[], Dict[str, Any]]] = {}  # guarded-by: _lock
        self._watermark_open = False  # guarded-by: _lock

    def register_owner(self, tag: str,
                       report: Callable[[], Dict[str, Any]]) -> None:
        with self._lock:
            self._owners[tag] = report

    def unregister_owner(self, tag: str) -> None:
        with self._lock:
            self._owners.pop(tag, None)

    def _live_buffers(self) -> Dict[str, Any]:
        total = 0
        count = 0
        by_dtype: Dict[str, int] = {}
        shapes: Dict[Tuple[str, Tuple[int, ...]], Dict[str, Any]] = {}
        try:
            import jax

            # observing must never be what opens the chip: a process
            # that has not touched its backend holds no buffers
            arrays = jax.live_arrays() if backend_initialized() else ()
            for a in arrays:
                try:
                    nbytes = int(a.nbytes)
                    dt = str(a.dtype)
                    shp = tuple(int(s) for s in a.shape)
                except Exception:
                    continue
                total += nbytes
                count += 1
                by_dtype[dt] = by_dtype.get(dt, 0) + nbytes
                key = (dt, shp)
                slot = shapes.get(key)
                if slot is None:
                    slot = shapes[key] = {"dtype": dt, "shape": list(shp),
                                          "count": 0, "bytes": 0}
                slot["count"] += 1
                slot["bytes"] += nbytes
        except Exception:
            pass
        top = sorted(shapes.values(), key=lambda s: -s["bytes"])[:12]
        return {"total_bytes": total, "count": count,
                "by_dtype": by_dtype, "top_shapes": top}

    def census(self) -> Dict[str, Any]:
        """One sample: live buffers + owner reports + gauges, plus a
        watermark advisory (once per above-watermark episode)."""
        live = self._live_buffers()
        with self._lock:
            owners = dict(self._owners)
        reports: Dict[str, Dict[str, Any]] = {}
        for tag, cb in owners.items():
            try:
                reports[tag] = dict(cb())
            except Exception:
                reports[tag] = {"error": "owner report failed"}
        try:
            # built-in owner: this process's emergency-vault footprint
            # (elastic/emergency.py) — recovery headroom competes with
            # the KV arena for the same HBM budget
            from ..elastic.emergency import vault_footprint

            vf = vault_footprint()
            if vf.get("entries"):
                reports["emergency_vault"] = vf
        except Exception:
            pass

        try:
            _hbm_gauge().set(float(live["total_bytes"]))
            for rep in reports.values():
                pages = rep.get("pages")
                if isinstance(pages, dict):
                    for state in ("free", "used", "shared", "cow"):
                        if state in pages:
                            _kv_pages_gauge().set(
                                float(pages[state]),
                                tags={"state": state})
        except Exception:
            pass

        advisory = None
        with self._lock:
            if self.watermark_bytes > 0:
                over = live["total_bytes"] >= self.watermark_bytes
                if over and not self._watermark_open:
                    self._watermark_open = True
                    advisory = {
                        "event": "device_advisory",
                        "kind": "memory_watermark",
                        "live_bytes": live["total_bytes"],
                        "watermark_bytes": self.watermark_bytes,
                        "ts": self._wall(),
                    }
                elif (not over and self._watermark_open
                      and live["total_bytes"]
                      < 0.9 * self.watermark_bytes):
                    self._watermark_open = False  # hysteresis re-arm
        if advisory is not None:
            led = self._ledger if self._ledger is not None else get_ledger()
            led.push_advisory(advisory)
        return {"ts": self._wall(), "live": live, "owners": reports,
                "watermark_bytes": self.watermark_bytes}


# ---------------------------------------------------------------------------
# Process singletons + module-level entry points
# ---------------------------------------------------------------------------

_singleton_lock = threading.Lock()
_ledger: Optional[CompilationLedger] = None
_census: Optional[DeviceMemoryCensus] = None


def get_ledger() -> CompilationLedger:
    global _ledger
    with _singleton_lock:
        if _ledger is None:
            _ledger = CompilationLedger()
        return _ledger


def get_census() -> DeviceMemoryCensus:
    global _census
    with _singleton_lock:
        if _census is None:
            _census = DeviceMemoryCensus()
        return _census


def jit(fun: Optional[Callable] = None, *, name: Optional[str] = None,
        analysis: Optional[bool] = None, **jit_kwargs) -> Any:
    """Process-ledger instrumented ``jax.jit`` (decorator or wrap call):

        step = device.jit(step_fn, name="serve.step", donate_argnums=(0,))
    """
    return get_ledger().jit(fun, name=name, analysis=analysis,
                            **jit_kwargs)


def instrument(jitted: Any, name: Optional[str] = None,
               analysis: Optional[bool] = None) -> InstrumentedProgram:
    """Route an already-jitted callable through the process ledger."""
    return get_ledger().instrument(jitted, name=name, analysis=analysis)


def reset_for_tests() -> None:
    """Fresh singletons (unit tests share one process)."""
    global _ledger, _census
    with _singleton_lock:
        _ledger = None
        _census = None


# ---------------------------------------------------------------------------
# KV flush + cluster read side
# ---------------------------------------------------------------------------


def backend_initialized() -> bool:
    """True once this process has initialised a jax backend (asking is
    free; `jax.devices()` / `jax.live_arrays()` would initialise one —
    and on a TPU host take the chip)."""
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge.backends_are_initialized())
    except Exception:
        return False


def backend_identity() -> Dict[str, Any]:
    """Which device this process computes on, as jax reports it:
    platform / device_kind / device_count, all None while the process
    has not initialised a backend."""
    if not backend_initialized():
        return {"platform": None, "device_kind": None, "device_count": None}
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def device_snapshot() -> Dict[str, Any]:
    """The local process's full device-observability snapshot — with how
    the process came up (`boot`) and when it stood still (`stalls`), so
    that both ship wherever the ledger ships."""
    from ray_tpu._private import common

    return {
        "ts": time.time(),
        **backend_identity(),
        "ledger": get_ledger().snapshot(),
        "memory": get_census().census(),
        "boot": dict(common.BOOT),
        "stalls": common.stalls(),
    }


def flush_device_snapshot(interval_s: float = 2.0,
                          force: bool = False) -> bool:
    """Ship the device snapshot to control-plane KV ns ``_device``
    (rate-limited, never raises — same contract as PR-4 telemetry's
    ``flush_snapshot``)."""
    led = get_ledger()
    now = time.monotonic()
    if not force and interval_s > 0 and \
            now - led._last_flush < interval_s:
        return False
    try:
        from ray_tpu._private import core as core_mod

        from .recorder import _kick_reattach

        core = core_mod._current_core
        if core is None or getattr(core, "_shutdown", False):
            return False
        led._last_flush = now
        cli = core.control
        if getattr(cli, "closed", False):
            _kick_reattach(core, cli)
            return False
        snap = device_snapshot()
        snap["worker_id"] = core.worker_id
        try:
            cli.call("kv_put", {
                "ns": DEVICE_NS,
                "key": f"{DEVICE_KEY_PREFIX}{core.worker_id}",
                "val": pickle.dumps(snap),
            }, timeout=5.0)
        except Exception:
            _kick_reattach(core, cli)
            return False
        return True
    except Exception:
        return False


def collect_device_stats(control_client) -> Dict[str, Any]:
    """Cluster-wide merge of every worker's ``_device`` snapshot — the
    shared read side for the dashboard route, the CLI and the state
    API."""
    workers: Dict[str, Dict[str, Any]] = {}
    try:
        keys = control_client.call(
            "kv_keys", {"ns": DEVICE_NS, "prefix": DEVICE_KEY_PREFIX},
            timeout=10.0) or []
        for k in keys:
            raw = control_client.call(
                "kv_get", {"ns": DEVICE_NS, "key": k}, timeout=10.0)
            if raw is None:
                continue
            try:
                snap = pickle.loads(raw)
            except Exception:
                continue
            wid = snap.get("worker_id") or k[len(DEVICE_KEY_PREFIX):]
            workers[wid] = snap
    except Exception:
        pass

    programs: Dict[str, Dict[str, Any]] = {}
    advisories: List[Dict[str, Any]] = []
    total_compiles = 0
    total_recompiles = 0
    live_bytes = 0
    for wid, snap in workers.items():
        led = snap.get("ledger") or {}
        total_compiles += int(led.get("total_compiles", 0))
        total_recompiles += int(led.get("total_recompiles", 0))
        for name, st in (led.get("programs") or {}).items():
            agg = programs.setdefault(name, {
                "compiles": 0, "recompiles": 0, "storm_episodes": 0,
                "workers": 0, "last_cause": None, "last_compile_ts": 0.0})
            agg["compiles"] += int(st.get("compiles", 0))
            agg["recompiles"] += int(st.get("recompiles", 0))
            agg["storm_episodes"] += int(st.get("storm_episodes", 0))
            agg["workers"] += 1
            if st.get("last_compile_ts", 0.0) >= agg["last_compile_ts"]:
                agg["last_compile_ts"] = st.get("last_compile_ts", 0.0)
                if st.get("last_cause") is not None:
                    agg["last_cause"] = st.get("last_cause")
        for adv in (led.get("advisories") or []):
            advisories.append({**adv, "worker_id": wid})
        mem = snap.get("memory") or {}
        live_bytes += int((mem.get("live") or {}).get("total_bytes", 0))
    advisories.sort(key=lambda a: a.get("ts", 0.0))
    return {
        "workers": workers,
        "programs": programs,
        "advisories": advisories,
        "total_compiles": total_compiles,
        "total_recompiles": total_recompiles,
        "live_bytes": live_bytes,
    }


#: a process's boot parts in wall order (`_private/common.BOOT`)
_BOOT_PARTS = ("cluster_start", "start", "connect", "register", "pool",
               "actor_wait", "actor_init")


def _slice(name: str, cat: str, pid: int, tid: int, t_wall: float,
           dur_s: float, args: Dict[str, Any]) -> Dict[str, Any]:
    return {"name": name, "ph": "X", "pid": pid, "tid": tid, "cat": cat,
            "ts": t_wall * 1e6, "dur": max(1.0, dur_s * 1e6), "args": args}


def compile_trace_events(workers: Dict[str, Dict[str, Any]],
                         pid: int = 90) -> List[Dict[str, Any]]:
    """Chrome-trace complete slices, one thread row per worker, on the
    wall clock: the process's boot parts, every recorded compile — the
    whole compiling call from where it was entered, its parts as args —
    and the stalls its tickers noted (``timeline.chrome_trace`` appends
    these)."""
    events: List[Dict[str, Any]] = []
    for tid, (wid, snap) in enumerate(sorted(workers.items())):
        slices: List[Dict[str, Any]] = []
        boot = snap.get("boot") or {}
        for part in _BOOT_PARTS:
            if f"{part}_s" in boot:
                slices.append(_slice(
                    f"boot {part}", "boot", pid, tid,
                    boot.get(f"{part}_wall", 0.0), boot[f"{part}_s"],
                    {"seconds": boot[f"{part}_s"]}))
        for rec in (snap.get("ledger") or {}).get("records", []):
            dur_s = rec.get("call_s") or rec.get("compile_s") or 0.0
            ev = _slice(
                f"compile {rec.get('program')}", "compile", pid, tid,
                rec.get("t_call_wall", rec.get("ts", 0.0) - dur_s), dur_s,
                {"program": rec.get("program"),
                 "nth_compile": rec.get("nth_compile"),
                 "durations": rec.get("durations"),
                 "run_s": rec.get("run_s"),
                 "cache_hit": rec.get("cache_hit"),
                 "cache_load_s": rec.get("cache_load_s")})
            cause = rec.get("cause")
            if cause and cause.get("arg") is not None:
                ev["args"]["cause"] = (f"{cause['arg']}: {cause['kind']} "
                                       f"{cause['old']} -> {cause['new']}")
            slices.append(ev)
        for st in snap.get("stalls") or []:
            slices.append(_slice(
                "stood still", "stall", pid, tid, st.get("t_wall", 0.0),
                st.get("late_s", 0.0), {"by": st.get("by")}))
        if slices:
            # meta rows only for workers that have something to draw, so
            # an empty (e.g. trial-filtered) timeline stays truly empty
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": f"xla-compile {wid[:12]}"}})
            events.extend(sorted(slices, key=lambda e: e["ts"]))
    if events:
        events.insert(0, {"name": "process_name", "ph": "M", "pid": pid,
                          "args": {"name": "xla compiles"}})
    return events
