"""The replica body of the serve cells: LLMServer's own implementation,
subclassed here only for what has to run in the process that holds the
chip — profiler start/stop, replica-side time stamps around
`stream_tokens`, and a snapshot of the engine's phase ring, the compile
ledger and the device.  Requests still go handle -> router -> replica ->
engine, untouched.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.serve.llm import _LLMServerImpl


def make_loader(conf: Dict, seed: int, overrides: Dict):
    """params_loader for the replica: the configuration's GPTConfig and
    weights made ON THE DEVICE from the seed in one jitted call."""

    def loader():
        import functools

        import jax

        from benchmarks.lib.modelcfg import gpt_config
        from ray_tpu.models import gpt

        cfg = gpt_config(conf, **overrides)
        params = jax.jit(functools.partial(gpt.init, cfg=cfg))(
            jax.random.PRNGKey(seed % (2 ** 31)))
        jax.block_until_ready(params)
        return cfg, params

    return loader


class BenchLLMServer(_LLMServerImpl):
    def __init__(self, *args, **kwargs):
        t0 = time.time()
        super().__init__(*args, **kwargs)
        self._bench_lock = threading.Lock()
        self._bench_stamps: Dict[int, List[float]] = {}   # guarded-by: _bench_lock
        self._bench_tracer: Optional[threading.Thread] = None
        self._bench_init = (t0, time.time())

    def stream_tokens(self, tokens, max_new_tokens: int = 16,
                      temperature: float = 0.0, seed: int = 0,
                      top_k=None, eos_id=None, key_offset: int = 0,
                      bench_id: Optional[int] = None):
        t_in = time.time()
        first = True
        for tok in super().stream_tokens(tokens, max_new_tokens, temperature,
                                         seed, top_k, eos_id, key_offset):
            if first and bench_id is not None:
                first = False
                with self._bench_lock:
                    self._bench_stamps[bench_id] = [t_in, time.time()]
            yield tok

    # -- what only the chip's process can do --------------------------------

    def bench_warm_keys(self, lengths: List[int]):
        """The engine splits a request's sampling key into `max_new_tokens`
        keys at admission (`_admit_one`): one tiny program per distinct
        length, compiled — or loaded from the cache, 0.1 s each — when that
        length is first seen.  Seen here first, they are in this process's
        jit cache before the window opens.  A generator, so that it runs on
        a stream thread and the replica's event loop keeps answering the
        controller's health probe."""
        import jax
        import numpy as np

        for n in sorted(set(lengths)):
            np.asarray(jax.random.split(jax.random.PRNGKey(0), int(n)))
        yield len(set(lengths))

    def bench_trace_start(self, trace_dir: str) -> bool:
        from benchmarks.drivers._common import start_trace

        start_trace(trace_dir)
        return True

    def bench_trace_stop(self) -> bool:
        """Returns at once; the profiler serialises its trace on a thread
        of its own so that the replica's event loop keeps serving."""
        from benchmarks.drivers._common import stop_trace

        self._bench_tracer = threading.Thread(
            target=stop_trace, name="bench-trace-stop", daemon=True)
        self._bench_tracer.start()
        return True

    def bench_snapshot(self, take_stamps: bool = False) -> Dict[str, Any]:
        from benchmarks.drivers._common import memory_peak_bytes
        from ray_tpu.telemetry import device as devtel

        tracer = self._bench_tracer
        if take_stamps and tracer is not None:
            tracer.join(timeout=120)
        led = devtel.get_ledger()
        snap = led.snapshot()
        eng = self._engine
        with self._bench_lock:
            stamps = dict(self._bench_stamps) if take_stamps else {}
        ident = devtel.backend_identity()
        return {
            "wall": time.time(), "perf": time.perf_counter(),
            "identity": {"platform": ident["platform"],
                         "kind": ident["device_kind"],
                         "count": ident["device_count"]},
            "memory_peak_bytes": memory_peak_bytes(),
            "counts": led.counts(),
            "compile_s": {n: p["durations_total_s"]
                          for n, p in snap["programs"].items()},
            "persistent_cache": snap["persistent_cache"],
            "init_wall": list(self._bench_init),
            "ring": eng.phase_ring() if eng is not None else [],
            "engine": eng.engine_stats() if eng is not None else {},
            "max_slots": eng.max_slots if eng is not None else 0,
            "stamps": stamps,
        }

    def bench_reference(self, sample: List[Dict], pad: int):
        """Outside the timed window: the sampled requests' served tokens
        against the plain reference on the weights this replica serves.
        A generator of one item, for `bench_warm_keys`'s reason: the
        reference's first compile in a checkout takes longer than the
        controller waits for a health probe."""
        from benchmarks.reference.check import served_gaps

        t0 = time.time()
        per = served_gaps(self._params, sample, pad)
        n = sum(p["n"] for p in per)
        yield {"worst_gap": max([p["max_gap"] for p in per] or [0.0]),
                "argmax_share": sum(p["n_argmax"] for p in per) / max(n, 1),
                "tokens_checked": n, "checked": len(per), "per_request": per,
                "seconds": time.time() - t0}
