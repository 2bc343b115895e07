#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of GPT-2-small with seeded random weights:

  (a) train   parallel.make_mesh -> models.training.make_train_step ->
              shard_batch; B=16, S=512, bf16, remat "dots"; one warm-up
              step, then timed steps each ended by block_until_ready.
  (a2) cache  a second process lowers and compiles the same train.step
              and must find it in the persistent compile cache.
  (b) serve   ray_tpu.init -> serve.run(LLMServer(TPU: 1)) with the
              default paged engine; requests over the request/response
              route and stream_tokens; then, the chip released, a child
              replays them through gpt.generate on the same weights.

With `--chips 4` it runs only what exists across chips, and what each is
compared with:

  (c) mesh    the same step on make_mesh(dp=2, fsdp=2) at global batch
              32 against the one-chip step on device 0; then fp32 vs
              int8 mesh_allreduce(impl="auto") on the four chips.
  (d) replicas  four TPU:1 replicas behind serve/_router, each on its
              own chip, compared with one replica's tokens.

One process owns a chip at a time: this parent never initialises a jax
backend (it checks that at its end), each phase is a process of its own,
run one after the other, and the first failing check ends the run with a
non-zero exit.  The last line of stdout is the result object; earlier
lines are per-phase JSON.  Numbers here are a smoke run's, not a
benchmark's.  `--rehearse` runs the same control flow at a toy size on the
CPU (prints `"platform": "cpu"`, never the result line, exits 2).

Agreement rule for served vs reference greedy tokens (bf16 argmax over
random weights can tie): exact match, or — at the FIRST differing position
only — the reference's top-2 logit margin there is under EPS_MARGIN and
the served token is one of those two.  Anything else fails.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
EPS_MARGIN = 0.125        # 8 bf16 ulps at logit magnitude 2..4
LOSS0_TOL = 0.5           # |first loss - ln(vocab)| for seeded random init
ATTN_FWD_TOL = 2e-2       # flash vs mha_reference, bf16 in, f32 compare
ATTN_GRAD_TOL = 6e-2
LOSS_MATCH_TOL = 3e-2     # (c) four-chip vs one-chip loss, first steps


class CheckFailed(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise CheckFailed(what)


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(rehearse: bool) -> dict:
    if rehearse:
        return dict(preset="nano", train_seq=128,
                    per_chip_batch=4, steps=3, max_seq=256,
                    attn_shape=(2, 4, 128, 16), max_new=8,
                    attn_impl="pallas_interpret", allreduce_n=1 << 14)
    return dict(preset="gpt2_small", train_seq=512,
                per_chip_batch=16, steps=5, max_seq=1024,
                attn_shape=(2, 4, 256, 64), max_new=24,
                attn_impl="pallas", allreduce_n=1 << 22)


def device_fields():
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d)}


def require_tpu(dev: dict, rehearse: bool):
    check(rehearse or dev["platform"] == "tpu",
          f"jax found no TPU: platform is {dev['platform']!r}")


def cache_counts() -> dict:
    """Persistent-compile-cache hits and misses of this process, as the
    compilation ledger counts them (its listener is installed with the
    first instrumented program)."""
    from ray_tpu.telemetry import device as devtel

    return devtel.get_ledger().snapshot()["persistent_cache"]


# ---------------------------------------------------------------------------
# (a) train, (a2) cache, (c) mesh
# ---------------------------------------------------------------------------


def _train_setup(sz: dict, seed: int, mesh, batch_size: int):
    import jax
    import numpy as np

    from ray_tpu.models import gpt
    from ray_tpu.models.training import make_train_step, shard_batch

    cfg = getattr(gpt.GPTConfig, sz["preset"])(
        max_seq=sz["train_seq"], remat_policy="dots")
    init_fn, step_fn = make_train_step(cfg, mesh)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch_size, sz["train_seq"] + 1)).astype(np.int32)
    batch = shard_batch({"inputs": toks[:, :-1], "targets": toks[:, 1:]},
                        mesh)
    return cfg, init_fn, step_fn, batch, jax.random.PRNGKey(seed)


def _run_steps(state, step_fn, batch, n_steps: int):
    """(losses, warm-up seconds, per-step seconds): one warm-up step
    (compile included), then n timed steps on the SAME batch."""
    import jax

    jax.block_until_ready(state)
    t0 = time.perf_counter()
    state, m = step_fn(state, batch)
    jax.block_until_ready(m)
    warm_s = time.perf_counter() - t0
    losses, secs = [float(m["loss"])], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        state, m = step_fn(state, batch)
        jax.block_until_ready(m)
        secs.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    return losses, warm_s, secs


def _kernel_in(text: str, rehearse: bool) -> bool:
    # on the chip the Pallas kernel is a Mosaic custom call; interpreted
    # (the CPU rehearsal) it leaves no such call to find
    return rehearse or "tpu_custom_call" in text


def _check_attention_vs_reference(sz: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import attention, mha_reference

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (jax.random.normal(kk, sz["attn_shape"], jnp.bfloat16)
                  for kk in ks)

    def run(fn):
        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32)
                    * g.astype(jnp.float32)).sum()
        out = fn(q, k, v)
        return (out, *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    got = run(lambda q, k, v: attention(q, k, v, causal=True,
                                        impl=sz["attn_impl"]))
    ref = run(lambda q, k, v: mha_reference(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True))
    errs = [float(np.max(np.abs(np.asarray(a, np.float32)
                                - np.asarray(b, np.float32))))
            for a, b in zip(got, ref)]
    check(all(math.isfinite(e) for e in errs), f"attention not finite: {errs}")
    check(errs[0] < ATTN_FWD_TOL,
          f"flash fwd vs mha_reference: max abs err {errs[0]} >= "
          f"{ATTN_FWD_TOL}")
    check(max(errs[1:]) < ATTN_GRAD_TOL,
          f"flash grads vs mha_reference: max abs err {errs[1:]} >= "
          f"{ATTN_GRAD_TOL}")
    return {"attn_shape": list(sz["attn_shape"]), "attn_fwd_err": errs[0],
            "attn_grad_err": errs[1:]}


def _check_stochastic_rounding(seed: int) -> dict:
    """stochastic=1 is a user setting of the int8 collectives, and on a
    TPU it is the Pallas kernel with the on-core PRNG (which no
    interpreter emulates): every value floor(y) or floor(y)+1, about
    half of the mid-fraction ones up, and the mean over draws unbiased."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.quantize import dequantize_blockwise, quantize_blockwise

    x = np.linspace(-1.0, 1.0, 8192, dtype=np.float32)
    y = x * 127.0                      # every block's absmax is ~1
    frac = y - np.floor(y)
    mid = (frac > 0.25) & (frac < 0.75)
    backs, up_share = [], []
    for s in range(16):
        q, sc = quantize_blockwise(jnp.asarray(x), 256, stochastic=True,
                                   seed=seed + s)
        backs.append(np.asarray(dequantize_blockwise(
            q, sc, x.shape, jnp.float32, 256)))
        err = np.max(np.abs(backs[-1] - x))
        check(err <= 1.01 / 127, f"stochastic quantize moved a value by "
                                 f"{err * 127:.3f} steps (more than one)")
        up_share.append(float(np.mean((backs[-1] > x)[mid])))
    # the mean of 16 draws sits within ~0.125 steps (one sigma) of x at
    # each element when the draws are fair; round-to-nearest would sit
    # at up to 0.5 everywhere off the grid
    bias = float(np.mean(np.abs(np.mean(backs, axis=0) - x)) * 127)
    check(all(0.3 < u < 0.7 for u in up_share),
          f"stochastic rounding is lopsided: share rounded up {up_share}")
    check(bias < 0.2, f"stochastic rounding is biased: the mean of 16 "
                      f"draws is off by {bias:.3f} steps on average")
    return {"stochastic_up_share": up_share[:4],
            "stochastic_bias_steps": bias}


def phase_train(args, sz: dict):
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.telemetry import device as devtel

    dev = device_fields()
    require_tpu(dev, args.rehearse)
    mesh = make_mesh()
    B = sz["per_chip_batch"]
    cfg, init_fn, step_fn, batch, key = _train_setup(sz, args.seed, mesh, B)
    state = init_fn(key)
    check(_kernel_in(step_fn.lower(state, batch).as_text(), args.rehearse),
          "train.step was lowered without the Pallas attention kernel "
          "(no tpu_custom_call): attention() took the XLA branch")
    losses, warm_s, secs = _run_steps(state, step_fn, batch, sz["steps"])
    ln_v = math.log(cfg.vocab_size)
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - ln_v) < LOSS0_TOL,
          f"first loss {losses[0]:.4f} not within {LOSS0_TOL} of "
          f"ln(vocab) = {ln_v:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the repeated batch: {losses}")
    attn = {**_check_attention_vs_reference(sz, args.seed),
            **_check_stochastic_rounding(args.seed)}
    med = sorted(secs)[len(secs) // 2]
    prog = devtel.get_ledger().snapshot()["programs"]["train.step"]
    emit("train", **dev, model=sz["preset"], batch=B, seq=sz["train_seq"],
         kernel_in_lowered_step=True, warmup_step_s=warm_s,
         compile_s=prog["durations_total_s"], step_s=secs,
         tokens_per_step=B * sz["train_seq"],
         smoke_tokens_per_s=B * sz["train_seq"] / med,
         losses=losses, ln_vocab=ln_v, **attn,
         persistent_cache=cache_counts(),
         cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR"))


def phase_cache(args, sz: dict):
    """A second process needs train.step: it must come from the cache."""
    import jax

    from ray_tpu.parallel.mesh import make_mesh

    dev = device_fields()
    require_tpu(dev, args.rehearse)
    mesh = make_mesh()
    _, init_fn, step_fn, batch, key = _train_setup(
        sz, args.seed, mesh, sz["per_chip_batch"])
    state = init_fn(key)
    jax.block_until_ready(state)
    hits0 = cache_counts()["hits"]
    t0 = time.perf_counter()
    step_fn.lower(state, batch).compile()
    dt = time.perf_counter() - t0
    check(cache_counts()["hits"] > hits0,
          f"train.step was compiled again ({dt:.1f}s) instead of being "
          f"found in {os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    emit("cache", **dev, program="train.step", lower_and_load_s=dt,
         persistent_cache=cache_counts())


def phase_mesh(args, sz: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.collective import xla_group
    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.sharding import tree_shardings

    dev = device_fields()
    require_tpu(dev, args.rehearse)
    check(dev["device_count"] == 4, f"(c) needs 4 devices, jax sees "
                                    f"{dev['device_count']}")
    B = 2 * sz["per_chip_batch"]
    # what it is compared with, first: the same global batch and seed on
    # device 0 alone
    one = make_mesh(devices=jax.devices()[:1])
    _, init1, step1, batch1, key = _train_setup(sz, args.seed, one, B)
    losses1, warm1, secs1 = _run_steps(init1(key), step1, batch1, 2)

    mesh = make_mesh(dp=2, fsdp=2)
    cfg, init4, step4, batch4, key = _train_setup(sz, args.seed, mesh, B)
    state = init4(key)
    t0 = time.perf_counter()
    text = step4.lower(state, batch4).compile().as_text()
    compile4_s = time.perf_counter() - t0
    check(_kernel_in(text, args.rehearse),
          "the compiled 4-chip train.step holds no tpu_custom_call: the "
          "Pallas kernel is not in the program")
    want = tree_shardings(gpt.logical_axes(cfg), mesh)
    whole = []
    for (path, leaf), w in zip(
            jax.tree_util.tree_leaves_with_path(state["params"]),
            jax.tree.leaves(want)):
        if not leaf.sharding.is_equivalent_to(w, leaf.ndim):
            whole.append(jax.tree_util.keystr(path))
        elif any(a is not None for a in w.spec) and \
                leaf.addressable_shards[0].data.size == leaf.size:
            whole.append(jax.tree_util.keystr(path) + " (whole on dev 0)")
    check(not whole, f"parameters not sharded as the rules say: {whole}")
    losses4, warm4, secs4 = _run_steps(state, step4, batch4, 2)
    diffs = [abs(a - b) for a, b in zip(losses1, losses4)]
    check(max(diffs) < LOSS_MATCH_TOL,
          f"4-chip losses {losses4} differ from one-chip {losses1} by "
          f"more than {LOSS_MATCH_TOL}")

    # fp32 vs int8 allreduce on the four chips; impl="auto" picks the
    # fused Pallas reduce-scatter on a TPU
    ax = make_mesh(dp=4)
    n = sz["allreduce_n"]
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(args.seed), (4, n), jnp.float32),
        NamedSharding(ax, P("dp")))
    ref = np.asarray(xla_group.mesh_allreduce(x, ax, "dp"))
    q_text = jax.jit(lambda a: xla_group.mesh_allreduce(
        a, ax, "dp", compression="int8")).lower(x).compile().as_text()
    # the fused reduce-scatter is the one Mosaic kernel here that talks
    # to other chips (remote DMA); the staged kernels do not
    check(args.rehearse or '"has_communication":true' in q_text,
          "int8 mesh_allreduce(impl='auto') compiled without the fused "
          "reduce-scatter kernel (no Mosaic call with communication)")
    got = np.asarray(xla_group.mesh_allreduce(x, ax, "dp",
                                              compression="int8"))
    # the bound of tests/test_collective_compression.py: each of the 4
    # contributions rounds once at absmax/127 per block, the sum once more
    rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    check(np.all(np.isfinite(got)) and rel < 0.05,
          f"int8 allreduce off by {rel:.4f} of the fp32 result's max")
    emit("mesh", **dev, mesh="dp=2,fsdp=2", global_batch=B,
         kernel_in_compiled_step=True, losses_one_chip=losses1,
         losses_four_chips=losses4, max_loss_diff=max(diffs),
         lower_and_compile_s_four_chips=compile4_s,
         warmup_step_s={"one_chip": warm1, "four_chips": warm4},
         step_s={"one_chip": secs1, "four_chips": secs4},
         int8_allreduce_rel_err=rel, allreduce_elems_per_chip=n)


# ---------------------------------------------------------------------------
# (b) serve, (d) replicas — the cluster driver never touches a backend
# ---------------------------------------------------------------------------


def _requests(sz: dict, seed: int):
    """Prompts landing in three prefill buckets (32-token steps), the
    second sharing its first 96 tokens (six 16-token pages) with the
    first, so that both being in flight together shares pages."""
    import numpy as np

    rng = np.random.default_rng(seed)
    vocab = 256 if sz["preset"] == "nano" else 50000
    long_ = rng.integers(0, vocab, 100).tolist()
    return [
        {"name": "long", "tokens": long_},
        {"name": "shared", "tokens": long_[:96]
         + rng.integers(0, vocab, 20).tolist()},
        {"name": "short", "tokens": rng.integers(0, vocab, 40).tolist()},
    ]


def _replica_snapshots(t_after: float, n: int, timeout_s: float = 30.0):
    """Device snapshots of the n workers that run serve.step, taken after
    t_after (the replicas flush on the controller's metrics probe)."""
    from ray_tpu.util.state import api as state

    deadline = time.time() + timeout_s
    while True:
        snaps = {wid: s for wid, s in
                 state.device_stats()["workers"].items()
                 if "serve.step" in s["ledger"]["programs"]
                 and s["ts"] > t_after}
        if len(snaps) >= n or time.time() > deadline:
            return snaps
        time.sleep(0.5)


def _compiles(snaps: dict) -> dict:
    return {wid: {name: (p["compiles"], p["recompiles"])
                  for name, p in s["ledger"]["programs"].items()}
            for wid, s in snaps.items()}


def _serve(args, sz: dict, n_replicas: int):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.util.state import api as state

    if args.rehearse:
        os.environ["RAY_TPU_NUM_CHIPS"] = str(n_replicas)
    phase = "serve" if n_replicas == 1 else "replicas"
    ray_tpu.init()
    sched = state.control_stats()["control"]["scheduler"]
    try:
        # a TPU task first: its worker opens the chip and is then pooled
        # holding it — the replica has to get that process or its chip
        @ray_tpu.remote(resources={"TPU": 1})
        def first_holder():
            import jax

            d = jax.devices()[0]
            return {"pid": os.getpid(), "platform": d.platform,
                    "chips": os.environ.get("TPU_VISIBLE_CHIPS")}

        holder = ray_tpu.get(first_holder.remote(), timeout=300)
        check(args.rehearse or holder["platform"] == "tpu",
              f"a TPU:1 task computed on {holder['platform']!r}")

        h = serve.run(
            LLMServer(num_replicas=n_replicas,
                      ray_actor_options={"resources": {"TPU": 1}}).bind(
                preset=sz["preset"], max_seq=sz["max_seq"]),
            name="smoke", route_prefix=None, blocking_timeout_s=600)
        reqs = _requests(sz, args.seed)
        n_new = sz["max_new"]

        def body(r):
            return {"tokens": r["tokens"], "max_new_tokens": n_new}

        # warm-up: the step program and one prefill bucket on every
        # replica (p2c spreads concurrent requests)
        t0 = time.perf_counter()
        warm = [h.remote(body(reqs[2])) for _ in range(4 * n_replicas)]
        [f.result(timeout_s=900) for f in warm]
        warm_s = time.perf_counter() - t0
        before = _replica_snapshots(time.time(), n_replicas, 60.0)
        check(len(before) == n_replicas,
              f"{len(before)} of {n_replicas} replicas reported a device "
              f"snapshot with serve.step after warm-up")
        ids0 = sorted(r.replica_id for r in
                      serve.status()["smoke"].deployments["LLMServer"].replicas)

        # long + shared in flight together, then the short one; then the
        # same greedy requests again over the streaming route
        t0 = time.perf_counter()
        out = {}
        for rnd in range(2 * n_replicas):
            futs = [(r["name"], h.remote(body(r))) for r in reqs]
            for name, f in futs:
                got = f.result(timeout_s=900)["completion"]
                check(len(got) == n_new,
                      f"request {name!r} returned {len(got)} tokens")
                check(out.setdefault(name, got) == got,
                      f"greedy request {name!r} gave different tokens on "
                      f"a second asking: {out[name]} vs {got}")
        for r in reqs:
            streamed = list(h.options(stream=True).stream_tokens.remote(
                r["tokens"], n_new))
            check(streamed == out[r["name"]],
                  f"stream_tokens({r['name']!r}) = {streamed} differs "
                  f"from the request/response route's {out[r['name']]}")
        serve_s = time.perf_counter() - t0

        after = _replica_snapshots(time.time(), n_replicas)
        check(set(after) == set(before),
              f"replica workers changed during the run: {sorted(before)} "
              f"-> {sorted(after)}")
        for wid, s in after.items():
            check(args.rehearse or s["platform"] == "tpu",
                  f"replica worker {wid} computed on {s['platform']!r}")
        st = serve.status()["smoke"].deployments["LLMServer"]
        ids1 = sorted(r.replica_id for r in st.replicas)
        check(ids0 == ids1 and "restarted" not in st.message,
              f"a replica was restarted: {ids0} -> {ids1} ({st.message!r})")
        c0, c1 = _compiles(before), _compiles(after)
        recompiled = {
            f"{wid}:{name}": (c0[wid].get(name, (0, 0)), now)
            for wid, progs in c1.items() for name, now in progs.items()
            if now[1] != c0[wid].get(name, (0, 0))[1]}
        check(not recompiled,
              f"programs recompiled after warm-up: {recompiled}")
        table = ray_tpu.get(serve.api._get_controller().get_replica_table
                            .remote("smoke", "LLMServer"), timeout=30)
        engines = [r["engine"] or {} for r in table["replicas"]]
        shared = sum(e.get("shared_pages", 0) for e in engines)
        check(shared > 0, f"no prefix page was shared: {engines}")
        workers = {w["worker_id"]: w for w in state.list_workers()}
        chips = {wid: tuple(workers[wid]["chips"]) for wid in after}
        pids = {wid: workers[wid]["pid"] for wid in after}
        check(len({c for cs in chips.values() for c in cs})
              == n_replicas == len(chips),
              f"replicas do not each hold a chip of their own: {chips}")
        compile_s = {
            wid: {name: p["durations_total_s"]
                  for name, p in s["ledger"]["programs"].items()}
            for wid, s in after.items()}
        any_snap = next(iter(after.values()))
        emit(phase, platform=any_snap["platform"],
             device_kind=any_snap["device_kind"],
             device_count=any_snap["device_count"],
             replicas=n_replicas, scheduler=sched,
             first_tpu_worker={**holder, "reused_by_replica":
                               holder["pid"] in pids.values()},
             replica_chips={w: list(c) for w, c in chips.items()},
             warmup_s=warm_s, requests_s=serve_s,
             requests=len(reqs) * 2 * n_replicas + len(reqs),
             new_tokens_each=n_new, shared_pages=shared,
             prefills=sum(e.get("prefills", 0) for e in engines),
             replica_compile_s=compile_s,
             recompiles_after_warmup=0, replica_restarts=0,
             persistent_cache={wid: s["ledger"].get("persistent_cache")
                               for wid, s in after.items()},
             stall_threshold_s=10.0)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    _assert_no_backend(phase)
    # for the parent to hand to the serve_ref phase, not a record
    print("SERVED " + json.dumps(
        {r["name"]: {"tokens": r["tokens"], "served": out[r["name"]]}
         for r in reqs}), flush=True)


def phase_serve_ref(args, sz: dict):
    """gpt.generate on the weights LLMServer builds without a loader
    (gpt.init(PRNGKey(0), cfg)), against the served tokens on stdin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt

    from ray_tpu.telemetry import device as devtel

    served = json.loads(sys.stdin.read())
    dev = device_fields()
    require_tpu(dev, args.rehearse)
    cfg = getattr(gpt.GPTConfig, sz["preset"])(max_seq=sz["max_seq"])
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    n_new = sz["max_new"]
    gen = devtel.jit(lambda p, t: gpt.generate(p, cfg, t, n_new),
                     name="smoke.generate")
    outcome, t_compile = {}, None
    for name, r in served.items():
        prompt = np.asarray(r["tokens"], np.int32)[None]
        t0 = time.perf_counter()
        ref = np.asarray(gen(params, prompt))[0, prompt.shape[1]:].tolist()
        t_compile = t_compile or time.perf_counter() - t0
        got = r["served"]
        if got == ref:
            outcome[name] = "exact"
            continue
        i = next(j for j, (a, b) in enumerate(zip(got, ref)) if a != b)
        ctx = np.asarray(r["tokens"] + ref[:i], np.int32)[None]
        logits = np.asarray(gpt.apply(params, jnp.asarray(ctx), cfg)
                            [0, -1].astype(jnp.float32))
        top2 = np.argsort(logits)[-2:][::-1]
        margin = float(logits[top2[0]] - logits[top2[1]])
        check(margin < EPS_MARGIN and got[i] in top2.tolist(),
              f"served tokens for {name!r} leave gpt.generate at position "
              f"{i} ({got[i]} vs {ref[i]}) where the reference's top-2 "
              f"margin is {margin:.4f} (epsilon {EPS_MARGIN}): "
              f"{got} vs {ref}")
        outcome[name] = {"first_divergence": i, "top2_margin": margin}
    emit("serve_ref", **dev, agreement=outcome, eps_margin=EPS_MARGIN,
         generate_first_call_s=t_compile, persistent_cache=cache_counts())


def _assert_no_backend(who: str):
    from ray_tpu.telemetry.device import backend_initialized

    check(not backend_initialized(),
          f"the {who} process initialised a jax backend: it would hold "
          f"the chip its children need")


PHASES = {"train": phase_train, "cache": phase_cache, "mesh": phase_mesh,
          "serve": lambda args, sz: _serve(args, sz, 1),
          "replicas": lambda args, sz: _serve(args, sz, 4),
          "serve_ref": phase_serve_ref}


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def run_phase(name: str, args, stdin: str = "") -> list:
    """One phase in a process of its own; its stdout lines, echoed.  A
    phase that fails ends the run here."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    t0 = time.time()
    proc = subprocess.run(cmd, input=stdin, stdout=subprocess.PIPE,
                          text=True, cwd=ROOT)
    lines = proc.stdout.splitlines()
    for ln in lines:
        if not ln.startswith("SERVED "):
            print(ln, flush=True)
    if proc.returncode != 0:
        print(f"chip_smoke: phase {name!r} FAILED (exit {proc.returncode}, "
              f"{time.time() - t0:.0f}s)", file=sys.stderr, flush=True)
        sys.exit(1)
    return lines


def phase_record(lines: list, phase: str) -> dict:
    for ln in reversed(lines):
        if ln.startswith("{"):
            rec = json.loads(ln)
            if rec.get("phase") == phase:
                return rec
    print(f"chip_smoke: phase {phase!r} printed no record", file=sys.stderr)
    sys.exit(1)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU; prints no result line")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)

    if args.phase:
        try:
            PHASES[args.phase](args, sizes(args.rehearse))
        except CheckFailed as e:
            print(f"chip_smoke[{args.phase}]: CHECK FAILED: {e}",
                  file=sys.stderr, flush=True)
            sys.exit(1)
        return

    import ray_tpu  # noqa: F401  (nothing of the repo here -> fail now)

    # one compile cache for every process this run starts — the phase
    # children, the raylet and its workers: where the variable is set jax
    # reads it itself; where it is not, a fixed git-ignored directory of
    # the checkout goes into the environment before any child exists
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    recs = []
    for name in (("train", "cache", "serve") if args.chips == 1
                 else ("mesh", "replicas")):
        lines = run_phase(name, args)
        recs.append(phase_record(lines, name))
    # the cluster is down and its workers gone: the chip is free for the
    # reference the served tokens are held to
    served = next(ln for ln in lines if ln.startswith("SERVED "))[7:]
    recs.append(phase_record(run_phase("serve_ref", args, served),
                             "serve_ref"))

    try:
        _assert_no_backend("parent")
    except CheckFailed as e:
        print(f"chip_smoke: CHECK FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    if args.rehearse:
        print("chip_smoke: rehearsal passed on the cpu; a rehearsal prints "
              "no result line", file=sys.stderr)
        sys.exit(2)
    kinds = {(r["platform"], r["device_kind"]) for r in recs}
    if len(kinds) != 1 or next(iter(kinds))[0] != "tpu":
        print(f"chip_smoke: phases disagree on the device: {kinds}",
              file=sys.stderr)
        sys.exit(1)
    platform, kind = next(iter(kinds))
    count = recs[0]["device_count"]  # the process that saw every chip
    if count != args.chips:
        print(f"chip_smoke: jax reported {count} device(s), the run was "
              f"for {args.chips}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)


if __name__ == "__main__":
    main()
