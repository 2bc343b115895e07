#!/usr/bin/env python3
"""Compile a configuration's programs at full size for a DESCRIBED v5e (no
chip, no run) and print what the chip's compiler says each needs per device.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py gpt2-medium [--batch 16 --remat dots]
    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py gpt2-large  [--slots 32]

A compile that passes is not a chip run: the bytes go into the
configuration file as "rehearsed", never a time.
"""

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _mem(compiled):
    m = compiled.memory_analysis()
    return {"argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "peak_estimate_bytes": m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.temp_size_in_bytes}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--batch", type=int)
    ap.add_argument("--remat")
    ap.add_argument("--slots", type=int)
    ap.add_argument("--pages", type=int)
    ap.add_argument("--buckets", default="32,256")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P, \
        SingleDeviceSharding

    from benchmarks.lib.modelcfg import gpt_config
    from ray_tpu.models import gpt
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import make_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        conf = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if "train" in conf:
        tr = conf["train"]
        chips = int(np.prod(list(tr["mesh"].values())))
        mesh = make_mesh(devices=list(topo.devices)[:chips], **tr["mesh"])
        per = args.batch or tr["batch_per_chip"]
        remat = args.remat or tr["remat_policy"]
        S = conf["n_positions"]
        cfg = gpt_config(conf, max_seq=S, remat_policy=remat,
                         attention_impl="pallas")
        import optax

        tx = optax.adamw(3e-4, weight_decay=0.1)   # the builder's default
        _, step_fn = make_train_step(cfg, mesh, tx)

        def init_state(key):        # the state init_fn builds, as shapes
            params = gpt.init(key, cfg)
            return {"params": params, "opt_state": tx.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        bs = NamedSharding(mesh, P(("dp", "fsdp", "ep"), None))
        b = jax.ShapeDtypeStruct((per * chips, S), jnp.int32, sharding=bs)
        t0 = time.time()
        lowered = step_fn.lower(state, {"inputs": b, "targets": b})
        compiled = lowered.compile()
        text = compiled.as_text()
        print(json.dumps({
            "config": args.config, "program": "train.step", "chips": chips,
            "batch_per_chip": per, "seq": S, "remat_policy": remat,
            "compile_s": round(time.time() - t0, 1),
            "kernel_in_program": "tpu_custom_call" in text,
            "collectives": {k: text.count(k + "(") + text.count(k + "-start(")
                            for k in ("all-gather", "reduce-scatter",
                                      "all-reduce", "all-to-all",
                                      "collective-permute")},
            "per_device": _mem(compiled)}), flush=True)
    if "serve" in conf:
        ek = dict(conf["serve"]["engine_kwargs"])
        slots = args.slots or ek["max_slots"]
        ps, total = ek["page_size"], ek["max_total"]
        maxp = -(-total // ps)
        pages = args.pages or ek.get("num_pages") or 1 + slots * maxp
        cfg = gpt_config(conf, max_seq=conf["serve"]["max_seq"])
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        # what the engine hands its programs: the serve view of the tree
        # (weights in the compute dtype) and the arena as the model lays
        # it out
        def shapes(tree):
            return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

        params = shapes(jax.eval_shape(
            lambda k: gpt.serve_view(gpt.init(k, cfg), cfg),
            jax.random.PRNGKey(0)))
        cache = shapes(jax.eval_shape(functools.partial(
            gpt.init_paged_cache, cfg, pages, ps)))
        arena_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                          for a in jax.tree.leaves(cache))
        from ray_tpu.serve._engine import ContinuousEngine

        eng = ContinuousEngine.__new__(ContinuousEngine)
        eng._jax, eng._gpt, eng._cfg, eng._fns = jax, gpt, cfg, {}
        V = cfg.vocab_size
        t0 = time.time()
        step = eng._fn("step")
        c = step.lower(
            params, cache, sds((slots, V), jnp.float32),
            sds((slots, 2), jnp.uint32), sds((slots,), jnp.float32),
            sds((slots,), jnp.int32), sds((slots, maxp), jnp.int32),
            sds((slots,), jnp.int32)).compile()
        print(json.dumps({"config": args.config, "program": "serve.step",
                          "max_slots": slots, "num_pages": pages,
                          "arena_bytes": arena_bytes,
                          "compile_s": round(time.time() - t0, 1),
                          "per_device": _mem(c)}), flush=True)
        for T in [int(x) for x in args.buckets.split(",")]:
            t0 = time.time()
            c = eng._fn(("prefill", T)).lower(
                params, cache, sds((T,), jnp.int32), sds((maxp,), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32)).compile()
            print(json.dumps({"config": args.config,
                              "program": f"serve.prefill:{T}",
                              "compile_s": round(time.time() - t0, 1),
                              "per_device": _mem(c)}), flush=True)


if __name__ == "__main__":
    main()
