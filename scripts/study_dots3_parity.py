"""How close the programs of `models/dots3.py` come to the plain reference
at the PUBLISHED widths, on the chip, and what moves the distance (PERF.md
section 6, PR 56): one prompt through the engine's own programs
(`check_dots3.replay_logits`: 512-row chunks over both pools, `serve.setrow`,
`serve.step`), the tokens it yields teacher-forced through the float32
reference (`check_dots3.served_gaps`), the three numbers of the cell's check
a variant.  No cluster: the engine is built in this process from the cell's
configuration file.

    python scripts/study_dots3_parity.py [--plen 6000] [--steps 16]
        [--only a,b] [--scales wq_b=0.5[,name=f]] [--rows n] [--heads n]
        [--timed] [seed]

`--rows` / `--heads` are the reference's block sizes (the traffic file's
`reference.rows` / `.heads` where not given); `--timed` waits for every
piece of the reference and reports the seconds of each (`pieces_s`).

Variants (a `reading` line each; chiprun_out/pr56/parity-<plen>-r<rows>h
<heads>.json):

  sound          the program and the reference as they are
  no_selection   BOTH sides attend every causal key in the full layers:
                 what bfloat16 costs without a selection to flip
  xla            the program's attention through the XLA bodies (no Pallas
                 kernel in a chunk or a step): the kernels against them
  <control>      any of `dots3_plain`'s controls by name (`--only`): the
                 sound program against a reference with that fault, as
                 `scripts/study_dots3_controls.py` runs it through the cell

`--scales` lays other factors over the configuration's `weights.scales`
(how the draw was shaped: a flat or a one-hot attention both hide what the
check is there to see).  `--toy` runs the rehearsal's sizes on the CPU.
"""
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TOY = "--toy" in sys.argv
TIMED = "--timed" in sys.argv
OUT = os.path.join(ROOT, "chiprun_out", "pr56")


def _option(name: str, default):
    if name not in sys.argv:
        return default
    at = sys.argv.index(name)
    value = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    return value


def say(**kw):
    print(json.dumps(kw), flush=True)


def reading(variant, conf, ek, seed, plen, steps, spec):
    import jax
    import numpy as np

    from benchmarks.drivers.replica_dots3 import shape_weights
    from benchmarks.lib.dots3cfg import model_config, reference_shape
    from benchmarks.reference import dots3_plain as plain
    from benchmarks.reference.check_dots3 import (compile_pieces,
                                                  replay_logits, served_gaps)
    from ray_tpu.models import deepseek_v3 as dm
    from ray_tpu.models import dots3 as m3
    from ray_tpu.serve._engine import ContinuousEngine

    A = importlib.import_module("ray_tpu.ops.attention")
    shape = reference_shape(conf)
    undo = []

    def patch(mod, name, value):
        undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    if variant == "no_selection":
        shape["control"] = "no_selection"
        patch(m3, "keep_top", lambda scores, visible, k: (
            visible, visible.sum(-1).astype("int32")))
    elif variant not in ("sound", "xla"):
        shape["control"] = variant
    elif variant == "xla":
        patch(dm, "latent_decode_uses_kernel", lambda rows, platform=None:
              False)
        patch(A, "streamed_attention_uses_kernel",
              lambda rows, platform=None: False)
    t0 = time.time()
    cfg = model_config(conf)
    params = shape_weights(m3.init(jax.random.PRNGKey(seed % 2 ** 31), cfg),
                           conf["weights"], seed)
    eng = ContinuousEngine(m3, cfg, params, **ek)
    del params
    try:
        prompt = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, plen).tolist()
        got, toks = replay_logits(eng, prompt, steps, steps)
        jax.block_until_ready(got)
        t1 = time.time()
        built, spent = None, {}
        if TIMED:       # every piece, and the draw, waited for and timed
            def timed(name, f):
                def call(*a):
                    t = time.time()
                    out = jax.block_until_ready(f(*a))
                    spent[name] = spent.get(name, 0.0) + time.time() - t
                    return out
                return call

            run, took = compile_pieces(shape, spec, steps)
            built = {n: timed(n, f) for n, f in run.items()}, took
            patch(plain, "draw_leaf", timed("draw_leaf", plain.draw_leaf))
        per = served_gaps(seed, shape, conf["weights"],
                          [{"rid": 0, "tokens": prompt, "served": toks}],
                          spec, steps, replay=(0, 0, got), built=built)[0]
    finally:
        eng.stop()
        for mod, name, value in undo:
            setattr(mod, name, value)
    say(phase="reading", variant=variant, scales=conf["weights"]["scales"],
        plen=plen, steps=steps, argmax_share=per["n_argmax"] / per["n"],
        worst_gap=per["max_gap"], logit_rel_rms=per["logit_rel_rms"],
        logit_max_abs=per["logit_max_abs"],
        median_top2_gap=per["median_top2_gap"], program_s=t1 - t0,
        reference_s=per["seconds"], programs_s=per["programs_s"],
        rows=spec["rows"], heads=spec["heads"],
        pieces_s=spent or None)
    return per


def main():
    plen, steps = int(_option("--plen", 6000)), int(_option("--steps", 16))
    only = _option("--only", None)
    scales = _option("--scales", None)
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    seed = int(args[0]) if args else 3000000019
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "dots3-note-prev-l5-e32.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "open-sparsectx.json")) as f:
        spec = dict(json.load(f)["reference"])
    ek = dict(conf["serve"]["engine_kwargs"])
    if TOY:
        os.environ["JAX_PLATFORMS"] = "cpu"
        with open(os.path.join(ROOT, "benchmarks", "tests",
                               "rehearsal_sparsectx.json")) as f:
            toy = json.load(f)
        conf.update(toy["config"])
        conf["serve"] = {"max_seq": toy["engine_kwargs"]["max_total"]}
        ek, spec = dict(toy["engine_kwargs"]), dict(
            toy["traffic"]["reference"])
        plen, steps = 60, 8
    spec.update(rows=int(_option("--rows", spec["rows"])),
                heads=int(_option("--heads", spec["heads"])))
    rows = int(spec["rows"])
    spec["max_context"] = -(-(plen + steps) // rows) * rows
    if scales:
        conf["weights"] = dict(conf["weights"], scales={
            **conf["weights"].get("scales", {}),
            **{k: float(v) for k, v in (kv.split("=")
                                        for kv in scales.split(","))}})
    import jax

    say(phase="device", platform=jax.devices()[0].platform,
        kind=jax.devices()[0].device_kind)
    out = []
    for variant in (only.split(",") if only
                    else ("sound", "no_selection", "xla")):
        per = reading(variant, conf, ek, seed, plen, steps, spec)
        out.append(dict(per, variant=variant,
                        scales=conf["weights"]["scales"]))
    os.makedirs(OUT, exist_ok=True)
    name = f"parity-{plen}-r{spec['rows']}h{spec['heads']}" + (
        "-" + scales.replace("=", "").replace(",", "-") if scales
        else "") + ".json"
    with open(os.path.join(OUT, name), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
