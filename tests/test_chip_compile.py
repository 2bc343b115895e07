"""Compile-only rehearsal for the TPU: the main path's programs, at their
real widths, handed to the chip's compiler for a *described* v5e 2x2 —
no chip attached, nothing runs, so nothing here is a chip result.  What
the compiler refuses here (a cast Mosaic lacks, a kernel GSPMD cannot
partition, an API the installed jax dropped) is what the first chip run
would have died on; interpret-mode tests see none of it.

One file on purpose: only one process may load libtpu, the topology is
described inside a module-scoped fixture (never at import), and every
compile runs in this test process.  Code that asks
`jax.default_backend()` sees the CPU here, so the kernel choice is
steered from the test (`impl="pallas"`, `attention_impl="pallas"`).
"""

import functools
import os
import re

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("x",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    """Shapes of `tree`, every leaf placed on `sharding`."""
    return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), tree)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _streamed_kernels(compiled) -> int:
    """Instructions of the compiled program that run streamed_attention's
    block kernel (ops/attention.py, PR 46)."""
    return len(re.findall(r"custom-call\(.*streamed_attention_block",
                          compiled.as_text()))


def _latent_kernels(compiled) -> int:
    """... that run the decode step's walk over latent pages
    (`latent_decode_attention`, PR 48)."""
    return len(re.findall(r"%latent_decode_attention[.\d]* = \S+ custom-call\(",
                          compiled.as_text()))


def _assert_grouped_products_take_a_row_block(compiled, moe_layers: int,
                                              a_layer: int = 2):
    """The held experts' grouped products (`jax.lax.ragged_dot`) as the
    chip's compiler wrote them: a `ragged-dot-none` custom call each,
    `a_layer` a MoE layer — two where gate and up lie in one leaf (gate|up,
    down: `deepseek_v3.layer_ffn`'s three models, PR 62), three where they
    lie apart (Command A+) — whose `ragged_dot_tiling` "tm,tk,tn" takes at
    most `ops/moe.ROW_BLOCK` rows a visited (expert, row block) — at
    tm = 512 a touched expert costs a 512-row product and not its weights'
    read (PR 53).  -> the tilings, {"tm,tk,tn": calls}."""
    from ray_tpu.ops.moe import ROW_BLOCK

    calls = [ln for ln in compiled.as_text().splitlines()
             if re.match(r"\s*%?ragged-dot-none[.\d]* = ", ln)]
    assert len(calls) == a_layer * moe_layers, len(calls)
    tilings = [re.search(r'ragged_dot_tiling="(\d+),\d+,\d+"', c)
               for c in calls]
    assert all(tilings), calls[0][:400]
    assert all(int(t.group(1)) <= ROW_BLOCK for t in tilings), [
        t.group(0) for t in tilings]
    found = [t.group(0).split('"')[1] for t in tilings]
    return {t: found.count(t) for t in set(found)}


def _gathered_blocks(compiled, slots: int):
    """Instructions of a step program's attention that gather, copy or
    transpose a block of cached latents for every slot ([slots, 4 pages,
    576, 128] or [slots, 512, 576], as the XLA body's `fetch` makes
    them)."""
    return [ln.strip()[:120] for ln in compiled.as_text().splitlines()
            if "mla_attend_step" in ln and re.search(
                r"= bf16\[%d,(4,576,128|512,576)\]\S* (gather|copy|transpose)\("
                % slots, ln)]


# -- flash attention ---------------------------------------------------------


# b8h25s1024 is `train-xl-fsdp4`'s shard a chip: an odd head count, and
# the one diagonal position of a whole-sequence block laid out statically.
# d128: twice the bytes a block in VMEM (the backward's 1024 x 1024 cap),
# one program a head at 1024 and scratch across key blocks at 8192
@pytest.mark.parametrize("shape", [(16, 12, 512, 64), (2, 12, 8192, 64),
                                   (8, 25, 1024, 64), (4, 8, 1024, 128),
                                   (1, 8, 8192, 128)],
                         ids=["b16s512", "b2s8192", "b8h25s1024",
                              "b4s1024d128", "b1s8192d128"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, shape, direction):
    from ray_tpu.ops.attention import attention

    def fwd(q, k, v):
        return attention(q, k, v, causal=True, impl="pallas")

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = fwd if direction == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    x = _sds(shape, jnp.bfloat16, one_chip)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    # fwd is one kernel; bwd re-runs it and adds the dq and dk/dv kernels
    assert _kernel_calls(compiled) >= (1 if direction == "fwd" else 3)


# -- int8 quantization kernels ----------------------------------------------


_QN, _QBLOCK = 1 << 20, 256


@pytest.mark.parametrize("stochastic", [False, True],
                         ids=["deterministic", "stochastic"])
def test_quantize_kernel_compiles(one_chip, stochastic):
    from ray_tpu.ops.quantize import quantize_blockwise

    fn = functools.partial(quantize_blockwise, block_size=_QBLOCK,
                           stochastic=stochastic, seed=7, impl="pallas")
    compiled = jax.jit(fn).lower(
        _sds((_QN,), jnp.float32, one_chip)).compile()
    assert _kernel_calls(compiled) == 1


def test_dequantize_kernel_compiles(one_chip):
    from ray_tpu.ops.quantize import dequantize_blockwise

    fn = functools.partial(dequantize_blockwise, shape=(_QN,),
                           dtype=jnp.float32, block_size=_QBLOCK,
                           impl="pallas")
    compiled = jax.jit(fn).lower(
        _sds((_QN,), jnp.int8, one_chip),
        _sds((_QN // _QBLOCK,), jnp.float32, one_chip)).compile()
    assert _kernel_calls(compiled) == 1


def test_dequant_accumulate_kernel_compiles(one_chip):
    from ray_tpu.ops.quantize import dequantize_accumulate

    world = 4
    fn = functools.partial(dequantize_accumulate, world=world,
                           block_size=_QBLOCK, impl="pallas")
    compiled = jax.jit(fn).lower(
        _sds((world * _QN,), jnp.int8, one_chip),
        _sds((world * _QN // _QBLOCK,), jnp.float32, one_chip)).compile()
    assert _kernel_calls(compiled) == 1


@pytest.mark.parametrize("sub", [4096, 65536])
def test_fused_reduce_scatter_compiles(mesh4, sub):
    """The one-kernel quantize -> remote-DMA exchange -> accumulate hop,
    for four chips (the interpret-mode twin lives in
    test_collective_pipeline.py)."""
    from jax import shard_map

    from ray_tpu.ops.quantize import fused_reduce_scatter

    def body(x):                      # [1, world, sub] per device
        return fused_reduce_scatter(x[0], "x")[None]

    fn = shard_map(body, mesh=mesh4, in_specs=P("x"), out_specs=P("x"),
                   check_vma=False)
    x = _sds((4, 4, sub), jnp.float32, NamedSharding(mesh4, P("x")))
    compiled = jax.jit(fn).lower(x).compile()
    assert _kernel_calls(compiled) == 1


def test_int8_mesh_allreduce_fused_compiles(mesh4):
    from ray_tpu.collective import xla_group

    fn = functools.partial(xla_group.mesh_allreduce, mesh=mesh4,
                           compression="int8", impl="fused")
    x = _sds((4, 1 << 20), jnp.float32, NamedSharding(mesh4, P("x")))
    compiled = jax.jit(fn).lower(x).compile()
    # the fused hop is the one Mosaic kernel that talks to other chips
    # (chip_smoke.py --chips 4 looks for the same marker on hardware)
    assert '"has_communication":true' in compiled.as_text()


# -- serving programs, GPT-2-small as LLMServer(preset="gpt2_small",
# -- max_seq=1024) builds them ----------------------------------------------


def _serve_view_shapes(gpt, cfg):
    """What the engine hands its programs, as shapes: the serve view of
    the f32 tree a loader delivers."""
    return jax.eval_shape(
        lambda k: gpt.serve_view(gpt.init(k, cfg), cfg),
        jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def serve_engine(one_chip):
    """A ContinuousEngine at the default serving shapes whose programs
    are lowered, never run: params and cache are shapes on the described
    chip."""
    from ray_tpu.models import gpt
    from ray_tpu.serve._engine import ContinuousEngine

    cfg = gpt.GPTConfig.gpt2_small(max_seq=1024)
    eng = ContinuousEngine(gpt, cfg, None)
    params = _on(_serve_view_shapes(gpt, cfg), one_chip)
    cache = _on(jax.eval_shape(functools.partial(
        gpt.init_paged_cache, cfg, eng.num_pages, eng.page_size)), one_chip)
    yield eng, cfg, params, cache
    eng.stop()


def _assert_arena_in_place(compiled, cache):
    """The program writes its rows into the arena it was given: the whole
    arena is aliased to the output, the chip keeps it as it is written
    (no padded layout to convert into and out of), and no instruction
    copies an arena-sized array."""
    side = cache["k"]
    arena = 2 * side.size * side.dtype.itemsize
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= arena
    dims = ",".join(map(str, side.shape))
    text = compiled.as_text()
    assert f"bf16[{dims}]{{3,2,1,0:" in text       # the parameter as given
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if f"= bf16[{dims}]" in ln
             and any(f" {op}(" in ln for op in
                     ("copy", "dynamic-update-slice", "transpose"))]
    assert not moved, moved


def _serve_args(serve_engine, one_chip, key):
    """The shapes `serve_engine`'s program `key` is lowered on (a prefill
    bucket's key is `("prefill", rows)`)."""
    eng, cfg, params, cache = serve_engine
    B, V, maxp = eng.max_slots, cfg.vocab_size, eng.max_pages_per_seq
    s = lambda shape, dt: _sds(shape, dt, one_chip)
    i32 = s((), jnp.int32)
    if key == "step":
        return (params, cache, s((B, V), jnp.float32), s((B, 2), jnp.uint32),
                s((B,), jnp.float32), s((B,), jnp.int32),
                s((B, maxp), jnp.int32), s((B,), jnp.int32))
    if key == "setrow":
        return (s((B, V), jnp.float32), s((V,), jnp.bfloat16), i32)
    if key == "copy_page":
        return (cache, i32, i32)
    return (params, cache, s((key[1],), jnp.int32), s((maxp,), jnp.int32),
            i32, i32)


@pytest.fixture(scope="module")
def serve_step_compiled(serve_engine, one_chip):
    return serve_engine[0]._fn("step").lower(
        *_serve_args(serve_engine, one_chip, "step")).compile()


def test_serve_step_compiles(serve_engine, serve_step_compiled):
    _assert_arena_in_place(serve_step_compiled, serve_engine[3])


def _assert_sampler_asks_its_operands(compiled, slots: int, vocab: int,
                                      switches: int = 0):
    """The step's sampler as the chip's compiler leaves it (PR 49,
    `ops/sampling.py`): no instruction sorts the vocabulary, in either
    branch (what sorts are left are a router's, over its experts, under
    the model's `moe_*` scopes), and ONE two-way `conditional`, whose
    predicate is computed from the `temps` operand and nothing else, so a
    batch in which nobody samples runs the branch that reads the logits
    alone (its argmax); the other takes all four operands.  `switches`:
    how many conditionals of MORE branches the model's own step brings."""
    text = compiled.as_text()
    sorts = [ln.strip()[:160] for ln in text.splitlines()
             if " sort(" in ln and (re.search(r"\[(\d+,)*%d\]" % vocab, ln)
                                    or not re.search(r'op_name="[^"]*/moe_', ln))]
    assert not sorts, sorts
    ways = lambda ln: re.search(r"branch_computations=\{([^}]*)\}",
                                ln).group(1).split(",")
    conds = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert len(conds) == 1 + switches, [ln[:160] for ln in conds]
    conds = [ln for ln in conds if len(ways(ln)) == 2]
    assert len(conds) == 1, [ln[:160] for ln in conds]
    defs = {m.group(1): ln for ln in text.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?(%\S+) = ", ln))}
    operands = lambda ln: re.search(
        r" [\w\-]+\((%[\w.\-]+(?:, %[\w.\-]+)*)\)", ln).group(1).split(", ")
    name = operands(conds[0])[0]
    while " parameter(" not in defs[name]:      # predicate <- ... <- temps
        (name,) = operands(defs[name])
    assert re.search(r"= f32\[%d\]\S* parameter\(" % slots, defs[name])
    assert 'op_name="temps"' in defs[name], defs[name][:300]
    # the branches: one takes the logits alone, the other all four operands
    heads = [next(ln for ln in text.splitlines()
                  if ln.startswith(b.strip() + " "))
             for b in ways(conds[0])]
    takes = sorted(len(re.findall(r"\w+\[[\d,]*\]", h.split("->")[0]))
                   for h in heads)
    assert takes == [1, 4], heads
    assert all(f"f32[{slots},{vocab}]" in h for h in heads)


def test_serve_step_sampler_asks_its_operands(serve_engine,
                                              serve_step_compiled):
    eng, cfg = serve_engine[:2]
    _assert_sampler_asks_its_operands(serve_step_compiled, eng.max_slots,
                                      cfg.vocab_size)


# sha256[:16] of the lowered text of the serve programs PR 49 left alone,
# at its parent commit (bdb81ff): the sampler is the step's, and the
# programs beside it lower to what they were
_PARENT_LOWERED = {"prefill": "f8c2e40c8944d752", "setrow": "f6c143d1011d4ab3",
                   "copy_page": "ca29b7a02da42e34"}


@pytest.mark.parametrize("key", [("prefill", 32), "setrow", "copy_page"],
                         ids=["prefill32", "setrow", "copy_page"])
def test_serve_programs_beside_the_step_lower_to_the_parents_text(
        serve_engine, one_chip, key):
    import hashlib

    text = serve_engine[0]._fn(key).lower(
        *_serve_args(serve_engine, one_chip, key)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_LOWERED[key if isinstance(key, str) else key[0]]


def _shapes(text):
    """Every array type the text names, as (dtype, dims)."""
    return {(dt, tuple(int(d) for d in dims.split(",")))
            for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]+)\]", text)}


def test_serve_step_reads_live_blocks_where_they_stand(serve_engine,
                                                       serve_step_compiled):
    """The step's attention as the chip's compiler leaves it: a loop a
    layer whose trip count is an operand's doing (the contexts' live
    length), whose turn gathers one block of pages a slot as the arena
    keeps them — bf16, [.., H*dh] — and nothing the size of `slots x
    max_total` positions, no f32 copy of a block of keys and no block
    split by heads (dh = 64 as a minor dimension pads to 128 lanes)."""
    from ray_tpu.models import gpt

    eng, cfg, params, cache = serve_engine
    B, ps, maxp = eng.max_slots, eng.page_size, eng.max_pages_per_seq
    H, dh, HD = cfg.n_heads, cfg.d_head, cfg.n_heads * cfg.d_head
    npb = gpt.kv_block_pages(cfg, ps, maxp)
    assert npb * ps == cfg.kv_block < eng.max_total
    text = serve_step_compiled.as_text()
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert len(loops) >= cfg.n_layers
    assert not [ln[:160] for ln in loops if "known_trip_count" in ln]
    shapes = _shapes(text)
    assert ("bf16", (B * npb, ps, HD)) in shapes       # a turn's gather
    wide = sorted(x for x in shapes
                  if B in x[1] and eng.max_total in x[1])
    assert not wide, wide
    block = {(B * npb, ps, HD), (B, npb * ps, HD), (B, npb, ps, HD)}
    assert not [x for x in shapes if x[0] == "f32" and x[1] in block]
    split = [x for x in shapes if x[1][0] in (B, B * npb)
             and x[1][-2:] == (H, dh) and np.prod(x[1]) >= B * npb * ps * HD]
    assert not split, split


@pytest.mark.parametrize("bucket", [32, 128])
def test_serve_prefill_compiles(serve_engine, one_chip, bucket):
    key = ("prefill", bucket)
    compiled = serve_engine[0]._fn(key).lower(
        *_serve_args(serve_engine, one_chip, key)).compile()
    _assert_arena_in_place(compiled, serve_engine[3])


def test_serve_setrow_and_copy_page_compile(serve_engine, one_chip):
    for key in ("setrow", "copy_page"):
        serve_engine[0]._fn(key).lower(
            *_serve_args(serve_engine, one_chip, key)).compile()


@pytest.mark.parametrize("key,module", [
    ("step", "jit_serve_step"), (("prefill", 32), "jit_serve_prefill"),
    ("setrow", "jit_serve_setrow"), ("copy_page", "jit_serve_copy_page")],
    ids=["step", "prefill", "setrow", "copy_page"])
def test_serve_programs_carry_their_names(serve_engine, one_chip, key, module):
    """What the device trace's `XLA Modules` line calls a program is its
    jitted function's name: the serve programs have names of their own
    (the train step stays `jit_step`), and the benchmark's
    `engine.decode_step_device_ms.chat` matches `^jit_serve_step`."""
    text = serve_engine[0]._fn(key).lower(
        *_serve_args(serve_engine, one_chip, key)).as_text()
    assert f"module @{module} " in text.split("\n", 1)[0], text[:200]


def test_flash_attention_kernels_carry_their_names(one_chip):
    """The three Pallas kernels are named in the compiled program (the
    instruction name and `op_name` are what the trace's `XLA Ops` line
    shows); target and operand counts, which the benchmark's readers
    match, stay as they were."""
    from ray_tpu.ops.attention import attention

    def loss(q, k, v):
        return attention(q, k, v, causal=True,
                         impl="pallas").astype(jnp.float32).sum()

    x = _sds((2, 4, 1024, 64), jnp.bfloat16, one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    operands = lambda ln: ln.split("custom-call(", 1)[1].split(
        "), custom_call_target", 1)[0].count("%")
    for name, n_operands in (("flash_attention_fwd", 3),
                             ("flash_attention_bwd_dkv", 6),
                             ("flash_attention_bwd_dq", 6)):
        named = [ln for ln in calls if f"({name})" in ln]
        assert len(named) == 1, (name, len(named))
        assert name in named[0].split(" = ", 1)[0]      # the instruction
        assert operands(named[0]) == n_operands


# -- the benchmark's served GPT-2: benchmarks/configs/gpt2-large.json ---------


@pytest.mark.parametrize("key", ["step", ("prefill", 32)],
                         ids=["step", "prefill32"])
def test_gpt2_large_serve_programs_read_compute_dtype_weights(one_chip, key):
    """`gpt2-large` keeps float32 parameters and multiplies in bf16.  The
    engine's programs take the serve view: every weight arrives as bf16,
    and in the text the chip's compiler leaves nothing reads a float32
    array of a weight's shape, whole or one layer's, or casts one — so
    no step and no prefill reads 3.1 GB of float32 to cast it again."""
    import json

    from benchmarks.lib.modelcfg import gpt_config
    from ray_tpu.models import gpt
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "gpt2-large.json")) as f:
        conf = json.load(f)
    cfg = gpt_config(conf)
    assert cfg.param_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    eng = ContinuousEngine(gpt, cfg, None, **conf["serve"]["engine_kwargs"])
    try:
        view = _serve_view_shapes(gpt, cfg)
        params = _on(view, one_chip)
        cache = _on(jax.eval_shape(functools.partial(
            gpt.init_paged_cache, cfg, eng.num_pages, eng.page_size)),
            one_chip)
        B, V, maxp = eng.max_slots, cfg.vocab_size, eng.max_pages_per_seq
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (params, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32), s((B, maxp), jnp.int32),
                    s((B,), jnp.int32))
        else:
            args = (params, cache, s((key[1],), jnp.int32),
                    s((maxp,), jnp.int32), i32, i32)
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    leaves = jax.tree.leaves(view)
    n = sum(w.size for w in leaves)
    assert n == gpt.num_params(cfg) == 774_090_240
    assert sum(w.size * w.dtype.itemsize for w in leaves) == 1_548_554_240
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):].split("\n", 1)[0]
    L = cfg.n_layers
    weights = {w.shape for w in leaves if w.dtype == jnp.bfloat16}
    assert len(weights) == 9
    for shape in weights:
        assert f"bf16[{','.join(map(str, shape))}]" in entry, shape
    # the matrices, whole and as one layer's slice of a stacked one (with
    # or without the unit axis a dynamic-slice leaves); the biases are
    # left out: a norm's scale has their shape and stays f32
    dims = set()
    for shape in weights:
        if shape[0] != L:
            dims.add(shape)
        elif np.prod(shape[1:]) > cfg.d_ff:
            dims |= {shape, shape[1:], (1,) + shape[1:]}
    assert len(dims) == 2 + 3 * 4          # embed, pos_embed; wq.. mlp_out
    # neither the program nor a fusion inside it is handed a float32 array
    # of such a shape or casts what it is handed to one in bf16
    handed = re.findall(r"= f32\[([\d,]+)\]\S* parameter\(", text)
    cast = re.findall(r"= bf16\[([\d,]+)\]\S* convert\(%param", text)
    assert handed
    bad = sorted({d for d in handed + cast
                  if tuple(map(int, d.split(","))) in dims})
    assert not bad, bad
    assert compiled.memory_analysis().argument_size_in_bytes < 2.1e9


# -- the train step ----------------------------------------------------------


@pytest.mark.parametrize("axes,batch", [({}, 16), ({"dp": 2, "fsdp": 2}, 32)],
                         ids=["one_chip", "dp2_fsdp2"])
def test_gpt2_small_train_step_compiles_with_kernel(topo, axes, batch):
    """make_train_step's own jitted step (B=16 a chip, S=512, dots remat),
    through make_mesh on the described devices.  On four chips the Pallas
    kernel must be IN the program — inside a shard_map, since GSPMD cannot
    partition a Mosaic call — not swapped for the blockwise XLA scan."""
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.models.training import make_train_step
    from ray_tpu.parallel.mesh import make_mesh

    n = int(np.prod(list(axes.values()) or [1]))
    mesh = make_mesh(devices=topo.devices[:n], **axes)
    cfg = gpt.GPTConfig.gpt2_small(max_seq=512, remat_policy="dots",
                                   attention_impl="pallas")
    tx = optax.adamw(3e-4, weight_decay=0.1)
    _, step_fn = make_train_step(cfg, mesh, tx)

    def init_state(key):            # the state init_fn builds, as shapes
        params = gpt.init(key, cfg)
        return {"params": params, "opt_state": tx.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    b = _sds((batch, 512), jnp.int32,
             NamedSharding(mesh, P(("dp", "fsdp", "ep"), None)))
    compiled = step_fn.lower(state, {"inputs": b, "targets": b}).compile()
    assert _kernel_calls(compiled) >= 3      # fwd + dq + dk/dv, per layer scan
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


# -- the second served model at its published widths: Command A+'s share of
# -- benchmarks/configs/command-a-plus-l4-e16.json ---------------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512)],
                         ids=["step", "prefill512"])
def test_cohere2_moe_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The mixed-pool serve programs (grouped expert products, streamed
    attention over two page pools) at the benchmark configuration's sizes:
    the chip's compiler takes them, weights + both arenas + temporaries
    stay under the chip's 15.75 GB, and the arenas are held once: the
    programs are given them to keep and write their rows in place."""
    import json

    from benchmarks.lib.cohere2cfg import model_config
    from ray_tpu.models import cohere2_moe as cm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "command-a-plus-l4-e16.json")) as f:
        conf = json.load(f)
    cfg = model_config(conf)
    eng = ContinuousEngine(cm, cfg, None, **conf["serve"]["engine_kwargs"])
    try:
        params = _on(jax.eval_shape(lambda k: {
            "embed": jnp.zeros((cfg.vocab_size, cfg.d_model),
                               cfg.param_dtype),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "layers": [cm.init_layer(k, cfg) for _ in cfg.layer_types]},
            jax.random.PRNGKey(0)), one_chip)
        cache = _on(jax.eval_shape(functools.partial(
            cm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (params, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (params, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32)
        # as on the chip: streamed_attention picks its body by the backend
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    # a chunk's four layers attend through ONE lowered block kernel (the
    # compiler inlines it a layer); a step's rows take the XLA body
    assert _streamed_kernels(compiled) == (0 if key == "step" else 4)
    assert _latent_kernels(compiled) == 0
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arenas = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert m.alias_size_in_bytes >= arenas > 1.7e9
    assert eng._widths == {"full": 128, "sliding": 37}
    assert 9.4e9 < sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params)) < 9.6e9
    assert total < 15.75 * 1024 ** 3, total
    # the grouped products are the chip's own kernel, not a dense fallback,
    # for a chunk's rows and a step's alike
    assert "ragged-dot" in compiled.as_text()
    _assert_grouped_products_take_a_row_block(compiled, moe_layers=4,
                                              a_layer=3)
    if key == "step":
        _assert_sampler_asks_its_operands(compiled, B, V)


# -- the third served model at its published widths: a pipeline stage of
# -- benchmarks/configs/brumby-14b-l8.json ------------------------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512)],
                         ids=["step", "prefill512"])
def test_brumby_serve_programs_fit_one_chip(one_chip, key):
    """The state-kind serve programs (the retention step kernel, the
    chunk's kernel) at the benchmark configuration's sizes, 16 slots:
    the chip's compiler takes them, weights + the state arena +
    temporaries stay under the chip's 15.75 GB, and the arena is held
    once: the programs are given it to keep, the step's kernel and the
    chunk's write-back update it where it stands, and no instruction
    copies an arena-sized array."""
    import json

    from benchmarks.lib.brumbycfg import model_config
    from ray_tpu.models import brumby as bm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "brumby-14b-l8.json")) as f:
        conf = json.load(f)
    cfg = model_config(conf, retention_impl="pallas")
    eng = ContinuousEngine(bm, cfg, None, **conf["serve"]["engine_kwargs"])
    try:
        V = cfg.vocab_size
        params = _on(jax.eval_shape(lambda k: bm.init(k, cfg),
                                    jax.random.PRNGKey(0)), one_chip)
        cache = _on(jax.eval_shape(functools.partial(
            bm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B = eng.max_slots
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (params, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32), {"ret": s((B, 1), jnp.int32)},
                    s((B,), jnp.int32))
        else:
            args = (params, cache, s((key[1],), jnp.int32),
                    {"ret": s((1,), jnp.int32)}, i32, i32)
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    assert (B, eng._pool_pages, eng.max_total) == (16, {"ret": 17}, 32768)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert arena == 8 * 17 * 8 * 136 * 8320 * 4 and 8.39e9 < weights < 8.41e9
    assert m.alias_size_in_bytes >= arena
    assert total < 15.75 * 1024 ** 3, total
    # a second copy of the arena would show here (a chunk keeps one
    # sequence's states of all layers, read and new: 2 x 290 MB)
    assert m.temp_size_in_bytes < arena // 4, m.temp_size_in_bytes
    text = compiled.as_text()
    dims = ",".join(map(str, cache.shape))
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if f"= f32[{dims}]" in ln
             and any(f" {op}(" in ln for op in ("copy", "transpose"))]
    assert not moved, moved
    # one kernel for every layer, under its name
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    name = calls[0].split(" = ", 1)[0].split()[-1].lstrip("%")
    if key == "step":
        assert name.startswith("retention_step")
        _assert_sampler_asks_its_operands(compiled, B, V)
    else:
        # the chunk's kernel (PR 57), charged to the scope the cell's
        # roofline reads: `replica_brumby.bench_program_scopes`' own call
        from benchmarks.drivers.replica_brumby import SCOPES
        from benchmarks.trace.scopes import scope_map

        assert name.startswith("retention_chunk")
        scopes = scope_map(text, SCOPES, {"retention_step": "retention_step"})
        assert scopes[name] == "retention_chunk"
        # nothing is F wide but a state and `phi`'s row of weights: the
        # XLA body kept a head's query features, bf16[5,512,8320], and
        # the keys' f32[512,8320] (907,330,560 B of temporaries)
        wide = set(re.findall(r"\w+\[([\d,]*),8320\]", text))
        assert wide and all(d.split(",")[-1] in ("136", "1") for d in wide), wide
        assert m.temp_size_in_bytes < 700e6, m.temp_size_in_bytes
    print(key, "total", total, "temp", m.temp_size_in_bytes)


# -- the fourth served model at its published widths: DeepSeek-V3's share of
# -- benchmarks/configs/deepseek-v3-l5-e16.json -------------------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512)],
                         ids=["step", "prefill512"])
def test_deepseek_v3_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The latent-page serve programs (absorbed attention in the step,
    expanded in the 512-row chunk, grouped routing, grouped expert
    products) from the benchmark's own `engine_kwargs`, 32 slots: the
    chip's compiler takes them; weights + the latent arena + temporaries
    stay under the chip's 15.75 GiB; the serve view is read in bf16 and
    holds `Wkvb` once, re-laid; the arena is donated and held once — a
    position one unpadded column of 576 values, 1,152 B — and no
    instruction copies or re-lays an arena-sized array (a row-at-a-time
    scatter made the compiler do both, 3.2 GB a program)."""
    import json

    from benchmarks.lib.deepseekcfg import model_config
    from ray_tpu.models import deepseek_v3 as dm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "deepseek-v3-l5-e16.json")) as f:
        conf = json.load(f)
    cfg = model_config(conf)
    view = _on(jax.eval_shape(
        lambda k: dm.serve_view(dm.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one_chip)
    eng = ContinuousEngine(dm, cfg, view, **conf["serve"]["engine_kwargs"])
    try:
        cache = _on(jax.eval_shape(functools.partial(
            dm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (view, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (view, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
        stats = eng.engine_stats()
    finally:
        eng.stop()
    assert _streamed_kernels(compiled) == (0 if key == "step" else 5)
    # the step's five latent layers walk their pages through ONE lowered
    # kernel (inlined a layer), and no block of every slot's latents is
    # gathered, copied or transposed for them
    assert _latent_kernels(compiled) == (5 if key == "step" else 0)
    assert not _gathered_blocks(compiled, B)
    assert (B, eng._pool_pages, eng.max_total, eng._widths, eng._share) == (
        32, {"full": 4353}, 17408, {"full": 136}, True)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(view))
    assert [a.shape for a in cache] == [(4353, 576, 128)] * 5
    assert arena == 5 * 4353 * 128 * 1152 and 9.13e9 < weights < 9.16e9
    assert stats["param_bytes"] == weights
    # bf16 but for the router and its bias (f32, kept and applied so)
    assert {str(w.dtype) for w in jax.tree.leaves(view)} == {"bfloat16",
                                                             "float32"}
    assert all("wkv_b" not in layer and layer["w_uk"].shape == (128, 128, 512)
               and layer["w_uv"].shape == (128, 512, 128)
               for layer in view["layers"])
    assert m.alias_size_in_bytes >= arena
    assert total < 15.75 * 1024 ** 3, total
    assert m.temp_size_in_bytes < arena // 8, m.temp_size_in_bytes
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= bf16\[4353,576,128\]\{[^}]*\} (copy|transpose)\(",
                          ln)]
    assert not moved, moved
    assert "{2,1,0" in re.search(r"bf16\[4353,576,128\]\{[^}]*\}",
                                 text).group(0)
    assert "ragged-dot" in text
    _assert_grouped_products_take_a_row_block(compiled, moe_layers=4)
    if key == "step":
        _assert_sampler_asks_its_operands(compiled, B, V)
    print(key, "total", total, "temp", m.temp_size_in_bytes)


# -- the fifth served model at its published widths: Ling-3.0-flash's share of
# -- benchmarks/configs/ling-3.0-flash-l7-e128.json ---------------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512)],
                         ids=["step", "prefill512"])
def test_ling3_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The mixed-cache serve programs (six KDA layers: the step's kernel
    over a state arena, the chunked WY form; one latent-attention layer
    over pages; 128 held experts) at the configuration's widths with an
    engine sized HERE — 64 slots of 18,432 positions — and not by the
    benchmark's `engine_kwargs`: the chip's compiler takes them; weights +
    latent pages + states + tails + temporaries stay under 15.75 GiB; the
    cache is donated and held once, the state arena moved by no copy."""
    import json

    from benchmarks.lib.ling3cfg import model_config
    from ray_tpu.models import ling3 as lm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "ling-3.0-flash-l7-e128.json")) as f:
        conf = json.load(f)
    cfg = model_config(conf, kda_impl="pallas")
    view = _on(jax.eval_shape(
        lambda k: lm.serve_view(lm.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one_chip)
    eng = ContinuousEngine(lm, cfg, view, max_slots=64, page_size=128,
                           max_total=18432,
                           num_pages={"full": 9217, "kda": 65},
                           prefill_bucket=512, prefill_chunk=512)
    try:
        cache = _on(jax.eval_shape(functools.partial(
            lm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (view, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (view, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    assert (eng._widths, eng._share, eng._main, eng._state_kinds) == (
        {"full": 144, "kda": 1}, False, "full", ["kda"])
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(view))
    assert cache["latent"][0].shape == (9217, 576, 128)
    assert cache["state"].shape == (6, 65, 32, 128, 128)
    assert cache["tail"].shape == (6, 65, 3, 96, 128)
    assert arena == (9217 * 128 * 1152 + 6 * 65 * (32 * 128 * 128 * 4
                                                   + 3 * 96 * 128 * 2))
    assert 10.46e9 < weights < 10.50e9
    assert m.alias_size_in_bytes >= arena
    assert total < 15.75 * 1024 ** 3, total
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= f32\[6,65,32,128,128\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)
             or re.search(r"= bf16\[9217,576,128\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)]
    assert not moved, moved
    assert "ragged-dot" in text
    # gate|up, N = 2F = 1,536 = 3 x 512, on whole 512 x 512 tiles — apart,
    # N = F = 768 = 3 x 256 held each to "128,512,256" (PR 62); the down
    # product's K = 768 keeps tk 256
    assert _assert_grouped_products_take_a_row_block(
        compiled, moe_layers=6) == {"128,512,512": 6, "128,256,512": 6}
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    if key == "step":       # one kernel a KDA layer, under its name, and
        #                     the latent layer's walk over its pages
        assert sum("kda_step" in c.split(" = ", 1)[0] for c in calls) == 6
        _assert_sampler_asks_its_operands(compiled, B, V)
        assert (_latent_kernels(compiled), _streamed_kernels(compiled)) == (
            1, 0)
        assert not _gathered_blocks(compiled, B)
    else:                   # the chunk: the latent layer's block kernel
        assert (_latent_kernels(compiled), _streamed_kernels(compiled)) == (
            0, 1)
        # no WY block's square of exponentials, alone or batched over the
        # chunk's blocks (PR 60: sub-blocks of 16 rows), no copy of a
        # layer's whole part of the state arena to read one entry of it,
        # and fewer temporaries than the program had with them
        squares = sorted(set(re.findall(r"f32\[(?:\d+,)*64,64,128\]", text)))
        assert not squares, squares
        parts = [ln.strip()[:120] for ln in text.splitlines()
                 if re.search(r"= f32\[1,65,32,128,128\]\{[^}]*\} "
                              r"(slice|copy)\(", ln)]
        assert not parts, parts
        assert m.temp_size_in_bytes <= 234_823_680, m.temp_size_in_bytes
    print(key, "total", total, "temp", m.temp_size_in_bytes)


# -- the sixth served model WHOLE: Phi-4-mini-flash-reasoning at its published
# -- widths, depth and vocabulary (benchmarks/configs/...) --------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512)],
                         ids=["step", "prefill512"])
def test_phi4flash_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The three-kind serve programs (nine Mamba layers: the step's kernel
    over a state arena, the chunk's scan kernel; eight windowed and one
    full differential-attention layer over pages; seven cross layers that
    read the one full arena; 200,064 logits a slot) of the WHOLE model with
    an engine sized HERE — 32 slots of 20,480 positions — and not by the
    benchmark's `engine_kwargs`: the chip's compiler takes them; weights +
    pages + states + tails + temporaries stay under 15.75 GiB; the cache
    is donated and held once, no arena moved by a copy."""
    import json

    from benchmarks.lib.phi4flashcfg import model_config
    from ray_tpu.models import phi4flash as pm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        conf = json.load(f)
    assert conf["reduced"] == []
    cfg = model_config(conf, mamba_impl="pallas")
    view = _on(jax.eval_shape(
        lambda k: pm.serve_view(pm.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one_chip)
    eng = ContinuousEngine(pm, cfg, view, max_slots=32, page_size=128,
                           max_total=20480,
                           num_pages={"full": 5121, "swa": 289, "mamba": 33},
                           prefill_bucket=512, prefill_chunk=512)
    try:
        cache = _on(jax.eval_shape(functools.partial(
            pm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (view, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (view, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32, s((), jnp.bool_))
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    assert (eng._widths, eng._share, eng._main, eng._state_kinds,
            eng._windowed) == ({"full": 160, "swa": 9, "mamba": 1}, False,
                               "full", ["mamba"], ["swa"])
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(view))
    assert cache["full"]["k"].shape == (5121, 128, 1280)
    assert len(cache["swa"]) == 8
    assert cache["swa"][0]["v"].shape == (289, 128, 1280)
    assert cache["state"].shape == (9, 33, 16, 5120)
    assert cache["tail"].shape == (9, 33, 3, 5120)
    page = 2 * 128 * 1280 * 2
    assert arena == (5121 * page + 8 * 289 * page
                     + 9 * 33 * (16 * 5120 * 4 + 3 * 5120 * 2))
    assert 7.68e9 < weights < 7.72e9
    assert m.alias_size_in_bytes >= arena
    assert total < 15.75 * 1024 ** 3, total
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= f32\[9,33,16,5120\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)
             or re.search(r"= bf16\[(5121|289),128,1280\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)]
    assert not moved, moved
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    named = lambda n: sum(n in c.split(" = ", 1)[0] for c in calls)
    gathered = [ln.strip()[:120] for ln in text.splitlines()
                if re.search(r"= bf16\[%d,(4,128|512),1280\]\S* "
                             r"(gather|copy|transpose)\(" % B, ln)]
    if key == "step":       # one kernel a Mamba layer, under its name; every
        #                     read of pages — eight of the shared cache, one
        #                     a windowed layer — a walk of the slots' own
        assert named("mamba_step") == 9 and named("mamba_chunk") == 0
        assert named("paged_decode_attention") == 8 + 8
        _assert_sampler_asks_its_operands(compiled, B, V)
        assert _streamed_kernels(compiled) == 0 and not gathered, gathered
    else:                   # the chunk: a scan kernel a Mamba layer, the
        #                     block kernel in the nine self-decoder layers,
        #                     the last chunk's seven one-row reads a walk
        assert named("mamba_chunk") == 9 and named("mamba_step") == 0
        assert named("paged_decode_attention") == 7
        assert _streamed_kernels(compiled) >= 1
        assert "conditional" in text
    print(key, "total", total, "temp", m.temp_size_in_bytes)


# -- the seventh served model at its published widths: dots3-note-prev's share
# -- of benchmarks/configs/dots3-note-prev-l5-e32.json ------------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512)],
                         ids=["step", "prefill512"])
def test_dots3_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The serve programs over TWO pools of latent pages (a full kind that
    keeps a 576-wide latent row and a 128-wide indexer row under one
    table, a sliding kind of 1,088-wide rows in a ring) from the
    benchmark's own `engine_kwargs`, 32 slots: the chip's compiler takes
    them — the block kernel and the decode walk each with the selection's
    (or the ring's) mask as one operand more —; weights + both arenas +
    temporaries stay under the chip's 15.75 GiB; every arena is donated
    and held once, and no instruction copies or re-lays one."""
    import json

    from benchmarks.lib.dots3cfg import model_config
    from ray_tpu.models import dots3 as m3
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "dots3-note-prev-l5-e32.json")) as f:
        conf = json.load(f)
    cfg = model_config(conf)
    view = _on(jax.eval_shape(
        lambda k: m3.serve_view(m3.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one_chip)
    eng = ContinuousEngine(m3, cfg, view, **conf["serve"]["engine_kwargs"])
    try:
        cache = _on(jax.eval_shape(functools.partial(
            m3.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (view, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (view, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    # five latent layers: the chunk's through the block kernel, the step's
    # a walk of the slots' own pages (two selected, three over a ring)
    assert _streamed_kernels(compiled) == (0 if key == "step" else 5)
    assert _latent_kernels(compiled) == (5 if key == "step" else 0)
    assert (B, eng._pool_pages, eng.max_total, eng._widths, eng._share) == (
        32, {"full": 8385, "sliding": 321}, 33536,
        {"full": 262, "sliding": 10}, False)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(view))
    assert [a.shape for a in jax.tree.leaves(cache["full"])] == [
        (8385, 128, 128), (8385, 576, 128)] * 2
    assert [a.shape for a in cache["sliding"]] == [(321, 1088, 128)] * 3
    assert arena == 8385 * 360448 + 321 * 835584
    assert 8.17e9 < weights < 8.19e9
    assert all("wkv_b" not in layer for layer in view["layers"])
    assert [layer["w_uk"].shape for layer in view["layers"]] == [
        (128, 128, 512)] * 2 + [(64, 192, 1024)] * 3
    assert mem.alias_size_in_bytes >= arena
    assert total < 15.75 * 1024 ** 3, total
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= bf16\[(8385,(576|128)|321,1088),128\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)]
    assert not moved, moved
    assert "ragged-dot" in text
    _assert_grouped_products_take_a_row_block(compiled, moe_layers=4)
    if key == "step":   # + a full layer's choice of how much of a table
        # its selection counts over (`dots3._select`), two full layers
        _assert_sampler_asks_its_operands(compiled, B, V, switches=2)
    print(key, "total", total, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes, "weights", weights, "arena", arena)


# -- the eighth served model at its published widths: Falcon-H1-34B's six
# -- layers of benchmarks/configs/falcon-h1-34b-l6.json -----------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512), ("prefill", 256)],
                         ids=["step", "prefill512", "prefill256"])
def test_falcon_h1_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The serve programs of a model whose EVERY layer writes pages and a
    state entry (six layers: the SSD step's kernel over the state arena
    and a walk of the slots' own K/V pages with FIVE query rows a key
    head; the SSD chunk's kernel and the block kernel in both prefill
    programs; 261,120 logits a slot) from the benchmark's own
    `engine_kwargs`, 64 slots: the chip's compiler takes them; weights +
    pages + states + tails + temporaries stay under 15.0e9 B (the issue's
    line: 96 slots read 15,012,200,448); the cache is donated and held
    once, no arena moved by a copy."""
    import json

    from benchmarks.lib.falconh1cfg import model_config
    from ray_tpu.models import falcon_h1 as fm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        conf = json.load(f)
    assert conf["reduced"] == ["num_hidden_layers"]
    cfg = model_config(conf, ssd_impl="pallas")
    view = _on(jax.eval_shape(
        lambda k: fm.serve_view(fm.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one_chip)
    eng = ContinuousEngine(fm, cfg, view, **conf["serve"]["engine_kwargs"])
    try:
        cache = _on(jax.eval_shape(functools.partial(
            fm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (view, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (view, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    assert (B, eng._pool_pages, eng._widths, eng._share, eng._main,
            eng._state_kinds, eng.queue_cap) == (
        64, {"full": 769, "ssm": 65}, {"full": 12, "ssm": 1}, False, "full",
        ["ssm"], 256)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(view))
    assert [a.shape for a in cache["k"]] == [(769, 128, 512)] * 6
    assert cache["state"].shape == (6, 65, 32, 128, 256)
    # the conv tail as whole tiles: laid [.., 3, 5120] its three rows of
    # an 8-row tile were re-laid by ten arena-wide copies a program
    assert cache["tail"].shape == (6, 65, 3, 40, 128)
    assert arena == 769 * 1572864 + 65 * 6 * (4194304 + 61440)
    assert weights == 10509189376
    assert all("wq" not in layer and layer["w_qkv"].shape == (5120, 3584)
               for layer in view["layers"])
    assert m.alias_size_in_bytes >= arena
    assert total < 15.0e9, total
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= f32\[6,65,(32,128,256|3,40,128)\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)
             or re.search(r"= bf16\[769,128,512\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)]
    assert not moved, moved
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    named = lambda n: sum(n in c.split(" = ", 1)[0] for c in calls)
    if key == "step":       # a kernel a layer each, under its name
        assert named("ssd_step") == 6 and named("ssd_chunk") == 0
        assert named("paged_decode_attention") == 6
        assert _streamed_kernels(compiled) == 0
        _assert_sampler_asks_its_operands(compiled, B, V)
    else:
        assert named("ssd_chunk") == 6 and named("ssd_step") == 0
        assert named("paged_decode_attention") == 0
        assert _streamed_kernels(compiled) >= 1
    print(key, "total", total, "temp", m.temp_size_in_bytes)


# -- the ninth served model at its published widths: LFM2-8B-A1B's thirteen
# -- layers of benchmarks/configs/lfm2-8b-a1b-l13.json ------------------------


@pytest.mark.parametrize("key", ["step", ("prefill", 512), ("prefill", 256)],
                         ids=["step", "prefill512", "prefill256"])
def test_lfm2_moe_serve_programs_fit_one_chip(one_chip, key, monkeypatch):
    """The serve programs of a model whose mixer is a gated short
    convolution in ten layers of thirteen (a tail entry of two rows a
    layer) and grouped-query attention with normed heads in three (pages),
    every one of 32 experts held in twelve, from the benchmark's own
    `engine_kwargs`, 64 slots of 72 pages: the chip's compiler takes them;
    weights + pages + tails + temporaries stay under 14.5e9 B; the cache is
    donated and held once, no arena moved by a copy; two grouped products
    an expert layer on 128-row blocks, gate|up tiled whole."""
    import json

    from benchmarks.lib.lfm2moecfg import model_config
    from ray_tpu.models import lfm2_moe as lm
    from ray_tpu.serve._engine import ContinuousEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2-8b-a1b-l13.json")) as f:
        conf = json.load(f)
    assert conf["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types"]
    cfg = model_config(conf)
    view = _on(jax.eval_shape(
        lambda k: lm.serve_view(lm.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one_chip)
    eng = ContinuousEngine(lm, cfg, view, **conf["serve"]["engine_kwargs"])
    try:
        cache = _on(jax.eval_shape(functools.partial(
            lm.init_paged_cache, cfg, eng._pool_pages, eng.page_size)),
            one_chip)
        B, V = eng.max_slots, cfg.vocab_size
        s = lambda shape, dt: _sds(shape, dt, one_chip)
        i32 = s((), jnp.int32)
        if key == "step":
            args = (view, cache, s((B, V), jnp.float32),
                    s((B, 2), jnp.uint32), s((B,), jnp.float32),
                    s((B,), jnp.int32),
                    {k: s((B, w), jnp.int32)
                     for k, w in eng._widths.items()}, s((B,), jnp.int32))
        else:
            args = (view, cache, s((key[1],), jnp.int32),
                    {k: s((w,), jnp.int32) for k, w in eng._widths.items()},
                    i32, i32)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = eng._fn(key).lower(*args).compile()
    finally:
        eng.stop()
    assert (B, eng._pool_pages, eng._widths, eng._share, eng._main,
            eng._state_kinds, eng.queue_cap) == (
        64, {"full": 4609, "conv": 65}, {"full": 72, "conv": 1}, False,
        "full", ["conv"], 256)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    arena = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(view))
    assert [a.shape for a in cache["k"]] == [(4609, 128, 512)] * 3
    # the tails as whole tiles (two rows of an 8-row tile are re-laid)
    assert cache["tail"].shape == (10, 65, 2, 16, 128)
    assert arena == 4609 * 786432 + 65 * 163840
    assert 9.21e9 < weights < 9.22e9, weights
    assert m.alias_size_in_bytes >= arena
    assert total < 14.5e9, total
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"= f32\[10,65,2,16,128\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)
             or re.search(r"= bf16\[4609,128,512\]\{[^}]*\} "
                          r"(copy|transpose)\(", ln)]
    assert not moved, moved
    tilings = _assert_grouped_products_take_a_row_block(compiled,
                                                        moe_layers=12)
    # gate|up K 2,048 x N 3,584 = 7 x 512; down K 1,792 = 7 x 256
    assert tilings == {"128,512,512": 12, "128,256,512": 12}, tilings
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    named = lambda n: sum(n in c.split(" = ", 1)[0] for c in calls)
    if key == "step":       # a walk of the slots' own pages a layer
        assert named("paged_decode_attention") == 3
        assert _streamed_kernels(compiled) == 0
        _assert_sampler_asks_its_operands(compiled, B, V)
    else:
        assert named("paged_decode_attention") == 0
        assert _streamed_kernels(compiled) >= 1
    print(key, "total", total, "temp", m.temp_size_in_bytes, "weights",
          weights, "arena", arena)
