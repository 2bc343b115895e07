"""`ops/attention.streamed_attention`: the Pallas block kernel a prefill
chunk takes on a TPU against the XLA body everything else takes (PR 46).

The kernel runs here under `interpret=True`; what is lowered for the chip
is lowered for the platform "tpu" from the CPU (Pallas lowers to Mosaic in
Python), with `jax.default_backend` patched where the predicate asks it.
`tests/test_chip_compile.py` compiles the kernel for a described v5e."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

A = importlib.import_module("ray_tpu.ops.attention")

T = S = 128


def _operands(Hkv, G, dh, dv, dtype, n_blocks, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, Hkv, G, T, dh), dtype)
    K = jax.random.normal(ks[1], (n_blocks, 1, Hkv, S, dh), dtype)
    V = jax.random.normal(ks[2], (n_blocks, 1, Hkv, S, dv), dtype)
    return q, K, V


def _both(q, K, V, qpos, kpos_of, n_blocks, window, dv):
    """(XLA body, kernel in interpret mode) on the same operands, the
    trip count traced."""
    def fetch(i):
        return K[i], V[i], kpos_of(i)

    def xla(n):
        return A.streamed_attention(q, qpos, fetch, n, window=window,
                                    v_dim=dv)

    def kernel(n):
        return A._streamed_kernel_loop(q, qpos, fetch, n, window,
                                       q.shape[-1] ** -0.5, dv,
                                       interpret=True)

    n = jnp.int32(n_blocks)
    return jax.jit(xla)(n), jax.jit(kernel)(n)


# the two chunk callers' (Hkv, G, dh, v_dim) at reduced head counts
COHERE = (2, 4, 128, 128)
DEEPSEEK = (4, 1, 192, 128)
CASES = {
    # name: (heads, dtype, window, n_blocks, mask)
    "cohere-f32-full": (COHERE, jnp.float32, None, 3, "causal"),
    "cohere-f32-window-in-block": (COHERE, jnp.float32, 40, 3, "causal"),
    "cohere-f32-window-past-context": (COHERE, jnp.float32, 4096, 3,
                                       "causal"),
    "cohere-f32-holes": (COHERE, jnp.float32, 200, 3, "holes"),
    "cohere-f32-dead-block": (COHERE, jnp.float32, None, 3, "dead-block"),
    "cohere-f32-masked-rows": (COHERE, jnp.float32, None, 2, "masked-rows"),
    "cohere-f32-no-key": (COHERE, jnp.float32, None, 2, "no-key"),
    "cohere-f32-zero-blocks": (COHERE, jnp.float32, 64, 0, "causal"),
    "cohere-bf16-full": (COHERE, jnp.bfloat16, None, 3, "causal"),
    "cohere-bf16-window": (COHERE, jnp.bfloat16, 100, 3, "holes"),
    "deepseek-f32-full": (DEEPSEEK, jnp.float32, None, 3, "causal"),
    "deepseek-f32-holes": (DEEPSEEK, jnp.float32, None, 3, "holes"),
    "deepseek-f32-masked-rows": (DEEPSEEK, jnp.float32, None, 2,
                                 "masked-rows"),
    "deepseek-f32-zero-blocks": (DEEPSEEK, jnp.float32, None, 0, "causal"),
    "deepseek-bf16-full": (DEEPSEEK, jnp.bfloat16, None, 3, "causal"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_the_xla_body(name):
    (Hkv, G, dh, dv), dtype, window, n_blocks, mask = CASES[name]
    q, K, V = _operands(Hkv, G, dh, dv, dtype, max(n_blocks, 1))
    # the chunk is the context's last T positions
    qpos = (max(n_blocks, 1) * S - T + jnp.arange(T, dtype=jnp.int32))[None]
    zero_rows = np.zeros(T, bool)

    def kpos_of(i):
        kpos = (i * S + jnp.arange(S, dtype=jnp.int32))[None]
        if mask == "holes":             # entries that hold no key
            kpos = jnp.where(kpos % 7 == 3, -1, kpos)
        if mask == "dead-block":        # block 1 holds nothing at all
            kpos = jnp.where(i == 1, -1, kpos)
        if mask == "no-key":            # no block holds anything
            kpos = jnp.full_like(kpos, -1)
        return kpos

    if mask == "masked-rows":
        # the first 16 rows stand before every key: they see none
        qpos = qpos.at[0, :16].set(-5 - jnp.arange(16))
        zero_rows[:16] = True
    if mask == "no-key" or n_blocks == 0:
        zero_rows[:] = True

    want, got = _both(q, K, V, qpos, kpos_of, n_blocks, window, dv)
    assert got.shape == want.shape == (1, Hkv, G, T, dv)
    assert got.dtype == want.dtype == dtype
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    # f32: the same sums in another order; bf16: one unit in the last
    # place of a value of order one
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 if dtype == jnp.float32 else 1e-2)
    assert np.all(got[:, :, :, zero_rows] == 0.0)
    assert np.all(np.any(got[:, :, :, ~zero_rows] != 0.0, axis=-1))


def test_predicate_is_rows_and_platform():
    uses = A.streamed_attention_uses_kernel
    assert jax.default_backend() == "cpu"
    assert not uses(512) and not uses(1)
    assert uses(512, "tpu") and uses(128, "tpu")
    assert not uses(1, "tpu") and not uses(127, "tpu")
    assert not uses(512, "cpu") and not uses(512, "gpu")


def _two_layers(rows, B=1):
    """A program of two attention layers as Command A+'s chunk has them
    (one windowed, one full), lowered for the platform "tpu"."""
    Hkv, G, dh = 2, 4, 128
    q = jax.ShapeDtypeStruct((B, Hkv, G, rows, dh), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4, B, Hkv, S, dh), jnp.bfloat16)
    qpos = jax.ShapeDtypeStruct((B, rows), jnp.int32)

    def program(q, K, V, qpos, n):
        def fetch(i):
            kpos = (i * S + jnp.arange(S, dtype=jnp.int32))[None]
            return K[i], V[i], jnp.broadcast_to(kpos, (B, S))
        x = A.streamed_attention(q, qpos, fetch, n, window=64)
        return A.streamed_attention(x, qpos, fetch, n)

    traced = jax.jit(program).trace(q, kv, kv, qpos,
                                    jax.ShapeDtypeStruct((), jnp.int32))
    return traced.lower(lowering_platforms=("tpu",)).as_text()


def test_a_decode_step_lowers_to_no_custom_call(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _two_layers(rows=1, B=8)
    assert "custom_call" not in text and "_streamed_block" not in text
    # ... and is the text the CPU's choice lowers to: one XLA body
    monkeypatch.undo()
    assert _two_layers(rows=1, B=8) == text


def test_a_chunk_program_holds_the_kernel_once(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _two_layers(rows=T)
    # two call sites (a windowed layer, a full one: the window is an
    # operand), ONE lowered function, one Mosaic module in it
    assert text.count("call @_streamed_block") == 2
    assert text.count("func.func private @_streamed_block") == 1
    assert text.count("tpu_custom_call") == 1
    assert "streamed_attention_block" in text


def test_a_model_chunk_program_holds_the_kernel_once(monkeypatch):
    """Command A+'s chunk program at toy size, 128 rows: three sliding
    layers (their loop's trip count static) and a full one (traced), both
    layer kinds through ONE lowered kernel function; its step program
    holds none."""
    from ray_tpu.models import cohere2_moe as cm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = cm.Cohere2MoEConfig.nano(max_seq=512, kv_block=128,
                                   sliding_window=128, d_head=128)
    params = jax.eval_shape(
        lambda: cm.serve_view(cm.init(jax.random.PRNGKey(0), cfg), cfg))
    kinds = cm.cache_kinds(cfg)
    cache = jax.eval_shape(
        lambda: cm.init_paged_cache(cfg, {k: 64 for k in kinds}, 16))
    widths = {k: (512 if w is None else w + 128) // 16
              for k, w in kinds.items()}
    i32 = jax.ShapeDtypeStruct((), jnp.int32)

    def lowered(fn, *args):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    chunk = lowered(
        lambda p, c, t, tabs, s, l: cm.paged_prefill(p, c, t, tabs, s, l,
                                                     cfg),
        params, cache, jax.ShapeDtypeStruct((128,), jnp.int32),
        {k: jax.ShapeDtypeStruct((w,), jnp.int32)
         for k, w in widths.items()}, i32, i32)
    assert chunk.count("call @_streamed_block") == cfg.n_layers
    assert chunk.count("func.func private @_streamed_block") == 1
    assert chunk.count("tpu_custom_call") == 1
    step = lowered(
        lambda p, c, t, tabs, pos: cm.paged_decode_step(p, c, t, tabs, pos,
                                                        cfg),
        params, cache, jax.ShapeDtypeStruct((4,), jnp.int32),
        {k: jax.ShapeDtypeStruct((4, w), jnp.int32)
         for k, w in widths.items()}, jax.ShapeDtypeStruct((4,), jnp.int32))
    assert "custom_call" not in step and "_streamed_block" not in step


# -- latent_decode_attention: the absorbed decode step's walk over each
# -- live slot's own latent pages (PR 48) ------------------------------------

PS, WIDTH, PAGES = 8, 12, 64        # positions a page, a table row, the arena
FULL = WIDTH * PS


def _latent_operands(B, dtype, seed=0):
    """q [B, H, d], an arena [PAGES, d, PS] whose null page is zeros, and
    a table of distinct pages a slot (slot b + 5 has slot b's)."""
    H, d = 8, 20
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, d), dtype)
    arena = jax.random.normal(ks[1], (PAGES, d, PS), dtype).at[0].set(0)
    ptab = 1 + jax.random.permutation(ks[2], PAGES - 1)[:WIDTH * min(B, 5)]
    ptab = jnp.tile(ptab.reshape(-1, WIDTH), (-(-B // 5), 1))[:B]
    return q, arena, ptab.astype(jnp.int32)


def _latent_xla(q, arena, ptab, ctx, scale, v_dim):
    """The XLA body on the same operands, its blocks gathered as
    `deepseek_v3.page_io` gathers them, every slot to the longest
    context."""
    B, H, d = q.shape
    npb = A._WALK_PAGES
    tabp = jnp.pad(ptab, ((0, 0), (0, -ptab.shape[1] % npb)))

    def fetch(i):
        t = jax.lax.dynamic_slice_in_dim(tabp, i * npb, npb, 1)
        rows = jnp.swapaxes(arena[t], 2, 3).reshape(B, npb * PS, d)
        kpos = jnp.broadcast_to(i * npb * PS + jnp.arange(npb * PS), (B, npb * PS))
        return rows[:, None], rows[:, None, :, :v_dim], kpos

    n_blocks = -(-jnp.max(ctx) // (npb * PS))
    o = A._streamed_xla(q[:, None, :, None], ctx[:, None] - 1, fetch,
                        n_blocks, None, scale, v_dim)
    return o[:, 0, :, 0]


# name: (slots, their contexts (a dict: the others are empty), dtype)
LATENT_CASES = {
    "every-slot-empty": (4, {}, jnp.float32),
    "one-live-slot-of-32": (32, {17: 37}, jnp.float32),
    "one-key": (3, {1: 1}, jnp.float32),
    "ends-mid-page": (3, {0: 3 * PS + 5}, jnp.float32),
    "ends-on-a-pages-last-position": (3, {2: 4 * PS}, jnp.float32),
    "ends-on-a-pages-first-position": (3, {1: 4 * PS + 1}, jnp.float32),
    "the-tables-full-width": (2, {0: FULL, 1: FULL - 1}, jnp.float32),
    "shared-prefix-pages": (4, {0: 41, 2: 30, 3: 17}, jnp.float32),
    "very-different-lengths": (8, {0: 1, 1: FULL, 3: 9, 4: 50, 6: 33, 7: 2},
                               jnp.float32),
    "very-different-lengths-bf16": (8, {0: 1, 1: FULL, 3: 9, 4: 50, 6: 33,
                                        7: 2}, jnp.bfloat16),
    "garbage-past-the-contexts": (5, {0: 13, 2: FULL - 3, 3: 32, 4: 1},
                                  jnp.float32),
}


@pytest.mark.parametrize("name", list(LATENT_CASES))
def test_latent_decode_kernel_matches_the_xla_body(name):
    B, live, dtype = LATENT_CASES[name]
    q, arena, ptab = _latent_operands(B, dtype)
    ctx = jnp.zeros(B, jnp.int32).at[jnp.asarray(list(live), jnp.int32)].set(
        jnp.asarray(list(live.values()), jnp.int32))
    v_dim, scale = 16, 0.3
    if name == "shared-prefix-pages":
        # copy-on-write prefixes: slots 2 and 3 begin with slot 0's pages
        ptab = ptab.at[2, :3].set(ptab[0, :3]).at[3, :2].set(ptab[0, :2])
    dirty = arena
    if name == "garbage-past-the-contexts":
        # what a walk must not reach: NaN in the null page and in every
        # page past a context's last (the table still names them), and —
        # finite, for the XLA body multiplies it by p = 0 too — a large
        # value in the last page's positions past the context
        last = -(-ctx // PS)                                    # [B]
        entry = jnp.arange(WIDTH)[None]
        arena = arena.at[jnp.where(entry >= last[:, None], ptab, 0)].set(0)
        tail = (entry[..., None] * PS + jnp.arange(PS) >= ctx[:, None, None]
                ) & (entry < last[:, None])[..., None]          # [B, W, PS]
        big = jnp.where(tail[:, :, None], 1e4, arena[ptab])
        arena = arena.at[jnp.where(entry < last[:, None], ptab, 0)].set(
            jnp.where((entry < last[:, None])[..., None, None], big, 0))
        dirty = arena.at[jnp.where(entry >= last[:, None], ptab, 0)].set(
            jnp.nan)
        assert bool(jnp.isnan(dirty[0]).all()) and float(arena.max()) == 1e4
    got = A.latent_decode_attention(q, dirty, ptab, ctx, scale=scale,
                                    v_dim=v_dim, interpret=True)
    want = _latent_xla(q, arena, ptab, ctx, scale, v_dim)
    assert got.shape == want.shape == (B, 8, v_dim)
    assert got.dtype == want.dtype == dtype
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    # the tolerance of test_kernel_matches_the_xla_body
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 if dtype == jnp.float32 else 1e-2)
    empty = np.asarray(ctx) == 0
    assert np.all(got[empty] == 0.0)
    assert np.all(np.any(got[~empty] != 0.0, axis=-1))
    assert int(A.latent_walked_keys(ctx, PS)) == sum(
        -(-c // PS) * PS for c in live.values())


def test_latent_decode_predicate_is_rows_and_platform():
    uses = A.latent_decode_uses_kernel
    assert jax.default_backend() == "cpu"
    assert not uses(1) and not uses(1, "cpu") and not uses(1, "gpu")
    assert uses(1, "tpu")
    assert not uses(2, "tpu") and not uses(128, "tpu")
    # a chunk's rows never take it, a step's rows never the block kernel
    assert not any(uses(r, "tpu") and A.streamed_attention_uses_kernel(r, "tpu")
                   for r in (1, 2, 127, 128, 512))


def _toy_programs(mod, cfg, rows=None, slots=4, page=16):
    """A model's step program (and its chunk program of `rows` rows),
    lowered for the platform "tpu" from shapes alone: tables as wide as
    `max_seq`, a sliding kind's as its window and a chunk, a state kind's
    one entry."""
    params = jax.eval_shape(
        lambda: mod.serve_view(mod.init(jax.random.PRNGKey(0), cfg), cfg))
    kinds = mod.cache_kinds(cfg)
    cache = jax.eval_shape(
        lambda: mod.init_paged_cache(cfg, {k: 64 for k in kinds}, page))
    widths = {k: 1 if w == "state" else
              (cfg.max_seq if w is None else w + 128) // page
              for k, w in kinds.items()}
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)

    def lowered(fn, *args):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    out = {"step": lowered(
        lambda p, c, t, tabs, pos: mod.paged_decode_step(p, c, t, tabs, pos,
                                                         cfg),
        params, cache, ints(slots),
        {k: ints(slots, w) for k, w in widths.items()}, ints(slots))}
    if rows:
        # a model that asks is told whether the chunk is its prompt's last
        last = ([jax.ShapeDtypeStruct((), jnp.bool_)]
                if getattr(mod, "PREFILL_KNOWS_LAST", False) else [])
        out["chunk"] = lowered(
            lambda p, c, t, tabs, *ops: mod.paged_prefill(p, c, t, tabs,
                                                          *ops, cfg=cfg),
            params, cache, ints(rows), {k: ints(w) for k, w in widths.items()},
            i32, i32, *last)
    return out


def _toy(name):
    """(module, config) of a served model at toy size, the widths its
    kernels need on a TPU (128 positions a page's lanes)."""
    from ray_tpu.models import (brumby, cohere2_moe, deepseek_v3, dots3,
                                falcon_h1, gpt, lfm2_moe, ling3, phi4flash)
    return {
        "phi-4-flash": lambda: (phi4flash, phi4flash.Phi4FlashConfig.nano(
            max_seq=512, kv_block=128, sliding_window=128, d_head=64)),
        "gpt2": lambda: (gpt, gpt.GPTConfig.nano(max_seq=512)),
        "command-a-plus": lambda: (cohere2_moe, cohere2_moe.Cohere2MoEConfig
                                   .nano(max_seq=512, kv_block=128,
                                         sliding_window=128, d_head=128)),
        "brumby": lambda: (brumby, brumby.BrumbyConfig.nano()),
        "deepseek-v3": lambda: (deepseek_v3, deepseek_v3.DeepSeekV3Config
                                .nano(max_seq=512, kv_block=128)),
        "ling-3": lambda: (ling3, ling3.Ling3Config.nano(max_seq=512,
                                                         kv_block=128)),
        "dots3": lambda: (dots3, dots3.Dots3Config.nano(
            max_seq=512, kv_block=128, window=128)),
        "falcon-h1": lambda: (falcon_h1, falcon_h1.FalconH1Config.nano(
            max_seq=512, kv_block=128, d_head=128, ssm_head_dim=128,
            d_state=128, ssm_chunk=128)),
        "lfm2-moe": lambda: (lfm2_moe, lfm2_moe.Lfm2MoeConfig.nano(
            max_seq=512, kv_block=128, d_head=128)),
    }[name]()


@pytest.mark.parametrize("name,latent_layers", [("deepseek-v3", 3),
                                                ("ling-3", 1)])
def test_a_latent_step_program_holds_the_decode_kernel_once(
        monkeypatch, name, latent_layers):
    """A step program's latent layers all walk their pages through ONE
    lowered function with one Mosaic module in it, and gather no block;
    the chunk program holds the block kernel as before and no walk; off
    the TPU neither program holds either."""
    mod, cfg = _toy(name)
    on_cpu = _toy_programs(mod, cfg, rows=128)
    assert "custom_call" not in on_cpu["step"] + on_cpu["chunk"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _toy_programs(mod, cfg, rows=128, page=128)
    step, chunk = text["step"], text["chunk"]
    assert step.count("call @_latent_decode") == latent_layers
    assert step.count("func.func private @_latent_decode") == 1
    assert step.count('kernel_name = "latent_decode_attention"') == 1
    assert "_streamed_block" not in step
    # the write gathers a page a slot; a block of a step's keys was
    # [slots, 128 positions, latent] gathered under the scope
    assert not re.findall(r"gather.*mla_attend_step", step)
    assert "_latent_decode" not in chunk
    assert chunk.count("call @_streamed_block") == latent_layers
    assert chunk.count("func.func private @_streamed_block") == 1


def _digest(text):
    """sha256 of a lowered program's text, less what a Mosaic module is
    serialised to (it names the source's path and the lowering's call
    stack, which differ from one process to the next)."""
    import hashlib
    return hashlib.sha256(re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', "",
                                 text).encode()).hexdigest()[:16]


# a program's `_digest`, lowered for the platform "tpu": every served
# model's step and chunk program.  `deepseek-v3`'s two and `ling-3`'s step
# were pinned at d07b063 (the parent of PR 50), the other seven at 67e7002
# (the parent of PR 48), `phi-4-flash`'s two by PR 51, which added them;
# PR 53 pinned the six of the three MoE models anew (`ops/moe`'s trips of
# grouped products), PR 57 `brumby`'s chunk (its retention a kernel; the
# digest leaves a Mosaic module out, so a change INSIDE a kernel moves
# none), PR 60 `ling-3`'s chunk (`ops/kda.kda_chunk` by sub-blocks and the
# carried entry read where it stands; its step stays), PR 62 the four of
# `deepseek-v3` and `ling-3` (the held experts' gate and up in one leaf: two
# grouped products a trip) and, new with it, `dots3`'s two, which run the
# same `layer_ffn`; `command-a-plus` keeps gate and up apart and kept its
# two digests; PR 64 the eight of the four models with routed experts
# (`command-a-plus`, `deepseek-v3`, `ling-3`, `dots3`: what `ops/moe`'s
# held experts do once a layer before their trips — one sort that carries
# each pair's token and weight, the loads by comparison; the other six
# stand).  PR 65 pinned `falcon-h1`'s and `lfm2-moe`'s four on its parent
# (4322a48), then moved what the models share into `models/served.py`: all
# eighteen stood, and `phi-4-flash`'s chunk alone was pinned anew
# (ce1a43a62b84b2a7 before) where `paged_prefill` took `served.carried_at`
# (a `dynamic_slice` of the entry for `state[j]`'s copy of a layer's part
# of the arena, as the other three state models).  A PR that moves or renames Python functions
# leaves every digest alone (the text carries no source locations; their
# kernels' source lines unmoved, the compile-cache keys stay too).  A PR
# that edits one of these programs finds the new digest in the failure and
# pins it.
PARENT_TEXT = {
    ("gpt2", "step"): "1921a8ec3c8503f9",
    ("gpt2", "chunk"): "74eee7fd9dc8f6cb",
    ("command-a-plus", "step"): "a994456c5c44057b",
    ("command-a-plus", "chunk"): "00d18a2a1d712aba",
    ("brumby", "step"): "c95c739da7c26ef7",
    ("brumby", "chunk"): "3890e9f178cbbf96",
    ("deepseek-v3", "step"): "3f921a3e5c3478de",
    ("deepseek-v3", "chunk"): "b0ba0dd299ec3dbc",
    ("ling-3", "step"): "3b7d18ab33f98ee0",
    ("ling-3", "chunk"): "0cced1068f44c019",
    ("phi-4-flash", "step"): "d0a2de26cb6e8b1b",
    ("phi-4-flash", "chunk"): "84cb688d77c6767c",
    ("dots3", "step"): "761cd07b8a152eca",
    ("dots3", "chunk"): "2d9a40e82f47d611",
    ("falcon-h1", "step"): "5643c706bd336a98",
    ("falcon-h1", "chunk"): "0692b98e74ad86d6",
    ("lfm2-moe", "step"): "b4c5b5a07ecac816",
    ("lfm2-moe", "chunk"): "00e99d960a685919",
}


@pytest.mark.parametrize("name", sorted({n for n, _ in PARENT_TEXT}))
def test_untouched_programs_lower_to_the_parents_text(monkeypatch, name):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod, cfg = _toy(name)
    text = _toy_programs(mod, cfg, rows=128, page=128)
    got = {(name, k): _digest(t) for k, t in text.items()
           if (name, k) in PARENT_TEXT}
    assert got == {k: v for k, v in PARENT_TEXT.items() if k[0] == name}


# -- paged decode attention (PR 51): a step's walk over K/V pages -------------


def _plain_decode(q, ka, va, ptab, kpos, qpos, window, scale):
    """One slot: q [H, R, d] against its table's pages laid end to end,
    key positions kpos [W * ps] (negative: nothing there)."""
    W, ps = ptab.shape[0], ka.shape[1]
    H, _, d = q.shape
    k = ka[ptab].reshape(W * ps, H, d)
    v = va[ptab].reshape(W * ps, H, d)
    dist = qpos - kpos
    ok = (kpos >= 0) & (dist >= 0)
    if window is not None:
        ok = ok & (dist < window)
    s = jnp.where(ok[None, None], jnp.einsum("hrd,shd->hrs", q, k) * scale,
                  -jnp.inf)
    return jnp.einsum("hrs,shd->hrd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("window", [None, 300], ids=["full", "ring"])
def test_paged_decode_attention_walks_what_a_query_sees(window):
    """The kernel in interpret mode against plain attention over each
    slot's own pages: a full kind's table walked as far as the context
    (one position, a page's edge, several blocks of four pages), a ring
    read through its window (pages the window has passed are entries of
    the table still), an empty slot fetching nothing and reading zero."""
    from ray_tpu.ops.attention import paged_decode_attention

    B, H, R, d, ps, P, W = 5, 2, 4, 128, 128, 48, 9
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, R, d), jnp.float32)
    ka = jax.random.normal(ks[1], (P, ps, H * d), jnp.float32)
    va = jax.random.normal(ks[2], (P, ps, H * d), jnp.float32)
    ptab = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, B * W + 1)).reshape(B, W))
    live = np.array([1, 0, 1, 1, 1])
    if window is None:
        qpos = np.array([299, 0, 0, 1151, 127])
        bases = np.broadcast_to(np.arange(W) * ps, (B, W))
        walk = np.where(live, qpos // ps + 1, 0)
    else:       # logical page lp in entry lp % W
        qpos = np.array([299, 0, 1500, 2047, 5])
        hi = (qpos // ps)[:, None]
        lp = hi - (hi - np.arange(W)[None]) % W
        bases = np.where(lp >= 0, lp * ps, -1)
        walk = np.where(live, W, 0)
    got = paged_decode_attention(
        q, ka, va, ptab, jnp.asarray(bases), jnp.asarray(qpos),
        jnp.asarray(walk), scale=0.125, window=window, interpret=True)
    for b in range(B):
        if not live[b]:
            assert float(jnp.abs(got[b]).max()) == 0.0
            continue
        kpos = jnp.where(bases[b][:, None] >= 0,
                         bases[b][:, None] + jnp.arange(ps), -1).reshape(-1)
        want = _plain_decode(q[b], ka, va, ptab[b], kpos, int(qpos[b]),
                             window, 0.125)
        assert float(jnp.abs(got[b] - want).max()) < 2e-6, b
