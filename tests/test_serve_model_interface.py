"""The serving engine's model interface (ray_tpu/serve/_engine.py, "The
model interface"): every served module is asked the same questions, and a
module that lacks a member is refused when an engine is made — by name,
before a weight is cast or a request admitted.

Shapes alone (`jax.eval_shape` at the `nano` sizes): nothing is compiled.
"""
import ast
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import (brumby, cohere2_moe, deepseek_v3, dots3,
                            falcon_h1, gpt, lfm2_moe, ling3, phi4flash,
                            served)
from ray_tpu.serve._engine import ContinuousEngine, _check_interface

MODELS = {
    "gpt2": (gpt, gpt.GPTConfig.nano()),
    "command-a-plus": (cohere2_moe, cohere2_moe.Cohere2MoEConfig.nano()),
    "brumby": (brumby, brumby.BrumbyConfig.nano()),
    "deepseek-v3": (deepseek_v3, deepseek_v3.DeepSeekV3Config.nano()),
    "ling-3": (ling3, ling3.Ling3Config.nano()),
    "dots3-note": (dots3, dots3.Dots3Config.nano()),
    "phi-4-flash": (phi4flash, phi4flash.Phi4FlashConfig.nano()),
    "falcon-h1": (falcon_h1, falcon_h1.FalconH1Config.nano()),
    "lfm2-moe": (lfm2_moe, lfm2_moe.Lfm2MoeConfig.nano()),
}
REQUIRED = ("cache_kinds", "init_paged_cache", "paged_decode_step",
            "paged_prefill", "serve_view")
SLOTS, PAGE, PAGES, ROWS = 4, 8, 9, 16

model = pytest.mark.parametrize("name", sorted(MODELS))


def _without(mod, *members):
    """`mod`'s members in a stub module, less `members`."""
    return types.SimpleNamespace(
        **{k: v for k, v in vars(mod).items() if k not in members})


def _is_state(w):
    return w == "state"


def _is_full(w):
    return w is None


def _operands(mod, cfg):
    """(view, kinds, cache, table widths) as an engine of SLOTS slots and
    PAGE positions a page makes them, as shapes."""
    view = jax.eval_shape(
        lambda: mod.serve_view(mod.init(jax.random.PRNGKey(0), cfg), cfg))
    kinds = mod.cache_kinds(cfg)
    cache = jax.eval_shape(
        lambda: mod.init_paged_cache(cfg, {k: PAGES for k in kinds}, PAGE))
    widths = {k: 1 if _is_state(w) else
              (cfg.max_seq if _is_full(w) else w + ROWS) // PAGE
              for k, w in kinds.items()}
    return view, kinds, cache, widths


def _shapes(tree):
    return jax.tree.map(lambda a: (a.shape, a.dtype), tree)


@model
def test_a_served_module_passes_the_check(name):
    assert _check_interface(*MODELS[name]) is None


@model
@pytest.mark.parametrize("member", REQUIRED)
def test_a_missing_member_is_named_when_the_engine_is_made(name, member):
    mod, cfg = MODELS[name]
    with pytest.raises(TypeError, match=f"{mod.__name__}.*`{member}`"):
        ContinuousEngine(_without(mod, member), cfg, None)


@model
def test_what_the_kinds_ask_for_is_required_and_nothing_else(name):
    mod, cfg = MODELS[name]
    kinds = mod.cache_kinds(cfg)
    assert kinds and all(
        _is_full(w) or _is_state(w) or (type(w) is int and w > 0)
        for w in kinds.values())
    # `state_leaves` iff a kind is a state, `copy_page` iff every kind
    # keeps every position: the engine calls each only then
    has_state = any(map(_is_state, kinds.values()))
    shares = all(map(_is_full, kinds.values()))
    assert hasattr(mod, "state_leaves") == has_state
    assert hasattr(mod, "copy_page") == shares
    for member, asked in (("state_leaves", has_state), ("copy_page", shares)):
        if asked:
            with pytest.raises(TypeError, match=f"`{member}`"):
                ContinuousEngine(_without(mod, member), cfg, None)
        else:
            _check_interface(_without(mod, member), cfg)


@model
def test_the_cache_is_made_from_the_engines_page_counts(name):
    mod, cfg = MODELS[name]
    _, kinds, cache, _ = _operands(mod, cfg)
    leaves = jax.tree.leaves(cache)
    assert leaves and all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
    if hasattr(mod, "state_leaves"):
        state = mod.state_leaves(cache)
        assert state and all(any(a is b for b in leaves) for a in state)
        # a model all of whose kinds are states keeps nothing else
        assert (len(state) == len(leaves)) == all(
            map(_is_state, kinds.values()))
    if hasattr(mod, "copy_page"):
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        assert _shapes(jax.eval_shape(mod.copy_page, cache, i32, i32)) == \
            _shapes(cache)


@model
@pytest.mark.parametrize("program", ["step", "chunk"])
def test_a_program_returns_logits_the_cache_and_its_stats(name, program):
    mod, cfg = MODELS[name]
    view, _, cache, widths = _operands(mod, cfg)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "step":
        logits, after, *stats = jax.eval_shape(
            lambda p, c, t, tabs, pos: mod.paged_decode_step(
                p, c, t, tabs, pos, cfg),
            view, cache, ints(SLOTS),
            {k: ints(SLOTS, w) for k, w in widths.items()}, ints(SLOTS))
        assert logits.shape == (SLOTS, cfg.vocab_size)
    else:
        # a module that asks (`PREFILL_KNOWS_LAST`) is told whether the
        # chunk is its prompt's last; no other takes the operand
        last = ([jax.ShapeDtypeStruct((), jnp.bool_)]
                if getattr(mod, "PREFILL_KNOWS_LAST", False) else [])
        logits, after, *stats = jax.eval_shape(
            lambda p, c, t, tabs, *ops: mod.paged_prefill(
                p, c, t, tabs, *ops, cfg=cfg),
            view, cache, ints(ROWS), {k: ints(w) for k, w in widths.items()},
            ints(), ints(), *last)
        if not last:
            with pytest.raises(TypeError):
                mod.paged_prefill(view, cache, ints(ROWS), {}, ints(), ints(),
                                  last, cfg=cfg)
        assert logits.shape == (cfg.vocab_size,)
    # the engine donates the cache: what comes back takes its place
    assert _shapes(after) == _shapes(cache)
    names = getattr(mod, "STEP_STATS", ())
    assert [(s.shape, s.dtype) for s in stats] == (
        [((len(names),), jnp.float32)] if names else [])


@model
def test_the_view_of_a_view_is_the_view(name):
    mod, cfg = MODELS[name]
    view = _operands(mod, cfg)[0]
    again = jax.eval_shape(lambda: mod.serve_view(mod.serve_view(
        mod.init(jax.random.PRNGKey(0), cfg), cfg), cfg))
    assert jax.tree.structure(again) == jax.tree.structure(view)
    assert _shapes(again) == _shapes(view)


# -- where a served module may take code from (PR 65) -------------------------

# what GPT-2 defines and a later model reuses
FROM_GPT = {"cast_leaves", "slot_embed", "unembed_table", "apply_norm",
            "qkv_of_normed", "attn_out"}
# a family's base: latent attention is deepseek_v3's
FAMILY = {"ray_tpu.models.ling3": "deepseek_v3",
          "ray_tpu.models.dots3": "deepseek_v3"}
# the one `_`-name a module may bind from another, `served.draw` as
# `_draw`, and only while the benchmark's drivers import it so (ROADMAP
# D17): module -> the driver lines that read it.  (`deepseek_v3._draw`,
# which replica_deepseek_v3.py:24 imports, is that module's own function:
# the recipe a dispatch a piece, kept for its first run's compiles.)
DRAW_BINDINGS = {
    "ray_tpu.models.ling3": {
        "replica_ling3.py": "from ray_tpu.models.ling3 import LEAVES, _draw",
        "replica_lfm2_moe.py": "from ray_tpu.models.ling3 import _draw",
        "replica_falcon_h1.py": "from ray_tpu.models.ling3 import _draw"},
    "ray_tpu.models.dots3": {
        "replica_dots3.py": "from ray_tpu.models.dots3 import LEAVES, _draw"},
}
REPO = pathlib.Path(__file__).resolve().parent.parent


def _tree(mod):
    return ast.parse(pathlib.Path(mod.__file__).read_text())


def _sibling_imports(tree):
    """[(the `ray_tpu.models` module a line of a module's source imports
    from — None: the package itself —, name, asname)]."""
    out = []
    for node in tree.body:
        if not isinstance(node, ast.ImportFrom):
            assert not (isinstance(node, ast.Import) and any(
                a.name.startswith("ray_tpu.models") for a in node.names))
            continue
        if node.level == 1:
            src = node.module
        elif (node.module or "").startswith("ray_tpu.models"):
            src = node.module[len("ray_tpu.models"):].lstrip(".") or None
        else:
            continue
        out += [(src, a.name, a.asname) for a in node.names]
    return out


@model
def test_a_served_module_takes_code_from_below_it_only(name):
    """A served module imports from `ray_tpu.ops`, from `models/served.py`,
    from `gpt` what GPT-2 defines and from its family's base — from no
    other sibling, and no name that starts with `_`; through a module's
    alias it reads public names only."""
    mod = MODELS[name][0]
    me, tree = mod.__name__, _tree(mod)
    allowed = {"served", "gpt", FAMILY.get(me)} - {None}
    aliases, draws = {}, 0
    for src, what, asname in _sibling_imports(tree):
        if src is None:                     # from . import <module> [as ..]
            assert what in allowed, (me, what)
            aliases[asname or what] = what
            continue
        assert src in allowed, (me, src)
        assert not what.startswith("_"), (me, src, what)
        if src == "gpt":
            assert what in FROM_GPT, (me, what)
        if (asname or what).startswith("_"):
            assert (src, what, asname) == ("served", "draw", "_draw"), (
                me, src, what, asname)
            draws += 1
    assert draws == (me in DRAW_BINDINGS), me
    for driver, line in DRAW_BINDINGS.get(me, {}).items():
        assert line in (REPO / "benchmarks" / "drivers" / driver).read_text()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id in aliases):
            assert not node.attr.startswith("_"), (me, node.attr)


def test_what_the_models_share_stands_on_the_ops_alone():
    """`models/served.py` imports the standard library, `jax` and
    `ray_tpu.ops`; what it offers is public and in `__all__`."""
    tree = _tree(served)
    assert not _sibling_imports(tree)
    for node in tree.body:
        if isinstance(node, ast.ImportFrom) and node.module != "__future__":
            assert node.module.split(".")[0] in ("jax", "ray_tpu"), node.module
            assert not node.module.startswith("ray_tpu") or \
                node.module.startswith("ray_tpu.ops"), node.module
    assert all(not n.startswith("_") and hasattr(served, n)
               for n in served.__all__)
    taken = {what for mod, _ in MODELS.values()
             for src, what, _ in _sibling_imports(_tree(mod))
             if src == "served"}
    assert taken <= set(served.__all__), taken - set(served.__all__)
