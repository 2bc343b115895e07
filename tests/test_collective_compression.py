"""Compressed collectives: block-wise int8 quantization, quantized
allreduce on both backends, and error-feedback training (tier-1; CPU
exercises the real numerics through the XLA-fallback kernels)."""

import dataclasses

import numpy as np
import pytest

import ray_tpu
from ray_tpu.collective.compression import (CompressionConfig,
                                            compress_array,
                                            compression_residual,
                                            decompress_array,
                                            parse_compression,
                                            result_block_size,
                                            set_group_compression,
                                            wire_bytes, wire_ratio)


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30)


# ---------------------------------------------------------------------------
# quantize/dequantize kernels (ops/quantize.py, XLA fallback on CPU)
# ---------------------------------------------------------------------------


def test_roundtrip_error_bound_per_block_size():
    """Unit-scale gaussian round-trip error: bounded for every block
    size, and coarser blocks (bigger absmax per scale) hurt."""
    import jax.numpy as jnp

    from ray_tpu.ops import dequantize_blockwise, quantize_blockwise

    x = np.random.default_rng(0).standard_normal(1 << 14).astype(np.float32)
    errs = {}
    for block in (64, 256, 1024):
        q, s = quantize_blockwise(jnp.asarray(x), block)
        assert q.dtype == jnp.int8 and s.shape == (x.size // block,)
        back = dequantize_blockwise(q, s, x.shape, jnp.float32, block)
        errs[block] = _rel(np.asarray(back), x)
        assert errs[block] < 1e-2, (block, errs[block])
    assert errs[64] < errs[256] < errs[1024]


def test_roundtrip_bf16_and_f32_inputs():
    import jax.numpy as jnp

    from ray_tpu.ops import dequantize_blockwise, quantize_blockwise

    x = np.random.default_rng(1).standard_normal(4096).astype(np.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        xj = jnp.asarray(x, dtype)
        q, s = quantize_blockwise(xj, 256)
        back = dequantize_blockwise(q, s, xj.shape, dtype, 256)
        assert back.dtype == dtype
        # bf16 adds its own ~0.4% mantissa rounding on top of int8
        assert _rel(np.asarray(back, np.float32),
                    np.asarray(xj, np.float32)) < 1.5e-2


def test_trailing_remainder_not_multiple_of_block():
    """A 1000-element array against block=256: the 232-element trailing
    remainder shares the last block with zero padding, which quantizes
    to exact zeros — shape, dtype, and accuracy all survive."""
    import jax.numpy as jnp

    from ray_tpu.ops import dequantize_blockwise, quantize_blockwise

    x = np.random.default_rng(2).standard_normal((10, 100)).astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x), 256)
    assert q.shape == (1024,) and s.shape == (4,)
    # padding lanes are exact zeros on the wire
    assert np.all(np.asarray(q)[1000:] == 0)
    back = dequantize_blockwise(q, s, x.shape, jnp.float32, 256)
    assert back.shape == x.shape
    assert _rel(np.asarray(back), x) < 1e-2


def test_stochastic_rounding_is_unbiased():
    import jax.numpy as jnp

    from ray_tpu.ops import dequantize_blockwise, quantize_blockwise

    x = np.linspace(-1.0, 1.0, 2048, dtype=np.float32)
    outs = []
    for seed in range(32):
        q, s = quantize_blockwise(jnp.asarray(x), 256, stochastic=True,
                                  seed=seed)
        outs.append(np.asarray(
            dequantize_blockwise(q, s, x.shape, jnp.float32, 256)))
        assert _rel(outs[-1], x) < 2e-2  # noisier than round-to-even
    # the average over draws converges on x: bias ≪ single-draw error
    assert _rel(np.mean(outs, axis=0), x) < 2e-3


def test_stochastic_kernel_interpret_agrees_with_xla():
    """The Pallas stochastic kernel (its random bits reach f32 through a
    signed 32-bit intermediate, because Mosaic has no uint32 -> f32 cast;
    tests/test_chip_compile.py holds it to the chip's compiler) against
    _quantize_xla on the same blocks: the same scales, and every value
    floor(y) or floor(y)+1 of the XLA path's y — never further, whatever
    the bits.  The interpreter's PRNG hands out zero bits, so how the
    draws are DISTRIBUTED is checked where there is a real one: on the
    chip, by chip_smoke.py's train phase."""
    import jax.numpy as jnp

    from ray_tpu.ops.quantize import (_as_blocks, _block_scales,
                                      _quantize_xla, quantize_blockwise)

    x = np.random.default_rng(5).standard_normal(64 * 256).astype(np.float32)
    blocks = _as_blocks(jnp.asarray(x), 256)
    _, s_xla = _quantize_xla(blocks, False, None)
    y = np.asarray(blocks * (1.0 / _block_scales(blocks))).reshape(-1)
    q, s = quantize_blockwise(jnp.asarray(x), 256, stochastic=True,
                              seed=3, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_xla), rtol=1e-6)
    q = np.asarray(q, np.float32)
    # 1e-3 of slack: the two paths' scales may differ in the last ulp
    assert np.all(q >= np.clip(np.floor(y - 1e-3), -127, 127))
    assert np.all(q <= np.clip(np.floor(y + 1e-3) + 1, -127, 127))


def test_host_codec_matches_jax_numerics():
    """compress_array (numpy, kv wire path) and the XLA-lowered kernels
    must agree bit-for-bit with deterministic rounding — error feedback
    recomputes residuals host-side relying on it."""
    import jax.numpy as jnp

    from ray_tpu.ops import dequantize_blockwise, quantize_blockwise

    x = np.random.default_rng(3).standard_normal(5000).astype(np.float32)
    cc = CompressionConfig(min_size=0)
    payload = compress_array(x, cc)
    q, s = quantize_blockwise(jnp.asarray(x), cc.block_size)
    assert np.array_equal(payload["v"], np.asarray(q))
    assert np.array_equal(payload["s"], np.asarray(s))
    host = decompress_array(payload)
    dev = np.asarray(dequantize_blockwise(q, s, x.shape, jnp.float32,
                                          cc.block_size))
    assert np.array_equal(host, dev)


# ---------------------------------------------------------------------------
# config / spec plumbing (collective/compression.py)
# ---------------------------------------------------------------------------


def test_spec_parsing_roundtrip_and_errors():
    cc = parse_compression("int8:block=512,stochastic=1,ef=0,min=64")
    assert cc == CompressionConfig(block_size=512, stochastic=True,
                                   error_feedback=False, min_size=64)
    assert parse_compression(cc.to_spec()) == cc
    assert parse_compression("int8") == CompressionConfig()
    assert parse_compression("") is None
    assert parse_compression("off") is None
    assert parse_compression(None) is None
    with pytest.raises(ValueError, match="dtype"):
        parse_compression("int4")
    with pytest.raises(ValueError, match="unknown compression spec key"):
        parse_compression("int8:bogus=1")


def test_wire_ratio_meets_budget():
    """int8 at block=256 must move ≤ ~0.3x of f32 on the wire, on the
    actual payload AND accounting for the finer result stage."""
    cc = CompressionConfig(min_size=0)
    x = np.random.default_rng(4).standard_normal(1 << 16).astype(np.float32)
    payload = compress_array(x, cc)
    assert wire_bytes(payload) / x.nbytes <= 0.27
    assert wire_ratio(x.size, cc) <= 0.27
    rcc = CompressionConfig(block_size=result_block_size(cc.block_size),
                            min_size=0)
    round_trip = (wire_ratio(x.size, cc) + wire_ratio(x.size, rcc)) / 2
    assert round_trip <= 0.3


def test_compression_resolution_precedence():
    from ray_tpu.collective.collective import _resolve_op_compression

    x = np.zeros(4096, np.float32)
    # explicit + incompatible op is an error ...
    with pytest.raises(ValueError, match="sum"):
        _resolve_op_compression(x, "max", "int8")
    try:
        set_group_compression("int8:block=128")
        # ... but a group DEFAULT steps aside for max/min silently
        assert _resolve_op_compression(x, "max", None) is None
        got = _resolve_op_compression(x, "sum", None)
        assert got is not None and got.block_size == 128
        # explicit off beats the default
        assert _resolve_op_compression(x, "sum", "off") is None
        # small payloads aren't worth the scale overhead
        assert _resolve_op_compression(np.zeros(8, np.float32),
                                       "sum", None) is None
        # non-float payloads pass through
        assert _resolve_op_compression(np.zeros(4096, np.int64),
                                       "sum", None) is None
    finally:
        set_group_compression(None)


# ---------------------------------------------------------------------------
# compiled quantized collectives (xla_group.py) on the 8-device CPU mesh
# ---------------------------------------------------------------------------


def _dp_mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("dp",))


def test_mesh_quantized_allreduce_matches_fp32():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.collective import xla_group

    mesh = _dp_mesh()
    world = mesh.shape["dp"]
    rng = np.random.default_rng(5)
    for n in (2048, 1000):  # multiple and non-multiple of world*block
        g = rng.standard_normal((world, n)).astype(np.float32)
        arr = jax.device_put(jnp.asarray(g),
                             NamedSharding(mesh, P("dp")))
        full = np.asarray(xla_group.mesh_allreduce(arr, mesh, "dp",
                                                   op="mean"))
        comp = np.asarray(xla_group.mesh_allreduce(
            arr, mesh, "dp", op="mean", compression="int8:min=0"))
        assert _rel(comp, full) < 1e-2, n
    with pytest.raises(ValueError, match="sum"):
        xla_group.mesh_allreduce(arr, mesh, "dp", op="max",
                                 compression="int8")


def test_mesh_quantized_reducescatter_and_allgather():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.collective import xla_group

    mesh = _dp_mesh()
    world = mesh.shape["dp"]
    rng = np.random.default_rng(6)
    x = rng.standard_normal((world, world * 256)).astype(np.float32)
    arr = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("dp")))
    rs_f = np.asarray(xla_group.mesh_reducescatter(arr, mesh, "dp"))
    rs_q = np.asarray(xla_group.mesh_reducescatter(arr, mesh, "dp",
                                                   compression="int8"))
    assert rs_q.shape == rs_f.shape
    assert _rel(rs_q, rs_f) < 1e-2
    ag_f = np.asarray(xla_group.mesh_allgather(arr, mesh, "dp"))
    ag_q = np.asarray(xla_group.mesh_allgather(arr, mesh, "dp",
                                               compression="int8"))
    assert ag_q.shape == ag_f.shape
    assert _rel(ag_q, ag_f) < 1e-2


# ---------------------------------------------------------------------------
# kv backend end-to-end (control-plane wire path)
# ---------------------------------------------------------------------------


@ray_tpu.remote
class CompressedWorker:
    def __init__(self, rank, world):
        self.rank = rank
        self.world = world

    def setup(self, group):
        from ray_tpu import collective as col

        col.init_collective_group(self.world, self.rank, backend="kv",
                                  group_name=group)
        return True

    def do_allreduce(self, group, seed, compression):
        from ray_tpu import collective as col

        x = np.random.default_rng(seed + self.rank).standard_normal(
            4096).astype(np.float32)
        return col.allreduce(x, group, op="mean", compression=compression)

    def do_grad_sync(self, group, steps):
        from ray_tpu.parallel import GradientSynchronizer

        sync = GradientSynchronizer(group_name=group,
                                    compression="int8:min=0")
        outs = []
        for t in range(steps):
            g = np.random.default_rng(100 * t + self.rank).standard_normal(
                2048).astype(np.float32)
            outs.append(sync({"w": g})["w"])
        return outs

    def destroy_and_count_keys(self, group):
        from ray_tpu import collective as col
        from ray_tpu.collective.collective import _NS, _kv

        before = _kv().call("kv_keys", {"ns": _NS, "prefix": f"{group}/"})
        col.destroy_collective_group(group)
        after = _kv().call("kv_keys", {"ns": _NS, "prefix": f"{group}/"})
        return len(before or []), len(after or [])


def test_kv_compressed_allreduce(ray_cluster):
    world = 2
    workers = [CompressedWorker.remote(r, world) for r in range(world)]
    assert all(ray_tpu.get([w.setup.remote("qg") for w in workers],
                           timeout=120))
    outs = ray_tpu.get(
        [w.do_allreduce.remote("qg", 7, "int8:min=0") for w in workers],
        timeout=120)
    expected = np.mean([np.random.default_rng(7 + r).standard_normal(4096)
                        for r in range(world)], axis=0).astype(np.float32)
    # all ranks land on the SAME quantized value, close to the exact mean
    assert np.array_equal(outs[0], outs[1])
    assert _rel(outs[0], expected) < 1e-2

    # GradientSynchronizer over the same group: synced, bounded error
    grads = ray_tpu.get([w.do_grad_sync.remote("qg", 3) for w in workers],
                        timeout=120)
    for t in range(3):
        assert np.array_equal(grads[0][t], grads[1][t])
        exact = np.mean([np.random.default_rng(100 * t + r)
                         .standard_normal(2048) for r in range(world)],
                        axis=0).astype(np.float32)
        assert _rel(grads[0][t], exact) < 2e-2


def test_destroy_sweeps_residual_mailbox_keys(ray_cluster):
    """A group's ops leave {name}/{op_idx}/... keys in the control-plane
    KV; destroy must sweep them, not just the caller's init key."""
    world = 1
    (w,) = [CompressedWorker.remote(r, world) for r in range(world)]
    assert ray_tpu.get(w.setup.remote("sweepg"), timeout=120)
    ray_tpu.get(w.do_allreduce.remote("sweepg", 1, None), timeout=120)
    before, after = ray_tpu.get(w.destroy_and_count_keys.remote("sweepg"),
                                timeout=120)
    assert before >= 2   # init key + allreduce mailbox entries
    assert after == 0


# ---------------------------------------------------------------------------
# error-feedback training (host-side dp simulation, 50 steps)
# ---------------------------------------------------------------------------


def _toy_dp_training(compressed, error_feedback, steps=50, world=4,
                     dim=2048, lr=0.5, seed=0):
    """Heterogeneous-worker quadratic: worker i pulls toward target t_i,
    so per-worker gradients stay O(1) at the optimum (only their mean
    vanishes) — exactly the regime where compression error accumulates
    without EF.  Mirrors GradientSynchronizer's pipeline: corrected
    contribution -> codec round trip -> mean -> result-stage requantize."""
    rng = np.random.default_rng(seed)
    center = rng.standard_normal(dim).astype(np.float32)
    targets = [center + rng.standard_normal(dim).astype(np.float32)
               for _ in range(world)]
    mean_t = np.mean(targets, axis=0)
    w = np.zeros(dim, np.float32)
    cc = CompressionConfig(min_size=0)
    rcc = dataclasses.replace(cc,
                              block_size=result_block_size(cc.block_size))
    residuals = [np.zeros(dim, np.float32) for _ in range(world)]
    for _ in range(steps):
        grads = [w - t for t in targets]
        if not compressed:
            g = np.mean(grads, axis=0)
        else:
            contribs = []
            for i in range(world):
                c = grads[i] + (residuals[i] if error_feedback else 0.0)
                contribs.append(decompress_array(compress_array(c, cc)))
                if error_feedback:
                    residuals[i] = compression_residual(c, cc)
            g = decompress_array(compress_array(
                np.mean(contribs, axis=0), rcc))
        w = w - lr * g
    loss = float(np.mean([0.5 * np.mean((w - t) ** 2) for t in targets]))
    excess = float(0.5 * np.mean((w - mean_t) ** 2))
    return loss, excess


def test_error_feedback_closes_training_gap():
    loss_ref, excess_ref = _toy_dp_training(False, False)
    loss_ef, excess_ef = _toy_dp_training(True, True)
    loss_raw, excess_raw = _toy_dp_training(True, False)
    # compressed-with-EF converges to within 5% of the uncompressed loss
    assert abs(loss_ef - loss_ref) / loss_ref < 0.05
    assert excess_ref < 1e-9        # uncompressed finds the optimum
    # and EF visibly closes the distance-to-optimum gap vs plain
    # compression (deterministic: fixed seeds)
    assert excess_ef < excess_raw / 1.5


# ---------------------------------------------------------------------------
# satellite: ulysses head-divisibility validation
# ---------------------------------------------------------------------------


def test_ulysses_validates_heads_divisible_by_sp():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.ops import ulysses_attention

    mesh = Mesh(np.array(jax.devices()), ("sp",))
    sp = mesh.shape["sp"]
    heads = sp + 1 if sp > 1 else 3
    q = jnp.zeros((1, heads, 2 * sp, 8), jnp.float32)
    with pytest.raises(ValueError, match=rf"heads \({heads}\).*\({sp}\)"):
        ulysses_attention(q, q, q, mesh, axis_name="sp")
    with pytest.raises(ValueError, match="not in"):
        ulysses_attention(q, q, q, mesh, axis_name="nope")
