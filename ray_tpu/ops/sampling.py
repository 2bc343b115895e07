"""The serve step's sampler: one token a slot out of `[slots, V]` logits,
at the cost its operands ask for.

`sample` is `gpt.sample_logits` a row — greedy argmax where a row's
temperature is 0, else temperature-scaled, optionally top-k-truncated
categorical — decided on the device from `temps` and `topks`:

* a batch in which no row has a temperature takes the argmax and
  nothing else (`lax.cond` on `any(temps > 0)`: the chip runs one
  branch, so no scale, no threshold, no noise and no key is read);
* a row's top-k threshold, the k-th largest of its scaled logits, is
  found by `kth_largest` (ops/select.py, where the selected latent
  attention of models/dots3.py finds its own) without sorting the row:
  selection over the bits of the values, one compare-and-count over
  `[slots, V]` a bit.
  The value is the one `jnp.sort(row)[V - k]` holds, so the filter, the
  draw and seed parity with `gpt.generate` are what a sort would give.

`gpt.sample_logits` stays the plain recipe `generate` runs: the parity
tests hold this module to it, and no train program traces this file.
"""

import jax
import jax.numpy as jnp
from jax import lax

from .select import kth_largest


def sample(logits, keys, temps, topks, dtype):
    """One token a slot -> [slots] int32.  `logits` [slots, V] as the
    engine carries them (f32), `keys` [slots, 2] uint32, `temps` [slots]
    f32 (0 = greedy), `topks` [slots] int32 (0 = top-k off; it only ever
    reaches a row that draws).

    The whole recipe runs in `dtype`, the model's compute dtype, though
    the logits arrive as f32: `categorical` draws its Gumbel noise in the
    logits' dtype, so sampling in f32 would draw other noise than
    `generate`'s bf16 path and break seed parity; and a near-tie's argmax
    resolves as `generate` resolves it."""
    V = logits.shape[-1]

    def greedy():
        return jnp.argmax(logits.astype(dtype), axis=-1).astype(jnp.int32)

    def drawn():
        # gpt.sample_logits, vectorized per slot: scale FIRST, then
        # truncate below the k-th largest at -1e30 (top-k off: k = V,
        # the row's minimum, which filters nothing)
        t = jnp.where(temps > 0, temps, 1.0).astype(dtype)
        scaled = logits.astype(dtype) / t[:, None]
        k_eff = jnp.clip(jnp.where(topks > 0, topks, V), 1, V)
        kth = kth_largest(scaled, k_eff)[:, None]
        filt = jnp.where(scaled < kth, -1e30, scaled)
        sampled = jax.vmap(jax.random.categorical)(keys, filt)
        return jnp.where(temps > 0, sampled, greedy()).astype(jnp.int32)

    return lax.cond(jnp.any(temps > 0), drawn, greedy)
