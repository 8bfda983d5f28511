"""Traced decode of encoded column buffers (the late-materialization
half of nds_tpu/columnar/).

Every decode here runs INSIDE the consuming query's jax trace, so XLA
fuses the shift/mask (bitpack) or scatter+scan run-id rebuild (RLE)
into the one compiled program — encoded columns never round-trip
through HBM at full width. The contract with the scan (``device_exec._Trace``): decode
returns values in EXACTLY the dtype the raw upload would have produced
(``EncSpec.dtype``), so every downstream operator — joins on codes,
filters, group keys, semi-joins — is oblivious to the encoding and
string bytes still materialize only at the result compactor.
"""

from __future__ import annotations

import jax.numpy as jnp

from nds_tpu.columnar.encodings import EncSpec
from nds_tpu.engine import kernels as KX


def _unpack_words(words, n: int, bits: int):
    """Gather+shift+mask unpack of ``n`` fields of ``bits`` bits from
    int32 words (low field first). int32 arithmetic throughout: the
    arithmetic right shift's sign extension is masked off."""
    per = 32 // bits
    idx = jnp.arange(n, dtype=jnp.int32)
    w = KX.take(words, idx // per)
    return (w >> ((idx % per) * bits)) & ((1 << bits) - 1)


def unpack_mask(words, n: int):
    return _unpack_words(words, n, 1).astype(bool)


def decode(spec: EncSpec, bufs: dict, key: str):
    """(values, validity) for one encoded scan column, traced. ``bufs``
    holds the encoded buffer set the executor uploaded under ``key``
    (+ ``#x``/``#v`` suffixes)."""
    n = spec.rows
    dt = jnp.dtype(spec.dtype)
    if spec.kind == "bitpack":
        words = bufs[key]
        if spec.bits >= 32:
            vals = (words.astype(jnp.int64) + spec.lo).astype(dt)
        else:
            field = _unpack_words(words, n, spec.bits)
            if -2**31 < spec.lo and spec.lo + (1 << spec.bits) < 2**31:
                # bias fits int32: stay on the native-width path
                vals = (field + spec.lo).astype(dt)
            else:
                vals = (field.astype(jnp.int64) + spec.lo).astype(dt)
    elif spec.kind == "rle":
        # run ids from run starts: scatter a 1 at each start, prefix-
        # sum, subtract 1 — linear work (a native scan on TPU), where
        # a searchsorted over run ends would pay a full sort of the
        # decoded length (measured 500x slower on XLA:CPU at 1M rows)
        starts = bufs[key + "#x"]
        seg = jnp.cumsum(jnp.zeros(n, jnp.int32).at[starts].add(
            jnp.int32(1))) - 1
        vals = KX.take(bufs[key], seg)
    else:
        vals = bufs[key]
    from nds_tpu.analysis import plan_verify
    if plan_verify.verify_enabled() and vals.dtype != dt:
        # encoded-dtype propagation invariant: a decode that hands
        # downstream operators a different dtype than the raw upload
        # would silently change packing/compare semantics
        raise plan_verify.PlanVerifyError(
            [f"decoded dtype {vals.dtype} != declared {dt} "
             f"for {key!r}"], "columnar decode")
    valid = bufs.get(key + "#v")
    if valid is not None and spec.mask_packed:
        valid = unpack_mask(valid, n)
    return vals, valid
