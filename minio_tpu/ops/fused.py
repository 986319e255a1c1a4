"""Fused erasure-encode + bitrot-hash device program.

One host dispatch turns [B, K, S] data shards into the [B, M, S] parity
shards plus the HighwayHash-256 digests of all K+M shards (data rows first).
The data rows are hashed on the device and stay there: the host holds them
already, so the program returns only what the host lacks. "Fused" here means
one *jitted XLA program* holding both stages -- the GF(2) bit-matmul encode
(ops/rs) and the device hash (on a TPU the VMEM-resident HighwayHash chain of
ops/highwayhash_pallas) -- with the packet-layout transform between them
staying device-resident. It is deliberately NOT a single kernel: encode
combines *across* shard rows while the hash wants independent streams on
lanes; the boundary costs one HBM round-trip of the shard bytes and keeps
both stages independently oracle-checked.

What PUT pays per 16 MiB window: one host->device transfer of the data
shards, one program launch, one device->host transfer of parity + digests.
The hash finalization (remainder packets, tail permutes, modular reduction)
runs as XLA epilogue exactly as ops/highwayhash_pallas already does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_SUBLANES = 8  # rows of a TPU vector tile


def make_step(encode_fn, hash_fn, name: str = "mtpu_encode_hash"):
    """Compose a parity-encode fn ([B, K, S] -> [B, M, S]) and a digest fn
    into one fused step.

    Returns the *unjitted* step so callers (models/pipeline) control the jit
    boundary; jit it once per (geometry, batch shape). `name` becomes the
    jitted module's name (`jit_<name>` on a profiler trace's module line);
    the two halves carry `mtpu.rs_encode` / `mtpu.hh256` scopes in their
    ops' metadata. Names only: the program computes what it computed.
    """

    def step(data_shards: jax.Array):
        """[B, K, S] -> ([B, M, S] parity, [B, K+M, 32] digests of data
        then parity rows)."""
        with jax.named_scope("mtpu.rs_encode"):
            parity = encode_fn(data_shards)
            all_shards = jnp.concatenate([data_shards, parity], axis=1)
        b, t, s = all_shards.shape
        rows = all_shards.reshape(b * t, s)
        if t < _SUBLANES:
            # Fewer shard rows than a tile has sublanes (2+2): the TPU
            # compiler folds this reshape into the hash's packet transform
            # and pads the [B, T, S] intermediate beyond the chip's memory
            # ([64, 4, 524288] wants 18 GB, and 100 s to compile). Behind a
            # barrier the two halves compile as they do alone.
            rows = jax.lax.optimization_barrier(rows)
        with jax.named_scope("mtpu.hh256"):
            digests = hash_fn(rows).reshape(b, t, 32)
        return parity, digests

    step.__name__ = step.__qualname__ = name
    return step
