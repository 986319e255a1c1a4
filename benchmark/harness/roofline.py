"""Peaks of the chips, and the least work the codec has to do.

The yardstick a later PR may not move: the operations and bytes are computed
from the geometry alone, whatever program implements the codec.

Peaks are the published ones, keyed by jax's ``device_kind``. A kind that is
not in the table is an error, never a default.
"""

from __future__ import annotations

DIGEST_BYTES = 32  # HighwayHash-256 per shard chunk

# Source: Google Cloud documentation, "TPU v5e" system architecture page:
# 16 GB HBM2 at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8 per chip.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                    "bf16_flops_per_s": 197e12, "hbm_bytes": 16e9,
                    "source": "Google Cloud docs, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in benchmark/harness/roofline.py PEAKS; "
            "add its published peaks with their source before measuring on it"
        ) from None


def shard_bytes(block_bytes: int, data: int) -> int:
    """ceil(block / K): the length of one shard's chunk of a full block."""
    return -(-block_bytes // data)


def block_bytes_moved(data: int, parity: int, shard_len: int, chunks: int = 1) -> int:
    """HBM bytes one full block needs at the least: K shards read once,
    M parity shards and one digest per shard chunk written."""
    read = data * shard_len
    written = parity * shard_len + DIGEST_BYTES * (data + parity) * chunks
    return read + written


def recon_bytes_moved(data: int, rebuilt: int, shard_len: int, digests: bool) -> int:
    """HBM bytes the reconstruct of one full block needs at the least: K
    surviving shards read once, `rebuilt` shards written, and one digest per
    rebuilt shard where the caller asks for them (heal does, a GET does not)."""
    return data * shard_len + rebuilt * shard_len + (DIGEST_BYTES * rebuilt if digests else 0)


def heal_bytes_moved(data: int, parity: int, lost: int, shard_len: int) -> int:
    """HBM bytes the heal of one full block needs at the least, whatever programs
    do it: the K+M-lost surviving rows read once (verified and the source of the
    rebuild alike), the `lost` rows written, one digest for every row (the
    survivors' to compare, the rebuilt rows' to store). Not today's separate
    verify (K+M-lost rows read) and reconstruct (K more read) programs' bytes:
    a program that fuses them does less, and must not read over 100 %."""
    return ((data + parity - lost) * shard_len + lost * shard_len
            + DIGEST_BYTES * (data + parity))


def block_gf_ops(data: int, parity: int, shard_len: int) -> int:
    """GF(2^8) multiply-adds of the parity rows: M x K per shard byte. Not the
    bound on a v5e (see least_seconds): kept so that the figure is on record."""
    return 2 * data * parity * shard_len


def least_seconds(blocks: int, data: int, parity: int, shard_len: int,
                  device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for `blocks` full blocks, and what
    bounds it. The HighwayHash rounds are 64-bit integer vector work for which
    no peak is published, so the hash counts by its bytes only; the GF
    multiply-adds, counted as int8 operations, stay far under the HBM time at
    these geometries, so HBM bounds."""
    p = peaks(device_kind)
    t_hbm = blocks * block_bytes_moved(data, parity, shard_len) / p["hbm_bytes_per_s"]
    t_ops = blocks * block_gf_ops(data, parity, shard_len) / p["int8_ops_per_s"]
    return (t_hbm, "hbm") if t_hbm >= t_ops else (t_ops, "int8")


def recon_least_seconds(blocks: int, data: int, rebuilt: int, shard_len: int,
                        device_kind: str) -> tuple[float, str]:
    """The same for a GET's reconstruct (no digests) of `rebuilt` rows of each
    of `blocks` full blocks: rebuilt x K multiply-adds per shard byte against
    the bytes of recon_bytes_moved."""
    p = peaks(device_kind)
    t_hbm = blocks * recon_bytes_moved(data, rebuilt, shard_len, False) / p["hbm_bytes_per_s"]
    t_ops = blocks * block_gf_ops(data, rebuilt, shard_len) / p["int8_ops_per_s"]
    return (t_hbm, "hbm") if t_hbm >= t_ops else (t_ops, "int8")


def heal_least_seconds(blocks: int, data: int, parity: int, lost: int, shard_len: int,
                       device_kind: str) -> tuple[float, str]:
    """The least time the chip could take to heal `lost` rows of each of
    `blocks` full blocks: heal_bytes_moved against HBM, lost x K multiply-adds a
    shard byte against the int8 peak (the hash, as in least_seconds, counts by
    its bytes only)."""
    p = peaks(device_kind)
    t_hbm = blocks * heal_bytes_moved(data, parity, lost, shard_len) / p["hbm_bytes_per_s"]
    t_ops = blocks * block_gf_ops(data, lost, shard_len) / p["int8_ops_per_s"]
    return (t_hbm, "hbm") if t_hbm >= t_ops else (t_ops, "int8")
