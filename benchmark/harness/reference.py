"""The plain reference of a healed shard file: numpy alone, nothing of the program.

What a drive of an erasure set holds for one object is, block by block, the
shard row the drive was given, each chunk behind its HighwayHash-256 digest
(the streaming bitrot layout ``digest || chunk``). This module computes that
file from the object's body, the geometry and the row, the way the reference
system defines it:

* Reed-Solomon over GF(2^8), polynomial x^8+x^4+x^3+x^2+1 (0x11d), generator 2;
  the systematic encode matrix is the (K+M) x K Vandermonde matrix times the
  inverse of its top K x K square (klauspost/reedsolomon's default, which
  ``cmd/erasure-coding.go`` instantiates); a block is split into K shards of
  ceil(block / K) bytes, the last zero-padded.
* HighwayHash-256 with the reference's bitrot key (``cmd/bitrot.go``), written
  here from the algorithm: four 4-lane u64 vectors, the zipper merge as the
  byte shuffle the SSE version uses, ten permute rounds, two modular
  reductions. numpy, vectorised over every chunk at once.

Written for the benchmark from the published algorithms (the program's own
oracles are ``minio_tpu/ops/rs_ref.py``, ``rs_matrix.py``, ``gf.py`` and
``highwayhash.py``; ``benchmark/tests/test_reference.py`` holds the two to the
same answers). Nothing here is imported by the program or imports it.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D
DIGEST_BYTES = 32

# The reference's bitrot key (cmd/bitrot.go: the first 100 decimals of pi
# hashed with a zero key).
BITROT_KEY = bytes.fromhex(
    "4be734fa8e238acd263e83e6bb968552040f935da39f441497e09d1322de36a0")
_INIT0 = np.array([0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
                   0x13198A2E03707344, 0x243F6A8885A308D3], dtype=np.uint64)
_INIT1 = np.array([0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
                   0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=np.uint64)
# Output byte i of a 16-byte lane pair is input byte _ZIPPER[i]
# (_mm_shuffle_epi8 with 0x070806090D0A040B:0x000F010E05020C03).
_ZIPPER = np.array([3, 12, 2, 5, 14, 1, 15, 0, 11, 4, 10, 13, 9, 6, 8, 7])
_LO32 = np.uint64(0xFFFFFFFF)
_U32 = np.uint64(32)


# -- GF(2^8) and the encode matrix ----------------------------------------------


@functools.lru_cache(maxsize=None)
def _gf_product() -> np.ndarray:
    """The full 256 x 256 product table, from exp (over 0..509) and log."""
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x = (x << 1) ^ (POLY if x & 0x80 else 0)
    prod = np.zeros((256, 256), dtype=np.uint8)
    nz = np.arange(1, 256)
    prod[1:, 1:] = exp[log[nz][:, None] + log[nz][None, :]]
    return prod


def _gf_pow(base: int, n: int) -> int:
    if n == 0:
        return 1
    if base == 0:
        return 0
    prod = _gf_product()
    x = 1
    for _ in range(n):
        x = int(prod[x, base])
    return x


def _gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    prod = _gf_product()
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for t in range(a.shape[1]):
        out ^= prod[a[:, t][:, None], b[t][None, :]]
    return out


def _gf_inverse(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8)."""
    prod = _gf_product()
    inv_of = {a: int(np.nonzero(prod[a] == 1)[0][0]) for a in range(1, 256)}
    n = m.shape[0]
    w = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next(r for r in range(col, n) if w[r, col])
        w[[col, pivot]] = w[[pivot, col]]
        w[col] = prod[inv_of[int(w[col, col])]][w[col]]
        for r in range(n):
            if r != col and w[r, col]:
                w[r] ^= prod[int(w[r, col])][w[col]]
    return w[:, n:]


@functools.lru_cache(maxsize=None)
def encode_matrix(k: int, m: int) -> np.ndarray:
    """The systematic (K+M) x K matrix: Vandermonde times its top square's inverse."""
    v = np.array([[_gf_pow(r, c) for c in range(k)] for r in range(k + m)], dtype=np.uint8)
    return _gf_matmul(v, _gf_inverse(v[:k]))


def shard_rows(blocks: np.ndarray, k: int, m: int, rows: list[int]) -> np.ndarray:
    """blocks: [B, K, S] data shards -> [len(rows), B, S], each row of the K+M."""
    prod = _gf_product()
    em = encode_matrix(k, m)
    out = np.zeros((len(rows), blocks.shape[0], blocks.shape[2]), dtype=np.uint8)
    for i, row in enumerate(rows):
        if row < k:
            out[i] = blocks[:, row]
            continue
        for j in range(k):
            c = int(em[row, j])
            if c:
                out[i] ^= prod[c][blocks[:, j]]
    return out


# -- HighwayHash-256 ---------------------------------------------------------------


def _rot32(x: np.ndarray) -> np.ndarray:
    return (x >> _U32) | (x << _U32)


def _zipper(v: np.ndarray) -> np.ndarray:
    b = v.shape[0]
    return (np.ascontiguousarray(v).view(np.uint8).reshape(b, 2, 16)[:, :, _ZIPPER]
            .reshape(b, 32).view(np.uint64))


class _Lanes:
    def __init__(self, b: int, key: bytes):
        k = np.frombuffer(key, dtype="<u8").astype(np.uint64)
        self.mul0 = np.tile(_INIT0, (b, 1))
        self.mul1 = np.tile(_INIT1, (b, 1))
        self.v0 = self.mul0 ^ k
        self.v1 = self.mul1 ^ _rot32(k)

    def update(self, packet: np.ndarray) -> None:
        self.v1 += self.mul0 + packet
        self.mul0 ^= (self.v1 & _LO32) * (self.v0 >> _U32)
        self.v0 += self.mul1
        self.mul1 ^= (self.v0 & _LO32) * (self.v1 >> _U32)
        self.v0 += _zipper(self.v1)
        self.v1 += _zipper(self.v0)


def _reduce(a3: np.ndarray, a2: np.ndarray, a1: np.ndarray, a0: np.ndarray):
    a3 = a3 & np.uint64(0x3FFFFFFFFFFFFFFF)
    one, two, s63, s62 = (np.uint64(n) for n in (1, 2, 63, 62))
    m1 = a1 ^ ((a3 << one) | (a2 >> s63)) ^ ((a3 << two) | (a2 >> s62))
    m0 = a0 ^ (a2 << one) ^ (a2 << two)
    return m0, m1


def highwayhash256(streams: np.ndarray, key: bytes = BITROT_KEY) -> np.ndarray:
    """[B, L] u8, B streams of one length -> [B, 32] u8 digests."""
    b, length = streams.shape
    st = _Lanes(b, key)
    full = length // 32
    words = np.ascontiguousarray(streams[:, :full * 32]).view("<u8").reshape(b, full, 4)
    words = words.astype(np.uint64)
    for i in range(full):
        st.update(words[:, i])
    r = length - full * 32
    if r:
        tail = streams[:, full * 32:]
        st.v0 += np.uint64((r << 32) + r)
        lo, hi = st.v1 & _LO32, st.v1 >> _U32
        c, back = np.uint64(r), np.uint64(32 - r)
        st.v1 = (((lo << c) | (lo >> back)) & _LO32) | ((((hi << c) | (hi >> back)) & _LO32) << _U32)
        packet = np.zeros((b, 32), dtype=np.uint8)
        whole4 = r & ~3
        packet[:, :whole4] = tail[:, :whole4]
        if r & 16:
            packet[:, 28:32] = tail[:, r - 4:r]
        elif r & 3:
            rest = tail[:, whole4:]
            packet[:, 16] = rest[:, 0]
            packet[:, 17] = rest[:, (r & 3) >> 1]
            packet[:, 18] = rest[:, (r & 3) - 1]
        st.update(packet.view("<u8").astype(np.uint64))
    for _ in range(10):
        st.update(_rot32(st.v0[:, [2, 3, 0, 1]]))
    h0, h1 = _reduce(st.v1[:, 1] + st.mul1[:, 1], st.v1[:, 0] + st.mul1[:, 0],
                     st.v0[:, 1] + st.mul0[:, 1], st.v0[:, 0] + st.mul0[:, 0])
    h2, h3 = _reduce(st.v1[:, 3] + st.mul1[:, 3], st.v1[:, 2] + st.mul1[:, 2],
                     st.v0[:, 3] + st.mul0[:, 3], st.v0[:, 2] + st.mul0[:, 2])
    out = np.stack([h0, h1, h2, h3], axis=1).astype("<u8")
    return np.ascontiguousarray(out).view(np.uint8).reshape(b, 32)


# -- the shard files of an object ----------------------------------------------------


def shard_files(body: bytes, k: int, m: int, block_bytes: int, rows: list[int]) -> list[bytes]:
    """The bitrot-framed file of each of `rows` for an object of full blocks:
    block by block, ``digest(chunk) || chunk`` of that row's chunk."""
    if not body or len(body) % block_bytes:
        raise ValueError("the reference covers objects of whole blocks")
    s = -(-block_bytes // k)
    n = len(body) // block_bytes
    padded = np.zeros((n, k * s), dtype=np.uint8)
    padded[:, :block_bytes] = np.frombuffer(body, dtype=np.uint8).reshape(n, block_bytes)
    chunks = shard_rows(padded.reshape(n, k, s), k, m, rows)  # [R, B, S]
    digests = highwayhash256(chunks.reshape(-1, s)).reshape(len(rows), n, DIGEST_BYTES)
    return [np.concatenate([digests[i], chunks[i]], axis=1).tobytes() for i in range(len(rows))]
