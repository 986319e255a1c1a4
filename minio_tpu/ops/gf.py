"""GF(2^8) field arithmetic for Reed-Solomon erasure coding.

The field is GF(2^8) with the primitive polynomial x^8+x^4+x^3+x^2+1 (0x11d)
and generator 2 -- the same field used by the reference's erasure codec
(klauspost/reedsolomon, used at /root/reference/cmd/erasure-coding.go:63), which
itself follows the Backblaze JavaReedSolomon construction. Bit-compatibility
with that construction is pinned by the golden self-test vectors re-hosted in
tests/test_rs_golden.py (reference: cmd/erasure-coding.go:158-216).

Everything here is host-side numpy: table generation, matrix algebra over the
field (inversion for decode), and scalar helpers. The device kernel in rs.py
consumes the *bit-expanded* GF(2) matrices built in rs_matrix.py.
"""

from __future__ import annotations

import functools

import numpy as np

# Primitive polynomial for GF(2^8): x^8 + x^4 + x^3 + x^2 + 1.
POLY = 0x11D
FIELD_SIZE = 256


@functools.lru_cache(maxsize=None)
def _tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(exp, log, mul) tables.

    exp[i] = 2^i for i in [0, 510) (doubled so exp[log a + log b] works
    without an explicit mod-255), log[2^i] = i, and the full 256x256
    multiplication table mul[a, b] = a*b in the field.
    """
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    log[0] = -1  # log(0) is undefined; guarded by callers.

    # Full multiplication table via the log/exp tables.
    a = np.arange(256)
    la = log[a]
    mul = np.zeros((256, 256), dtype=np.uint8)
    nz = a[1:]
    mul[np.ix_(nz, nz)] = exp[(la[nz][:, None] + la[nz][None, :])]
    return exp, log, mul


def exp_table() -> np.ndarray:
    return _tables()[0]


def log_table() -> np.ndarray:
    return _tables()[1]


def mul_table() -> np.ndarray:
    """Full 256x256 GF(2^8) multiplication table (uint8)."""
    return _tables()[2]


def gf_mul(a: int, b: int) -> int:
    return int(mul_table()[a, b])


def gf_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by zero")
    if a == 0:
        return 0
    exp, log, _ = _tables()
    return int(exp[(log[a] - log[b]) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of zero")
    exp, log, _ = _tables()
    return int(exp[255 - log[a]])


def gf_exp(base: int, n: int) -> int:
    """base**n in the field (Backblaze galExp semantics)."""
    if n == 0:
        return 1
    if base == 0:
        return 0
    exp, log, _ = _tables()
    return int(exp[(log[base] * n) % 255])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Matrix product over GF(2^8). a: [n,k] u8, b: [k,m] u8 -> [n,m] u8."""
    mul = mul_table()
    # products[i, j, t] = a[i, t] * b[t, j]; XOR-reduce over t.
    prods = mul[a[:, :, None], b.T[None, :, :].swapaxes(1, 2)]  # [n, k, m]
    return np.bitwise_xor.reduce(prods, axis=1)


def mat_inv(m: np.ndarray) -> np.ndarray:
    """Invert a square matrix over GF(2^8) by Gauss-Jordan elimination.

    Raises ValueError if the matrix is singular.
    """
    n = m.shape[0]
    assert m.shape == (n, n)
    mul = mul_table()
    work = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        # Pivot: find a row at/under `col` with nonzero entry in `col`.
        pivot = None
        for r in range(col, n):
            if work[r, col] != 0:
                pivot = r
                break
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            work[[col, pivot]] = work[[pivot, col]]
        # Scale pivot row to make the pivot 1.
        inv_p = gf_inv(int(work[col, col]))
        work[col] = mul[work[col], inv_p]
        # Eliminate the column from every other row.
        for r in range(n):
            if r != col and work[r, col] != 0:
                factor = work[r, col]
                work[r] ^= mul[work[col], factor]
    return work[:, n:].copy()


def mul_by_scalar(vec: np.ndarray, c: int) -> np.ndarray:
    """Multiply a u8 array elementwise by field scalar c."""
    return mul_table()[c][vec]
