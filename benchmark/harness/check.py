"""What decides ``correct``.

The reference is the record each client keeps of its own keys (the sha256 of
the last body the server acknowledged, or that the key was deleted): the same
operations on the same data give the same answers. It is independent of the
program; the store is held to it at the timed sizes, on the objects the timed
window itself wrote:

    ops_failed            ops from ramp to drain that did not answer as the
                          record says (an HTTP error, wrong bytes, a wrong
                          length, a deleted key that still answers)
    readback_mismatch     after the window a sample, drawn from the seed, of the
                          keys written or deleted from ramp to drain, the last
                          one acknowledged always, is read back whole and
                          compared (sha256; 404 for a deleted key)
    degraded_mismatch     a sample, drawn from the seed, of the objects PUT in
                          the window, the last one acknowledged in it, has as
                          many data shards removed from its drives as the
                          configuration says may be lost, and is read back:
                          every byte then comes through the parity the device
                          wrote and the device's reconstruct program
    degraded_short        objects the sample should have held (the size asked
                          for, or every object PUT in the window where there
                          are fewer, and never under one) less objects checked
    device_blocks_missing full blocks the clients' acknowledged PUTs held less
                          the blocks the device codec counted as encoded (never
                          below 0): the device, not the host codec, did the work

Every comparison is exact, so every limit is 0. The control (one parity shard
fewer than the configuration states, which a run cannot tell from the outside
until drives are lost) reads ``degraded_mismatch`` = the sample size.
"""

from __future__ import annotations

import random

from . import window

LIMITS = {
    "ops_failed": 0,
    "readback_mismatch": 0,
    "degraded_mismatch": 0,
    "degraded_short": 0,
    "device_blocks_missing": 0,
}


def _acknowledged_writes(ops: list) -> list:
    """Acknowledged PUTs and DELETEs, ramp to drain, in the order they ended.
    Each key belongs to one client, so a key's ops are in order."""
    return [op for op in sorted(ops, key=lambda o: o[window.END])
            if op[window.OK] and op[window.KIND] in ("PUT", "DELETE")]


def _draw(keys_oldest_first: list[str], seed: int, n: int) -> list[str]:
    """n of the keys (all, where there are fewer), drawn from the seed, the
    newest always among them."""
    if not keys_oldest_first or n <= 0:
        return []
    newest = keys_oldest_first[-1]
    rest = sorted(set(keys_oldest_first) - {newest})
    random.Random(seed).shuffle(rest)
    return [newest] + rest[:n - 1]


def readback_sample(ops: list, seed: int, n: int) -> list[str]:
    """Keys with an acknowledged PUT or DELETE, ramp to drain."""
    return _draw([op[window.KEY] for op in _acknowledged_writes(ops)], seed + 1, n)


def degraded_sample(ops: list, t0: float, t1: float, seed: int, n: int) -> list[str]:
    """Keys whose last acknowledged write, ramp to drain, was a PUT that ended
    inside the window: only those still hold what the window wrote."""
    last = {op[window.KEY]: op for op in _acknowledged_writes(ops)}
    live = [op for op in last.values()
            if op[window.KIND] == "PUT" and t0 <= op[window.END] < t1]
    live.sort(key=lambda o: o[window.END])
    return _draw([op[window.KEY] for op in live], seed, n)


def full_blocks_put(ops: list, block_bytes: int) -> int:
    return sum(op[window.NBYTES] // block_bytes for op in ops
               if op[window.OK] and op[window.KIND] == "PUT")


def decide(numbers: dict[str, float]) -> tuple[bool, dict]:
    """(correct, compared): each number beside its limit. A number that was not
    read fails: nothing is correct by default."""
    compared, correct = {}, True
    for name, limit in LIMITS.items():
        value = numbers.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or value > limit:
            correct = False
    return correct, compared
