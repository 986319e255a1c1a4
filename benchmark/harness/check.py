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
                          the window, the last one acknowledged in it, is
                          brought to as many missing data shards as the
                          configuration says may be lost, and is read back:
                          every byte then comes through the parity the device
                          wrote and the device's reconstruct program. A run
                          that wrote nothing (no PUT or DELETE sent from ramp
                          to drain) over a traffic file with a `populate` step
                          draws the sample the same way from the populated
                          pool: each client holds the sha256 of what
                          `populate` put. The run's log says which pool a
                          sample was drawn from
    degraded_short        objects the sample should have held (the size asked
                          for, or every object of the pool it was drawn from
                          where there are fewer, and never under one) less
                          objects checked. A run with neither a PUT in the
                          window nor a populated pool reads 1
    device_blocks_missing full blocks the clients' acknowledged PUTs held less
                          the blocks the device codec counted as encoded, plus
                          full blocks of the acknowledged GETs, ramp to drain,
                          of keys the `prepare` step degraded less the blocks
                          the codec counted as reconstructed (each difference
                          never below 0): the device, not the host codec, did
                          the work. Nothing is added where `prepare` lost no
                          shards. An acknowledged MPUT holds the full blocks
                          of its parts, each part a stream of its own (8 of an
                          8 MiB part, 64 of the upload)

A cell whose traffic heals (op HEAL, drives replaced before every op) is held
to five numbers more, and `device_blocks_missing` counts the blocks its
acknowledged heals rebuilt (the pool's full blocks, each op) against the
codec's `blocks_reconstructed`:

    heals_short           1 where no HEAL was acknowledged from ramp to drain
                          (each acknowledged one reported every object of the
                          pool scanned and healed and none failed), else 0
    healed_shard_mismatch shard files on the replaced drives that differ, after
                          the drain, from the sha256 recorded right after
                          `populate`, before the first swap (a missing file
                          differs)
    reference_mismatch    of a sample, drawn from the seed, of the pool's
                          objects, healed shard files on the replaced drives
                          that differ from what the plain reference
                          (harness/reference.py: numpy RS and HighwayHash-256
                          from the seeded body, the configuration's stated
                          geometry) gives
    host_fallback_recon_blocks, host_fallback_digest_chunks
                          blocks rebuilt and chunks verified by the host codec
                          from ramp to drain: the device verifies and rebuilds
                          a heal, or the cell times something else

Its degraded sample loses its data shards on drives other than the replaced
ones, so that the healed rows are among the survivors the read needs.

A cell whose configuration has several nodes (harness/cluster.py) is held to
one number more, and reads its samples back across nodes:

    reference_mismatch    of a sample, drawn from the seed, of the keys whose
                          last acknowledged write, ramp to drain, was a PUT
                          that ended inside the window, the shard files (the
                          newest data directory on every drive of the set,
                          whichever node holds it) that differ from what the
                          plain reference gives for the body put; an empty
                          sample reads 1

Its read-back and degraded samples are read through the node after the one
the key's client writes through (client i writes through node i mod N, reads
back through node i + 1 mod N): an acknowledged write is read back from
another node, and the degraded read is rebuilt by that node's chip.

An MPUT (a whole multipart upload) is a write like a PUT: its key's record is
the sha256 of the whole body and the ETag the parts' md5s give, both held at
read-back; the degraded sample reads completed uploads back across their part
boundaries.

Every comparison is exact, so every limit is 0. The control (one parity shard
fewer than the configuration states, which a run cannot tell from the outside
until drives are lost) reads ``degraded_mismatch`` = the sample size; where the
`prepare` step lost as many data shards as the configuration tolerates, every
GET of the window fails too (``ops_failed``). A heal cell's control (13+3)
heals its three rows and its files differ from the reference's 12+4
(``reference_mismatch``); its degraded sample cannot be read
(``degraded_mismatch``).
"""

from __future__ import annotations

import random

from . import window

WRITES = ("PUT", "MPUT")  # what leaves a body on a key's record

LIMITS = {
    "ops_failed": 0,
    "readback_mismatch": 0,
    "degraded_mismatch": 0,
    "degraded_short": 0,
    "device_blocks_missing": 0,
}
HEAL_LIMITS = {
    "heals_short": 0,
    "healed_shard_mismatch": 0,
    "reference_mismatch": 0,
    "host_fallback_recon_blocks": 0,
    "host_fallback_digest_chunks": 0,
}


CLUSTER_LIMITS = {"reference_mismatch": 0}


def limits(heals: bool, cluster: bool = False) -> dict[str, float]:
    """The numbers a cell is held to: a heal cell's five more, a cluster's one."""
    return {**LIMITS, **(HEAL_LIMITS if heals else {}), **(CLUSTER_LIMITS if cluster else {})}


def _acknowledged_writes(ops: list) -> list:
    """Acknowledged PUTs, MPUTs and DELETEs, ramp to drain, in the order they ended.
    Each key belongs to one client, so a key's ops are in order."""
    return [op for op in sorted(ops, key=lambda o: o[window.END])
            if op[window.OK] and op[window.KIND] in WRITES + ("DELETE",)]


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
            if op[window.KIND] in WRITES and t0 <= op[window.END] < t1]
    live.sort(key=lambda o: o[window.END])
    return _draw([op[window.KEY] for op in live], seed, n)


def reference_sample(ops: list, t0: float, t1: float, seed: int, n: int) -> list[str]:
    """Keys whose last acknowledged write, ramp to drain, was a PUT (one
    stream of blocks, as the reference lays it out) that ended inside the
    window; drawn apart from the degraded sample."""
    last = {op[window.KEY]: op for op in _acknowledged_writes(ops)}
    live = sorted((op for op in last.values()
                   if op[window.KIND] == "PUT" and t0 <= op[window.END] < t1),
                  key=lambda o: o[window.END])
    return _draw([op[window.KEY] for op in live], seed + 3, n)


def wrote_nothing(ops: list) -> bool:
    """No PUT or DELETE was sent from ramp to drain, acknowledged or not:
    every key of a populated pool still holds what `populate` put."""
    return not any(op[window.KIND] in WRITES + ("DELETE",) for op in ops)


def pool_sample(pool_keys: list[str], seed: int, n: int) -> list[str]:
    """The degraded sample of a window that wrote nothing: drawn from the keys
    `populate` put (sorted, so the seed alone decides)."""
    return _draw(sorted(pool_keys), seed, n)


def full_blocks_put(ops: list, block_bytes: int, part_bytes: int | None = None) -> int:
    """Full blocks of the acknowledged PUTs and MPUTs. An MPUT's parts are
    `part_bytes` each, the last the rest, and every part is a stream of its own."""
    total = 0
    for op in ops:
        if not op[window.OK] or op[window.KIND] not in WRITES:
            continue
        n = op[window.NBYTES]
        if op[window.KIND] == "MPUT":
            total += n // part_bytes * (part_bytes // block_bytes) + n % part_bytes // block_bytes
        else:
            total += n // block_bytes
    return total


def degraded_blocks_got(ops: list, degraded: set[str], block_bytes: int) -> int:
    """Full blocks of the acknowledged GETs of keys the prepare step degraded,
    ramp to drain. A key counts until its client first sends a PUT or DELETE
    of it (a key's ops are in order: one client owns it): what is written
    again is whole again."""
    never = float("inf")
    rewritten: dict[str, float] = {}
    for op in ops:
        if op[window.KIND] in WRITES + ("DELETE",) and op[window.KEY] in degraded:
            rewritten[op[window.KEY]] = min(rewritten.get(op[window.KEY], never), op[window.START])
    return sum(op[window.NBYTES] // block_bytes for op in ops
               if op[window.OK] and op[window.KIND] == "GET" and op[window.KEY] in degraded
               and op[window.END] <= rewritten.get(op[window.KEY], never))


def healed_blocks(ops: list, block_bytes: int) -> int:
    """Full blocks the acknowledged HEALs rebuilt, ramp to drain: each op's
    bytes are the pool's, whole blocks."""
    return sum(op[window.NBYTES] // block_bytes for op in ops
               if op[window.OK] and op[window.KIND] == "HEAL")


def decide(numbers: dict[str, float],
           held_to: dict[str, float] | None = None) -> tuple[bool, dict]:
    """(correct, compared): each number beside its limit. A number that was not
    read fails: nothing is correct by default."""
    compared, correct = {}, True
    for name, limit in (held_to or LIMITS).items():
        value = numbers.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or value > limit:
            correct = False
    return correct, compared
