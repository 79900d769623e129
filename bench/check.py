"""The comparison that decides ``correct``: program against reference.

Every number is a count of things that differ, and each limit is 0: the
peer is deterministic integer arithmetic, so a sound run reproduces the
reference exactly, and any acknowledged block, bit, head or table slot
that differs is a fault.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench import reference

LIMITS = {
    "blocks_missing": 0,  # reference blocks not stored, and stored extras
    "wire_rows_differ": 0,  # ordered txs whose stored bytes differ
    "valid_bits_differ": 0,  # per-tx validity bits in the stored blocks
    "chain_links_differ": 0,  # blocks whose number/prev/hash differ
    "heads_differ": 0,  # journal head, and the peer's ledger head
    "state_slots_differ": 0,  # committed table slots (key, version, value)
    "replica_slots_differ": 0,  # endorser replica slots
    "overflow_differs": 0,  # sticky overflow flag
}


class ChannelOutputs(NamedTuple):
    """What one channel's peer holds after the run, on the host."""

    blocks: list  # stored blocks (block_no, prev_hash, block_hash, wire,
    # valid), in chain order
    table: tuple  # committed world state: keys, versions, values
    replica: tuple  # the endorser replica's table
    journal_head: np.ndarray  # (2,) u32
    ledger_head: np.ndarray | None  # the peer's own, where it keeps one
    overflow_bits: int


def _slots_differ(table: tuple, ref: tuple) -> int:
    keys, vers, vals = table
    rk, rv, rva = ref
    if keys.shape != rk.shape:
        return int(rk.shape[0] * rk.shape[1])
    bad = ((keys != rk).any(-1) | (vers != rv) | (vals != rva).any(-1))
    return int(bad.sum())


def compare_channel(out, ref: reference.ChannelReference) -> dict:
    """Counts of differences for one channel. Runs the reference's wire
    pass, so call it once per reference."""
    n = dict.fromkeys(LIMITS, 0)
    stored = {sb.block_no: sb for sb in out.blocks}
    ref_nos = {b.block_no for b in ref.blocks}
    n["blocks_missing"] = (len(ref_nos - stored.keys())
                           + len(stored.keys() - ref_nos))
    bs = ref.block_txs
    for lo, words in ref.wire_chunks():
        got = np.zeros_like(words)
        present = []
        for i, k in enumerate(range(lo // bs, (lo + words.shape[0]) // bs)):
            sb = stored.get(k)
            if sb is None:
                continue
            if sb.wire.shape != (bs, 4 * words.shape[1]):
                n["wire_rows_differ"] += bs
                continue
            got[i * bs:(i + 1) * bs] = np.ascontiguousarray(
                sb.wire).view("<u4")
            present.append(i)
        diff = (got != words).any(axis=1).reshape(-1, bs)
        n["wire_rows_differ"] += int(diff[present].sum())
    ref.finish()
    for b in ref.blocks:
        sb = stored.get(b.block_no)
        if sb is None:
            continue
        n["valid_bits_differ"] += int((np.asarray(sb.valid, bool)
                                       != b.valid).sum())
        n["chain_links_differ"] += int(
            not (np.array_equal(sb.prev_hash, b.prev_hash)
                 and np.array_equal(sb.block_hash, b.block_hash)))
    n["heads_differ"] = int(not np.array_equal(out.journal_head,
                                               ref.journal_head))
    if out.ledger_head is not None:
        n["heads_differ"] += int(not np.array_equal(out.ledger_head,
                                                    ref.ledger_head))
    table = ref.table()
    n["state_slots_differ"] = _slots_differ(out.table, table)
    n["replica_slots_differ"] = _slots_differ(out.replica, table)
    n["overflow_differs"] = int(bool(out.overflow_bits) != ref.overflow)
    return n


def compare(outputs: list, refs: list) -> dict:
    """Sum of the per-channel counts over every channel."""
    total = dict.fromkeys(LIMITS, 0)
    for out, ref in zip(outputs, refs, strict=True):
        for k, v in compare_channel(out, ref).items():
            total[k] += v
    return total


def verdict(counts: dict) -> bool:
    return all(counts[k] <= lim for k, lim in LIMITS.items())
