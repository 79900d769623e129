"""The control of the check: the reference in the program's place, with one
guarantee the configurations state broken. It has to come out as not
correct. The benchmark's own runs never run it.

The broken guarantee: every block a round acknowledges is in the store.
The control's store loses the last acknowledged block of each channel.

    python3 bench/control.py --workload <name> --rounds <n> --seeds <s>...

prints, per seed, each number the check compares beside its limit, for a
stream of ``n`` rounds (the rounds one run drives, warm-up included).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import NamedTuple

import numpy as np

if __package__ in (None, ""):  # run as a script: put the checkout first
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from bench import check, reference, spec  # noqa: E402


class StoredBlock(NamedTuple):
    block_no: int
    prev_hash: np.ndarray
    block_hash: np.ndarray
    wire: np.ndarray
    valid: np.ndarray


def reference_outputs(ref) -> check.ChannelOutputs:
    """A finished reference channel, shaped as a peer's outputs."""
    bs = ref.block_txs
    wires = {}
    for lo, words in ref.wire_chunks():
        for i in range(words.shape[0] // bs):
            wires[lo // bs + i] = np.ascontiguousarray(
                words[i * bs:(i + 1) * bs]).view(np.uint8)
    ref.finish()
    table = ref.table()
    return check.ChannelOutputs(
        blocks=[StoredBlock(b.block_no, b.prev_hash, b.block_hash,
                            wires[b.block_no], b.valid)
                for b in ref.blocks],
        table=table, replica=table, journal_head=ref.journal_head,
        ledger_head=ref.ledger_head, overflow_bits=int(ref.overflow))


def lose_last_block(out):
    """The guarantee broken: the last acknowledged block is not stored."""
    return out._replace(blocks=out.blocks[:-1])


def readings(cell, seed: int, n_rounds: int) -> dict:
    def replay():
        return reference.replay(cell.config, cell.traffic, seed, n_rounds)

    outs = [lose_last_block(reference_outputs(r)) for r in replay()]
    return check.compare(outs, replay())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = spec.load_cell(root, args.workload)
    for seed in args.seeds:
        counts = readings(cell, seed, args.rounds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "rounds": args.rounds,
                          "correct": check.verdict(counts),
                          "check": counts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
