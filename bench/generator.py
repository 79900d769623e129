"""The one traffic generator: rounds of transfer proposals from a seed.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``);
this module reads any of them. The loop is closed: each round is one
client batch per channel, sent when the previous round has returned.

Per round and channel it draws ``txs_per_round`` transfers with source
and destination accounts all distinct within the round (so no two
transactions of a round touch the same account), amounts in
``[amount_min, amount_max]``, client ids in ``[0, clients)`` and nonces
distinct per round. Accounts come from the configuration's key space
(``n_accounts``). Every seed draws the same sizes; only the accounts,
amounts and clients move with it.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class RoundProposals(NamedTuple):
    """One channel's client batch, host-side u32 arrays of one length."""

    src: np.ndarray
    dst: np.ndarray
    amount: np.ndarray
    client: np.ndarray
    nonce: np.ndarray


class Generator:
    """Deterministic stream of rounds: the same seed gives the same rounds
    in the same order, for the run and for the reference alike."""

    def __init__(self, traffic: dict, n_accounts: int, n_channels: int,
                 seed: int):
        if traffic["loop"] != "closed" or traffic["chaincode"] != "transfer":
            raise ValueError(f"unsupported traffic {traffic}")
        self.n = int(traffic["txs_per_round"])
        if 2 * self.n > n_accounts:
            raise ValueError("a round needs 2 distinct accounts per tx")
        self.lo = int(traffic["amount_min"])
        self.hi = int(traffic["amount_max"])
        self.clients = int(traffic["clients"])
        self.n_accounts = n_accounts
        self.n_channels = n_channels
        self.rng = np.random.default_rng(seed)
        self.rounds = 0

    def next_round(self) -> list[RoundProposals]:
        """The next round: one batch per channel."""
        out = []
        base = np.uint64(self.rounds * self.n)
        for _ in range(self.n_channels):
            accts = self.rng.choice(self.n_accounts, size=2 * self.n,
                                    replace=False).astype(np.uint32)
            nonce = ((base + np.arange(self.n, dtype=np.uint64))
                     & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            out.append(RoundProposals(
                src=accts[:self.n],
                dst=accts[self.n:],
                amount=self.rng.integers(self.lo, self.hi + 1, size=self.n,
                                         dtype=np.uint32),
                client=self.rng.integers(0, self.clients, size=self.n,
                                         dtype=np.uint32),
                nonce=nonce,
            ))
        self.rounds += 1
        return out
