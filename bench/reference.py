"""Plain sequential reference of the committing peer, in NumPy.

It imports nothing of the program. From the same proposals it computes,
one channel at a time and one block at a time, what a FastFabric peer
with an in-memory hash-table world state must produce:

  endorse   transfer chaincode against the replica (read src/dst, move
            ``amount``), read versions as observed, tx id from the nonce,
            a Carter-Wegman MAC per endorser over the message words;
  marshal   the wire layout: header, checksum, read/write sets, tags,
            opaque filler; checksum = FNV chain over the words after it;
  order     consensus order = stable sort by a hash of the tx id, the
            metadata join taking each ordered id's first wire row;
            blocks of ``block_txs``;
  validate  read versions against the state at block start, and no
            earlier valid tx of the block writing a key this tx touches;
  commit    insert-or-update of valid writes in block order: an update
            bumps the version, an insert takes the bucket's next free
            slot (version 1) or is dropped when the bucket is full;
  ledger    per-block body digest over the wire and validity bits,
            chained by block number; the state journal chains the write
            sets the same way.

The world state is kept per account (keys are a bijection of account
ids), with each account's bucket slot fixed at its first insert; the
table the program holds is rebuilt from that at the end.
"""

from __future__ import annotations

import numpy as np

from bench.generator import Generator

U32 = np.uint32
MASK = 0xFFFFFFFF
SEED_A = 0x9E3779B9
SEED_B = 0x85EBCA6B
FNV_PRIME = 0x01000193
CHECKSUM_SEED = 0x811C9DC5
JOURNAL_TAG = 0x4A524E4C
MAC_R_SEED = 0x1234ABCD
MAC_S_SEED = 0xFEED5EED
P31 = (1 << 31) - 1
CHECKSUM_WORD = 4
CHUNK_BLOCKS = 160  # blocks of wire words built at once


def fmix(x: np.ndarray) -> np.ndarray:
    """murmur3 32-bit finalizer on a u32 array."""
    x = np.asarray(x, U32)
    x = x ^ (x >> U32(16))
    x = x * U32(0x85EBCA6B)
    x = x ^ (x >> U32(13))
    x = x * U32(0xC2B2AE35)
    return x ^ (x >> U32(16))


def fmix_int(x: int) -> int:
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & MASK
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & MASK
    return x ^ (x >> 16)


def hash_rows(words_t: np.ndarray, seeds: tuple) -> list:
    """FNV-style chain over the rows of ``words_t`` (W, N) u32, once per
    seed: h = h * P + w; h ^= h >> 15; then fmix. Returns one (N,) per
    seed."""
    hs = [np.full(words_t.shape[1], s, U32) for s in seeds]
    p = U32(FNV_PRIME)
    for w in words_t:
        for i, h in enumerate(hs):
            h = h * p + w
            hs[i] = h ^ (h >> U32(15))
    return [fmix(h) for h in hs]


def hash_ints(words, seed: int) -> int:
    h = seed
    for w in words:
        h = (h * FNV_PRIME + int(w)) & MASK
        h ^= h >> 15
    return fmix_int(h)


def account_keys(acct: np.ndarray) -> np.ndarray:
    """(N,) account ids -> (N, 2) paired keys; word 0 never 0 or ~0."""
    k0 = fmix(acct ^ U32(SEED_A))
    k0 = np.where(k0 == 0, U32(1), k0)
    k0 = np.where(k0 == U32(MASK), U32(MASK - 1), k0)
    return np.stack([k0, fmix(acct ^ U32(SEED_A ^ SEED_B))], axis=-1)


def mac_keys(n: int) -> tuple:
    e = np.arange(n, dtype=U32)
    r = np.maximum(fmix(e ^ U32(MAC_R_SEED)).astype(np.uint64) % P31, 1)
    s = np.maximum(fmix(e ^ U32(MAC_S_SEED)).astype(np.uint64) % P31, 1)
    return r, s


def mac_tags(msg: np.ndarray, n_endorsers: int) -> np.ndarray:
    """(N, W) message words -> (N, NE) tags: Horner mod 2^31 - 1."""
    r, s = mac_keys(n_endorsers)
    m = msg.astype(np.uint64) % P31
    out = np.empty((msg.shape[0], n_endorsers), U32)
    for e in range(n_endorsers):
        acc = np.zeros(msg.shape[0], np.uint64)
        for i in range(msg.shape[1]):
            acc = (acc * r[e] + m[:, i]) % P31
        out[:, e] = (acc + s[e]) % P31
    return out


class Block:
    """One reference block: its number, validity bits and chain hashes."""

    __slots__ = ("block_no", "valid", "prev_hash", "block_hash")

    def __init__(self, block_no: int, valid: np.ndarray):
        self.block_no = block_no
        self.valid = valid
        self.prev_hash = None
        self.block_hash = None


class ChannelReference:
    """One channel's peer, fed round by round, finished once."""

    def __init__(self, config: dict):
        d = config["dims"]
        if d["rk"] != 2 or d["wk"] != 2:
            raise ValueError("the transfer reference reads and writes 2 keys")
        self.vw, self.ne, self.words = d["vw"], d["ne"], d["payload_words"]
        self.opaque = 5 + 3 * 2 + (2 + self.vw) * 2 + self.ne
        self.block_txs = config["block_txs"]
        self.n_buckets = config["n_buckets"]
        self.slots = config["slots"]
        n_acc = config["n_accounts"]
        self.keys = account_keys(np.arange(n_acc, dtype=U32))
        self.version = np.zeros(n_acc, U32)
        self.value = np.zeros((n_acc, self.vw), U32)
        self.slot = np.full(n_acc, -1, np.int64)
        self.used = np.zeros(self.n_buckets, np.int64)
        self.overflow = False
        self.blocks: list[Block] = []
        self._msg = []  # per round: (N, 22) message words, ordered
        self._pos = []  # per round: wire row of each ordered tx at marshal
        self._writes = []  # per round: (N, 12) write-set words, ordered
        self.n_txs = 0

    # -- pass 1: the state machine, round by round ---------------------------

    def round(self, p) -> None:
        n = p.src.shape[0]
        if n % self.block_txs:
            raise ValueError(f"round of {n} not a multiple of blocks")
        src, dst = p.src.astype(U32), p.dst.astype(U32)
        ks, kd = self.keys[src], self.keys[dst]
        # Endorse against the replica (= the peer's state at round start).
        vs, vd = self.version[src].copy(), self.version[dst].copy()
        new_s = self.value[src, 0] - p.amount.astype(U32)
        new_d = self.value[dst, 0] + p.amount.astype(U32)
        x = fmix(p.nonce.astype(U32) ^ U32(SEED_A)) ^ src ^ (dst * U32(3))
        tid = np.stack([fmix(x ^ U32(SEED_A)),
                        fmix(x ^ U32(SEED_A ^ SEED_B))], axis=-1)
        zero = np.zeros(n, U32)
        vals_s = [new_s, src] + [zero] * (self.vw - 2)
        vals_d = [new_d, dst] + [zero] * (self.vw - 2)
        writes = np.stack([ks[:, 0], ks[:, 1], kd[:, 0], kd[:, 1],
                           *vals_s, *vals_d], axis=-1)
        msg = np.concatenate([
            tid, p.client.astype(U32)[:, None], zero[:, None],
            ks, kd, vs[:, None], vd[:, None], writes,
        ], axis=1)

        # Order: stable sort by the id hash; the join takes each ordered
        # id's first row (a repeated id ships the first one's payload).
        mix = fmix(tid[:, 0] ^ fmix(tid[:, 1] ^ U32(SEED_A)) ^ U32(SEED_A))
        order = np.argsort(mix, kind="stable")
        id64 = (tid[:, 0].astype(np.uint64) << np.uint64(32)) | tid[:, 1]
        _, first, inv = np.unique(id64, return_index=True,
                                  return_inverse=True)
        rows = first[inv[order]]

        # Validate and commit block by block.
        for lo in range(0, n, self.block_txs):
            r = rows[lo:lo + self.block_txs]
            valid = self._validate(src[r], dst[r], vs[r], vd[r])
            self._commit(src[r], dst[r], new_s[r], new_d[r], valid)
            self.blocks.append(Block(len(self.blocks), valid))
        self._msg.append(msg[rows])
        self._pos.append(rows)
        self._writes.append(writes[rows])
        self.n_txs += n

    def _validate(self, src, dst, vs, vd) -> np.ndarray:
        fresh = (self.version[src] == vs) & (self.version[dst] == vd)
        touched = np.concatenate([src, dst])
        if np.unique(touched).size == touched.size:
            return fresh  # no tx of the block shares a key with another
        valid = np.zeros(src.size, bool)
        written: set = set()
        for i in range(src.size):
            ok = bool(fresh[i]) and not ({int(src[i]), int(dst[i])}
                                         & written)
            if ok:
                written |= {int(src[i]), int(dst[i])}
            valid[i] = ok
        return valid

    def _commit(self, src, dst, new_s, new_d, valid) -> None:
        acct = np.stack([src, dst], axis=1)[valid].ravel()
        val = np.stack([new_s, new_d], axis=1)[valid].ravel()
        # A key written twice in one block: the first write applies.
        _, first = np.unique(acct, return_index=True)
        first.sort()
        acct, val = acct[first], val[first]
        old = self.slot[acct] >= 0
        self.version[acct[old]] += U32(1)
        new = acct[~old]
        if new.size:
            bucket = (self.keys[new, 0] & U32(self.n_buckets - 1)).astype(
                np.int64)
            by = np.argsort(bucket, kind="stable")
            b_sorted = bucket[by]
            rank = np.arange(new.size) - np.searchsorted(b_sorted, b_sorted)
            slot = np.empty(new.size, np.int64)
            slot[by] = self.used[b_sorted] + rank
            fits = slot < self.slots
            self.overflow |= bool((~fits).any())
            np.add.at(self.used, bucket[fits], 1)
            self.slot[new[fits]] = slot[fits]
            self.version[new[fits]] = U32(1)
            applied = old.copy()
            applied[~old] = fits
            acct, val = acct[applied], val[applied]
        self.value[acct, 0] = val
        self.value[acct, 1] = acct.astype(U32)

    # -- pass 2: wire words, digests and chains -------------------------------

    def wire_chunks(self):
        """Yield ``(lo, words)``: the ordered stream's wire words
        (N, payload_words) u32 from tx ``lo`` on, CHUNK_BLOCKS whole
        blocks at a time; computes each tx's body digest on the way."""
        msg = np.concatenate(self._msg)
        pos = np.concatenate(self._pos).astype(U32)
        self._d1 = np.empty(self.n_txs, U32)
        self._d2 = np.empty(self.n_txs, U32)
        n_opaque = self.words - self.opaque
        # The opaque body of a tx depends only on its row in the round's
        # marshal batch: word j of row i is fmix((i * n_opaque + j + 1)
        # ^ SEED_A).
        rows = np.arange(int(pos.max()) + 1, dtype=U32)[:, None]
        filler = fmix((rows * U32(n_opaque)
                       + np.arange(n_opaque, dtype=U32)[None, :] + U32(1))
                      ^ U32(SEED_A))  # (rows, n_opaque)
        chunk = CHUNK_BLOCKS * self.block_txs

        def build(lo):
            m = msg[lo:lo + chunk]
            w = np.empty((self.words, m.shape[0]), U32)
            w[:4] = m[:, :4].T
            w[5:self.opaque - self.ne] = m[:, 4:].T
            w[self.opaque - self.ne:self.opaque] = mac_tags(m, self.ne).T
            w[self.opaque:] = filler[pos[lo:lo + chunk]].T
            (w[CHECKSUM_WORD],) = hash_rows(w[CHECKSUM_WORD + 1:],
                                            (CHECKSUM_SEED,))
            self._d1[lo:lo + m.shape[0]], self._d2[lo:lo + m.shape[0]] = (
                hash_rows(w, (SEED_A, SEED_B)))
            return lo, w.T

        for lo in range(0, self.n_txs, chunk):
            yield build(lo)

    def finish(self) -> None:
        """Chain the blocks and the journal (after ``wire_chunks`` ran
        to its end)."""
        bs = self.block_txs
        nb = len(self.blocks)
        v = np.stack([b.valid for b in self.blocks]).astype(U32)  # (NB, bs)
        body = self._fold(self._d1.reshape(nb, bs), self._d2.reshape(nb, bs),
                          v)
        writes = np.concatenate(self._writes)
        w1, w2 = hash_rows(np.ascontiguousarray(writes.T), (SEED_A, SEED_B))
        wsd = self._fold(w1.reshape(nb, bs), w2.reshape(nb, bs), v)
        prev, head = (0, 0), (0, 0)
        for b, (b0, b1), (j0, j1) in zip(self.blocks, body, wsd):
            msg = (prev[0], prev[1], b.block_no, b0, b1)
            b.prev_hash = np.array(prev, U32)
            prev = (hash_ints(msg, SEED_A), hash_ints(msg, SEED_B))
            b.block_hash = np.array(prev, U32)
            msg = (JOURNAL_TAG, head[0], head[1], b.block_no, j0, j1)
            head = (hash_ints(msg, SEED_A), hash_ints(msg, SEED_B))
        self.journal_head = np.array(head, U32)
        self.ledger_head = np.array(prev, U32)

    @staticmethod
    def _fold(d1, d2, v):
        """Per block: hash of (digest ^ validity) over its txs, 2 seeds."""
        (h1,) = hash_rows(np.ascontiguousarray((d1 ^ v).T), (SEED_A,))
        (h2,) = hash_rows(np.ascontiguousarray((d2 ^ (v << U32(1))).T),
                          (SEED_B,))
        return np.stack([h1, h2], axis=-1)

    def table(self) -> tuple:
        """The hash table a peer holds: keys (NB, S, 2), versions (NB, S),
        values (NB, S, VW)."""
        keys = np.zeros((self.n_buckets, self.slots, 2), U32)
        vers = np.zeros((self.n_buckets, self.slots), U32)
        vals = np.zeros((self.n_buckets, self.slots, self.vw), U32)
        acct = np.flatnonzero(self.slot >= 0)
        b = (self.keys[acct, 0] & U32(self.n_buckets - 1)).astype(np.int64)
        s = self.slot[acct]
        keys[b, s] = self.keys[acct]
        vers[b, s] = self.version[acct]
        vals[b, s] = self.value[acct]
        return keys, vers, vals


def replay(config: dict, traffic: dict, seed: int, n_rounds: int) -> list:
    """One reference per channel, fed the first ``n_rounds`` rounds the
    generator draws from ``seed``: the stream a run of the cell drove."""
    refs = [ChannelReference(config) for _ in range(config["n_channels"])]
    gen = Generator(traffic, config["n_accounts"], config["n_channels"],
                    seed)
    for _ in range(n_rounds):
        for ref, batch in zip(refs, gen.next_round()):
            ref.round(batch)
    return refs
