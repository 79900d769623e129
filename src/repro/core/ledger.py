"""Hash-chained ledger + the decoupled block store (Opt P-II storage role).

Paper mapping: every peer appends validated blocks (with per-tx validity
flags kept *in* the block — Fabric semantics) to the blockchain log.
FastFabric moves that log off the critical path to a storage cluster
(§III-F); the committer only computes the chain hash and ships the block.

``append_hash`` is the on-critical-path part (jit-able, tiny); ``BlockStore``
is the off-path storage role: it receives validated blocks asynchronously
(host callback / separate mesh role in the distributed runtime), keeps the
full chain, and can rebuild world state by replay — which is exactly the
durability argument that lets P-I drop the database (§III-E).
"""

from __future__ import annotations

import functools
import os
import queue
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, types, unmarshal, world_state
from repro.obs.trace import NULL_TRACER

U32 = jnp.uint32

GENESIS = jnp.zeros((2,), U32)


def channel_dir(base: str, channel: int) -> str:
    """Where channel ``channel``'s files live under ``base``.

    Channel 0 IS ``base`` — every pre-multi-channel directory layout
    (spill dirs, journal segment dirs, snapshot dirs) is exactly channel
    0's layout, so single-channel deployments keep their paths and old
    directories restore as channel 0. Other channels nest one level down.
    """
    if channel == 0:
        return base
    return os.path.join(base, f"channel_{channel:04d}")


def load_spilled_blocks(spill_dir: str, start_block: int,
                        channel: int = 0) -> list["StoredBlock"]:
    """Read a channel's spilled blocks from ``start_block`` upward until
    the first gap. The restore path uses this to rebuild the suffix a
    snapshot doesn't cover (FabricEngine.restore with a snapshot trailing
    the journal tip)."""
    d = channel_dir(spill_dir, channel)
    out: list[StoredBlock] = []
    bno = start_block
    while True:
        path = os.path.join(d, f"block_{bno:08d}.npz")
        if not os.path.exists(path):
            return out
        with np.load(path) as z:
            out.append(StoredBlock(
                block_no=bno,
                prev_hash=z["prev_hash"], block_hash=z["block_hash"],
                wire=z["wire"], valid=z["valid"],
            ))
        bno += 1


def block_body_digest(wire: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Content digest of a block body: per-tx digests + validity flags,
    folded order-dependently. (2,) u32."""
    n, wb = wire.shape
    words = jax.lax.bitcast_convert_type(
        wire.reshape(n, wb // 4, 4), U32
    ).reshape(n, wb // 4)
    d1 = hashing.hash_words(words, seed=hashing.SEED_A)  # (N,)
    d2 = hashing.hash_words(words, seed=hashing.SEED_B)
    v = valid.astype(U32)
    h1 = hashing.hash_words((d1 ^ v)[None, :], seed=hashing.SEED_A)[0]
    h2 = hashing.hash_words((d2 ^ (v << 1))[None, :], seed=hashing.SEED_B)[0]
    return jnp.stack([h1, h2])


def append_hash(prev_hash: jnp.ndarray, block_no: jnp.ndarray,
                body_digest: jnp.ndarray) -> jnp.ndarray:
    """Chain: H(prev || block_no || body). (2,) u32."""
    words = jnp.concatenate(
        [prev_hash, jnp.atleast_1d(block_no).astype(U32), body_digest]
    )[None, :]
    return jnp.stack(
        [
            hashing.hash_words(words, seed=hashing.SEED_A)[0],
            hashing.hash_words(words, seed=hashing.SEED_B)[0],
        ]
    )


class StoredBlock(NamedTuple):
    block_no: int
    prev_hash: np.ndarray
    block_hash: np.ndarray
    wire: np.ndarray
    valid: np.ndarray


@jax.jit
def _link_hash(prev, block_no, wire, valid):
    """Chain hash of one stored block (``verify_chain``), one program."""
    return append_hash(prev, block_no, block_body_digest(wire, valid))


@functools.partial(jax.jit, static_argnames=("dims",))
def _replay_block(st, wire, valid, dims):
    """Apply one stored block's valid writes (``replay_state``)."""
    dec = unmarshal.unmarshal(wire, dims)
    return world_state.commit_vectorized(
        st, dec.txb.write_keys, dec.txb.write_vals, valid
    ).state


class BlockStore:
    """The storage-cluster role: async, append-only, off the critical path.

    A writer thread drains a queue of device blocks, copies them to host
    (the 'remote gRPC call' of §III-F) and appends to an in-memory chain
    [+ optional directory spill]. ``verify_chain`` / ``replay_state`` give
    the durability guarantee that justifies P-I.

    When a ``journal`` (storage/journal.StateJournal) is attached, the same
    writer thread also emits each block's validated write sets into it —
    journal materialization rides the storage role, off the commit path.
    ``prune_upto`` compacts the chain up to the last snapshot: pruned
    history stays authenticated because the chain re-anchors at the hash of
    the last pruned block (``base_hash``), which the covering snapshot's
    recovery path cross-checks.

    ONE store (one writer thread, one queue) multiplexes every channel of a
    multi-channel engine: submitted blocks are channel-tagged, and the
    store keeps per-channel chains, re-anchor bases and journals — the
    paper's storage cluster serves all channels, but each channel's chain
    verifies independently (cross-channel isolation: a corrupted record in
    channel i's chain or journal fails only channel i's checks). The
    channel-0 surface (``.chain``, ``.base_block_no``, ``.base_hash``,
    channel-less method calls) is the pre-multi-channel API unchanged.

    With a live ``tracer`` the writer records one ``store.append`` span per
    block: from the dequeue through the host copy, the spill and the
    journal to the chain append, with ``queued_s`` (submit to dequeue).
    """

    def __init__(self, spill_dir: str | None = None, *, journal=None,
                 tracer=NULL_TRACER):
        self._q: "queue.Queue" = queue.Queue()
        self._tracer = tracer
        self.chains: dict[int, list[StoredBlock]] = {0: []}
        self.base_block_nos: dict[int, int] = {0: -1}
        self.base_hashes: dict[int, np.ndarray] = {
            0: np.zeros(2, np.uint32)
        }
        self._spill_dir = spill_dir
        self._journals: dict[int, object] = {}
        if journal is not None:
            self._journals[0] = journal
        self._err: Exception | None = None
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    # -- channel plumbing --------------------------------------------------

    def _chan(self, channel: int) -> list[StoredBlock]:
        if channel not in self.chains:
            self.chains[channel] = []
            self.base_block_nos[channel] = -1
            self.base_hashes[channel] = np.zeros(2, np.uint32)
        return self.chains[channel]

    def set_journal(self, channel: int, journal) -> None:
        """Attach channel ``channel``'s state journal to the writer."""
        self._journals[channel] = journal

    @property
    def chain(self) -> list[StoredBlock]:
        """Channel 0's chain (single-channel compat; the returned list is
        live — callers may index/mutate it, as the tamper tests do)."""
        return self._chan(0)

    @chain.setter
    def chain(self, value: list[StoredBlock]) -> None:
        self.chains[0] = value

    @property
    def base_block_no(self) -> int:
        return self.base_block_nos[0]

    @base_block_no.setter
    def base_block_no(self, value: int) -> None:
        self.base_block_nos[0] = value

    @property
    def base_hash(self) -> np.ndarray:
        return self.base_hashes[0]

    @base_hash.setter
    def base_hash(self, value: np.ndarray) -> None:
        self.base_hashes[0] = value

    @property
    def _journal(self):
        return self._journals.get(0)

    @_journal.setter
    def _journal(self, value) -> None:
        if value is None:
            self._journals.pop(0, None)
        else:
            self._journals[0] = value

    def _spill_path(self, channel: int, bno: int) -> str:
        d = channel_dir(self._spill_dir, channel)
        # Channel subdirs are created on demand; the BASE dir must already
        # exist — a missing base is a misconfiguration the writer fail-stops
        # on (and a contract the storage tests pin).
        if channel != 0:
            os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"block_{bno:08d}.npz")

    # -- the writer --------------------------------------------------------

    def submit(self, block_no, prev_hash, block_hash, wire, valid,
               channel: int = 0) -> None:
        self._chan(channel)  # channel registered caller-side: the writer
        # thread then only appends to an existing list (no dict mutation
        # races between submit and the drain thread).
        self._q.put((block_no, prev_hash, block_hash, wire, valid, channel,
                     time.perf_counter()))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            if self._err is not None:
                # Fail-stop: once an append failed, drop everything behind
                # it. Appending past the failure would leave a silent gap
                # in whichever sink raised while the others kept growing;
                # dropping keeps chain and journal consistent up to the
                # failure point, and the next drain()/close() surfaces the
                # error (and the gap fails verify_chain if writing resumes).
                self._q.task_done()
                continue
            spill_path = None
            try:
                channel, t_submit = item[-2:]
                with self._tracer.span(
                    "store.append", block_no=int(item[0]), channel=channel,
                    queued_s=time.perf_counter() - t_submit,
                ):
                    bno, prev, bh, wire, valid = jax.device_get(item[:-2])
                    sb = StoredBlock(int(bno), prev, bh, wire, valid)
                    if self._spill_dir is not None:
                        spill_path = self._spill_path(channel, int(bno))
                        np.savez(
                            spill_path, prev_hash=prev, block_hash=bh,
                            wire=wire, valid=valid,
                        )
                    jrnl = self._journals.get(channel)
                    if jrnl is not None:
                        jrnl.append_block(int(bno), wire, valid)
                # Chain append last: a block is in the chain only if every
                # sink (spill, journal) accepted it, so the sinks can never
                # silently trail the chain.
                self._chan(channel).append(sb)
            except Exception as e:  # surfaced on drain()/close()
                self._err = e
                # Un-spill this block so no sink leads the chain: a reader
                # of the spill directory must never see a block the chain
                # and journal fail-stopped before.
                if spill_path is not None:
                    try:
                        os.remove(spill_path)
                    except OSError:
                        pass
            finally:
                self._q.task_done()

    def _surface_err(self) -> None:
        """Raise a latched writer error exactly once, then clear it so the
        store is usable again (the dropped tail is detectable: replays of
        the gap fail verify_chain)."""
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def close(self) -> None:
        self._q.put(None)
        self._t.join()
        self._surface_err()

    def drain(self) -> None:
        """Block until everything submitted so far is stored."""
        self._q.join()
        self._surface_err()

    def resume(self, channel: int = 0) -> int:
        """Supervised restart after a writer failure.

        The writer fail-stops on the first sink error: the failed block and
        everything submitted behind it are dropped (never silently
        appended). ``resume`` reopens the store from the last durably
        stored block: it waits for the writer to finish discarding the
        in-flight suffix, clears the latched error, and returns the next
        block number expected on ``channel``. The supervisor resubmits the
        dropped suffix from there and the chain continues gap-free —
        instead of relying on ``verify_chain`` to flag the hole after the
        fact. Safe to call with no failure latched (it is then just "where
        do I resume from"). The error is NOT surfaced: resuming is the
        handled-error path.
        """
        self._q.join()
        self._err = None
        ch = self._chan(channel)
        last = ch[-1].block_no if ch else self.base_block_nos[channel]
        return last + 1

    # --- Compaction (snapshot-covered prefix) ----------------------------

    def prune_upto(self, block_no: int, channel: int = 0) -> int:
        """Drop ``channel``'s blocks <= ``block_no`` (covered by a
        snapshot) from memory and from the spill directory. Returns the
        number dropped. Call only with the writer drained."""
        ch = self._chan(channel)
        dropped = [sb for sb in ch if sb.block_no <= block_no]
        if dropped:
            self.chains[channel] = [
                sb for sb in ch if sb.block_no > block_no
            ]
            self.base_block_nos[channel] = dropped[-1].block_no
            self.base_hashes[channel] = dropped[-1].block_hash
            if self._spill_dir is not None:
                for sb in dropped:
                    path = self._spill_path(channel, sb.block_no)
                    if os.path.exists(path):
                        os.remove(path)
        return len(dropped)

    # --- Durability guarantees -------------------------------------------

    def verify_chain(self, channel: int = 0) -> bool:
        prev = self.base_hashes.get(channel, np.zeros(2, np.uint32))
        for sb in self.chains.get(channel, ()):
            if not np.array_equal(sb.prev_hash, prev):
                return False
            expect = _link_hash(
                jnp.asarray(prev), jnp.uint32(sb.block_no),
                jnp.asarray(sb.wire), jnp.asarray(sb.valid),
            )
            if not np.array_equal(np.asarray(expect), sb.block_hash):
                return False
            prev = sb.block_hash
        return True

    def replay_state(
        self, dims: types.FabricDims, n_buckets: int, slots: int,
        start_state: world_state.HashState | None = None,
        resize_at: dict[int, int] | None = None,
        channel: int = 0,
    ) -> world_state.HashState:
        """Rebuild ``channel``'s world state from its chain (crash
        recovery for P-I).

        ``start_state``: when the prefix was pruned, replay resumes from the
        covering snapshot's state instead of genesis. ``resize_at`` maps a
        boundary block number to the GLOBAL bucket count(s) the elastic
        state resized to right after that block — an int, or a list of
        ints applied in order when several resizes landed at the same
        boundary (a lossy shrink between two grows must replay lossy, so
        the steps cannot be collapsed into their composition). Sourced
        from the engine re-anchor log / journal re-anchor records; replay
        crosses the resize epochs and lands on the live layout.
        """
        st = (world_state.create(n_buckets, slots, dims.vw)
              if start_state is None else start_state)
        resize_at = {
            b: list(nb) if isinstance(nb, (list, tuple)) else [nb]
            for b, nb in (resize_at or {}).items()
        }

        def cross(st, boundary):
            for nb in resize_at.pop(boundary, ()):
                st = world_state.resize(st, nb).state
            return st

        for sb in self.chains.get(channel, ()):
            st = cross(st, sb.block_no - 1)
            st = _replay_block(st, jnp.asarray(sb.wire),
                               jnp.asarray(sb.valid), dims)
            st = cross(st, sb.block_no)
        for boundary in sorted(resize_at):
            st = cross(st, boundary)
        return st
