"""Endorser role: speculative chaincode execution + endorsement tags.

Paper mapping (§II-B, §III-G): endorsers execute a client's transaction in a
sandbox against their *replica* of world state, record the read/write sets
with observed versions, and sign the result. FastFabric splits endorsers onto
dedicated hardware; they no longer validate — they receive validated blocks
from the committer and just apply the deltas to their state replica.

The benchmark chaincode is the paper's money transfer: read two accounts,
write both (amount moves from src to dst; word 0 of the value is the
balance, remaining value words carry an asset tag).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import crypto, hashing, types, unmarshal
from repro.core import world_state as ws

U32 = jnp.uint32


class Proposal(NamedTuple):
    """Client proposal for the transfer chaincode."""

    src: jnp.ndarray  # (B,) u32 account ids
    dst: jnp.ndarray  # (B,) u32
    amount: jnp.ndarray  # (B,) u32
    client: jnp.ndarray  # (B,) u32
    nonce: jnp.ndarray  # (B,) u32 — makes tx ids unique


def _account_key(acct: jnp.ndarray) -> jnp.ndarray:
    h1, h2 = hashing.hash_pair(acct)
    return jnp.stack([hashing.nonzero_key(h1), h2], axis=-1)  # (B, 2)


def execute_and_endorse(
    state: ws.HashState,
    prop: Proposal,
    dims: types.FabricDims,
    *,
    n_endorsers: int | None = None,
) -> types.TxBatch:
    """Sandbox-execute the transfer chaincode and endorse the result.

    Reads src/dst balances from the endorser's replica, computes the
    post-transfer balances, and records read versions as observed. The
    returned TxBatch carries valid endorsement tags from ``ne`` endorsers.
    """
    if dims.rk < 2 or dims.wk < 2:
        raise ValueError("transfer chaincode needs rk>=2, wk>=2")
    b = prop.src.shape[0]
    k_src = _account_key(prop.src)
    k_dst = _account_key(prop.dst)

    look_src = ws.lookup(state, k_src)
    look_dst = ws.lookup(state, k_dst)
    bal_src = look_src.values[:, 0]
    bal_dst = look_dst.values[:, 0]
    # Transfer executes even from empty accounts (balance wraps) — validity
    # here is about *state versions*, not business rules, matching the
    # paper's all-valid workload.
    new_src = bal_src - prop.amount
    new_dst = bal_dst + prop.amount

    read_keys = jnp.zeros((b, dims.rk, 2), U32)
    read_keys = read_keys.at[:, 0].set(k_src).at[:, 1].set(k_dst)
    read_vers = jnp.zeros((b, dims.rk), U32)
    read_vers = read_vers.at[:, 0].set(look_src.versions)
    read_vers = read_vers.at[:, 1].set(look_dst.versions)

    write_keys = read_keys[:, : dims.wk]
    write_vals = jnp.zeros((b, dims.wk, dims.vw), U32)
    write_vals = write_vals.at[:, 0, 0].set(new_src)
    write_vals = write_vals.at[:, 1, 0].set(new_dst)
    # Asset tag: carried through value words 1+ (content the store must keep).
    if dims.vw > 1:
        write_vals = write_vals.at[:, 0, 1].set(prop.src)
        write_vals = write_vals.at[:, 1, 1].set(prop.dst)

    tx_id = jnp.stack(
        hashing.hash_pair(
            hashing.hash_u32(prop.nonce) ^ prop.src ^ (prop.dst * jnp.uint32(3))
        ),
        axis=-1,
    )
    txb = types.TxBatch(
        tx_id=tx_id,
        client=prop.client,
        channel=jnp.zeros((b,), U32),
        read_keys=read_keys,
        read_vers=read_vers,
        write_keys=write_keys,
        write_vals=write_vals,
        endorse_tags=jnp.zeros((b, dims.ne), U32),
    )
    tags = crypto.endorse_batch(txb, n_endorsers or dims.ne)
    return txb._replace(endorse_tags=tags)


def apply_validated(
    state: ws.HashState, txb: types.TxBatch, valid: jnp.ndarray
) -> ws.HashState:
    """Endorser-cluster replica update: apply a validated block's deltas
    without re-validating (§III-G)."""
    return ws.commit_vectorized(
        state, txb.write_keys, txb.write_vals, valid
    ).state


def replay_blocks(
    state: ws.HashState, wires: jnp.ndarray, valids: jnp.ndarray,
    dims: types.FabricDims,
) -> tuple[ws.HashState, jnp.ndarray]:
    """Endorser-cluster replica update for a run of retired blocks in one
    program: ``wires`` (n_blocks, B, 4*payload_words) u8 and ``valids``
    (n_blocks, B) bool. Each block's write set, decoded from its wire,
    applies in block order, so a key rewritten by a later block ends as
    that block left it. Returns the new replica and the count of valid
    transactions (a device scalar)."""

    # A static loop, not a scan: XLA then keeps the table in fast memory
    # across the blocks, where a scan body moves it there and back per
    # block.
    for b in range(wires.shape[0]):
        txb = unmarshal.unmarshal(wires[b], dims).txb
        state = apply_validated(state, txb, valids[b])
    return state, valids.sum(dtype=jnp.int32)


endorse_jit = jax.jit(
    execute_and_endorse, static_argnames=("dims", "n_endorsers")
)
# The replica is donated: the update writes the table in place instead of
# copying it (the wires and valids stay live for the block store).
replay_blocks_jit = jax.jit(
    replay_blocks, static_argnames=("dims",), donate_argnums=(0,)
)
