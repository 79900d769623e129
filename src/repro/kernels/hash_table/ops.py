"""Jit'd dispatch for the hash-table kernels.

``use_pallas`` selects the Pallas kernel (default: the XLA reference
path). ``interpret`` runs that kernel in the Pallas interpreter; only a
caller without a TPU asks for it (the kernel tests on the CPU).

Also enforces the VMEM-residency sizing rule from kernel.py: a table that
exceeds the budget is not rejected — it is dispatched through the sharded
path (launch/state_sharding's high-bit bucket partition), running the
kernel once per shard with each slice VMEM-resident. Queries/writes route
to their owner shard by the high bits of the global bucket index, the same
partition the mesh ``model`` axis uses in launch/fabric_step.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import world_state as ws
from repro.kernels.hash_table import kernel, ref

VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def table_bytes(tkeys, tvals) -> int:
    """VMEM bytes the kernels hold for this table (tile padding included;
    kernel.vmem_bytes)."""
    nb, s, vw = tvals.shape
    return kernel.vmem_bytes(nb, s, vw)


def _n_shards(tkeys, tvals) -> int:
    """Fewest power-of-two bucket shards whose packed slice fits the
    budget (a shard's tiles pad on their own, so count per shard)."""
    nb, s, vw = tvals.shape
    m = 1
    while (kernel.vmem_bytes(nb // m, s, vw) > VMEM_BUDGET_BYTES
           and m < nb):
        m *= 2
    return m


def lookup(tkeys, tvers, tvals, queries, *, use_pallas: bool = False,
           interpret: bool = False):
    """(found, versions, values) for a batch of paired-hash queries."""
    if use_pallas:
        m = _n_shards(tkeys, tvals)
        if m > 1:
            return _sharded_lookup_scan(tkeys, tvers, tvals, queries, m,
                                        interpret)
        return kernel.lookup(
            tkeys, tvers, tvals, queries, interpret=interpret
        )
    return ref.lookup_ref(tkeys, tvers, tvals, queries)


def commit(tkeys, tvers, tvals, wkeys, wvals, active,
           *, use_pallas: bool = False, interpret: bool = False):
    """Sequential insert-or-update commit. Returns (keys, vers, vals, ovf)."""
    if use_pallas:
        m = _n_shards(tkeys, tvals)
        if m > 1:
            return _sharded_commit_scan(tkeys, tvers, tvals, wkeys, wvals,
                                        active, m, interpret)
        return kernel.commit(
            tkeys, tvers, tvals, wkeys, wvals, active, interpret=interpret
        )
    return ref.commit_ref(tkeys, tvers, tvals, wkeys, wvals, active)


def commit_window(tkeys, tvers, tvals, log_keys, log_vals, log_bumps,
                  log_new):
    """Fused window commit (one LWW scatter pass; world_state.commit_window
    log contract). Over-budget tables dispatch per bucket shard: the log is
    replayed once per shard with non-owned entries blanked/masked, exactly
    the owner-shard masking of launch/state_sharding.commit_window_routed.
    The scatter itself is pure XLA (no per-write Pallas loop to fuse), so
    there is no separate kernel path. Returns (keys, vers, vals)."""
    m = _n_shards(tkeys, tvals)
    if m > 1:
        return _sharded_commit_window(
            tkeys, tvers, tvals, log_keys, log_vals, log_bumps, log_new, m
        )
    return ref.commit_window_ref(
        tkeys, tvers, tvals, log_keys, log_vals, log_bumps, log_new
    )


# ---------------------------------------------------------------------------
# Sharded dispatch: one jitted lax.scan over the bucket shards, each slice
# within the VMEM budget (ROADMAP "pipeline slice loads with probes": XLA
# overlaps the next slice's load with the current probe, and the whole
# sharded sweep is ONE compiled program instead of n_shards separate
# dispatches). Results/writes are routed by owner shard.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_shards", "interpret"))
def _sharded_lookup_scan(tkeys, tvers, tvals, queries, n_shards: int,
                         interpret: bool):
    nb = tkeys.shape[0]
    sk, sv, sva = ws.split_table(tkeys, tvers, tvals, n_shards)
    owner = ws.shard_of(nb, n_shards, queries)  # (Q,)
    q = queries.shape[0]
    vw = tvals.shape[2]

    def body(carry, xs):
        found, vers, vals = carry
        m, k, v, va = xs
        f, ver, val = kernel.lookup(k, v, va, queries, interpret=interpret)
        mine = owner == m
        return (
            jnp.where(mine, f, found),
            jnp.where(mine, ver, vers),
            jnp.where(mine[:, None], val, vals),
        ), None

    init = (
        jnp.zeros((q,), bool),
        jnp.zeros((q,), jnp.uint32),
        jnp.zeros((q, vw), jnp.uint32),
    )
    (found, vers, vals), _ = jax.lax.scan(
        body, init, (jnp.arange(n_shards), sk, sv, sva)
    )
    return found, vers, vals


@functools.partial(jax.jit, static_argnames=("n_shards", "interpret"))
def _sharded_commit_scan(tkeys, tvers, tvals, wkeys, wvals, active,
                         n_shards: int, interpret: bool):
    nb = tkeys.shape[0]
    sk, sv, sva = ws.split_table(tkeys, tvers, tvals, n_shards)
    owner = ws.shard_of(nb, n_shards, wkeys)  # (K,)

    def body(ovf, xs):
        m, k, v, va = xs
        k2, v2, va2, o = kernel.commit(
            k, v, va, wkeys, wvals, active & (owner == m),
            interpret=interpret,
        )
        return ovf | o, (k2, v2, va2)

    ovf, (ks, vs, vls) = jax.lax.scan(
        body, jnp.asarray(False), (jnp.arange(n_shards), sk, sv, sva)
    )
    okeys, overs, ovals = ws.merge_table(ks, vs, vls)
    return okeys, overs, ovals, ovf


@functools.partial(jax.jit, static_argnames=("n_shards",))
def _sharded_commit_window(tkeys, tvers, tvals, log_keys, log_vals,
                           log_bumps, log_new, n_shards: int):
    nb = tkeys.shape[0]
    sk, sv, sva = ws.split_table(tkeys, tvers, tvals, n_shards)
    owner = ws.shard_of(nb, n_shards, log_keys)  # (L,)

    def body(_, xs):
        m, k, v, va = xs
        mine = owner == m
        st = ws.commit_window(
            ws.HashState(k, v, va),
            jnp.where(mine[:, None], log_keys, jnp.uint32(0)),
            log_vals, log_bumps & mine, log_new & mine,
        )
        return None, (st.keys, st.versions, st.values)

    _, (ks, vs, vls) = jax.lax.scan(
        body, None, (jnp.arange(n_shards), sk, sv, sva)
    )
    return ws.merge_table(ks, vs, vls)
