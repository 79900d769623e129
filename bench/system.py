"""The system under test: the program's engine, built from a config file.

The benchmark drives the program only through ``FabricEngine.run_round``
(one channel) or ``FabricEngine.run_rounds`` (several) and reads back
what the peer acknowledged: the store's blocks, the committed table, the
journal and ledger heads, the endorser replica.
"""

from __future__ import annotations

import dataclasses
import math
import jax.numpy as jnp
import numpy as np

from bench.check import ChannelOutputs
from repro.core import endorser, engine, types
from repro.launch import fabric_step as fs
from repro.launch.mesh import make_mesh
from repro.pipeline import engine_bridge


def _host_table(state) -> tuple:
    return tuple(np.asarray(a) for a in (state.keys, state.versions,
                                         state.values))


class System:
    """One engine per the configuration, on ``devices``."""

    def __init__(self, config: dict, devices: list, *, obs: bool):
        dims = types.FabricDims(**config["dims"])
        self.n_channels = int(config["n_channels"])
        ecfg = dataclasses.replace(
            engine.FASTFABRIC, dims=dims,
            orderer=dataclasses.replace(engine.FASTFABRIC.orderer,
                                        block_size=config["block_txs"]),
            n_buckets=config["n_buckets"], slots=config["slots"],
            n_endorsers=config["n_endorsers"], n_channels=self.n_channels,
            obs=obs,
        )
        wc = None
        if config["commit_path"] == "window":
            shape = tuple(config["mesh"])
            wc = engine_bridge.MeshWindowCommitter(
                dims, fs.FabricStepConfig(
                    pipeline_depth=config["pipeline_depth"]),
                mesh=make_mesh(shape, devices=devices[:math.prod(shape)]),
                n_buckets=config["n_buckets"], slots=config["slots"],
                n_channels=self.n_channels,
            )
        elif config["commit_path"] != "host":
            raise ValueError(f"unknown commit_path {config['commit_path']!r}")
        self.eng = engine.FabricEngine(ecfg, window_committer=wc)

    @property
    def tracer(self):
        return self.eng.obs.tracer

    def prepare(self, batches) -> list:
        """Host batches (generator.RoundProposals) -> device proposals."""
        return [endorser.Proposal(*(jnp.asarray(a) for a in b))
                for b in batches]

    def run(self, proposals: list) -> list:
        """One closed-loop round on every channel; per-channel RoundStats."""
        if self.n_channels == 1:
            return [self.eng.run_round(proposals[0])]
        return self.eng.run_rounds(proposals)

    def drain(self) -> None:
        self.eng.store.drain()

    def memory_peak_bytes(self, devices) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)

    def outputs(self) -> list[ChannelOutputs]:
        """Every channel's acknowledged results, copied to the host."""
        eng = self.eng
        wc = eng.window_committer
        out = []
        for c in range(self.n_channels):
            ch = eng.chans[c]
            if wc is None:
                table = _host_table(ch.peer_state.hash_state)
                ledger_head = np.asarray(ch.peer_state.ledger_head)
            else:
                table = _host_table(wc.hash_state(c))
                ledger_head = None
            out.append(ChannelOutputs(
                blocks=list(eng.store.chains.get(c, [])),
                table=table,
                replica=_host_table(ch.endorser_state),
                journal_head=np.asarray(eng._peer_journal_head(c)),
                ledger_head=ledger_head,
                overflow_bits=int(eng.overflow_bits(c)),
            ))
        return out

    def close(self) -> None:
        """Stop the store's writer thread and drop the device state."""
        self.eng.store.close()
        self.eng = None
