"""The four-channel window path with the exchange between chips left out:
every chip commits chip 0's window instead of its own channel's. The run
must come out as not correct. (Channels on a 1x1 mesh here: the window
state of all four sits on the one CPU device, and the fault is the same
missing hand-over of each channel's blocks to the state that holds it.)
"""

import json
import os
import time

import jax
import jax.numpy as jnp

from bench import harness, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_channels_fed_one_chips_window_are_not_correct(monkeypatch):
    from repro.pipeline import engine_bridge

    real = engine_bridge.MeshWindowCommitter.commit_windows

    def commit_windows(self, wires, tx_ids):
        return real(self, jnp.broadcast_to(wires[:1], wires.shape),
                    jnp.broadcast_to(tx_ids[:1], tx_ids.shape))

    monkeypatch.setattr(engine_bridge.MeshWindowCommitter, "commit_windows",
                        commit_windows)
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "ff-4ch-pipe8.json")))
    cfg = dict(cfg, dims=dict(cfg["dims"], payload_words=32), n_buckets=256,
               n_accounts=1024, block_txs=16, mesh=[1, 1])
    traffic = json.load(open(os.path.join(ROOT,
                                          "bench/traffic/transfer800.json")))
    cell = spec.Cell(ROOT, {"name": "4ch", "chips": 1}, cfg,
                     dict(traffic, txs_per_round=32), [], [])
    res = harness.run_cell(cell, 2**31 + 3, 0.2, False, jax.devices()[:1],
                           t_start=time.perf_counter(), log=lambda m: None)
    json.dumps(res)
    assert res["correct"] is False
    assert res["check"]["state_slots_differ"]["value"] > 0
    assert res["check"]["wire_rows_differ"]["value"] == 0  # ordered right
