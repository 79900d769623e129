"""The harness loop on the CPU at a tiny size, through ``run_cell`` (the
run after the command's chip check).

The cell comes from a throwaway checkout in a temporary directory: its
own BENCHMARK.json, configuration, traffic mix and per-layer metric, all
new files, none of them known to the harness. Then the timed path is
broken underneath in each way this cell can fail, and ``correct`` must
come out false.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, reference, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
E2E = ["committed_tps", "commit_latency_p50_ms", "commit_latency_p95_ms",
       "setup_s"]
METRIC = '''
def read(ctx):
    return float(len(ctx.rounds))
'''


def _tiny(config: dict, **kw) -> dict:
    return dict(config, dims=dict(config["dims"], payload_words=32),
                n_buckets=256, n_accounts=1024, block_txs=16, **kw)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with one new config, traffic mix and metric."""
    base = tmp_path_factory.mktemp("checkout")
    spec_in = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(base / "bench" / d)
    host = json.load(open(os.path.join(ROOT, "bench/configs/ff-host.json")))
    json.dump(_tiny(host, name="tiny-host"),
              open(base / "bench/configs/tiny-host.json", "w"))
    tr = json.load(open(os.path.join(ROOT, "bench/traffic/transfer800.json")))
    json.dump(dict(tr, txs_per_round=32),
              open(base / "bench/traffic/transfer32.json", "w"))
    (base / "bench/metrics/rounds_in_window.py").write_text(METRIC)
    json.dump({
        **spec_in,
        "configs": [{"name": "tiny-host", "source": "test",
                     "file": "bench/configs/tiny-host.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny-host.transfer32", "config": "tiny-host",
                       "traffic": "transfer32", "chips": 1, "why": "test"}],
        "per_layer": [{"name": "rounds_in_window", "unit": "count",
                       "better": "higher", "source": "program_counter",
                       "layer": "test", "moves": "committed_tps"}],
    }, open(base / "BENCHMARK.json", "w"))
    return str(base)


def _run(root, trace=False, seconds=0.3, seed=2**31 + 11):
    cell = spec.load_cell(root, "tiny-host.transfer32")
    return harness.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                            t_start=time.perf_counter(), log=lambda m: None)


def test_throwaway_cell_gives_a_contract_shaped_correct_result(root):
    res = _run(root)
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["attempted"] % 32 == 0
    assert list(res["metrics"]) == E2E
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["committed_tps"]["unit"] == "tx/s"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["check"]) == set(check.LIMITS)
    assert all(c == {"value": 0, "limit": 0} for c in res["check"].values())
    json.dumps(res)


def test_throwaway_metric_is_read_in_a_traced_run(root):
    res = _run(root, trace=True)
    assert res["correct"] is True, res["check"]
    assert res["metrics"]["rounds_in_window"]["value"] >= 1
    assert res["metrics"]["rounds_in_window"]["unit"] == "count"


def _stale_state(monkeypatch):
    """A commit step that returns its state unchanged."""
    from repro.core import committer

    real = committer.commit_block

    def commit(state, wire, dims, cfg):
        keep = jax.tree.map(lambda a: jnp.array(a, copy=True), state)
        return real(state, wire, dims, cfg)._replace(state=keep)

    monkeypatch.setattr(committer, "commit_block", commit)


def _half_batch(monkeypatch):
    """Half of each round's batch left out."""
    from repro.core import engine

    real = engine.FabricEngine._round

    def round_(self, proposals, channel):
        half = proposals.src.shape[0] // 2
        return real(self, jax.tree.map(lambda a: a[:half], proposals),
                    channel)

    monkeypatch.setattr(engine.FabricEngine, "_round", round_)


def _altered_answer(monkeypatch):
    """One validity bit per block flipped where the committer makes it."""
    from repro.core import committer

    real = committer.commit_block

    def commit(state, wire, dims, cfg):
        res = real(state, wire, dims, cfg)
        return res._replace(valid=res.valid.at[0].set(~res.valid[0]))

    monkeypatch.setattr(committer, "commit_block", commit)


@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _altered_answer])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["check"].values())


def test_repeated_tx_id_is_judged_like_the_program(root):
    """Two txs of one round with one tx id: the metadata join ships the
    first one's payload twice, and the copy fails MVCC. The reference
    must agree with the engine byte for byte."""
    from bench.generator import RoundProposals
    from bench.system import System

    cell = spec.load_cell(root, "tiny-host.transfer32")
    cfg = cell.config
    rng = np.random.default_rng(1)
    accts = rng.choice(1024, 64, replace=False).astype(np.uint32)
    src, dst = accts[:32].copy(), accts[32:].copy()
    nonce = np.arange(32, dtype=np.uint32)
    h = reference.fmix(nonce ^ np.uint32(reference.SEED_A))
    # Find a nonce for tx 1 whose id equals tx 0's with an unused source.
    want = h[0] ^ src[0] ^ (dst[0] * np.uint32(3))
    for lo in range(1, 1 << 30, 1 << 24):
        n = np.arange(lo, lo + (1 << 24), dtype=np.uint32)
        s = (want ^ reference.fmix(n ^ np.uint32(reference.SEED_A))
             ^ (dst[1] * np.uint32(3)))
        hit = np.flatnonzero((s < 1024) & ~np.isin(s, accts))
        if hit.size:
            nonce[1], src[1] = n[hit[0]], s[hit[0]]
            break
    else:
        pytest.fail("no colliding nonce found")
    batch = RoundProposals(src, dst, np.full(32, 5, np.uint32),
                           np.zeros(32, np.uint32), nonce)
    system = System(cfg, jax.devices()[:1], obs=False)
    stats = system.run(system.prepare([batch]))
    system.drain()
    outs = system.outputs()
    system.close()
    assert stats[0].n_valid == 31
    ref = reference.ChannelReference(cfg)
    ref.round(batch)
    counts = check.compare(outs, [ref])
    assert check.verdict(counts), counts
    assert sum(b.valid.sum() for b in ref.blocks) == 31
