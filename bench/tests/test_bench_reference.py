"""The reference agrees with the program's primitives it restates, and
its control (the reference in the program's place, one guarantee broken)
comes out as not correct."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, reference, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_hash_and_mac_restate_the_program():
    from repro.core import crypto, hashing, types

    rng = np.random.default_rng(3)
    words = rng.integers(0, 2**32, size=(37, 9), dtype=np.uint32)
    for seed in (reference.SEED_A, reference.SEED_B,
                 reference.CHECKSUM_SEED):
        (ours,) = reference.hash_rows(np.ascontiguousarray(words.T), (seed,))
        np.testing.assert_array_equal(
            ours, np.asarray(hashing.hash_words(jnp.asarray(words),
                                                seed=np.uint32(seed))))
    txb = types.make_transfer_batch(types.TEST_DIMS, 16, seed=5)
    msg = np.asarray(types.message_words(txb))
    np.testing.assert_array_equal(
        reference.mac_tags(msg, 3), np.asarray(crypto.endorse_batch(txb, 3)))


def _small_cell(n_channels):
    cell = spec.load_cell(ROOT, "ff-host.transfer800")
    cfg = dict(cell.config, dims=dict(cell.config["dims"], payload_words=32),
               n_buckets=256, n_accounts=1024, block_txs=16,
               n_channels=n_channels)
    return spec.Cell(ROOT, cell.workload, cfg,
                     dict(cell.traffic, txs_per_round=32),
                     cell.end_to_end, cell.per_layer)


@pytest.mark.parametrize("n_channels", [1, 4])
def test_control_is_not_correct(n_channels):
    cell = _small_cell(n_channels)
    counts = control.readings(cell, seed=2**31 + 7, n_rounds=6)
    assert not check.verdict(counts)
    assert counts["blocks_missing"] == n_channels
    # The reference in the program's place, unbroken, is correct.
    sound = [control.reference_outputs(r) for r in reference.replay(
        cell.config, cell.traffic, 2**31 + 7, 6)]
    counts = check.compare(sound, reference.replay(
        cell.config, cell.traffic, 2**31 + 7, 6))
    assert check.verdict(counts), counts


def test_a_full_bucket_drops_the_insert_and_latches_overflow():
    cfg = dict(_small_cell(1).config, n_buckets=8, slots=2, n_accounts=64)
    ref = reference.ChannelReference(cfg)
    tr = json.load(open(os.path.join(ROOT, "bench/traffic/transfer800.json")))
    from bench.generator import Generator

    gen = Generator(dict(tr, txs_per_round=16), 64, 1, 9)
    ref.round(gen.next_round()[0])
    assert ref.overflow
    assert (ref.used <= 2).all() and ref.used.sum() == (ref.slot >= 0).sum()
