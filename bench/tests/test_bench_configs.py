"""The benchmark's files keep to their contract, and every configuration's
account keys fit its hash table."""

import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and _text_ok(c["source"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    cells = {}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _text_ok(w["why"]) and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json"))
        cells[w["name"]] = w
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 2
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["source"] == "host_clock"
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= set(cells)
        assert callable(spec.metric_reader(ROOT, m["name"]))


@pytest.mark.parametrize("cfg", [c["name"] for c in SPEC["configs"]])
def test_account_keys_fit_their_buckets(cfg):
    """Computed from the program's own key hash: no bucket of the table
    can receive more keys than it has slots, at any seed."""
    from repro.core import endorser

    path = {c["name"]: c["file"] for c in SPEC["configs"]}[cfg]
    c = json.load(open(os.path.join(ROOT, path)))
    keys = np.asarray(endorser._account_key(
        jnp.arange(c["n_accounts"], dtype=jnp.uint32)))
    load = np.bincount(keys[:, 0] & (c["n_buckets"] - 1),
                       minlength=c["n_buckets"]).max()
    assert load == c["max_bucket_load"] <= c["slots"]
    np.testing.assert_array_equal(
        keys, reference.account_keys(np.arange(c["n_accounts"],
                                               dtype=np.uint32)))
