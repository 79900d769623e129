"""Quickstart: the FastFabric engine in 60 seconds.

Runs one round of money-transfer transactions through the full
execute-order-validate-commit flow under both configs, verifies the chain,
and shows the plug-and-play invariant (identical world state).

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import engine
from repro.core import world_state as ws
from repro.launch import compile_cache


def main() -> None:
    compile_cache.enable()
    print("=== FastFabric on JAX: quickstart ===\n")
    digests = {}
    for name, cfg in (("fabric-1.2 (baseline)", engine.FABRIC_V12),
                      ("fastfabric (O-I..P-III)", engine.FASTFABRIC)):
        eng = engine.FabricEngine(cfg)
        props = eng.make_proposals(500, seed=42)
        eng.run_round(props)  # warmup (jit compile)
        stats = eng.run_round(eng.make_proposals(500, seed=43))
        checks = eng.verify()
        # The baseline keeps peer state in the sorted (LevelDB-like) store,
        # so compare the endorser replicas — hash tables in every config.
        digests[name] = np.asarray(ws.state_digest(eng.endorser_state))
        print(f"{name:26s} {stats.tps:10,.0f} tx/s  "
              f"valid {stats.n_valid}/{stats.n_txs}  checks={checks}")
        if eng.store:
            eng.store.close()

    a, b = digests.values()
    print(f"\nworld-state digests match across configs: "
          f"{bool(np.array_equal(a, b))}")
    print("(the optimizations change throughput, never semantics)")


if __name__ == "__main__":
    main()
