"""Readings that the limit of ``correct`` of a DeepSeek-V2 cell
(``systems/mla_moe_serve.py``) is set from: the program's number and the
control's, per seed, as ``controls.py`` reads them for the other systems.

    python3 benchmarks/chip/controls_mla_moe.py --workload serve.mla_moe.chat \\
        --seeds 1 2 3 ...

One process on the chip (it exits 2 without one). For each seed it sets
the cell up, makes enough ``generate`` calls for the requests a run's
check samples, and reads the widest gap of a served token below the
float32 reference's best logit (``program``) and the same for the tokens
that the reference computed with float8 matmuls puts first
(``control``). Each seed prints one JSON line; the limit lies between the
largest program reading and the smallest control reading.
"""
from __future__ import annotations

import importlib
import sys
from typing import Dict

import controls
import run as harness


def mla_moe_readings(config, traffic, seed: int) -> Dict:
    from reference.mla_moe_f32 import served_gaps
    system = importlib.import_module("systems.mla_moe_serve")
    key, rng = harness.derive_key(seed)
    st = system.setup(config, traffic, key, harness._span)
    calls = -(-traffic["check_requests"] // traffic["batch"])
    run = {"served": [c for _ in range(calls) for c in system.window(
        st, 0.0, harness._span, rng)["served"]]}
    failed, requests = system.sample(st, run, rng)
    gaps = served_gaps(config, key, requests, control=True)
    return {"malformed": failed,
            "program": {"served_logit_gap":
                        system.widest_gap(gaps["served"])},
            "control": {"served_logit_gap":
                        system.widest_gap(gaps["control"])},
            "tokens": int(sum(len(g) for g in gaps["served"]))}


if __name__ == "__main__":
    controls.READINGS["mla_moe_serve"] = mla_moe_readings
    sys.exit(controls.main())
