"""A CPU-sized copy of BENCHMARK.json: every cell keeps its traffic mix
and runs a small circuit of its configuration's recipe
(``bench/tests/data/tiny-*.json``).  The four-chip mix
(``bench/mixes/amp-x4.json``) gets a cell here if the benchmark has
none, so its fault is still checked."""

import copy

from bench import harness

TINY = {"syc-30q-m16": "bench/tests/data/tiny-syc.json"}
FOUR = {"name": "syc30.amp.x4", "config": "syc-30q-m16",
        "traffic": "amp-x4", "chips": 4, "why": "four-chip fault check"}


def tiny_spec() -> dict:
    spec = copy.deepcopy(harness.load_spec())
    for c in spec["configs"]:
        c["file"] = TINY[c["name"]]
    if all(w["name"] != FOUR["name"] for w in spec["workloads"]):
        spec["workloads"].append(dict(FOUR))
    return spec
