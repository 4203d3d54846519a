"""A residual configuration that the benchmark takes as new files alone.

``data/residual/`` holds a family module (``bottleneck.py``: a stem with
a padded max-pool, then bottleneck blocks that branch and join in an
``add``) and a configuration of one block at tiny widths.  ``add_to``
writes them, an offline mix of slot 8 and their entries into a copy of
the benchmark, as a configuration PR would add them to the repository.
"""
import json
import os
import shutil

from bench import spec

DATA = os.path.join(os.path.dirname(__file__), "data", "residual")
NAME = "resnet-bottleneck-tiny"
FAMILY = "bottleneck"
TRAFFIC = "offline-b8"
CELL = f"{NAME}.{TRAFFIC}"


def config() -> dict:
    return spec.load_json(os.path.join(DATA, f"{NAME}.json"))


def layers(cfg: dict) -> list[dict]:
    fam = spec.load_module(os.path.join(DATA, f"{FAMILY}.py"),
                           spec.module_name("family", FAMILY))
    return fam.layers(cfg)


def add_to(root) -> None:
    """The family, the configuration, a mix and their entries, in the
    benchmark at ``root``; no file already there is written."""
    bench = os.path.join(root, "bench")
    for src, dst in ((f"{FAMILY}.py", f"models/{FAMILY}.py"),
                     (f"{NAME}.json", f"configs/{NAME}.json")):
        assert not os.path.exists(os.path.join(bench, dst))
        shutil.copy(os.path.join(DATA, src), os.path.join(bench, dst))
    traffic = os.path.join(bench, "traffic", f"{TRAFFIC}.json")
    assert not os.path.exists(traffic)
    with open(traffic, "w") as f:
        json.dump({"driver": "offline", "why": "test", "max_batch": 8,
                   "call_batch": 16, "pool_calls": 2}, f)
    path = os.path.join(root, "BENCHMARK.json")
    doc = spec.load_json(path)
    cfg = config()
    doc["configs"].append({"name": NAME, "source": cfg["source"],
                           "file": f"bench/configs/{NAME}.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": NAME,
                             "traffic": TRAFFIC, "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m and m["name"] != "setup_s":
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(doc, f)
