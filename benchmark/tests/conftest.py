"""``pytest benchmark/tests -q``: run by hand, on the CPU, under a
minute; not collected by the repo's tier-1 run (which collects
``tests/``)."""

import copy
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")

# the tiny benchmark is the real BENCHMARK.json with its cells,
# configurations and the serving mix renamed to the tiny ones under
# ``tiny/``: every metric, unit, layer and arrow is rehearsed as shipped
CELLS = {"gpt2m-train": "tiny-gpt2-train",
         "gpt2m-serve-chat": "tiny-gpt2-serve",
         "resnet50-train": "tiny-resnet-train"}
CONFIGS = {"gpt2-medium": "tiny-gpt2", "resnet50": "tiny-resnet"}
TRAFFIC = {"serve-chat-closed16": "serve-tiny"}


def make_tiny_doc():
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["paths"], doc["run_seconds"] = ["."], 1
    doc["configs"] = [
        dict(c, name=CONFIGS[c["name"]], source="test only",
             file="configs/%s.json" % CONFIGS[c["name"]])
        for c in doc["configs"]]
    doc["workloads"] = [
        dict(w, name=CELLS[w["name"]], config=CONFIGS[w["config"]],
             traffic=TRAFFIC.get(w["traffic"], w["traffic"]))
        for w in doc["workloads"]]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELLS[w] for w in m["workloads"]]
    return doc


@pytest.fixture(scope="session")
def _tiny_doc():
    return make_tiny_doc()


@pytest.fixture
def tiny_doc(_tiny_doc):
    return copy.deepcopy(_tiny_doc)


@pytest.fixture(scope="module")
def spec(_tiny_doc):
    from benchmark.spec import Spec

    return Spec(TINY, doc=copy.deepcopy(_tiny_doc))
