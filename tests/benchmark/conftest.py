"""What ``tiny_tree.py`` needs to know of the cells added since it was
written. ``tiny_tree.build`` maps EVERY configuration and mix of
``BENCHMARK.json`` to a tiny one through its module dictionaries and raises
on a name it does not know, so a new cell adds its two tiny files under
``cells/`` and its two names here, or no test of this directory builds a tree
any more; ``tiny_tree.py`` itself stays as it is. The tiny cell's limits are
its own test's (``test_benchmark_moonlight.py``).

``test_benchmark_scopes.py::test_new_entries_resolve_and_the_tiny_tree_still_builds``
pins PR 26's twelve metrics to the END of ``per_layer``, where a later PR's
entries have to go. It runs, every assert of it, on the manifest up to those
twelve; that they still stand where PR 26 put them, and that what follows
them is PR 28's, name by name, is ``test_entries_are_appended_and_resolve``.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny_tree  # noqa: E402

tiny_tree.CONFIGS["moonlight-16b-a3b"] = "tiny-moonlight"
tiny_tree.MIXES["train-8k"] = "tiny-train-8k"

_PINNED_TAIL = "test_new_entries_resolve_and_the_tiny_tree_still_builds"
_LAST_OF_PR26 = "step.unscoped_ms"


@pytest.fixture(autouse=True)
def _manifest_up_to_pr26(request, monkeypatch, tmp_path):
    if request.node.name != _PINNED_TAIL:
        return
    with open(os.path.join(tiny_tree.ROOT, "BENCHMARK.json")) as f:
        record = json.load(f)
    names = [m["name"] for m in record["per_layer"]]
    record["per_layer"] = record["per_layer"][: names.index(_LAST_OF_PR26) + 1]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(record, f)
    monkeypatch.setattr(request.module, "ROOT", str(tmp_path))
