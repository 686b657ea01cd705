import re
from pathlib import Path

import pytest

from bench.lib import spec

B = spec.benchmark()
NAMES = [m["name"] for m in B["end_to_end"] + B["per_layer"]]


@pytest.mark.parametrize("name", NAMES)
def test_metric_reader_found_by_name(name):
    assert callable(spec.reader(name))


@pytest.mark.parametrize("w", [w["name"] for w in B["workloads"]])
def test_cell_files_found_by_name(w):
    cell = spec.workload(B, w)
    assert spec.config_file(B, cell["config"])["config"]
    assert spec.traffic_file(cell["traffic"])["classes"]
    assert spec.cell_file(w)["rate_per_s"] > 0
    assert spec.metrics_of(B, w, "end_to_end")
    assert spec.metrics_of(B, w, "per_layer")


def test_no_cell_or_metric_named_in_harness_code():
    """The harness finds every cell, configuration, mix and metric by the
    names in BENCHMARK.json; its code names none of them."""
    names = ([w["name"] for w in B["workloads"]]
             + [c["name"] for c in B["configs"]]
             + [w["traffic"] for w in B["workloads"]] + NAMES)
    code = [spec.BENCH / "run.py"] + list((spec.BENCH / "lib").glob("*.py"))
    for path in code:
        text = Path(path).read_text()
        for n in names:
            assert not re.search(rf"[\"'/]{re.escape(n)}[\"'.]", text), \
                (path, n)
