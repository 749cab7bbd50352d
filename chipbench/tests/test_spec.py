"""Configurations, mixes, limits and metrics are found by name."""
import pytest

import spec

BENCH = spec.load_benchmark()


def test_every_cell_loads_by_name():
    for w in BENCH["workloads"]:
        c = spec.cell(BENCH, w["name"])
        assert c["config"]["name"] == w["config"]
        assert c["traffic"]["batch_size"] >= 1
        assert c["limits"]["max_logit_gap"] > 0
        assert spec.reference(c["config"]["reference"]).served_logits


def test_every_metric_is_discovered_by_name():
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("find", [spec.config, spec.traffic, spec.limits,
                                  spec.metric_reader, spec.reference])
def test_unknown_name_raises(find):
    with pytest.raises(KeyError):
        find("no-such-name")


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        spec.workload(BENCH, "no-such-cell")
