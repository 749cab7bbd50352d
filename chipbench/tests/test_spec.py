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


def test_the_benchmark_keeps_its_listing_rules():
    assert spec.listing_faults(BENCH) == []


def _metric(bench, name):
    return next(m for m in bench["per_layer"] if m["name"] == name)


def _mesh_only(bench, cells):
    bench["per_layer"].append(dict(_metric(bench, "decode_step_ms"),
                                   name=spec.MESH_ONLY, layer="collectives",
                                   workloads=cells))


def _mesh_cell(bench):
    bench["workloads"][0]["chips"] = 4


# each way to break the listing, and what ``listing_faults`` says of it
BROKEN = {
    "a name given twice": (lambda b: b["workloads"].append(
        dict(b["workloads"][0])), "names two entries"),
    "a metric of every cell with a list": (lambda b: _metric(
        b, "decode_attn_ms").update(workloads=[b["workloads"][0]["name"]]),
        "reads every cell and carries a workloads list"),
    "a list naming no cell": (lambda b: _mesh_only(b, ["no-such-cell"]),
                              "lists 'no-such-cell', no cell"),
    "a metric with no reader": (lambda b: b["per_layer"].append(
        dict(_metric(b, "mfu"), name="no_such_metric")), "no metrics file"),
    "a configuration read from another file": (lambda b: b["configs"][
        0].update(file="chipbench/configs/other.json"), "is not the one read"),
    "a configuration no cell runs": (lambda b: b["workloads"].pop(),
                                     "is run by no cell"),
    "a metric moving nothing end to end": (lambda b: _metric(
        b, "mfu").update(moves="no_such_metric"), "no end-to-end metric"),
    "a cell of four chips and no collective metric": (
        _mesh_cell, "not for the cells of more than one chip"),
    "the collective metric on a one-chip cell": (
        lambda b: _mesh_only(b, [b["workloads"][0]["name"]]),
        "not for the cells of more than one chip"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_each_listing_rule_is_held(fault):
    import copy
    bench = copy.deepcopy(BENCH)
    breaks, said = BROKEN[fault]
    breaks(bench)
    faults = spec.listing_faults(bench)
    assert any(said in f for f in faults), faults
