"""Configurations, traffic mixes, generators and metrics are found by
name; an unknown name is an error."""
import json

import pytest

from benchtiny import BENCH, bench, harness


@pytest.mark.parametrize("spare", [False, True])
def test_every_cell_of_the_benchmark_loads(spare):
    b = bench() if spare else harness.load_benchmark()
    for w in b["workloads"]:
        c = harness.cell(w["name"], b)
        assert c.chips == w["chips"]
        assert c.config["name"] == w["config"]
        assert {"solver", "tol", "maxiter", "clients", "block_width",
                "ramp_steps", "grace_s", "relres_limit"} <= set(c.traffic)
        assert "setup_s" in [m.name for m in c.end_to_end]
        assert c.per_layer


@pytest.mark.parametrize("spare", [False, True])
def test_every_named_file_exists_and_is_found(spare):
    b = bench() if spare else harness.load_benchmark()
    for cfg in b["configs"]:
        data = json.loads((BENCH.parent / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        harness.load_module("generators", data["matrix"]["generator"])
    for w in b["workloads"]:
        harness.load_json("traffic", w["traffic"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_metric_lists_follow_the_workloads_key():
    c = harness.cell("poisson7_128.cg_w8", bench())
    names = [m.name for m in c.per_layer]
    assert "block_diag_roofline" not in names
    assert "block_diag_roofline" in [
        m.name for m in harness.cell("hpcg27_104.bjacobi16_cg_w8",
                                     bench()).per_layer]
    assert names[:2] == ["slot_fill_pct", "iters_to_tol"]


@pytest.mark.parametrize("kind,loader", [
    ("configs", harness.load_json), ("traffic", harness.load_json),
    ("generators", harness.load_module), ("metrics", harness.load_module)])
def test_unknown_names_are_errors(kind, loader):
    with pytest.raises(KeyError, match="no_such_name"):
        loader(kind, "no_such_name")


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload named"):
        harness.cell("no_such.cell")


def test_generated_sizes_must_match_the_configuration():
    cfg = {"matrix": {"generator": "laplace7", "nx": 4}, "n": 64, "nnz": 1}
    with pytest.raises(ValueError, match="nnz"):
        harness.generate(cfg)
