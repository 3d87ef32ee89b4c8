"""BENCHMARK.json names only things whose files exist and agree with it."""

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


def test_every_name_has_its_files_and_they_agree():
    bench = load("BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        spec = load("perfbench", "workloads", cell["name"] + ".json")
        assert spec["name"] == cell["name"] and spec["config"] == cell["config"]
        assert spec["chips"] == cell["chips"] and spec["why"] == cell["why"]
        assert cell["name"] == cell["config"] + "." + cell["traffic"]
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "drivers", spec["driver"] + ".py"))
        assert cell["config"] in configs
    for name, entry in configs.items():
        spec = load(entry["file"])
        assert spec["source"] == entry["source"] and spec["reduced"] == entry["reduced"]
        for kind in ("work", "reference"):
            assert os.path.isfile(os.path.join(ROOT, "perfbench", kind, name + ".py"))
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    cells = {c["name"] for c in bench["workloads"]}
    for section in ("end_to_end", "per_layer"):
        for entry in bench[section]:
            spec = load("perfbench", "metrics", entry["name"] + ".json")
            for key in ("unit", "better", "source"):
                assert spec[key] == entry[key], (entry["name"], key)
            assert spec["section"] == section
            assert os.path.isfile(os.path.join(ROOT, "perfbench", "metrics", entry["name"] + ".py"))
            assert set(entry.get("workloads", [])) <= cells
            if section == "per_layer":
                assert entry["moves"] in end_to_end and entry["layer"] == spec["layer"]


def test_peaks_name_their_source():
    for kind, row in load("perfbench", "peaks.json").items():
        assert row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0 and row["source"]


def test_each_cell_reports_what_the_contract_asks():
    from perfbench.run import cell_metrics

    bench = load("BENCHMARK.json")
    for cell in bench["workloads"]:
        end_to_end = [m["name"] for m in cell_metrics(bench, cell["name"], "end_to_end")]
        per_layer = cell_metrics(bench, cell["name"], "per_layer")
        assert "setup_s" in end_to_end and len(end_to_end) >= 2 and per_layer
        assert all(m["moves"] in end_to_end for m in per_layer)
    host = [m["name"] for m in cell_metrics(bench, "pca_3000.host_parts", "per_layer")]
    assert "ingest_ms" in host and "fit_call_ms" not in host
    device = [m["name"] for m in cell_metrics(bench, "kmeans_3000_k1000.device_rows", "per_layer")]
    assert "lloyd_iters" in device and "fit_mfu" in device and "ingest_ms" not in device


def test_no_metric_file_without_an_entry_and_no_entry_twice():
    """A metric that nothing reports leaves a line in the ledger that never
    gets a second reading: a renamed metric takes its old files with it."""
    bench = load("BENCHMARK.json")
    named = [m["name"] for section in ("end_to_end", "per_layer") for m in bench[section]]
    assert len(named) == len(set(named))
    metrics = os.path.join(ROOT, "perfbench", "metrics")
    files = {f[:-5] for f in os.listdir(metrics) if f.endswith(".json")}
    readers = {f[:-3] for f in os.listdir(metrics) if f.endswith(".py") and not f.startswith("_")}
    assert files == readers == set(named)


def test_the_funnel_rate_goes_by_its_own_name():
    from perfbench.run import cell_metrics

    bench = load("BENCHMARK.json")
    host = [m["name"] for m in cell_metrics(bench, "pca_3000.host_parts", "per_layer")]
    assert "ingest_gb_per_s" in host and "h2d_gb_per_s" not in host
    spec = load("perfbench", "metrics", "ingest_gb_per_s.json")
    assert spec["moves"] == "host_fit_rows_per_s" and "ingest_ms" in spec["reads"]
