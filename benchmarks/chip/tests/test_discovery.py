"""Configurations, mixes and metric readers are found by name; every
name in BENCHMARK.json has its file; unknown devices are errors."""
import pytest

import common


def test_every_cell_has_its_files():
    spec = common.benchmark_spec()
    names = {c["name"] for c in spec["configs"]}
    for cell in spec["workloads"]:
        assert cell["config"] in names
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        cfg = common.load_config(cell["config"])
        assert cfg["name"] == cell["config"]
        assert "online" in common.load_mix(cell["traffic"])
    for c in spec["configs"]:
        cfg = common.load_json(common.ROOT / c["file"])
        assert set(c["reduced"]) <= set(cfg) and cfg["reduced"] == \
            c["reduced"]


def test_every_metric_has_a_reader():
    for m in common.benchmark_spec()["per_layer"]:
        assert callable(common.metric_reader(m["name"]))


def test_metrics_of_a_cell():
    e2e = common.metrics_of("qwen3-4b.docqa-chat", "end_to_end")
    assert "setup_s" in e2e and "offline_out_tok_s" in e2e
    assert "online_itl_p95_ms" not in e2e
    assert "online_itl_p95_ms" in common.metrics_of("qwen3-4b.gen-chat",
                                                    "end_to_end")
    layer = common.metrics_of("qwen3-4b.docqa-chat", "per_layer")
    assert "device.idle_pct" in layer and "frontdoor.itl_p95_ms" in layer
    assert common.metrics_of("no-such.cell", "per_layer") == {
        m["name"]: m for m in common.benchmark_spec()["per_layer"]
        if "workloads" not in m}


def test_peaks_by_device_kind():
    assert common.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        common.find_cell("qwen3-4b.no-such-mix")


def test_no_reader_returns_zero_without_data():
    """With nothing to read, every reader returns None, never 0."""
    import cell
    ctx = cell.ReadContext(common.load_config("qwen3-4b"),
                           common.peaks("TPU v5 lite"), [])
    for m in common.benchmark_spec()["per_layer"]:
        assert common.metric_reader(m["name"])(ctx) is None, m["name"]


def test_every_layer_metric_moves_what_its_cells_report():
    """A per-layer metric's ``moves`` is an end-to-end metric that each
    cell it lists reports; every cell reports ``setup_s``, another
    end-to-end metric and a per-layer one."""
    spec = common.benchmark_spec()
    for cell in spec["workloads"]:
        e2e = common.metrics_of(cell["name"], "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        layer = common.metrics_of(cell["name"], "per_layer")
        assert layer, cell["name"]
        for name, m in layer.items():
            assert m["moves"] in e2e, (cell["name"], name)
