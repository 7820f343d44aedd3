"""The harness finds every piece by name, and a new cell, configuration
or per-layer metric is new files only."""

import json
import os
import shutil

import pytest

import cells
import tiny

BENCH = tiny.BENCH


def _bench():
    with open(os.path.join(tiny.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_lists_what_is_there():
    bench = _bench()
    assert bench["paths"] == ["benchmarks/tpu"]
    assert {w["name"] for w in bench["workloads"]} == set(cells.list_cells())
    for w in bench["workloads"]:
        wl = cells.load_cell(w["name"]).workload
        for k in ("config", "traffic", "chips", "why"):
            assert wl[k] == w[k], (w["name"], k)
    for c in bench["configs"]:
        path = os.path.join(tiny.CHECKOUT, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(BENCH, m["name"]).read)
        for w in m["workloads"]:
            e2e = {x["name"] for x in cells.load_cell(w).end_to_end}
            assert m["moves"] in e2e
    for w in bench["workloads"]:
        c = cells.load_cell(w["name"])
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           f"{c.driver}.py"))
        assert "setup_s" in {m["name"] for m in c.end_to_end}
        assert len(c.end_to_end) >= 2 and c.per_layer


def test_program_config_is_the_file():
    for name in ("qwen3-4b",):
        with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
            cfg = json.load(f)
        a = cells.program_config(cfg)
        assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.head_dim_,
                a.d_ff, a.vocab, a.qk_norm, a.tie_embeddings) == (
            cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["qk_norm"], cfg["tie_word_embeddings"])


def test_a_cell_and_a_metric_are_new_files_only(tmp_path):
    root = tiny.build(str(tmp_path))
    assert "tiny.decode" in cells.list_cells(root)
    # a new cell: one workload file and its BENCHMARK.json entries
    with open(os.path.join(root, "workloads", "tiny.decode.json")) as f:
        wl = json.load(f)
    wl.update(name="tiny.decode_short", traffic="decode_short")
    wl["mix"]["prompt"] = {"dist": "uniform", "lo": 8, "hi": 16}
    with open(os.path.join(root, "workloads", "tiny.decode_short.json"),
              "w") as f:
        json.dump(wl, f)
    # a new per-layer metric: one reader file and its entry
    with open(os.path.join(root, "metrics", "first_token_share.py"),
              "w") as f:
        f.write("def read(run):\n    c = run.counters\n"
                "    return 100.0 * c['first_tokens'] / max(1, "
                "c['decode_tokens'] + c['first_tokens'])\n")
    path = os.path.join(os.path.dirname(os.path.dirname(root)),
                        "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["end_to_end"]:
        if m["name"] in ("output_tokens_per_s",):
            m["workloads"].append("tiny.decode_short")
    bench["per_layer"].append({
        "name": "first_token_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "serving host loop",
        "moves": "output_tokens_per_s", "workloads": ["tiny.decode_short"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    assert "tiny.decode_short" in cells.list_cells(root)
    cell = cells.load_cell("tiny.decode_short", root)
    assert cell.config["name"] == "tiny-internlm2"
    assert cell.driver == "closed_loop"
    assert [m["name"] for m in cell.end_to_end] == ["output_tokens_per_s",
                                                    "setup_s"]
    assert "first_token_share" in [m["name"] for m in cell.per_layer]
    reader = cells.load_module(root, "metrics", "first_token_share")
    run = type("R", (), {"counters": {"first_tokens": 1,
                                      "decode_tokens": 3}})
    assert reader.read(run) == 25.0
    assert cells.metric_reader(root, "ttft_steps_p95.chat").read(
        type("R", (), {"counters": {}})) is None
    # a split metric without a file of its own reads with the unsplit one
    assert cells.metric_reader(root, "device_idle_share.serve").read(
        type("R", (), {"trace_summary": None})) is None


def test_unknown_pieces_are_errors():
    with pytest.raises(KeyError):
        cells.load_cell("no-such-cell")
    with pytest.raises(KeyError):
        cells.peaks("TPU v9 imaginary")
    assert cells.peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12
