"""The harness finds every piece by name, and a new cell, configuration,
model family or per-layer metric is new files only."""

import json
import os
import shutil
import time

import jax
import pytest

import cells
import tiny
import weights

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


def _config(name):
    if name in tiny.CONFIGS:
        return dict(tiny.CONFIGS[name])
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "configs"))
    if f.endswith(".json")) + sorted(tiny.CONFIGS))
def test_program_config_is_the_file(name):
    from repro.models import transformer as T

    cfg = _config(name)
    fam = cells.family(cfg)
    a = cells.program_config(cfg)
    assert a.dtype == cfg["torch_dtype"]
    for k, f in fam.arch.FIELDS.items():
        assert getattr(a, f) == cfg[k], (name, k)
    # the family's weights tree is the program's, leaf for leaf
    want = jax.tree.map(lambda s: (s.shape, s.dtype), T.param_specs(a))
    got = jax.eval_shape(weights.make_fn(cfg), weights.seed_key(0))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), got) == want


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b", "jamba-v0.1-52b"])
def test_transformer_family_refuses_other_architectures(arch):
    cfg = dict(tiny.CONFIGS["tiny-qwen3"], arch=arch)
    with pytest.raises(ValueError, match="not a dense full-attention LM"):
        cells.program_config(cfg)


@pytest.mark.parametrize("family,error,names", [
    (None, KeyError, "tiny-internlm2.json"),
    ("no_such", FileNotFoundError, os.path.join("families", "no_such.py")),
])
def test_a_config_names_a_family_that_has_files(tmp_path, family, error,
                                                names):
    root = tiny.build(str(tmp_path))
    cfg = dict(tiny.CONFIGS["tiny-internlm2"])
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    with open(os.path.join(root, "configs", "tiny-internlm2.json"),
              "w") as f:
        json.dump(cfg, f)
    with pytest.raises(error) as e:
        cells.load_cell("tiny.decode", root)
    assert names in str(e.value)


class _StepClock:
    """``time.perf_counter`` that moves only as the engine steps, by
    ``dt`` a step: a window then holds the same steps, and a run the same
    requests, however fast the host is."""

    def __init__(self, monkeypatch, dt: float):
        from repro.serve import continuous

        self.t = 0.0
        step = continuous.ContinuousEngine.step

        def ticking(engine):
            self.t += dt
            return step(engine)

        monkeypatch.setattr(continuous.ContinuousEngine, "step", ticking)
        monkeypatch.setattr(time, "perf_counter", lambda: self.t)


def test_a_family_is_new_files_only(tmp_path, monkeypatch):
    import harness
    import run as runner

    root = tiny.build(str(tmp_path), {
        "tiny.decode": tiny.WORKLOADS["tiny.decode"],
        "tiny.decode_toy": dict(tiny.WORKLOADS["tiny.decode"],
                                config="tiny-toy")})
    # a new family: its three files, here copies of the transformer's
    for part in ("families", "reference", "counts"):
        shutil.copy(os.path.join(root, part, "transformer.py"),
                    os.path.join(root, part, "toy.py"))
    toy = dict(tiny.CONFIGS["tiny-internlm2"], name="tiny-toy", family="toy")
    with open(os.path.join(root, "configs", "tiny-toy.json"), "w") as f:
        json.dump(toy, f)
    fam = cells.load_cell("tiny.decode_toy", root).family
    assert [m.__file__ for m in (fam.arch, fam.reference, fam.counts)] == [
        os.path.join(root, part, "toy.py")
        for part in ("families", "reference", "counts")]
    w_toy = weights.make(toy, 2**31 + 5, root)
    w = weights.make(dict(toy, family="transformer"), 2**31 + 5, root)
    assert jax.tree.all(jax.tree.map(lambda x, y: bool((x == y).all()),
                                     w, w_toy))
    # every piece of the run that depends on the model reaches it here
    used = set()
    for mod, fn in ((fam.arch, "program_config"), (fam.arch, "shapes"),
                    (fam.reference, "served_gaps"),
                    (fam.counts, "causal_pairs")):
        def spy(*a, _fn=getattr(mod, fn), _name=fn, **kw):
            used.add(_name)
            return _fn(*a, **kw)
        monkeypatch.setattr(mod, fn, spy)

    runs = []
    peak = harness.Run.memory_peak

    def keep(run):
        runs.append(run)
        return peak(run)

    monkeypatch.setattr(harness.Run, "memory_peak", keep)
    _StepClock(monkeypatch, 1 / 16)       # exact in binary: no drift
    out = {}
    for cell in ("tiny.decode", "tiny.decode_toy"):
        res = runner.run_cell(["--workload", cell, "--seed",
                               str(2**31 + 5), "--seconds", "1"],
                              root=root, allow_cpu=True)
        assert res["correct"] is True, res["checks"]
        c = runs[-1].counters        # each step's clock reading aside
        out[cell] = res["checks"], dict(c, live=[(k, r) for _, k, r
                                                 in c["live"]])
    assert used == {"program_config", "shapes", "served_gaps",
                    "causal_pairs"}
    assert out["tiny.decode"] == out["tiny.decode_toy"]


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
