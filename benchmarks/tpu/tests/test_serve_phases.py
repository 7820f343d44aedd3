"""Engine phases and named programs read from a profiler trace
(``serve_trace``) and the per-layer metrics that read them, on a
hand-built trace, on the recorded ``data/v5e_paged_decode.xplane.pb``
(no engine spans), and on ``data/v5e_serve_steps.xplane.pb``: six steps
of a tiny serving engine on one TPU v5e, one dense prefill and six
decodes, trimmed to what the reductions read (``record_serve_steps.py``)."""

import os
import shutil
import types

import pytest

import cells
import serve_trace as S
import tiny
import trace_reduce as T

DATA = os.path.join(tiny.BENCH, "data")
PAGED = os.path.join(DATA, "v5e_paged_decode.xplane.pb")
STEPS = os.path.join(DATA, "v5e_serve_steps.xplane.pb")

# Host clock, in ns.  Window [1000, 21000).  bench.step [2000, 12000)
# holds serve.step [2100, 11900), which holds serve.admit [2200, 4000)
# (with serve.prefill [2300, 3000)), serve.decode [4500, 5000),
# serve.sync [5000, 9000) and serve.commit [9000, 10000).  The device's
# clock runs 500 ns behind the host's: the prefill program runs
# [2500, 3000) and the decode program [4600, 8300) on it, completed on
# the host at 3600 and 8800, so the offset is 500 and both runs link.
SYNTH = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 4600000 duration_ps: 3700000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 500000
             stats { metadata_id: 9 int64_value: 11 } }
    events { metadata_id: 4 offset_ps: 4600000 duration_ps: 3700000
             stats { metadata_id: 9 int64_value: 12 } }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[4] fusion(bf16[4] %a)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = bf16[4] while(bf16[4] %b)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_serve_prefill(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_serve_decode(456)" } }
  stat_metadata { key: 9 value { id: 9 name: "_c" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 2100000 duration_ps: 9800000 }
    events { metadata_id: 4 offset_ps: 2200000 duration_ps: 1800000 }
    events { metadata_id: 5 offset_ps: 2300000 duration_ps: 700000 }
    events { metadata_id: 6 offset_ps: 4500000 duration_ps: 500000 }
    events { metadata_id: 7 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 8 offset_ps: 9000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "completions" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 3600000 duration_ps: 1000
             stats { metadata_id: 9 int64_value: 11 } }
    events { metadata_id: 10 offset_ps: 8800000 duration_ps: 1000
             stats { metadata_id: 9 int64_value: 12 } }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "serve.step" } }
  event_metadata { key: 4 value { id: 4 name: "serve.admit" } }
  event_metadata { key: 5 value { id: 5 name: "serve.prefill" } }
  event_metadata { key: 6 value { id: 6 name: "serve.decode" } }
  event_metadata { key: 7 value { id: 7 name: "serve.sync" } }
  event_metadata { key: 8 value { id: 8 name: "serve.commit" } }
  event_metadata { key: 10 value { id: 10 name: "CompleteCallbacks" } }
  stat_metadata { key: 9 value { id: 9 name: "_c" } }
}
"""


def _profile(text=SYNTH):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


@pytest.fixture
def synth():
    return S.from_profile(_profile())


def _reader(name):
    return cells.metric_reader(tiny.BENCH, name)


def test_programs_are_on_the_host_clock(synth):
    assert synth.links == 2
    assert synth.module_runs("jit_serve_decode") == [(5100, 8800)]
    assert synth.module_runs("jit_serve_prefill") == [(3000, 3500)]
    assert synth.module_runs("jit_serve_chunk") == []
    assert synth.program_seconds() == {
        "jit_serve_decode": pytest.approx(3700e-9),
        "jit_serve_prefill": pytest.approx(500e-9)}
    # the operations moved by the same offset
    assert synth.trace.busy("/device:TPU:0") == [(3000, 3500), (5100, 8800)]


def test_one_gap_splits_over_nested_spans(synth):
    # idle [1000, 3000): 1000 outside any span, 100 in bench.step, 100 in
    # serve.step, 100 in serve.admit, 700 in serve.prefill; idle
    # [3500, 5100): 500 admit, 500 step, 500 decode, 100 sync; idle
    # [8800, 21000): 200 sync, 1000 commit, 1900 step, 100 bench.step,
    # 9000 outside
    gaps = dict(synth.idle_gaps())
    assert gaps == {
        "bench.window": pytest.approx(10000e-9),
        "serve.step": pytest.approx(2500e-9),
        "serve.commit": pytest.approx(1000e-9),
        "serve.prefill": pytest.approx(700e-9),
        "serve.admit": pytest.approx(600e-9),
        "serve.decode": pytest.approx(500e-9),
        "serve.sync": pytest.approx(300e-9),
        "bench.step": pytest.approx(200e-9)}
    busy = T._length(synth.trace.busy("/device:TPU:0"))
    assert sum(gaps.values()) == pytest.approx((20000 - busy) * 1e-9)
    assert synth.idle_gaps()[0][0] == "bench.window"


def test_without_engine_spans_gaps_keep_bench_names():
    text = "\n".join(line for line in SYNTH.splitlines()
                     if '"serve.' not in line)
    gaps = dict(S.from_profile(_profile(text)).idle_gaps())
    # [2000, 3000) + [3500, 5100) + [8800, 12000) under bench.step
    assert gaps == {"bench.window": pytest.approx(10000e-9),
                    "bench.step": pytest.approx(5800e-9)}


def test_step_host_time_leaves_out_the_sync(synth):
    # serve.step 9800 ns less serve.sync 4000 ns
    assert synth.step_host_s() == [pytest.approx(5800e-9)]


def test_readers_on_the_hand_built_trace(synth):
    run = types.SimpleNamespace(trace_summary=synth.trace, trace_dir="-",
                                serve_phases=synth)
    assert _reader("decode_device_ms.decode").read(run) == pytest.approx(
        3700e-6)
    for name in ("step_host_ms.decode", "step_host_ms.chat"):
        assert _reader(name).read(run) == pytest.approx(5800e-6)


@pytest.mark.parametrize("name", ["decode_device_ms.decode",
                                  "step_host_ms.decode", "step_host_ms.chat"])
def test_readers_are_none_without_trace_or_spans(name, tmp_path, capsys):
    reader = _reader(name)
    assert reader.read(types.SimpleNamespace(trace_summary=None)) is None
    assert reader.read(types.SimpleNamespace(
        trace_summary=None, trace_dir=str(tmp_path))) is None
    # a trace of a program without engine spans or named programs
    shutil.copy(PAGED, tmp_path / "x.xplane.pb")
    run = types.SimpleNamespace(trace_summary=T.load(PAGED),
                                trace_dir=str(tmp_path))
    assert reader.read(run) is None
    assert "# clock_links: 3" in capsys.readouterr().out


def test_trace_reduce_reads_the_recorded_trace_as_before():
    tr = T.load(PAGED)
    assert tr.idle_share() == pytest.approx(0.995208240225834, rel=1e-12)
    secs, n = tr.kernel("paged_decode")
    assert n == 3 and secs == pytest.approx(0.000159985, rel=1e-12)
    ops = tr.op_seconds()
    assert len(ops) == 14
    assert sum(ops.values()) == pytest.approx(0.000169405, rel=1e-12)
    # the split puts the same idle time under the same spans
    split = dict(S.load(PAGED).idle_gaps())
    assert sum(split.values()) == pytest.approx(sum(
        s for _, s in tr.idle_gaps()), rel=1e-9)
    assert max(split, key=split.get) == "bench.submit"
    assert set(split) <= {"bench.submit", "bench.step", "bench.window"}


@pytest.fixture(scope="module")
def steps():
    return S.load(STEPS)


def test_recorded_engine_steps_share_the_host_clock(steps):
    assert os.path.getsize(STEPS) <= 200_000
    assert steps.links > 0
    names = {n for n, _, _ in steps.spans}
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.decode",
            "serve.sync", "serve.commit"} <= names
    decodes = [(s, e) for n, s, e in steps.spans if n == "serve.decode"]
    syncs = sorted((s, e) for n, s, e in steps.spans if n == "serve.sync")
    runs = steps.module_runs("jit_serve_decode")
    assert decodes and len(runs) == len(decodes)
    for (ds, _), (rs, re) in zip(decodes, runs):
        nxt = next(e for s, e in syncs if s >= ds)
        assert ds <= rs and re <= nxt, (ds, rs, re, nxt)
    assert steps.module_runs("jit_serve_prefill")
    assert steps.module_runs("jit_serve_sample")
    # the kernel keeps its own name under the named decode program
    kernels = {o.name.split(".")[0] for ops in steps.trace.ops.values()
               for o in ops if o.mosaic}
    assert "paged_decode" in kernels


def test_readers_on_the_recorded_engine_steps(steps):
    run = types.SimpleNamespace(trace_summary=steps.trace, trace_dir="-",
                                serve_phases=steps)
    d = _reader("decode_device_ms.decode").read(run)
    h = _reader("step_host_ms.decode").read(run)
    assert 0 < d < 100 and 0 < h < 1000
    gaps = dict(steps.idle_gaps())
    assert any(n.startswith("serve.") for n in gaps)
    assert sum(gaps.values()) == pytest.approx(
        steps.trace.window_s - steps.trace.busy_s(), rel=1e-9)


def test_traced_tiny_run_reads_the_engine_steps(tmp_path, capsys):
    import run as runner

    root = tiny.build(str(tmp_path))
    res = runner.run_cell(["--workload", "tiny.chat", "--seed",
                           str(2**31 + 55), "--seconds", "2", "--trace", "1"],
                          root=root, allow_cpu=True)
    assert res["metrics"]["step_host_ms.chat"]["value"] > 0
    assert "# clock_links: " in capsys.readouterr().out
