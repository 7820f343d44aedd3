"""Record a few steps of a tiny serving engine on one chip into a small
profiler trace, ``data/v5e_serve_steps.xplane.pb``, which the tests of
``serve_trace`` read.

    python3 benchmarks/tpu/record_serve_steps.py --raw RAW      # on the chip
    python3 benchmarks/tpu/record_serve_steps.py --trim RAW [--out PATH]

``--raw`` runs the reduced ``qwen3-4b`` (2 layers, d 128) with 2 slots
and 16-token blocks.  It first serves the same sizes untraced, so that
every program it runs is compiled, and admits a 40-token prompt; then,
with the profiler on and inside a ``bench.window`` span, it admits a
20-token prompt (a dense prefill) and steps the engine until both are
done: 8 and 3 new tokens, every decode at one table width.  It fails
without a TPU.

``--trim`` keeps what ``trace_reduce`` and ``serve_trace`` read: the
device's ``XLA Modules`` and ``XLA Ops`` lines, and the host's
``bench.*``, ``serve.*`` and ``CompleteCallbacks`` events, at their
recorded times; it drops the other planes, lines and events, and the
operations' ``source`` and ``source_stack`` statistics.  It needs the
``XPlane`` protocol buffer module that the TensorFlow package ships, and
no chip.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "v5e_serve_steps.xplane.pb")
HOST_EVENTS = ("bench.", "serve.", "CompleteCallbacks")
DEVICE_LINES = ("XLA Modules", "XLA Ops")
DROPPED_STATS = ("source", "source_stack")


def record(raw: str) -> dict:
    import jax
    import numpy as np
    import trace_reduce
    from repro.configs.registry import get_config
    from repro.models import transformer as T
    from repro.serve import ContinuousConfig, ContinuousEngine, Request

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {dev.platform}")
    cfg = get_config("qwen3-4b", reduced=True)
    engine = ContinuousEngine(cfg, T.init_params(cfg, 0), ContinuousConfig(
        max_len=128, n_slots=2, block_size=16, seed=0))
    rng = np.random.default_rng(0)

    def request(n, new):
        return Request(rng.integers(2, cfg.vocab, n).astype(np.int32),
                       max_new_tokens=new)

    engine.run([request(40, 8), request(20, 3)])
    engine.submit(request(40, 8))
    engine.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    steps = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                engine.submit(request(20, 3))
                while not engine.idle:
                    engine.step()
                    steps += 1
        finally:
            jax.profiler.stop_trace()
        shutil.copy(trace_reduce.find_xplane(tmp), raw)
    return {"device": dev.device_kind, "steps": steps,
            "bytes": os.path.getsize(raw)}


def _drop(repeated, drop) -> None:
    """Delete, in place, the elements of a repeated field that ``drop``
    selects."""
    for i in reversed(range(len(repeated))):
        if drop(repeated[i]):
            del repeated[i]


def _keep(plane, keep_line, keep_event) -> None:
    """Drop, in place, the lines and events of ``plane`` not kept, the
    event metadata nothing refers to any more, and the dropped
    statistics."""
    names = {k: m.name for k, m in plane.event_metadata.items()}
    for line in plane.lines:
        _drop(line.events, lambda e: not keep_event(names[e.metadata_id]))
    _drop(plane.lines, lambda line: not (keep_line(line.name) and line.events))
    used = {e.metadata_id for line in plane.lines for e in line.events}
    for k in [k for k in plane.event_metadata if k not in used]:
        del plane.event_metadata[k]
    stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
    for m in plane.event_metadata.values():
        _drop(m.stats,
              lambda s: stat_names.get(s.metadata_id) in DROPPED_STATS)


def trim(raw: str, out: str) -> int:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(raw, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            _keep(plane, lambda n: n in DEVICE_LINES, lambda n: True)
        elif plane.name == "/host:CPU":
            _keep(plane, lambda n: True, lambda n: n.startswith(HOST_EVENTS))
    _drop(space.planes, lambda p: not (p.name.startswith("/device:TPU:")
                                       or p.name == "/host:CPU"))
    with open(out, "wb") as f:
        f.write(space.SerializeToString())
    return os.path.getsize(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--raw", help="record on the chip into this file")
    what.add_argument("--trim", help="trim this recorded file")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args(argv)
    if args.trim:
        print({"bytes": trim(args.trim, args.out)})
        return 0
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                       "src")]
    print(record(args.raw))
    return 0


if __name__ == "__main__":
    sys.exit(main())
