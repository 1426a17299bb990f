"""Record ``fixtures/program_trace.xplane.pb`` (and its ``.json``): a small
traced serving window on one TPU with a window compile in it.

    python3 benchmarks/chip/tests/record_program_trace.py          # on a TPU
    python3 benchmarks/chip/tests/record_program_trace.py --trim   # anywhere

The second command cuts the recorded trace down to the events the
benchmark's reduction reads (``reduce.load_events``): on the host the
program's and the harness's spans and the runtime's completion marks, on
the device the ``XLA Ops`` and ``XLA Modules`` lines.  It needs the XPlane
protocol buffers that TensorFlow ships.

A smoke-size DeepSeek engine (eBPF fault policy, prefix cache) serves two
48-token requests to the end, so that their prefill and decode variants are
compiled; then a profiler session (the benchmark's options) covers a window
with the harness's spans: two requests of the same shape are served, the
host sleeps 3 ms (``harness.wait``), and a 96-token request arrives, whose
prefill variant compiles inside the window.  The JSON holds the engine's
stats at the window's start and end and its request times.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))


def main() -> None:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.configs.base import get_smoke_config
    from repro.core import Profile, ProfileRegion
    from repro.models import PagedLayout, materialize, model_spec
    from repro.serving import Request, ServingEngine

    cfg = get_smoke_config("deepseek_7b")
    params = materialize(jax.random.PRNGKey(0), model_spec(cfg))
    layout = PagedLayout(num_blocks=128, block_tokens=16, max_blocks=16)
    prof = Profile("chat", [
        ProfileRegion(0, 4, (0, 150_000, 600_000, 2_500_000)),
        ProfileRegion(4, 16, (0, 0, 0, 0))])
    eng = ServingEngine(cfg, params, layout, max_batch=4, policy="ebpf",
                        profile=prof, prefix_cache=16)
    rng = np.random.default_rng(0)

    def submit(rid: int, n: int) -> None:
        eng.submit(Request(rid=rid, prompt=rng.integers(1, cfg.vocab, n)
                           .tolist(), max_new_tokens=4))

    def serve() -> None:
        while True:
            with TraceAnnotation("harness.step"):
                more = eng.step()
            if not more:
                return

    for rid in (0, 1):
        submit(rid, 48)
    serve()
    jax.block_until_ready(eng.cache)

    logdir = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    stats0 = eng.stats.snapshot()
    with TraceAnnotation("harness.window"):
        for rid in (2, 3):
            submit(rid, 48)
        serve()
        with TraceAnnotation("harness.wait"):
            time.sleep(0.003)
        submit(4, 96)
        serve()
        jax.block_until_ready(eng.cache)
    stats1 = eng.stats.snapshot()
    jax.profiler.stop_trace()

    out = HERE / "fixtures" / "program_trace"
    src = sorted(logdir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(src, out.with_suffix(".xplane.pb"))
    out.with_suffix(".json").write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "stats0": stats0, "stats1": stats1,
        "request_times": [vars(t) for t in eng.request_times()]},
        indent=1) + "\n")
    print(f"{out}.xplane.pb: {out.with_suffix('.xplane.pb').stat().st_size}"
          f" bytes; compiles in the window: "
          f"{stats1['compiles'] - stats0['compiles']} "
          f"{stats1['compiles_by_entry']}")
    shutil.rmtree(logdir, ignore_errors=True)


def trim(path: Path) -> None:
    sys.path.insert(0, str(HERE.parent))
    import program_trace
    import reduce
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    host = set(program_trace.SPANS) | {reduce.WINDOW_SPAN, reduce.DONE_EVENT,
                                       "harness.step", "harness.wait"}
    space = xplane_pb2.XSpace()
    space.ParseFromString(path.read_bytes())
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            keep = {i for i, m in plane.event_metadata.items()
                    if m.name in host}
            lines = list(plane.lines)
            for ln in lines:
                evs = [e for e in ln.events if e.metadata_id in keep]
                del ln.events[:]
                ln.events.extend(evs)
        elif reduce.DEVICE_PLANE.match(plane.name):
            lines = [ln for ln in plane.lines
                     if ln.name in ("XLA Ops", "XLA Modules")]
        else:
            lines = []
        lines = [ln for ln in lines if ln.events]
        kept = [type(ln)() for ln in lines]
        for new, old in zip(kept, lines):
            new.CopyFrom(old)
        del plane.lines[:]
        plane.lines.extend(kept)
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for i in [i for i in plane.event_metadata if i not in used]:
            del plane.event_metadata[i]
        for m in plane.event_metadata.values():      # names are read
            del m.stats[:]
    path.write_bytes(space.SerializeToString())
    print(f"{path}: {path.stat().st_size} bytes")


if __name__ == "__main__":
    if sys.argv[1:] == ["--trim"]:
        trim(HERE / "fixtures" / "program_trace.xplane.pb")
    else:
        main()
