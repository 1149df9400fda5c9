"""Live serving's wall clock against the JAX profiler's, and the token
stream of a fused decode horizon on the real engine (reduced model)."""
import glob
import os
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.profiler import ProfileData                        # noqa: E402

from repro.core.latency import SLO                           # noqa: E402
from repro.engine.request import Request, State              # noqa: E402
from repro.launch import serve                               # noqa: E402
from repro.serving import ServingLoop, Tracer, WallClock     # noqa: E402
from repro.serving.tracing import CLOCK_SYNC, STEP_PLAN      # noqa: E402


def _host_events(log_dir):
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(line.events)
    return out


def test_tracer_spans_align_with_profiler_annotations(tmp_path):
    """A step span and a plain ``TraceAnnotation`` around the same sleep
    land within 1 ms of each other once the tracer's stamps are placed
    on the profiler's timeline through its ``taichi.clock`` event."""
    tr = Tracer()
    tr.clock = WallClock()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.clock_sync()
        time.sleep(0.02)
        with jax.profiler.TraceAnnotation("test.sleep"):
            with tr.step(STEP_PLAN, iid=3, seq=7):
                time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    sync = next(e for e in evs if e.name == CLOCK_SYNC)
    outer = next(e for e in evs if e.name == "test.sleep")
    inner = next(e for e in evs if e.name == STEP_PLAN)
    assert dict(inner.stats) == {"iid": 3, "seq": 7}
    t_ns = int(dict(sync.stats)["t_ns"])
    (name, t0, t1, attrs), = tr.steps
    assert name == STEP_PLAN and attrs == {"iid": 3, "seq": 7}
    start = Tracer.profile_ns(t0, sync.start_ns, t_ns)
    end = Tracer.profile_ns(t1, sync.start_ns, t_ns)
    for ev in (outer, inner):
        assert abs(start - ev.start_ns) < 1e6
        assert abs(end - (ev.start_ns + ev.duration_ns)) < 1e6
    assert t1 - t0 > 0.049


def test_horizon_stream_carries_each_token():
    """A K=8 decode horizon streams each of its tokens once, in order:
    every request's streamed ids equal its ``output_tokens``."""
    slo = SLO(ttft=5.0, tpot=0.5)
    eng = serve.build_engine("smollm-135m", slo, reduced=True, horizon=8)
    rng = np.random.default_rng(1)
    reqs = [Request(prompt_len=n, max_new_tokens=24,
                    prompt_tokens=[int(x) for x in rng.integers(
                        1, eng.cfg.vocab_size, size=n)],
                    arrival=0.25 * i)
            for i, n in enumerate((13, 29, 7, 40, 21, 16))]
    loop = ServingLoop(eng.cluster, slo, arrivals=iter(reqs))
    loop.run()
    assert all(r.state == State.FINISHED for r in loop.requests)
    assert max(i.horizon_peak for i in eng.cluster.instances) == 8
    for r in loop.requests:
        streamed = [tok for _, tok in loop._handles[r.rid].tokens]
        assert streamed == r.output_tokens and len(set(streamed)) > 1
