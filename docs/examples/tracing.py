import tempfile

import jax

from repro.common import spans
from repro.core.scenario import Scenario, Sweep, run
from repro.serving.engine import ServingPlane

sc = Scenario(n_requests=200)
sweep = Sweep(policy=("MO", "HA"), n_users=(5, 15))
plane = ServingPlane.build(Scenario(n_users=12), window=32)
run(sc, sweep)              # compile outside the profile
plane.run(64)

# 1. Tracing is on exactly while a profile is being captured: every
#    repro.* span lands on the host plane of the trace (TensorBoard,
#    Perfetto) and in a fixed-size in-memory buffer. With no profile
#    running, a span costs one check and records nothing.
spans.clear()
with jax.profiler.trace(tempfile.mkdtemp()):
    run(sc, sweep)
    plane.run(128)

# 2. Read the buffer: Span(name, span_id, parent_id, t0, t1, ids) on
#    time.perf_counter, and Count(name, parent_id, t, n, ids). A span's
#    ids include its parent's: each child of a window carries window=k.
total: dict[str, float] = {}
for r in spans.records():
    if isinstance(r, spans.Span):
        total[r.name] = total.get(r.name, 0.0) + r.t1 - r.t0
for name, sec in sorted(total.items()):
    print(f"{name:28s} {1e3 * sec:9.3f} ms")
admitted = sum(r.n for r in spans.records()
               if isinstance(r, spans.Count) and r.name == "plane.admitted")
assert admitted == 128
