"""How `annotated.xplane.pb` and `annotated.expect.json` were recorded (on
the chip, PR 25): six matrix products with pauses between them, each
pause partly inside an `es.dispatch` annotation, partly inside an
`es.collect` one and partly outside both, as the program's dispatcher
workers leave them (`elasticsearch_tpu/search/batcher.py`). Between two
launches the device idles for

    a 40 ms sleep inside `es.collect` (after the result is back),
    a 60 ms sleep outside any annotation,
    a 100 ms sleep inside `es.dispatch` (before the next launch);

during the last two pauses a second thread holds one `es.collect` open, so
there the sleep outside counts as collect too, and the one inside
`es.dispatch` stays dispatch (dispatch wins where both cover). A sleep
overshoots, on the chip's machine by about a millisecond, so the script
times each part on the host's clock and writes the sums beside the trace
(`annotated.expect.json`, in ms): what the recording is known to hold,
from a clock that is not the profiler's. The two clocks differ by real
latencies, a few tenths of a millisecond a pause (a result reaches the
host after the device is done, a launch the device after the call), which
is why the sleeps are long. Run on a TPU; writes under
`chiprun_out/`. Checked by `benchmarks/tests/test_idle_reader.py`.
"""

import glob
import json
import os
import shutil
import threading
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

LAUNCHES, SECOND_THREAD_FROM = 6, 3
out = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chiprun_out", "sample_trace_annotated")
shutil.rmtree(out, ignore_errors=True)
f = jax.jit(lambda a: (a @ a).sum())
x = jnp.ones((2048, 2048), jnp.bfloat16)
f(x).block_until_ready()
go, stop = threading.Event(), threading.Event()


def second_worker():
    go.wait()
    with TraceAnnotation("es.collect", family="knn", rows=1):
        stop.wait()


other = threading.Thread(target=second_worker)
other.start()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(out, profiler_options=opts)
now = time.perf_counter
known = {"dispatch": 0.0, "collect": 0.0, "elsewhere": 0.0}
for i in range(LAUNCHES):
    t = now()
    with TraceAnnotation("es.dispatch", family="knn", rows=1):
        if i:
            time.sleep(0.100)
        y = f(x)
    if i:
        known["dispatch"] += now() - t
    with TraceAnnotation("es.collect", family="knn", rows=1):
        y.block_until_ready()
        t = now()
        if i == SECOND_THREAD_FROM:
            go.set()
        if i < LAUNCHES - 1:
            time.sleep(0.040)
    if i < LAUNCHES - 1:
        known["collect"] += now() - t
        t = now()
        time.sleep(0.060)
        known["collect" if i >= SECOND_THREAD_FROM else "elsewhere"] += (
            now() - t)
stop.set()
other.join()
jax.profiler.stop_trace()
with open(os.path.join(out, "annotated.expect.json"), "w") as fh:
    json.dump({"idle_ms": {k: v * 1e3 for k, v in known.items()},
               "device": str(jax.devices()[0].device_kind)}, fh, indent=1)
    fh.write("\n")
src = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(src, os.path.join(out, "annotated.xplane.pb"))
print("recorded", src, os.path.getsize(src), "bytes on", jax.devices())
