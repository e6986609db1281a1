"""How `by_request.xplane.pb` and `by_request.expect.json` were recorded
(on the chip, PR 35): six matrix products with pauses between them, each
pause walked through the places a request can be while the device idles,
under the four annotations the program puts on the profiler's clock
(`es.dispatch` / `es.collect`: a dispatcher worker,
`elasticsearch_tpu/search/batcher.py`; `es.http` around `es.search`: a
request thread, `rest/server.py` and `rest/actions.py`). Between two
launches the device idles for

    a 30 ms sleep inside `es.collect` (inside `es.search`, inside `es.http`),
    a 50 ms sleep inside `es.search` alone (the waiter awake, hit building),
    a 40 ms sleep inside `es.http` alone (the response),
    a 60 ms sleep outside every annotation (the client's turn-around),
    a 20 ms sleep inside the next `es.http` (read, parse),
    a 25 ms sleep inside its `es.search` (plan, submit),
    a 35 ms sleep inside `es.dispatch` (before the next launch);

during the last two pauses a second thread holds one `es.search` open (a
second request waiting in the search), so there the sleeps in `es.http`
alone and outside everything count as `search` too: a worker's phase wins
over any request's place, `search` over `front`, and `none` needs the
server empty. As `record_annotated.py` does, the script times each part
on the host's clock and writes the sums beside the trace
(`by_request.expect.json`, in ms). Run on a TPU; writes under
`chiprun_out/`. Checked by `benchmarks/tests/test_idle_by_request.py`.
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
    os.path.abspath(__file__)))), "chiprun_out", "sample_trace_by_request")
shutil.rmtree(out, ignore_errors=True)
f = jax.jit(lambda a: (a @ a).sum())
x = jnp.ones((2048, 2048), jnp.bfloat16)
f(x).block_until_ready()
go, stop = threading.Event(), threading.Event()


def second_request():
    go.wait()
    with TraceAnnotation("es.http"):
        with TraceAnnotation("es.search", route="_search"):
            stop.wait()


other = threading.Thread(target=second_request)
other.start()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(out, profiler_options=opts)
now = time.perf_counter
known = {"dispatch": 0.0, "collect": 0.0, "front": 0.0, "search": 0.0,
         "none": 0.0}


def pause(where: str, seconds: float, t: float) -> float:
    """Sleeps, and books the time since `t` (the end of the part before)
    to `where`; -> the end of this part."""
    time.sleep(seconds)
    known[where] += now() - t
    return now()


t = 0.0
for i in range(LAUNCHES):
    crowded = i > SECOND_THREAD_FROM  # the second request is in the search
    with TraceAnnotation("es.http"):
        if i:
            t = pause("search" if crowded else "front", 0.020, t)
        with TraceAnnotation("es.search", route="_search"):
            if i:
                t = pause("search", 0.025, t)
            with TraceAnnotation("es.dispatch", family="knn", rows=1):
                if i:
                    t = pause("dispatch", 0.035, t)
                y = f(x)
            with TraceAnnotation("es.collect", family="knn", rows=1):
                y.block_until_ready()
                t = now()
                if i == SECOND_THREAD_FROM:
                    go.set()
                if i < LAUNCHES - 1:
                    t = pause("collect", 0.030, t)
            if i < LAUNCHES - 1:
                t = pause("search", 0.050, t)
        crowded = i >= SECOND_THREAD_FROM
        if i < LAUNCHES - 1:
            t = pause("search" if crowded else "front", 0.040, t)
    if i < LAUNCHES - 1:
        t = pause("search" if crowded else "none", 0.060, t)
stop.set()
other.join()
jax.profiler.stop_trace()
with open(os.path.join(out, "by_request.expect.json"), "w") as fh:
    json.dump({"idle_ms": {k: v * 1e3 for k, v in known.items()},
               "device": str(jax.devices()[0].device_kind)}, fh, indent=1)
    fh.write("\n")
src = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(src, os.path.join(out, "by_request.xplane.pb"))
print("recorded", src, os.path.getsize(src), "bytes on", jax.devices())
