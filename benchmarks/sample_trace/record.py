"""How `sample.xplane.pb` was recorded (on the chip, PR 23): a few
matrix products with pauses between them, so that busy time and gaps
are both plain to see. Run on a TPU; writes under `chiprun_out/`."""

import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp

out = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chiprun_out", "sample_trace")
shutil.rmtree(out, ignore_errors=True)
f = jax.jit(lambda a: (a @ a).sum())
x = jnp.ones((2048, 2048), jnp.bfloat16)
f(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(out, profiler_options=opts)
for _ in range(5):
    f(x).block_until_ready()
    time.sleep(0.02)
jax.profiler.stop_trace()
src = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(src, os.path.join(out, "sample.xplane.pb"))
print("recorded", src, os.path.getsize(src), "bytes on", jax.devices())
