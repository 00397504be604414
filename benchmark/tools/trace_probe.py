"""Records a small trace on the device it runs on (a jitted scan of matrix
products under the benchmark's host spans) and says what is in it. Run by
hand through the chip tool; the recorded file is what `tests/` keeps as its
small recorded trace. Writes under chiprun_out/trace_probe/."""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = os.path.join("chiprun_out", "trace_probe")
shutil.rmtree(out, ignore_errors=True)
os.makedirs(out, exist_ok=True)


@jax.jit
def work(a, b):
    def body(c, _):
        return jnp.tanh(c @ b), jnp.sum(c)
    c, s = jax.lax.scan(body, a, None, length=8)
    return c, s


n = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
a = jnp.ones((n, n), jnp.bfloat16)
b = jnp.ones((n, n), jnp.bfloat16) * 0.01
work(a, b)[0].block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(out, profiler_options=opts)
for i in range(4):
    with jax.profiler.TraceAnnotation("bench.fit_call"):
        c, s = work(a, b)
        with jax.profiler.TraceAnnotation("bench.readback"):
            float(s[-1])
    with jax.profiler.TraceAnnotation("bench.listener"):
        time.sleep(0.002)
jax.profiler.stop_trace()
path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
print("trace", path, os.path.getsize(path), "bytes")
shutil.copy(path, os.path.join(out, "probe.xplane.pb"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_dump
sys.argv = [sys.argv[0], path, "probe"]
trace_dump.main()
