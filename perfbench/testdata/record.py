#!/usr/bin/env python3
"""How ``small.xplane.pb`` was recorded, on the chip: five runs of one
small jitted program with pauses between them, traced as ``run.py``
traces (no Python tracer). ``tests/test_xplane.py`` holds ``xplane.py``
to what this trace shows when read by hand (``python perfbench/xplane.py
perfbench/testdata/small.xplane.pb``).

    python perfbench/testdata/record.py <directory>
"""

import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import xplane

out = sys.argv[1]
step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
x = jnp.ones((512, 512), jnp.float32)
step(x).block_until_ready()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(out, profiler_options=opts)
for _ in range(5):
    step(x).block_until_ready()
    time.sleep(0.01)
jax.profiler.stop_trace()
path = xplane.find(out)
shutil.copy(path, os.path.join(out, "small.xplane.pb"))
print(path, os.path.getsize(path), "bytes", jax.devices())
xplane.describe(path)
print(xplane.reduce(path, 0.0, 1))
