#!/usr/bin/env python3
"""Record the small trace kept under tests/data/: a jitted `tiny_step` run
N times with a pause between runs, under jax.profiler, on whatever device
JAX finds. Used once, on the chip (PR 24), to have a real v5e trace small
enough to commit; kept so that the recording can be made again.

    python3 benchmark/tests/make_trace.py <out-dir>
"""

import glob
import shutil
import sys
import time

RUNS, PAUSE_S = 6, 0.01


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    tiny_step(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(RUNS):
        tiny_step(x).block_until_ready()
        time.sleep(PAUSE_S)
    jax.profiler.stop_trace()
    found = glob.glob(out_dir + "/**/*.xplane.pb", recursive=True)
    shutil.copy(found[-1], out_dir + "/tiny_step.xplane.pb")
    print(jax.devices()[0].device_kind, found[-1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
