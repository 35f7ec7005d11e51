"""On-chip probe: does an array keep its stored layout through JAX's
persistent compilation cache?

    python scripts/layout_cache_probe.py        # on the chip, ~1 minute

Why it exists (PERF.md section 6, PR 32).  The whole-field layout copies
of the word2vec step (ROADMAP S1) go away when a ``(rows, 300)`` field is
kept row-major with ``jax.experimental.layout.Format``.  On jax / jaxlib
0.9.0 with libtpu 0.0.34 that cannot be shipped: an executable READ BACK
from the persistent cache hands out arrays that say they have the device's
default layout whatever layout they have (the deserialized executable
carries no output layouts, and "none" reads as "default").  JAX lowers
every later program — the step, ``device_put``, an eager ``x[idx]`` — from
what the array says, so in a warm process the first consumer is compiled
for the wrong layout and the run dies with ``expected parameter 0 of size
... {0,1:T(8,128)} but got buffer ... {1,0:T(8,128)}``.  A cold process, in
which everything is compiled, works: that is how PR 27 measured its gain
and lost its set-up time.

The probe runs the same small program in two processes against one fresh
cache directory and prints, for the second (every program a cache hit),
what an array pinned row-major says and whether it can be used.  The last
line is one JSON object; ``"layouts_survive_the_cache": true`` is what a
retake of S1 by stored layout needs to see first.  The parent process
imports no JAX (it would hold the chip).
"""

import json
import os
import subprocess
import sys
import tempfile

ROWS, WIDTH = 40960, 300      # tall and not 128-wide: column-major default


def child() -> None:
    import jax
    import jax.monitoring
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    hits = []
    jax.monitoring.register_event_listener(
        lambda e, **_: hits.append(e)
        if e == "/jax/compilation_cache/cache_hits" else None)
    dev = jax.devices()[0]
    fmt = Format(Layout(major_to_minor=(0, 1)), SingleDeviceSharding(dev))
    init = jax.jit(lambda k: jax.random.normal(k, (ROWS, WIDTH), jnp.float32),
                   out_shardings=fmt)
    step = jax.jit(lambda x: x + 1.0, donate_argnums=0, out_shardings=fmt)
    x = init(jax.random.key(0))
    out = {"platform": dev.platform, "cache_hits_for_init": len(hits),
           "says": list(x.format.layout.major_to_minor)}
    want = np.asarray(x)       # the transfer reads the buffer's own layout
    for name, use, add in (
            ("eager_gather", lambda: np.asarray(x[::97]), 0.0),
            ("pinned_step", lambda: np.asarray(step(x))[::97], 1.0)):
        try:
            out[name] = bool(np.array_equal(use(),
                                            want[::97] + np.float32(add)))
        except Exception as e:      # noqa: BLE001 — the finding itself
            out[name] = f"{type(e).__name__}: {str(e)[:160]}"
    print(json.dumps(out), flush=True)


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child()
        return 0
    runs = []
    with tempfile.TemporaryDirectory(prefix="layout_probe_") as cache:
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
        for _ in range(2):
            done = subprocess.run([sys.executable, __file__, "--child"],
                                  env=env, capture_output=True, text=True)
            lines = done.stdout.strip().splitlines()
            if done.returncode or not lines:
                print(done.stderr[-2000:], file=sys.stderr)
                return 1
            runs.append(json.loads(lines[-1]))
            print(("cold: " if len(runs) == 1 else "warm: ") + lines[-1])
    cold, warm = runs
    ok = (warm["cache_hits_for_init"] > 0 and warm["says"] == cold["says"]
          == [0, 1] and warm["eager_gather"] is True
          and warm["pinned_step"] is True)
    # off the chip row-major IS the default layout: nothing is probed
    print(json.dumps({"platform": cold["platform"],
                      "probed": cold["platform"] == "tpu",
                      "layouts_survive_the_cache": ok}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
