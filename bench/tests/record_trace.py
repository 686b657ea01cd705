"""Record the small device trace that ``test_trace.py`` reduces.

Run on a TPU host from the checkout root:

  python3 bench/tests/record_trace.py OUT_DIR

Inside one ``bench.window`` span it traces two bursts of device work,
each under a host span ``bench.step``, each followed by a host sleep
under ``bench.idle``: a paged
decode attention call (the program's ``paged_decode_attention`` kernel)
followed by a 2048x2048 matmul, twice.  The profile lands under
``OUT_DIR/plugins/profile/<run>/``; copy its ``.xplane.pb`` to
``bench/tests/data/small.xplane.pb``.  The expected numbers the test
checks are printed as JSON on the last line.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(out_dir: str) -> None:
    from repro.kernels.paged_attention import paged_decode_attention
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: this recorder traces the chip")
    B, H, KVH, hd, page, n_pages = 4, 16, 8, 128, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (n_pages, page, KVH, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (n_pages, page, KVH, hd), jnp.float32)
    bt = jnp.arange(B * 16, dtype=jnp.int32).reshape(B, 16) % n_pages
    lens = jnp.array([256, 200, 100, 17], jnp.int32)
    a = jax.random.normal(ks[3], (2048, 2048), jnp.float32)

    @jax.jit
    def work(q, kp, vp, bt, lens, a):
        o = paged_decode_attention(q, kp, vp, bt, lens)
        return o, a @ a

    jax.block_until_ready(work(q, kp, vp, bt, lens, a))     # compile
    from bench.lib.driver import start_trace
    start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(work(q, kp, vp, bt, lens, a))
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    print(json.dumps({"kernel": "paged_decode_attention", "calls": 2,
                      "idle_span": "bench.idle", "sleep_s": 0.05}))


if __name__ == "__main__":
    main(sys.argv[1])
