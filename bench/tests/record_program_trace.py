"""Record the small device trace that ``test_program_trace.py`` reduces.

Run on a TPU host from the checkout root:

  python3 bench/tests/record_program_trace.py OUT_DIR

Inside one ``bench.window`` span it runs three steps, each under
``bench.step`` and the program's own spans, opened by an enabled
``StepTracer`` as the serving path opens them: ``repro.driver.execute``
around ``repro.engine.decode.dispatch`` (a jitted loop of 2048x2048
float32 matmuls, about 20 ms of device work), ``repro.engine.decode.wait``
(the host blocked on its result) and ``repro.engine.decode.post`` (a
20 ms host sleep with the device idle); then a 30 ms host sleep under
``bench.wait`` alone.  The profile lands under
``OUT_DIR/plugins/profile/<run>/``; copy its ``.xplane.pb`` to
``bench/tests/data/program.xplane.pb``.  The expected numbers the test
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
import numpy as np  # noqa: E402

STEPS, POST_S, WAIT_S = 3, 0.02, 0.03


def main(out_dir: str) -> None:
    from repro.telemetry.exporters import StepTracer
    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: this recorder traces the chip")
    a = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.float32)
    a = a / 64.0

    @jax.jit
    def scan(a):
        return jax.lax.fori_loop(0, 200, lambda i, x: jnp.tanh(x @ a),
                                 a).sum()

    np.asarray(scan(a))                                    # compile
    tr = StepTracer()
    from bench.lib.driver import start_trace
    start_trace(out_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.step"):
                with tr.span("repro.driver.execute"):
                    with tr.span("repro.engine.decode.dispatch"):
                        out = scan(a)
                    with tr.span("repro.engine.decode.wait"):
                        np.asarray(out)
                    with tr.span("repro.engine.decode.post"):
                        time.sleep(POST_S)
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(WAIT_S)
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    waits = [s.end_ns - s.start_ns for s in tr.spans()
             if s.name.endswith(".wait")]
    print(json.dumps({"steps": STEPS, "post_s": POST_S, "wait_s": WAIT_S,
                      "window_s": t1 - t0,
                      "wait_span_s": [w * 1e-9 for w in waits]}))


if __name__ == "__main__":
    main(sys.argv[1])
