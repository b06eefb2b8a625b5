"""How ``tpu_toy_steps.xplane.pb`` was recorded (PR 23, one TPU v5e chip):

    chiprun -- python3 benchmarks/tests/fixtures/record_tpu_trace.py chiprun_out/fixture

Four executions of a toy jitted step — a ``fori_loop`` (a ``while`` op whose
event spans its body's) around a matmul and a Pallas kernel called
``toy_kernel`` — with the harness's mark after each, traced with the options
the benchmark uses. Small on purpose: the file is a test fixture.
"""

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
from benchmarks.drivers.es_train import profile_options  # noqa: E402
from benchmarks.record import MARK  # noqa: E402


def toy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


@jax.jit
def toy_step(x, w):
    def body(_, h):
        h = jnp.tanh(h @ w)
        return pl.pallas_call(toy_kernel, out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
                              name="toy_kernel")(h)
    return jax.lax.fori_loop(0, 4, body, x).sum()


def main(out: str) -> None:
    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    toy_step(x, w).block_until_ready()
    jax.profiler.start_trace(out, profiler_options=profile_options())
    for _ in range(4):
        toy_step(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation(MARK):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    print(sorted(str(p) for p in Path(out).rglob("*.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
