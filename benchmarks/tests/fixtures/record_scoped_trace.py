"""How ``scoped_toy_steps.*`` were recorded (PR 25, one TPU v5e chip):

    chiprun -- python3 benchmarks/tests/fixtures/record_scoped_trace.py chiprun_out/fixture

Four executions of a toy step that names its work the way the program does:
``jax.named_scope("generate")`` around a ``fori_loop`` (a ``while`` op) whose
body is a matmul under ``generate/dit_ffn`` and a Pallas kernel called
``toy_kernel``; ``reward/score`` around a reduction; one transpose-and-add
under no scope. Left beside this file:

- ``scoped_toy_steps.xplane.pb``: the profiler's trace, taken with the options
  the benchmark uses; the program's tracer was enabled, so its ``epoch``,
  ``enqueue`` and ``fetch`` spans are also events of the host plane;
- ``scoped_toy_steps.scopes.json``: ``obs/xla_cost.scope_table`` of the very
  executable that ran (the join is by instruction name). When the table
  learned to mark what it infers (``~generate``), the file was written again
  from the same program compiled for the compile-only ``v5e:2x2`` topology:
  the same instructions, the same scopes, five of them now marked;
- ``scoped_toy_steps.trace.jsonl``: the tracer's own file, those three spans.

Small on purpose: the files are test fixtures.
"""

import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT))
from benchmarks.drivers.es_train import profile_options  # noqa: E402
from benchmarks.record import MARK  # noqa: E402
from hyperscalees_t2i_tpu.obs.trace import Tracer  # noqa: E402
from hyperscalees_t2i_tpu.obs.xla_cost import scope_table  # noqa: E402

NAME = "scoped_toy_steps"


def toy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def toy_step(x, w):
    with jax.named_scope("generate"):
        def body(_, h):
            with jax.named_scope("dit_ffn"):
                h = jnp.tanh(h @ w)
            return pl.pallas_call(toy_kernel, out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
                                  name="toy_kernel")(h)
        h = jax.lax.fori_loop(0, 4, body, x)
    with jax.named_scope("reward"), jax.named_scope("score"):
        r = jnp.exp(h.astype(jnp.float32) * 1e-3).sum()
    return r + (x.T + 1.0).astype(jnp.float32).max()  # under no scope


def main(out: str) -> None:
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)
    x = jnp.ones((512, 512), jnp.bfloat16)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)
    compiled = jax.jit(toy_step).lower(x, w).compile()
    (out / f"{NAME}.scopes.json").write_text(json.dumps(scope_table(compiled), sort_keys=True, indent=0))
    jax.device_get(compiled(x, w))
    tracer = Tracer(out / f"{NAME}.trace.jsonl")
    jax.profiler.start_trace(str(out / "profile"), profiler_options=profile_options())
    for epoch in range(4):
        with tracer.span("epoch", epoch=epoch):
            with tracer.span("enqueue"):
                y = compiled(x, w)
            with tracer.span("fetch"):
                jax.device_get(y)
        with jax.profiler.TraceAnnotation(MARK):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    tracer.close()
    pb = sorted((out / "profile").rglob("*.xplane.pb"))[-1]
    shutil.copy(pb, out / f"{NAME}.xplane.pb")
    shutil.rmtree(out / "profile")
    print(sorted(f"{p.name} {p.stat().st_size}" for p in out.iterdir()))


if __name__ == "__main__":
    main(sys.argv[1])
