"""Serving admission: fit-check an adapter-batch geometry BEFORE it executes.

Two call sites, one verdict:

- **offline** (``tools/preflight.py --serve RUNG:A[:RANK]``):
  :func:`analyze_serve_geometry` abstract-lowers the serve program from
  ``ShapeDtypeStruct`` trees — zero weights, CPU-only — and appends a
  ``site="serve"`` ledger record; the preflight CLI renders the fit table
  and exits nonzero on a no-fit. This is how an operator answers "can this
  chip take adapter-batch 8 at rank 16?" without touching an accelerator.
- **online** (``ServeEngine._ensure_program``): the engine compiles the real
  program (compiling is host-side and safe — executing is what OOMs), reads
  the compiled ``memory_analysis`` peak from its own ledger record, and
  :func:`check_fit` refuses the geometry loudly — naming both numbers —
  before the first batch ever dispatches. An oversized geometry is a
  refused admission, never an OOM mid-traffic.

The budget is the device's HBM capacity (``utils/mfu`` table by device
kind) unless the engine config overrides it; unknown capacity (CPU rigs,
unlisted chips) admits with the gate recorded as unarmed — the preflight
path is then the only gate, same convention as the bench chain fit gate.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple


class ServeShedError(RuntimeError):
    """A submit refused by the OVERLOAD layer (serve/overload.py): deadline
    already expired, brownout priority shedding, or an open per-adapter
    circuit breaker. A typed refusal — like :class:`~.batcher.QueueFullError`
    for backpressure — so the load harness counts sheds apart from errors
    and keeps their censored waits in the open-loop tail. ``reason`` is the
    bounded shed vocabulary ("deadline" / "brownout_priority" /
    "breaker_open")."""

    def __init__(self, reason: str, detail: str = ""):
        self.reason = reason
        super().__init__(
            f"request shed ({reason})" + (f": {detail}" if detail else "")
        )


class ServeAdmissionError(RuntimeError):
    """A serving geometry was refused by the fit gate (est peak HBM exceeds
    the budget). Carries the numbers so CLIs can exit nonzero naming them."""

    def __init__(self, label: str, peak_bytes: float, budget_bytes: float,
                 budget_source: str):
        self.label = label
        self.peak_bytes = float(peak_bytes)
        self.budget_bytes = float(budget_bytes)
        self.budget_source = budget_source
        super().__init__(
            f"serve admission REFUSED for {label}: est peak HBM "
            f"{peak_bytes / 1e9:.3f} GB > budget {budget_bytes / 1e9:.3f} GB "
            f"({budget_source}) — shrink adapter_batch/images_per_request or "
            "verify a smaller geometry offline with tools/preflight --serve"
        )


def resolve_hbm_budget(
    override_bytes: Optional[float] = None,
) -> Tuple[Optional[float], str]:
    """(budget bytes or None, source string). Override wins; else the running
    device's capacity by kind. Off the TPU there is no capacity to protect
    (None: gate unarmed); a TPU kind the capacity table lacks raises
    (utils/mfu.device_chip)."""
    if override_bytes is not None:
        return float(override_bytes), "configured hbm_budget_bytes"
    import jax

    from ..utils.mfu import device_hbm_bytes

    cap = device_hbm_bytes()
    if cap is not None:
        return float(cap), f"device capacity ({jax.devices()[0].device_kind})"
    return None, "unknown (gate unarmed)"


def check_fit(
    label: str,
    peak_bytes: Optional[float],
    budget_bytes: Optional[float],
    budget_source: str,
) -> bool:
    """True when the gate ARMED and passed; False when it could not arm
    (unknown peak or budget — recorded, not refused); raises
    :class:`ServeAdmissionError` on a real no-fit."""
    if peak_bytes is None or budget_bytes is None:
        return False
    if peak_bytes > budget_bytes:
        raise ServeAdmissionError(label, peak_bytes, budget_bytes, budget_source)
    return True


def parse_serve_geometry(spec: str) -> Tuple[str, int, Optional[int]]:
    """``RUNG:ADAPTERS[:RANK]`` → (rung, adapter_batch, rank or None).
    The preflight ``--serve`` argument format."""
    parts = [p.strip() for p in spec.split(":") if p.strip()]
    if not 2 <= len(parts) <= 3:
        raise ValueError(
            f"serve geometry must be RUNG:ADAPTERS[:RANK], got {spec!r}"
        )
    rung = parts[0]
    try:
        adapters = int(parts[1])
        rank = int(parts[2]) if len(parts) == 3 else None
    except ValueError:
        raise ValueError(
            f"serve geometry ADAPTERS/RANK must be integers, got {spec!r}"
        ) from None
    if adapters < 1 or (rank is not None and rank < 1):
        raise ValueError(f"serve geometry values must be >= 1, got {spec!r}")
    return rung, adapters, rank


def abstract_serve_inputs(
    rung: str,
    adapter_batch: int,
    images_per_request: int,
    rank: Optional[int] = None,
):
    """Everything the serve program's ``.lower()`` needs, as abstract trees.

    Mirrors ``tools/preflight.abstract_step_inputs``'s generator half (same
    ``rungs.sana_rung_model`` configs, same bf16 cast, same abstract int8
    base quantization when the rung ships it) minus the reward towers —
    serving is generate-only. Nothing is allocated; the flagship geometry
    analyzes on a laptop CPU in seconds.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ..backends.base import generate_parts
    from ..backends.sana_backend import SanaBackend
    from ..models import dcae, sana
    from ..rungs import (
        BENCH_PROMPT_SET,
        PROMPT_EMBED_LEN,
        RUNG_PLAN,
        rung_opt,
        sana_rung_model,
    )
    from ..utils.pytree import cast_floating

    if rung not in RUNG_PLAN:
        raise ValueError(f"unknown rung {rung!r} (have: {sorted(RUNG_PLAN)})")
    scale = RUNG_PLAN[rung][0]
    opt = rung_opt(rung)
    spec = sana_rung_model(scale)
    bcfg = spec["bcfg"]
    if rank is not None:
        bcfg = dataclasses.replace(bcfg, lora_r=rank)
    prompts = list(BENCH_PROMPT_SET)
    M, Ltxt = len(prompts), PROMPT_EMBED_LEN
    key = jax.random.PRNGKey(0)

    base_quant = opt.get("base_quant", "off")

    def q(tree):
        if base_quant == "off":
            return tree
        from ..ops.quant import maybe_quantize_tree

        return jax.eval_shape(lambda t: maybe_quantize_tree(t, base_quant), tree)

    backend = SanaBackend(bcfg)
    backend.params = q(jax.eval_shape(
        lambda k: cast_floating(sana.init_sana(k, bcfg.model), jnp.bfloat16), key
    ))
    if bcfg.decode_images:
        backend.vae_params = q(jax.eval_shape(
            lambda k: cast_floating(dcae.init_decoder(k, bcfg.vae), jnp.bfloat16),
            key,
        ))
    backend.prompts = prompts
    backend.prompt_embeds = jax.ShapeDtypeStruct(
        (M, Ltxt, bcfg.model.caption_dim), jnp.float32
    )
    backend.prompt_mask = jax.ShapeDtypeStruct((M, Ltxt), jnp.bool_)

    gen_p, _ = generate_parts(backend)
    frozen = backend.frozen
    theta = jax.eval_shape(backend.init_theta, key)
    A, B = adapter_batch, images_per_request
    stacked = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((A,) + tuple(l.shape), l.dtype), theta
    )
    ids = jax.ShapeDtypeStruct((A, B), jnp.int32)
    keys = jax.ShapeDtypeStruct((A,) + tuple(key.shape), key.dtype)
    return gen_p, frozen, stacked, ids, keys, opt


def analyze_serve_geometry(
    rung: str,
    adapter_batch: int,
    images_per_request: Optional[int] = None,
    rank: Optional[int] = None,
    member_batch: Optional[int] = None,
    ledger: Any = None,
) -> Dict[str, Any]:
    """Abstract-lower + CPU-compile one serving geometry; return (and
    optionally ledger-append) its ``site="serve"`` program record, extended
    with the geometry fields the fit table renders."""
    import jax

    from ..obs.xla_cost import program_record
    from ..parallel.pop_eval import make_adapter_batch_generator
    from ..rungs import SERVE_PLAN

    plan = SERVE_PLAN.get(rung, {})
    B = images_per_request if images_per_request is not None else int(
        plan.get("images_per_request", 1)
    )
    mb = member_batch if member_batch is not None else int(
        plan.get("member_batch", 0)
    )
    gen_p, frozen, stacked, ids, keys, opt = abstract_serve_inputs(
        rung, adapter_batch, B, rank
    )
    serve_fn = make_adapter_batch_generator(gen_p, adapter_batch, B, mb)
    t0 = time.perf_counter()
    lowered = jax.jit(serve_fn).lower(frozen, stacked, ids, keys)
    lowering_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    rec = program_record(
        site="serve", label=f"serve-{rung}-a{adapter_batch}",
        lowered=lowered, compiled=compiled,
        lowering_s=lowering_s, compile_s=compile_s,
        geometry={"rung": rung, "adapter_batch": adapter_batch,
                  "images_per_request": B, "member_batch": mb,
                  "lora_rank": rank, "base_quant": opt.get("base_quant", "off")},
        extra={"rung": rung, "imgs_per_dispatch": adapter_batch * B},
    )
    # the same chip-true peak/bytes corrections every training-rung record
    # gets (XLA:CPU float-legalization copies a native chip never allocates)
    # — the fit verdict must judge serving by the same instrument. Lazy
    # import: tools.preflight's module level pulls only obs/rungs, so this
    # cannot cycle back into serve/.
    from ..tools.preflight import _add_chip_true_estimates

    _add_chip_true_estimates(rec, (frozen, stacked), compiled)
    if ledger is not None:
        ledger.write(rec)
    return rec
