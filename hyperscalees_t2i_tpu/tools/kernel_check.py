"""Compile, run and compare each Pallas kernel with its XLA composition, at
the shapes its real caller passes it.

    python -m hyperscalees_t2i_tpu.tools.kernel_check [--kernels a,b]
        [--compile_only] [--out FILE]

The CPU tier can only *interpret* the three kernels in ``ops/``; whether
Mosaic accepts them, and whether what it builds agrees with the XLA path, is
a fact about a chip. This is the one place that establishes it: every case
below is a call a model really makes (the dense sites of Sana-Sprint 1.6B and
VAR-d16 under ``--base_quant int8`` with the member axis the
benchmark's cells put in front, the VAR ten-scale KV cache, Infinity's masked
cross-attention, the hybrid cell's 64 recurrent states a DeltaNet layer), run
with ``interpret=False`` and compared with the
XLA form the gate would otherwise choose. A case may name a twin — the same
kernel over the same arithmetic on another grid (``fused_qlora`` once a
member; ``decode_attention`` one head a grid step, :func:`one_head_a_step`):
the run counts the outputs that differ from it bit for bit, prints
microseconds a call of both, and an attention case fails on any difference.
``chip_smoke.py`` runs the first
case of every kernel the TPU gates select and fails on a disagreement.

``--compile_only`` lowers and compiles each case for a TPU v5e *without a
chip* (libtpu's compile-only topology, ``jax.experimental.topologies``):
Mosaic's verdict, no numbers. It is how a kernel change is rehearsed in a
sandbox that has no accelerator.

Exit code 1 when any case failed to compile or missed its tolerance; one
JSON object per case on stdout (and in ``--out``).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

# bf16 keeps 8 significand bits: a rounding moves a value by at most 2^-8
# relatively, and two results that round to different neighbours sit one
# spacing — up to 2^-7 of the largest magnitude — apart. Every case compares
# bf16 results, relative to the reference's largest magnitude.
_BF16_EPS = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Case:
    kernel: str  # the pallas_call name (ops/pallas_gate.selected_kernels key)
    label: str
    make: Callable[[jax.Array], Tuple[Any, ...]]  # key -> args
    kernel_fn: Callable[..., Any]  # an array, or a tuple of them: each is held to tol
    xla_fn: Callable[..., Any]
    tol: float  # bound on max|kernel − xla| / max|xla|
    tol_reason: str
    # the same kernel over the same arithmetic on another grid, as (name, fn)
    # — "per_member": called once a member (what vmap's default batching of a
    # Pallas call amounts to); "one_head_a_step": a grid step a (sequence,
    # head). Where set, the chip run times both and counts the outputs that
    # differ bit for bit; ``twin_exact`` fails the case on any.
    twin: Optional[Tuple[str, Callable[..., jax.Array]]] = None
    twin_exact: bool = False


def _factored(key, m: int, n: int, r_e: int, noise_dtype):
    from ..lora import FactoredDelta

    kw, ku, kv = jax.random.split(key, 3)
    return FactoredDelta(
        jax.random.normal(kw, (m, n), jnp.float32) / jnp.sqrt(m),
        jax.random.normal(ku, (m, r_e), jnp.float32).astype(noise_dtype),
        jax.random.normal(kv, (n, r_e), jnp.float32).astype(noise_dtype),
        jnp.float32(0.01 / 2.0),  # σ/√r_e at the trainer's σ=0.01, rank 4
    )


def _qlora_case(label: str, T: int, din: int, dout: int, members: int = 0) -> Case:
    """One dense site under int8 base + fused factored members: bf16
    activations, s8 base, LoRA rank 8, ES rank 4, bf16 noise store.
    ``members`` > 0 adds the member axis ``lax.map(batch_size=member_batch)``
    vmaps over (activations and the noise slices are per member; the base
    and the unperturbed factors are shared)."""
    from ..lora import FactoredDelta
    from ..ops.fused_qlora import fused_qlora_dense, xla_fused_qlora
    from ..ops.quant import quantize_kernel

    def make(key):
        kx, kq, ka, kb = jax.random.split(key, 4)
        lead = (members,) if members else ()
        x = jax.random.normal(kx, (*lead, T, din), jnp.float32).astype(jnp.bfloat16)
        qk = quantize_kernel(jax.random.normal(kq, (din, dout), jnp.float32) / jnp.sqrt(din))
        a = _factored(ka, din, 8, 4, jnp.bfloat16)
        b = _factored(kb, 8, dout, 4, jnp.bfloat16)
        if members:
            per_member = lambda f, k: FactoredDelta(
                f.w,
                jax.random.normal(k, (members, *f.u.shape), jnp.float32).astype(f.u.dtype),
                jnp.broadcast_to(f.v, (members, *f.v.shape)),
                jnp.full((members,), f.c),
            )
            a, b = per_member(a, ka), per_member(b, kb)
        return x, qk, a, b

    def run(fn):
        def one(x, qk, a, b):
            return fn(x, qk, {"a": a, "b": b}, 2.0)

        if not members:
            return one
        axes = FactoredDelta(None, 0, 0, 0)
        return jax.vmap(one, in_axes=(0, None, axes, axes))

    kernel = lambda x, qk, leaf, s: fused_qlora_dense(x, qk, leaf, s, use_pallas=True)

    def per_member(x, qk, a, b):
        pick = lambda f, k: FactoredDelta(f.w, f.u[k], f.v[k], f.c[k])
        return jnp.stack([kernel(x[k], qk, {"a": pick(a, k), "b": pick(b, k)}, 2.0)
                          for k in range(members)])

    return Case(
        "fused_qlora", label, make, run(kernel), run(xla_fused_qlora),
        twin=("per_member", per_member) if members > 1 else None,
        tol=4 * _BF16_EPS,
        tol_reason="the XLA form rounds a_k, b_k and both partial products to "
                   "bf16 before the sum; the kernel keeps f32 until one final "
                   "rounding: four bf16 roundings apart",
    )


def one_head_a_step(attend: Callable[..., jax.Array], q, k, v, mask=None) -> jax.Array:
    """``attend(q, k, v, mask)`` with a grid step a (sequence, head): the
    heads folded into the sequence axis, ``[B·H, n, 1, dh]``, so that the
    kernel's rule can put one head in a step and no more — the grid
    ``decode_attention`` had up to PR 35, as a case of the kernel it has now.
    What a step holds changes no head's arithmetic, so the two agree bit for
    bit (here on the chip, in ``tests/test_attention.py`` interpreted)."""
    B, nq, H, dh = q.shape
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(B * H, t.shape[1], 1, dh)
    out = attend(fold(q), fold(k), fold(v), None if mask is None else jnp.repeat(mask, H, axis=0))
    return jnp.moveaxis(out.reshape(B, H, nq, dh), 1, 2)


def _attention_case(label: str, B: int, nq: int, L: int, kv_len: Optional[int],
                    H: int = 16, dh: int = 64, masked: bool = False, members: int = 0) -> Case:
    """One ``decode_attention`` call; ``members`` > 0 puts the member axis
    ``lax.map(batch_size=member_batch)`` vmaps over in front of every operand."""
    from ..ops.attention import decode_attention

    def make(key):
        kq, kk, kv, km = jax.random.split(key, 4)
        lead = (members,) if members else ()
        bf = lambda k, s: jax.random.normal(k, (*lead, *s), jnp.float32).astype(jnp.bfloat16)
        args = (bf(kq, (B, nq, H, dh)), bf(kk, (B, L, H, dh)), bf(kv, (B, L, H, dh)))
        if masked:
            # padded text: a valid prefix per row, never empty
            n_valid = jax.random.randint(km, (*lead, B, 1), 1, L + 1)
            args += (jnp.arange(L) < n_valid,)
        return args

    def run(use_pallas, fold=False):
        def attend(q, k, v, mask=None):
            # a head's softmax scale is the unfolded call's, 1/sqrt(dh)
            return decode_attention(q, k, v, kv_len=kv_len, kv_mask=mask,
                                    use_pallas=use_pallas)

        fn = functools.partial(one_head_a_step, attend) if fold else attend
        return jax.vmap(fn) if members else fn

    return Case(
        "decode_attention", label, make, run(True), run(False),
        twin=("one_head_a_step", run(True, fold=True)), twin_exact=True,
        tol=2 * _BF16_EPS,
        tol_reason="both sides hold the softmax in f32 and round the output "
                   "to bf16 once; the online-softmax rescaling reorders the "
                   "f32 sums, so results may round to neighbouring bf16 "
                   "values: one spacing, 2^-7 of the largest magnitude",
    )


def _gated_delta_case(label: str, lead: Tuple[int, ...], H: int = 32, dk: int = 128, dv: int = 128) -> Case:
    """One decode position of a Gated DeltaNet layer (Qwen3-Next widths: 32
    value heads of 128 x 128 float32 state): the kernel against the
    ``jax.numpy`` step, the output and the new state each."""
    from ..ops.gated_delta import gated_delta_step, xla_gated_delta_step

    def make(key):
        kq, kk, kv, kg, kb, ks = jax.random.split(key, 6)

        def unit(k):
            t = jax.random.normal(k, (*lead, H, dk), jnp.float32)
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True))

        return (unit(kq) / jnp.sqrt(dk), unit(kk), jax.random.normal(kv, (*lead, H, dv), jnp.float32),
                -jax.random.uniform(kg, (*lead, H)), jax.random.uniform(kb, (*lead, H)),
                jax.random.normal(ks, (*lead, H, dk, dv), jnp.float32))

    kernel = lambda *args: gated_delta_step(*args, use_pallas=True)
    return Case(
        "gated_delta_step", label, make,
        jax.vmap(kernel) if len(lead) > 1 else kernel, xla_gated_delta_step,
        tol=1e-5,
        tol_reason="both sides multiply and add in float32 and nothing is "
                   "rounded narrower; the kernel is free to sum the 128 terms "
                   "of k^T S and q^T S in another order than XLA's reduce (16 "
                   "partial sums of 8 rows, then across sublanes): a few "
                   "float32 spacings, 2^-23 each, of the largest term — 1e-5 "
                   "leaves a factor ten (a v5e read 0.0, PR 32: the same order)",
    )


# VAR default geometry (models/var.VARConfig): 16 heads × 64, ten scales
# 1,2,3,4,5,6,8,10,13,16 → queries pn² against the cache prefix written so
# far, batch = 2 × prompts (CFG) — 4 prompts here, as the `ar` rung has.
_VAR_SCALES = ((1, 1), (4, 5), (9, 14), (16, 30), (25, 55), (36, 91),
               (64, 155), (100, 255), (169, 424), (256, 680))


def cases() -> List[Case]:
    out = [
        # Sana-Sprint 1.6B (models/sana.SanaConfig): 32×32 latent tokens per
        # image tile (reward_tile 1), d_model 2240, caption dim 2304 × 32
        # synthesized tokens, AdaLN 6·d. The member-axis case is first: it is
        # what pop_eval's lax.map(batch_size=1) lowers, the flagship call.
        _qlora_case("sana attn to_q/k/v/out, member axis 1: x[1,1024,2240] @ s8[2240,2240]",
                    1024, 2240, 2240, members=1),
        _qlora_case("sana attn to_q/k/v/out: x[1024,2240] @ s8[2240,2240]", 1024, 2240, 2240),
        _qlora_case("sana attn2 to_k/v on caption: x[32,2240] @ s8[2240,2240]", 32, 2240, 2240),
        _qlora_case("sana caption_proj/linear_1: x[32,2304] @ s8[2304,2240]", 32, 2304, 2240),
        _qlora_case("sana time_embed/linear: x[1,2240] @ s8[2240,13440]", 1, 2240, 13440),
        _qlora_case("sana proj_out: x[1024,2240] @ s8[2240,32]", 1024, 2240, 32),
        # what the benchmark's Sana cells call (member_batch 2, 300 caption
        # tokens): the member axis in front of every site
        _qlora_case("sana attn site, member axis 2: x[2,1024,2240] @ s8[2240,2240]",
                    1024, 2240, 2240, members=2),
        _qlora_case("sana attn2 to_k/v on caption, member axis 2: x[2,300,2240] @ s8[2240,2240]",
                    300, 2240, 2240, members=2),
        _qlora_case("sana caption_proj/linear_1, member axis 2: x[2,300,2304] @ s8[2304,2240]",
                    300, 2304, 2240, members=2),
    ]
    # VAR-d16 (models/var.VARConfig: width 1024, MLP 4096) under member_batch
    # 4: a scale's rows are 2 (CFG) x 4 classes x pn² — 8 at scale 0, 128 at
    # scale 3, 2048 at scale 9
    out += [
        _qlora_case(f"var {site}, member axis 4: x[4,{rows},{din}] @ s8[{din},{dout}]",
                    rows, din, dout, members=4)
        for site, din, dout in (("qkv", 1024, 3072), ("attn_proj", 1024, 1024),
                                ("fc1", 1024, 4096), ("fc2", 4096, 1024))
        for rows in (8, 128, 2048)
    ]
    # what ops/fused_qlora.py's own rule for the member axis is for: members of
    # few rows, whose rows share a token block and the base's one read. The
    # decode sites of the lm_ar cell (openPangu-Ultra-MoE widths, member_batch
    # 8 x 8 sequences: ffn gate/up, MLA wuq, wdkv, wdq, the shared expert's
    # down) and VAR's small scales at fc1 (member_batch 4).
    out += [
        _qlora_case(f"lm_ar decode {site}, member axis 8: x[8,8,{din}] @ s8[{din},{dout}]",
                    8, din, dout, members=8)
        for site, din, dout in (("ffn gate/up", 7680, 18432), ("mla wuq", 1536, 24576),
                                ("mla wdkv", 7680, 576), ("mla wdq", 7680, 1536),
                                ("shared down", 2048, 7680))
    ]
    out += [
        _qlora_case(f"var fc1 small scale, member axis 4: x[4,{rows},1024] @ s8[1024,4096]",
                    rows, 1024, 4096, members=4)
        for rows in (8, 72, 200)
    ]
    out += [
        _attention_case(f"var scale {i}: q[8,{nq},16,64] vs cache[8,680,16,64] kv_len {kv}",
                        8, nq, 680, kv)
        for i, (nq, kv) in reversed(list(enumerate(_VAR_SCALES)))
    ]
    # what the benchmark's VAR cell calls (member_batch 4): the member axis in
    # front, the cache as long as its valid prefix (models/var.py concatenates
    # the row blocks written so far) — the two scales whose last block is part
    # rows, part the wrapper's zeros
    out += [
        _attention_case(f"var scale {i}, member axis 4: q[4,8,{nq},16,64] vs cache[4,8,{kv},16,64]",
                        8, nq, kv, kv, members=4)
        for i, (nq, kv) in ((9, _VAR_SCALES[9]), (8, _VAR_SCALES[8]))
    ]
    # the hybrid cell's decode step (qwen3next80b-ep4-train-pop8x8): 64
    # sequences' states a DeltaNet layer; in the step pop_eval's vmap puts the
    # member axis in front, 8 members x 8 sequences
    out += [
        _gated_delta_case("qwen3-next decode, 64 sequences: state f32[64,32,128,128]", (64,)),
        _gated_delta_case("qwen3-next decode, member axis 8: state f32[8,8,32,128,128]", (8, 8)),
    ]
    out += [
        # Infinity cross-attention (models/infinity.py): bool text mask; 16
        # synthesized tokens (backends/infinity_backend) and T5's 512
        _attention_case("infinity cross-attn: q[8,256,16,64] vs text[8,16,16,64] + mask",
                        8, 256, 16, None, masked=True),
        _attention_case("infinity cross-attn: q[8,256,16,64] vs text[8,512,16,64] + mask",
                        8, 256, 512, None, masked=True),
    ]
    return out


def _us_per_call(fn: Callable[..., jax.Array], args: Tuple[Any, ...], calls: int = 100) -> float:
    """Microseconds a call of ``fn(*args)``: ``calls`` of them in one program
    (each one's first argument takes a zero made from the last one's output,
    so they run in order and none is hoisted), by the host's clock around
    the whole, after one run that compiles."""
    import time

    def chain(x, *rest):
        def step(x, _):
            y = fn(x, *rest)
            return x + (y[..., :1] * 0).astype(x.dtype), None

        return jax.lax.scan(step, x, None, length=calls)[0]

    chain = jax.jit(chain)
    jax.block_until_ready(chain(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(chain(*args))
    return (time.perf_counter() - t0) / calls * 1e6


def run_case(case: Case, compile_only_device: Optional[Any] = None) -> Dict[str, Any]:
    """Compile (and, with a chip, run and compare) one case. Raises whatever
    tracing or Mosaic raises; a missed tolerance is reported in the record
    (``ok`` false), not raised."""
    rec: Dict[str, Any] = {"kernel": case.kernel, "case": case.label}
    key = jax.random.PRNGKey(0)
    if compile_only_device is not None:
        from jax.sharding import SingleDeviceSharding

        s = SingleDeviceSharding(compile_only_device)
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(case.make, key),
        )
        for fn in (case.kernel_fn, *(case.twin or ())[1:]):  # the twin is Mosaic's to refuse too
            jax.jit(fn).lower(*args).compile()
        return {**rec, "ok": True, "compiled_for": compile_only_device.device_kind}
    args = jax.jit(case.make)(key)
    f32_leaves = lambda fn: [t.astype(jnp.float32) for t in jax.tree_util.tree_leaves(jax.jit(fn)(*args))]
    gots, refs = f32_leaves(case.kernel_fn), f32_leaves(case.xla_fn)

    def apart(got, ref):
        diff, scale = float(jnp.max(jnp.abs(got - ref))), float(jnp.max(jnp.abs(ref)))
        return diff / max(scale, 1e-30), diff, scale

    # where a kernel returns several arrays, each is held to the tolerance at
    # its own scale: the record is of the one furthest from its reference
    rel, diff, scale = max(map(apart, gots, refs))
    got = gots[0]
    differ = 0
    if case.twin is not None:
        name, twin_fn = case.twin
        differ = int(jnp.sum(got != jax.jit(twin_fn)(*args).astype(jnp.float32)))
        rec.update({
            f"differ_from_{name}": differ, "outputs": int(got.size),
            "us_per_call": round(_us_per_call(case.kernel_fn, args), 2),
            f"us_per_call_{name}": round(_us_per_call(twin_fn, args), 2),
        })
    return {
        **rec, "max_abs_diff": diff, "max_abs_ref": scale, "rel": rel,
        "tol": case.tol, "tol_reason": case.tol_reason,
        "ok": bool(rel <= case.tol and all(bool(jnp.all(jnp.isfinite(g))) for g in gots)
                   and not (case.twin_exact and differ)),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", default="",
                    help="comma list of pallas_call names (default: all)")
    ap.add_argument("--compile_only", action="store_true",
                    help="compile for a TPU v5e topology without a chip")
    ap.add_argument("--out", default=None, help="also write the records here (JSONL)")
    args = ap.parse_args(argv)
    want = {k for k in args.kernels.split(",") if k}
    device = None
    if args.compile_only:
        from jax.experimental import topologies

        device = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu"
        ).devices[0]
    elif jax.default_backend() != "tpu":
        print("kernel_check: no TPU backend (pass --compile_only for Mosaic's "
              "verdict without a chip)", file=sys.stderr)
        return 2
    records = []
    for case in cases():
        if want and case.kernel not in want:
            continue
        try:
            rec = run_case(case, device)
        except Exception as e:  # the report is the point: record, go on
            rec = {"kernel": case.kernel, "case": case.label, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000],
                   "traceback_tail": traceback.format_exc()[-1500:]}
        records.append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in records)
    return 0 if records and all(r["ok"] for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
