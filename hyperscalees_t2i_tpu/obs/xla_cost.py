"""Per-compiled-program XLA ledger: cost/memory normalization + roofline.

Every AOT compile in the project (trainer epoch steps, chained programs,
bench rungs, preflight abstract lowerings) produces one JSON record in a
``programs.jsonl`` ledger next to the run's other obs artifacts, carrying:

- the **geometry key** (m, r, pop, member_batch, sharding layout) and chain
  depth, so a record is attributable to exactly one program shape;
- ``compiled.cost_analysis()`` normalized across backends (flops, bytes
  accessed, transcendentals — some backends return a list, some a dict,
  some nothing);
- ``compiled.memory_analysis()`` normalized to argument/output/temp/
  generated-code bytes and a **peak-HBM estimate** (their sum — XLA's own
  convention for live-at-once accounting), with an arguments-only fallback
  when the backend lacks the API;
- lowering/compile wall times and StableHLO line count/size/hash (the
  program-size evidence PERF.md used to hand-transcribe);
- the compile's **provenance** (:class:`CompileProvenance`): whether the
  executable was compiled or read from the persistent cache, under which
  key, and jax's own seconds for tracing, StableHLO and the backend;
- a **donation audit** of ``donate_argnums``: bytes the caller offered vs
  alias bytes XLA actually reused — a silently-dropped donation doubles
  peak HBM at flagship geometry.

``roofline(...)`` classifies a measured step against the program's static
cost: compute-bound, bandwidth-bound, or latency-bound (measured time far
above both hardware terms — the host-dispatch signature). Peak FLOP/s and HBM bandwidth come from ``utils/mfu.py``'s
per-chip tables.

Import discipline: this module is **stdlib-only at import time** (mirrors
``obs.heartbeat``/``obs.metrics``) — bench.py's ladder parent imports the
``obs`` package and must never pay, or trigger, a jax import. Functions that
need device identity import jax lazily and only read state that already
exists.
"""

from __future__ import annotations

import hashlib
import json
import logging
import re
import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Union


def normalize_cost_analysis(compiled: Any) -> Dict[str, Optional[float]]:
    """``compiled.cost_analysis()`` → ``{flops, bytes_accessed,
    transcendentals}`` (None per field when absent/non-positive).

    Backends disagree on the return shape (list-of-dict vs dict) and on which
    keys exist; every consumer in the repo previously open-coded this
    extraction (utils/mfu.py, bench.py) — this is now the one copy.
    """
    out: Dict[str, Optional[float]] = {
        "flops": None, "bytes_accessed": None, "transcendentals": None,
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for field, key in (
            ("flops", "flops"),
            ("bytes_accessed", "bytes accessed"),
            ("transcendentals", "transcendentals"),
        ):
            v = ca.get(key)
            if v is not None and float(v) > 0:
                out[field] = float(v)
    except Exception:
        pass
    return out


def normalize_memory_analysis(compiled: Any) -> Optional[Dict[str, float]]:
    """``compiled.memory_analysis()`` → byte-count dict, or None when the
    backend doesn't implement the API (callers fall back to arguments-only
    accounting). ``peak_bytes`` is argument+output+temp+generated-code — the
    live-at-once estimate the HBM fit verdict uses."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        out = {}
        for field, attr in (
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
            ("generated_code_bytes", "generated_code_size_in_bytes"),
            ("alias_bytes", "alias_size_in_bytes"),
        ):
            out[field] = float(getattr(ma, attr))
        # aliased (donated) argument space is reused for outputs — it must
        # not be double-counted as both argument and output residency
        out["peak_bytes"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
            + out["generated_code_bytes"] - out["alias_bytes"]
        )
        return out
    except Exception:
        return None


def stablehlo_stats(lowered: Any) -> Dict[str, Any]:
    """StableHLO text stats of a ``Lowered``: line count, byte size, and a
    short content hash — the regenerable form of PERF.md's hand-made
    "program-size evidence" table — plus the Pallas kernels the program
    carries: every Mosaic custom call by the ``name=`` its ``pallas_call``
    was given, with its count in the text (a call inside a scan body counts
    once), and under ``pallas_members_per_block`` — for a kernel whose call
    declares it — how many of those sites put how many members of the
    ``vmap``ped chunk into one token block (``{"fused_qlora": {"1": 28, "8":
    29}}``), under ``pallas_heads_per_block`` how many put how many of a
    sequence's heads into one grid step (``{"decode_attention": {"16":
    10}}``). What the kernel gates selected (``ops/pallas_gate``) can then be
    held against what was actually lowered. ``{}`` when ``as_text`` is
    unavailable."""
    try:
        text = lowered.as_text()
    except Exception:
        return {}
    import re

    kernels: Dict[str, int] = {}
    # what a kernel says in its call's metadata (ops/fused_qlora.py,
    # ops/attention.py): sites by the declared number
    declared: Dict[str, Dict[str, Dict[str, int]]] = {"members_per_block": {}, "heads_per_block": {}}
    for name, attrs in re.findall(
        r'@tpu_custom_call\(.*?kernel_name = "([^"]+)"(.*?) : \(tensor<', text
    ):
        kernels[name] = kernels.get(name, 0) + 1
        for key, by_kernel in declared.items():
            # (a JSON string inside MLIR text: its quotes print as \22)
            g = re.search(key + r'(?:\\22|\\?")\s*:\s*(?:\\22|\\?")(\d+)', attrs)
            if g:
                by_g = by_kernel.setdefault(name, {})
                by_g[g.group(1)] = by_g.get(g.group(1), 0) + 1
    return {
        "stablehlo_lines": text.count("\n") + 1,
        "stablehlo_bytes": len(text),
        "stablehlo_sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
        "pallas_kernels": kernels,
        **{f"pallas_{key}": by_kernel for key, by_kernel in declared.items()},
    }


def _flat_avals(compiled: Any):
    """Flat argument avals of a Compiled/Lowered (``in_avals`` is
    ``(args_tuple, kwargs_dict)``); None when the API is absent."""
    try:
        args, kwargs = compiled.in_avals
        flat = []
        import jax

        for tree in (*args, kwargs):
            flat.extend(jax.tree_util.tree_leaves(tree))
        return flat
    except Exception:
        return None


def _aval_bytes(aval: Any) -> float:
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return float(size * aval.dtype.itemsize)
    except Exception:
        return 0.0


def _hlo_alias_configured(compiled: Any) -> Optional[bool]:
    """Whether the optimized HLO carries a non-empty ``input_output_alias``
    config. Needed because executables deserialized from the persistent
    compile cache report ``alias_size_in_bytes == 0`` even when donation is
    in effect — the HLO attribute survives serialization. None = can't say
    (no ``as_text`` on this backend)."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    import re

    m = re.search(r"input_output_alias=\{(.*?)\}", text)
    if m is None:
        return False
    return bool(m.group(1).strip())


def donation_audit(compiled: Any) -> Dict[str, Any]:
    """Compare what the caller offered for donation against what XLA aliased.

    ``donate_argnums`` on a Compiled is flat *leaf* positions. ``honored``
    is None when the backend can't say (no memory_analysis and no HLO
    text); False when bytes were offered but nothing was aliased — the
    silent failure that doubles θ's HBM residency (donation dropped by a
    copy/sharding change).
    """
    out: Dict[str, Any] = {
        "donated_leaves": 0, "donated_bytes": 0.0,
        "alias_bytes": None, "honored": None,
    }
    try:
        donate = tuple(compiled.donate_argnums)
    except Exception:
        return out
    out["donated_leaves"] = len(donate)
    flat = _flat_avals(compiled)
    if flat is not None:
        out["donated_bytes"] = sum(
            _aval_bytes(flat[i]) for i in donate if i < len(flat)
        )
    mem = normalize_memory_analysis(compiled)
    if mem is not None:
        out["alias_bytes"] = mem["alias_bytes"]
    if out["donated_bytes"] > 0:
        if out["alias_bytes"]:
            out["honored"] = True
        else:
            # alias bytes 0/absent: either donation was really dropped or
            # this executable came from the persistent cache (deserialized
            # stats lose aliasing) — the optimized HLO is authoritative
            out["honored"] = _hlo_alias_configured(compiled)
    return out


# dtype-name → byte size for HLO shape strings (f32[4,16]{1,0} etc.);
# collectives only ever carry these (token/opaque shapes are zero-size)
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# op names extracted from the optimized HLO. `-start` variants count once
# (async collectives lower to start/done pairs — the `done` is bookkeeping,
# not a second transfer; `-done` lines never match because the regex
# requires `(` directly after the op name / `-start` suffix).
_COLLECTIVE_OP_NAMES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
)


def _hlo_shape_elements(shape_text: str):
    """``(dtype, dims-string, bytes)`` per ``dtype[dims]`` token in an HLO
    shape string (tuples yield one entry per element; unknown dtypes count
    as 0 bytes)."""
    import re

    out = []
    for dtype, dims in re.findall(r"([a-z][a-z0-9]*)\[([0-9,]*)\]", shape_text):
        size = _HLO_DTYPE_BYTES.get(dtype)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dtype, dims, float(n * size) if size is not None else 0.0))
    return out


def _hlo_shape_bytes(shape_text: str) -> float:
    """Total bytes of every ``dtype[dims]`` token in an HLO result-shape
    string (handles tuples: ``(f32[4]{0}, bf16[8,2]{1,0})``)."""
    return sum(b for _, _, b in _hlo_shape_elements(shape_text))


def _start_op_result_bytes(shape_text: str) -> float:
    """Result bytes of an async ``*-start`` collective, whose HLO result is
    a tuple carrying BOTH the operand and the destination buffers (plus, on
    some backends, ``u32[]`` context scalars): ``(f32[128], f32[128])`` for
    all-reduce-start, ``(f32[1,128], f32[8,128])`` for all-gather-start.
    Summing the whole tuple would double-count the transfer — strip the
    integer-scalar context elements, then count only the second half (the
    destination buffers), matching the sync ops' result-shape convention.
    Falls back to half the tuple total on an unrecognized layout (odd
    element count) — possibly inexact, never doubled."""
    data = [
        (dt, dims, b) for dt, dims, b in _hlo_shape_elements(shape_text)
        if not (dims == "" and dt in ("u32", "s32", "u64", "s64", "pred"))
    ]
    if not data:
        return 0.0
    if len(data) % 2:
        return sum(b for _, _, b in data) / 2.0
    return sum(b for _, _, b in data[len(data) // 2:])


def collective_stats(compiled: Any) -> Dict[str, Any]:
    """Cross-device collectives of the optimized HLO module: op count, total
    result bytes, and a per-op-kind breakdown.

    The module XLA hands back is the *per-device* (post-partition) program,
    so the byte total is per-device traffic — the numerator of the
    comms-roofline floor (``roofline(collective_bytes=...)``), NOT divided
    again by device count. Bytes are the collective's **result** shape: for
    all-reduce that equals the reduced payload each device contributes; for
    all-gather it is the full gathered buffer each device receives — the
    live-bytes-through-the-interconnect convention, one rule for every op.
    ``{}`` when the backend has no ``as_text`` (nothing claimed, nothing
    wrong)."""
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    import re

    pat = re.compile(
        r"=\s*([^=]*?)\s(" + "|".join(_COLLECTIVE_OP_NAMES) + r")(-start)?\("
    )
    ops = 0
    total = 0.0
    breakdown: Dict[str, Dict[str, float]] = {}
    for line in text.splitlines():
        m = pat.search(line)
        if m is None:
            continue
        shape_text, kind, is_start = m.group(1), m.group(2), m.group(3)
        b = (
            _start_op_result_bytes(shape_text) if is_start
            else _hlo_shape_bytes(shape_text)
        )
        ops += 1
        total += b
        slot = breakdown.setdefault(kind, {"ops": 0, "bytes": 0.0})
        slot["ops"] += 1
        slot["bytes"] += b
    return {
        "collective_ops": ops,
        "collective_bytes": total,
        "collective_breakdown": breakdown,
    }


# The device-time vocabulary: every ``jax.named_scope`` the step program
# enters, and the only names :func:`scope_table` looks for. A top-level scope
# is one stage of the ES step; the inner names split one stage; the indexed
# ones carry a number (``scale3``, ``stage0``). Kept here, beside the table
# writer, and nowhere else: a scope entered under another name is
# ``unattributed``.
TOP_SCOPES = ("es_noise", "generate", "decode", "reward", "es_update")
INNER_SCOPES = (
    "dit_embed_out", "dit_self_attn", "dit_cross_attn", "dit_ffn",     # Sana DiT
    "blocks", "head", "sample", "msvq_accumulate",                     # VAR, inside scale<k>
    "lm_prefill", "lm_decode_step",                                    # lm_ar: the two phases of generate
    "lm_mla", "attend", "lm_dense_ffn", "lm_moe", "router", "experts", "shared", "lm_head",  # inside them
    "lm_gdn", "conv", "delta_rule", "gdn_out", "lm_attn",              # the hybrid family's mixers (lm_attn -> attend)
    "lm_hc", "hc_coeff", "hc_sinkhorn", "hc_mix",                      # hyper-connection streams around every sub-layer
    "lm_ssm", "ssd", "ssm_out",                                        # a Mamba-2 mixer (lm_ssm -> conv, ssd, ssm_out)
    "lm_swa",                                                          # sliding-window attention (lm_swa -> attend)
    "preprocess", "clip_b", "clip_h", "score",                         # rewards
    "perturb",                                                         # es_noise: one member's adapter
    "fitness", "update", "health",                                     # es_update
)
INDEXED_SCOPES = ("scale", "stage")  # VAR scale<k>, DC-AE decode stage<k>
_INDEXED_SCOPE = r"(?:%s)\d+" % "|".join(INDEXED_SCOPES)
UNATTRIBUTED = "unattributed"
INFERRED = "~"  # prefix of a table entry that scope_table inferred from the graph


# How scope_table and kv_cache_whole_ops read ``compiled.as_text()``: the
# computations fusions call, a computation's opening line, and an
# instruction's opcode (the first `word(` after its result shape).
_FUSED_CALLEE = r"\bfusion\(.*?\bcalls=%?([\w.\-]+)"
_COMPUTATION_HEAD = r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$"
_OPCODE = r"\s([a-z][\w\-]*)\("


def scope_of(op_name: str) -> str:
    """Innermost vocabulary path in an instruction's ``op_name`` metadata:
    ``jit(f)/while/body/closed_call/vmap(generate)/dit_ffn/mul`` →
    ``generate/dit_ffn``. The whole path is searched (some ops carry only its
    tail, a transform wraps a scope as ``vmap(generate)``); inner names count
    only after a top-level one, each once, in the order they nest."""
    import re

    path: list = []
    for token in re.findall(r"\w+", op_name):
        if token in TOP_SCOPES:
            path = [token]  # the innermost top-level scope wins
        elif path and token not in path and (
            token in INNER_SCOPES or re.fullmatch(_INDEXED_SCOPE, token)
        ):
            path.append(token)
    return "/".join(path) if path else UNATTRIBUTED


def scope_table(compiled: Any) -> Dict[str, str]:
    """``{instruction name: scope}`` of the optimized HLO module — what joins
    a profiler trace (whose device events are named by the optimized module's
    instruction names and carry no ``op_name``) to the program's
    ``jax.named_scope`` names. A fusion carries its root's metadata, so it
    takes its root's scope. An instruction whose ``op_name`` holds no
    vocabulary scope is the compiler's own (a layout copy, an async
    ``copy-done``, the zero fill of a scan's output, a rewritten reduction
    that kept only ``reduce_window_sum``): it exists for the instructions
    that consume its result and takes the scope they share (the common
    prefix of their paths, looked for through tuples and other such
    instructions, never through a loop or a call), failing that the one its
    operands share. Such an entry is a guess from the graph, not the
    program's word, and is marked: ``~generate/dit_ffn`` (:data:`INFERRED`),
    so that a reader can say how much of a scope's time is inferred. The
    guess needs the operands printed as ``%name`` lists; on a jax that prints
    them otherwise it finds nothing, and those instructions stay
    ``unattributed``. Control flow and plumbing (``while``, ``call``,
    ``conditional``, tuples, parameters, constants) are only ever what their
    own metadata says, and what nothing scoped consumes or feeds (loop
    counters, the member loop itself) stays ``unattributed``. Instructions
    inside fused computations never run as ops of their own and are left out.
    ``{}`` when the backend has no ``as_text``."""
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    import os.path
    import re

    fused = set(re.findall(_FUSED_CALLEE, text))
    head = re.compile(_COMPUTATION_HEAD)
    inst = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
    opcode = re.compile(_OPCODE)
    op_name = re.compile(r'op_name="([^"]*)"')
    control = ("while", "call", "conditional")
    plumbing = control + ("tuple", "get-tuple-element", "parameter", "constant")
    table: Dict[str, str] = {}
    opcodes: Dict[str, str] = {}
    operands: Dict[str, list] = {}
    users: Dict[str, list] = {}
    skip = False
    for line in text.splitlines():
        if not line.startswith(" "):
            m = head.match(line)
            if m:
                skip = m.group(1) in fused
            continue
        if skip:
            continue
        m = inst.match(line)
        if m is None:
            continue
        name = m.group(1)
        meta = op_name.search(line)
        table[name] = scope_of(meta.group(1)) if meta else UNATTRIBUTED
        op = opcode.search(line, m.end() - 1)
        opcodes[name] = op.group(1) if op else ""
        # operands are printed as %names, without shapes: up to the first `)`
        args = line[op.end():line.find(")", op.end())] if op else ""
        operands[name] = re.findall(r"%([\w.\-]+)", args)
        for o in operands[name]:
            users.setdefault(o, []).append(name)

    def shared_scope(start: str, edges: Dict[str, list]) -> str:
        """Common prefix of the scopes reached from ``start`` along ``edges``,
        walking through instructions that have none themselves."""
        found, seen, frontier = [], {start}, [start]
        while frontier:
            nxt = []
            for n in frontier:
                for o in edges.get(n, ()):
                    if o in seen or o not in table:
                        continue
                    seen.add(o)
                    if table[o] != UNATTRIBUTED:
                        found.append(table[o].split("/"))
                    elif opcodes[o] not in control:
                        nxt.append(o)
            frontier = nxt
        # commonprefix compares any sequences element by element: here, paths
        return "/".join(os.path.commonprefix(found)) or UNATTRIBUTED

    inferred = {}
    for name, scope in table.items():
        if scope == UNATTRIBUTED and opcodes[name] not in plumbing:
            got = shared_scope(name, users)
            if got == UNATTRIBUTED:
                got = shared_scope(name, operands)
            if got != UNATTRIBUTED:
                inferred[name] = INFERRED + got
    table.update(inferred)
    return table


def kv_cache_whole_ops(compiled: Any, cache_shape: Any) -> Dict[str, int]:
    """Instructions of the optimized HLO module, by opcode, whose result is
    as large as a generator's whole KV cache: the K or V stack as the model
    keeps it (``cache_shape``, VAR: ``[depth, 2B, L, H, dh]``, noted at trace
    time as ``kv_cache_shape``) or one layer of it (``cache_shape[1:]``; a
    matrix, the head's kernel noted as ``lm_head_shape``, only whole),
    with or without one further axis (the member axis of a ``vmap``ped
    chunk). What a scale of generation needs is its own rows; each op counted
    here fills, copies or re-lays the cache whole — or, as a
    ``dynamic-update-slice``, writes rows into it in place, which is why the
    count is by opcode. A fusion is named by its root,
    ``fusion(dynamic-update-slice)``; a tuple result counts when an element
    has the shape (a ``while`` that carries the cache). Instructions inside
    fused computations never run as ops of their own and are left out, as are
    those that move nothing (``parameter``, ``tuple``, ``get-tuple-element``,
    ``bitcast``) and the ``-start`` half of an async pair. ``{}`` when the
    backend has no ``as_text``."""
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    import re

    stack = tuple(int(d) for d in cache_shape)
    wanted = (stack, stack[1:]) if len(stack) > 2 else (stack,)  # a matrix (``lm_head_shape``) has no layers

    def whole(dims: tuple) -> bool:
        return dims in wanted or any(
            dims[:i] + dims[i + 1:] in wanted for i in range(len(dims))
        )

    fused = set(re.findall(_FUSED_CALLEE, text))
    head = re.compile(_COMPUTATION_HEAD)
    inst = re.compile(r"^\s+(ROOT\s+)?%?[\w.\-]+\s+=\s")
    opcode = re.compile(_OPCODE)
    calls = re.compile(r"\bcalls=%?([\w.\-]+)")
    free = ("parameter", "tuple", "get-tuple-element", "bitcast")
    roots: Dict[str, str] = {}
    found = []  # (opcode, fused computation called or None)
    current = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = head.match(line)
            current = m.group(1) if m else current
            continue
        m = inst.match(line)
        op = opcode.search(line, m.end() - 1) if m else None
        if op is None:
            continue
        if m.group(1):
            roots[current] = op.group(1)
        if current in fused or op.group(1) in free or op.group(1).endswith("-start"):
            continue
        result = line[m.end():op.start()]
        if any(
            whole(tuple(int(d) for d in dims.split(",") if d))
            for dims in re.findall(r"[a-z][a-z0-9]*\[([0-9,]*)\]", result)
        ):
            callee = calls.search(line) if op.group(1) == "fusion" else None
            found.append((op.group(1), callee.group(1) if callee else None))
    counts: Dict[str, int] = {}
    for op, callee in found:
        name = f"fusion({roots.get(callee, '?')})" if op == "fusion" else op
        counts[name] = counts.get(name, 0) + 1
    return counts


# ops through which the dequant dataflow cone propagates (elementwise /
# data-movement steps between the s8 source and the consuming dot/conv);
# `bitcast` is free in XLA (no buffer) and deliberately absent
_DEQUANT_PROPAGATE_OPS = (
    "convert", "multiply", "copy", "transpose", "reshape", "fusion",
    "dynamic-slice", "slice",
)


def legalization_stats(compiled: Any) -> Dict[str, Any]:
    """Materialized float-legalization buffers in the optimized HLO — the
    CPU-only copies a native-bf16/int8 chip never allocates. Two measured
    classes (both verified in this container's optimized HLO, PERF.md
    rounds 10 and 14):

    - ``int8_dequant_copy_bytes`` — the int8-dequant cone of a
      ``--base_quant int8`` program (see below);
    - ``bf16_upcast_copy_bytes`` — f32 clones of bf16 *entry parameters*
      (``convert(bf16 %Arg_N)`` → f32 at top level): XLA:CPU cannot execute
      bf16 dot/conv and clones every bf16 param tree it carries through its
      loops. Measured, not estimated — the 2×-argument-bytes estimate the
      peak correction uses (``cpu_f32_upcast_bytes``) counts clones of
      every bf16 arg; this counts the ones the compiler actually made
      (top-level f32 ``convert`` instructions whose operand is a bf16
      ``parameter`` instruction — if a compiler release restructures them
      the measure degrades to 0 and the chip-true bytes estimate degrades
      toward the raw figure: conservative, never flattering).

    The int8 cone: XLA:CPU cannot feed an s8 operand to a dot/convolution —
    every ``dequantize_kernel`` site lowers to a *materialized* chain of
    kernel-sized float buffers: ``convert(s8)``, the broadcast scale, the
    ``multiply``, sometimes a bf16 re-cast and an f32 re-upcast (stacked
    kernels dequantize per layer slice inside scan bodies; unstacked
    conv/dense kernels are dequantized whole, some hoisted into ENTRY and
    carried through while-loop state). A chip with native int8 operand
    fusion (weight-only-quant matmul — every TPU kind in utils/mfu.py)
    keeps the whole chain in the operand read and never allocates any of
    it. Measured by dataflow: within each non-fused computation, every
    float instruction reachable from an s8 value through
    :data:`_DEQUANT_PROPAGATE_OPS` (plus the full-kernel-size scale
    ``broadcast`` feeding a cone ``multiply``) contributes its output
    bytes; the cone stops at the consuming dot/convolution. Also returns
    ``int8_dequant_hoisted_bytes`` (the ENTRY-computation subset — created
    outside loop bodies and carried through the while state, provably live
    across the member loop and so part of the CPU peak) and
    ``int8_dequant_ops``.

    Instructions inside *fused computations* (``calls=``/``to_apply=``
    interiors) never materialize and are skipped — a fusion contributes its
    single output buffer. ``{}`` when the backend has no ``as_text``.
    """
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    import re

    interior = set(re.findall(r"(?:calls|to_apply)=%?([\w.-]+)", text))
    # computation headers: `%name (params) -> type {` — params/types may be
    # tuples with nested parens, so match structurally (` -> ` + trailing
    # `{`), not by balancing
    header = re.compile(r"^\s*(ENTRY\s+)?%?([\w.-]+)\s+\(.*->.*\{\s*$")
    instr = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(\w+)\[([\d,]*)\][^\s]*\s+([\w-]+)\("
    )
    # parse: computation -> {instr name: (dtype, shape_bytes, op, operands)}
    comps: Dict[str, Dict[str, Any]] = {}
    entry_name = None
    current = None
    for line in text.splitlines():
        h = header.match(line)
        if h:
            current = h.group(2)
            if h.group(1) is not None:
                entry_name = current
            continue
        if current is None or current in interior:
            continue
        m = instr.match(line)
        if m is None:
            continue
        name, dtype, shape, op = m.group(1), m.group(2), m.group(3), m.group(4)
        rhs = line.split("=", 1)[1]
        operands = re.findall(r"%([\w.-]+)", rhs)
        nelem = 1
        for d in shape.split(","):
            if d:
                nelem *= int(d)
        comps.setdefault(current, {})[name] = (
            dtype, nelem * _HLO_DTYPE_BYTES.get(dtype, 4), op, operands
        )
    total = 0.0
    hoisted = 0.0
    ops = 0
    upcast = 0.0
    float_dts = ("f32", "bf16", "f16")
    for cname, instrs in comps.items():
        # measured bf16-parameter f32 clones (any computation level — the
        # big ones are ENTRY-hoisted, sliced reads happen per loop body)
        for n, (dt, nb, op, args) in instrs.items():
            if op != "convert" or dt != "f32" or len(args) != 1:
                continue
            src = instrs.get(args[0])
            if src is not None and src[0] == "bf16" and src[2] == "parameter":
                upcast += nb
        cone = set(n for n, (dt, _, _, _) in instrs.items() if dt == "s8")
        if not cone:
            continue
        # fixed-point propagation (chains are short; a few passes suffice)
        changed = True
        members = set()
        while changed:
            changed = False
            for n, (dt, nb, op, args) in instrs.items():
                if n in members or dt not in float_dts:
                    continue
                if op not in _DEQUANT_PROPAGATE_OPS:
                    continue
                if any(a in cone for a in args):
                    members.add(n)
                    cone.add(n)
                    changed = True
        # full-size scale broadcasts: float broadcasts feeding a cone
        # multiply at the multiply's own (kernel) shape
        for n in list(members):
            dt, nb, op, args = instrs[n]
            if op != "multiply":
                continue
            for a in args:
                ai = instrs.get(a)
                if ai and ai[2] == "broadcast" and ai[0] in float_dts \
                        and ai[1] == nb and a not in members:
                    members.add(a)
        for n in members:
            nb = instrs[n][1]
            total += nb
            ops += 1
            if cname == entry_name:
                hoisted += nb
    return {
        "int8_dequant_copy_bytes": total,
        "int8_dequant_hoisted_bytes": hoisted,
        "int8_dequant_ops": ops,
        "bf16_upcast_copy_bytes": upcast,
    }


def roofline(
    flops: Optional[float],
    bytes_accessed: Optional[float],
    measured_step_s: Optional[float] = None,
    *,
    peak_flops: Optional[float],
    hbm_bw: Optional[float],
    n_devices: int = 1,
    latency_factor: float = 2.0,
    collective_bytes: Optional[float] = None,
    ici_bw: Optional[float] = None,
) -> Dict[str, Any]:
    """Classify one step against the hardware roofline.

    ``t_compute_s = flops / (peak_flops·n)`` and ``t_bandwidth_s =
    bytes / (hbm_bw·n)`` are the two hardware floors; ``t_comms_s =
    collective_bytes / ici_bw`` joins them when the program's collective
    traffic and the chip's ICI bandwidth are both known (``collective_bytes``
    comes from the per-device partitioned module — :func:`collective_stats`
    — so it is NOT divided by ``n_devices``). ``t_roofline_s`` is the max of
    the known floors (the predicted step time at 100% efficiency on the
    binding resource). Classification rules (documented in PERF.md):

    - **latency** — measured > ``latency_factor`` × roofline: the step is
      dominated by costs the program model doesn't see (dispatch RTT,
      host sync, kernel-launch overhead);
    - **comms** — the interconnect floor is the (strictly) largest: the
      step is bound by collective traffic, not local compute or HBM;
    - **compute** — compute floor ≥ bandwidth floor;
    - **bandwidth** — bandwidth floor > compute floor;
    - ``None`` — peaks unknown (CPU / unrecognized chip) or no cost data.
    """
    n = max(int(n_devices), 1)
    t_c = flops / (peak_flops * n) if flops and peak_flops else None
    t_b = bytes_accessed / (hbm_bw * n) if bytes_accessed and hbm_bw else None
    t_m = collective_bytes / ici_bw if collective_bytes and ici_bw else None
    t_roof = max(t_c or 0.0, t_b or 0.0, t_m or 0.0) or None
    intensity = flops / bytes_accessed if flops and bytes_accessed else None
    ridge = peak_flops / hbm_bw if peak_flops and hbm_bw else None
    bound = None
    if t_roof is not None:
        if measured_step_s is not None and measured_step_s > latency_factor * t_roof:
            bound = "latency"
        elif t_m is not None and t_m > max(t_c or 0.0, t_b or 0.0):
            bound = "comms"
        elif (t_c or 0.0) >= (t_b or 0.0):
            bound = "compute"
        else:
            bound = "bandwidth"
    return {
        "t_compute_s": t_c,
        "t_bandwidth_s": t_b,
        "t_comms_s": t_m,
        "t_roofline_s": t_roof,
        "intensity": intensity,
        "ridge_intensity": ridge,
        "bound": bound,
    }


# --------------------------------------------------------------- provenance
# jax publishes what a compile did through ``jax.monitoring`` and logs the
# persistent cache's key at DEBUG; the names below are jax 0.9's.
_CACHE_USED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_TO_STABLEHLO_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILER_LOGGER = "jax._src.compiler"
_CACHE_KEY_LOGGER = "jax._src.cache_key"
# the parts ``jax._src.cache_key.get`` hashes into the key, in its order
CACHE_KEY_PARTS = (
    "computation", "jax_lib version", "backend version", "XLA flags",
    "compile_options", "accelerator_config", "compression", "custom_hook",
)
_KEY_LOGGED = re.compile(r"(?:cache hit|CACHE MISS) for '.*' with key '([^']+)'")
_PART_LOGGED = re.compile(r"get_cache_key hash of serialized (.+): ([0-9a-f]+)$")


class CompileProvenance:
    """Listens, for the length of a ``with`` block around one program's
    ``lower()`` and ``compile()``, to what jax says about them: the
    ``jax.monitoring`` events above and the cache-key lines of jax's compiler
    log. Everything it installed is removed when the block ends.

    ``key_parts=True`` also turns on ``jax._src.cache_key``'s DEBUG lines,
    the hash of each of the key's eight parts; jax computes them by
    serializing the module a second time, so only a traced run asks.

    Afterwards :meth:`fields` is what ``programs.jsonl`` records and
    :meth:`lower_spans` jax's own timing of the lowering's two halves, for
    the tracer. Where this jax gives no such event or line the field is
    None; nothing here raises."""

    def __init__(self, key_parts: bool = False):
        self.want_key_parts = bool(key_parts)
        self.cache_used = False
        self.cache_hit = False
        self.cache_key: Optional[str] = None
        self.cache_read_s: Optional[float] = None
        self.key_parts: Dict[str, str] = {}
        self._spans: list = []  # (name, t0, t1) on the perf_counter clock
        self._undo: list = []

    # ---- listeners (jax calls them with the event's name first)
    def _on_event(self, event: str, **_: Any) -> None:
        if event == _CACHE_USED_EVENT:
            self.cache_used = True
        elif event == _CACHE_HIT_EVENT:
            self.cache_hit = True

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == _CACHE_READ_EVENT:
            self.cache_read_s = float(duration)

    def _on_time_span(self, event: str, start: float, end: float, **_: Any) -> None:
        name = {_JAXPR_TRACE_EVENT: "jaxpr_trace", _TO_STABLEHLO_EVENT: "to_stablehlo",
                _BACKEND_COMPILE_EVENT: "backend_compile"}.get(event)
        if name is not None:
            wall, perf = self._anchor  # jax stamps these with time.time()
            self._spans.append((name, perf + (start - wall), perf + (end - wall)))

    def _capture(self, logger_name: str, pattern, take) -> None:
        """Read ``logger_name``'s DEBUG lines through a filter that passes on
        only what the logger would have let through anyway."""
        logger = logging.getLogger(logger_name)
        was_level, was_enabled_for = logger.level, logger.getEffectiveLevel()

        def seen(record: logging.LogRecord) -> bool:
            try:
                found = pattern.search(record.getMessage())
                if found:
                    take(*found.groups())
            except Exception:
                pass
            return record.levelno >= was_enabled_for

        logger.addFilter(seen)
        logger.setLevel(logging.DEBUG)
        self._undo.append(lambda: (logger.setLevel(was_level), logger.removeFilter(seen)))

    def __enter__(self) -> "CompileProvenance":
        self._anchor = (time.time(), time.perf_counter())
        try:
            from jax import monitoring

            for register, unregister, callback in (
                (monitoring.register_event_listener,
                 monitoring.unregister_event_listener, self._on_event),
                (monitoring.register_event_duration_secs_listener,
                 monitoring.unregister_event_duration_listener, self._on_duration),
                (monitoring.register_event_time_span_listener,
                 monitoring.unregister_event_time_span_listener, self._on_time_span),
            ):
                register(callback)
                self._undo.append(lambda u=unregister, c=callback: u(c))
            self._capture(_COMPILER_LOGGER, _KEY_LOGGED,
                          lambda key: setattr(self, "cache_key", key))
            if self.want_key_parts:
                self._capture(_CACHE_KEY_LOGGER, _PART_LOGGED, self.key_parts.__setitem__)
        except Exception:
            pass  # a jax without these: the fields stay None
        return self

    def __exit__(self, *exc: Any) -> None:
        while self._undo:
            try:
                self._undo.pop()()
            except Exception:
                pass

    @property
    def cache(self) -> str:
        """``hit``: read from the persistent cache; ``miss``: the cache was
        asked and the backend compiled; ``off``: no cache was in use."""
        if self.cache_hit:
            return "hit"
        return "miss" if self.cache_used or self.cache_key else "off"

    def seconds(self, name: str) -> Optional[float]:
        """jax's own seconds of ``jaxpr_trace`` (the outermost function's:
        the jitted functions it calls are traced inside it and report too),
        ``to_stablehlo`` or ``backend_compile`` (cache read included)."""
        got = [t1 - t0 for n, t0, t1 in self._spans if n == name]
        if not got:
            return None
        return round(max(got) if name == "jaxpr_trace" else sum(got), 4)

    def lower_spans(self) -> list:
        """``(name, t0, t1)`` of ``jaxpr_trace`` (the outermost one) and
        ``to_stablehlo`` on the ``perf_counter`` clock."""
        traces = [s for s in self._spans if s[0] == "jaxpr_trace"]
        outer = max(traces, key=lambda s: s[2] - s[1], default=None)
        return [s for s in self._spans if s[0] == "to_stablehlo" or s is outer]

    def fields(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "cache": self.cache,
            "cache_key": self.cache_key,
            "cache_read_s": round(self.cache_read_s, 4) if self.cache_read_s is not None else None,
            "backend_compile_s": self.seconds("backend_compile"),
            "jaxpr_trace_s": self.seconds("jaxpr_trace"),
            "to_stablehlo_s": self.seconds("to_stablehlo"),
        }
        if self.want_key_parts:
            out["cache_key_parts"] = dict(self.key_parts) or None
        return out


class ProgramLedger:
    """Append-only ``programs.jsonl`` writer — one JSON line per AOT compile.

    ``ProgramLedger(None)`` is a disabled no-op (non-master processes),
    mirroring ``Tracer(None)``. Writes are lock-guarded and never raise:
    losing a ledger line must not kill a training run.
    """

    # in-memory mirror cap: the live exporter reads recent records for its
    # program gauges; a run compiles dozens of programs, never thousands
    _KEEP = 256

    def __init__(self, path: Optional[Union[str, Path]] = None):
        self.path = Path(path) if path is not None else None
        self._lock = threading.Lock()
        self.records: list = []  # recent records (bounded), newest last
        if self.path is not None:
            self.path.parent.mkdir(parents=True, exist_ok=True)

    @property
    def enabled(self) -> bool:
        return self.path is not None

    def write(self, record: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.records.append(record)
            if len(self.records) > self._KEEP:
                del self.records[: -self._KEEP]
        line = json.dumps(record, default=str) + "\n"
        try:
            with self._lock, self.path.open("a") as f:
                f.write(line)
        except OSError:
            pass

    def program_gauges(self) -> Dict[str, Any]:
        """Ledger-derived gauges for the live /metrics exporter: one set per
        compiled program label (latest record wins), plus the total count —
        the ledger's headline numbers without re-reading programs.jsonl."""
        with self._lock:
            recs = list(self.records)
        out: Dict[str, Any] = {"programs/recorded": len(recs)}
        latest: Dict[str, Dict[str, Any]] = {}
        for r in recs:
            latest[str(r.get("label", "?"))] = r
        for label, r in latest.items():
            for key in ("flops", "bytes_accessed", "peak_bytes", "compile_s"):
                if r.get(key) is not None:
                    out[f"program/{label}/{key}"] = r[key]
        return out


_NULL_LEDGER = ProgramLedger(None)
_LEDGER: ProgramLedger = _NULL_LEDGER
# Geometry noted by layers that know it at trace time (parallel/pop_eval.py
# publishes its pop/member_batch/sharding layout while the enclosing step is
# being lowered); merged into the next record at the compile site, which
# only knows (m, r).
_GEOMETRY_CONTEXT: Dict[str, Any] = {}


def set_ledger(ledger: Optional[ProgramLedger]) -> ProgramLedger:
    """Install the process-global ledger (``None`` → disabled). Returns it."""
    global _LEDGER
    _LEDGER = ledger if ledger is not None else _NULL_LEDGER
    return _LEDGER


def get_ledger() -> ProgramLedger:
    return _LEDGER


def note_program_geometry(**attrs: Any) -> None:
    """Merge geometry facts into the context attached to the *next* ledger
    records. Called at jax trace time from layers (pop_eval) that know the
    sharding layout the compile site can't see."""
    _GEOMETRY_CONTEXT.update(attrs)


def program_record(
    *,
    site: str,
    label: str,
    lowered: Any = None,
    compiled: Any = None,
    geometry: Optional[Dict[str, Any]] = None,
    chain: int = 1,
    lowering_s: Optional[float] = None,
    compile_s: Optional[float] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Assemble one ledger record from a Lowered/Compiled pair.

    Pure assembly — no ledger write, no registry side effects (that's
    :func:`record_compile`). Tolerates partial inputs: a record from a
    backend without memory_analysis still carries cost + argument bytes,
    with ``peak_bytes_source`` saying how the estimate degraded.
    """
    # Consume the noted context: it describes the program just traced (the
    # lowering that preceded this record). Clearing prevents a stale layout
    # from one compile leaking into records of later, unrelated programs.
    global _GEOMETRY_CONTEXT
    noted, _GEOMETRY_CONTEXT = _GEOMETRY_CONTEXT, {}
    rec: Dict[str, Any] = {
        "ts": time.time(),
        "site": site,
        "label": label,
        "chain": int(chain),
        "geometry": {**noted, **(geometry or {})},
        "lowering_s": round(lowering_s, 4) if lowering_s is not None else None,
        "compile_s": round(compile_s, 4) if compile_s is not None else None,
    }
    if lowered is not None:
        rec.update(stablehlo_stats(lowered))
    if compiled is not None:
        rec.update(normalize_cost_analysis(compiled))
        mem = normalize_memory_analysis(compiled)
        flat = _flat_avals(compiled)
        arg_bytes = sum(_aval_bytes(a) for a in flat) if flat is not None else None
        rec["argument_bytes"] = arg_bytes
        if mem is not None:
            rec.update(mem)
            rec["peak_bytes_source"] = "memory_analysis"
        else:
            # arguments-only floor: params must at least be resident
            rec["peak_bytes"] = arg_bytes
            rec["peak_bytes_source"] = "arguments_only" if arg_bytes else None
        rec["donation"] = donation_audit(compiled)
        # cross-device collective traffic of the partitioned module (empty
        # on single-device programs: zero ops, zero bytes — still recorded,
        # so "no collectives" is a stated fact, not a missing field)
        rec.update(collective_stats(compiled))
    if rec.get("flops") and rec.get("bytes_accessed"):
        rec["intensity"] = rec["flops"] / rec["bytes_accessed"]
    # device identity, read lazily and only if a backend already exists —
    # this module must never trigger a jax import or backend init
    try:
        import sys

        if "jax" in sys.modules:
            from .multihost import jax_backend_initialized

            if jax_backend_initialized():
                import jax

                d = jax.devices()[0]
                rec["platform"] = d.platform
                rec["device_kind"] = getattr(d, "device_kind", None)
                rec["n_devices"] = len(jax.devices())
    except Exception:
        pass
    if extra:
        rec.update(extra)
    return rec


def record_compile(**kwargs: Any) -> Dict[str, Any]:
    """Build a program record, write it to the installed ledger, and surface
    the compiler's peak as an ``obs/`` gauge (→ next ``metrics.jsonl`` row).
    With the tracer enabled, also write the program's op → scope table
    (:func:`scope_table`) to ``scopes/<label>.json`` beside the ledger and
    name it in the record, and count the ops as large as the KV cache, the
    recurrent states or the head a generator noted (:func:`kv_cache_whole_ops`
    on ``kv_cache_shape``, ``recurrent_state_shape`` and ``lm_head_shape``:
    the stack of the layers' states, one layer's, with or without the member
    axis; the head's one kernel). The one call every compile site makes;
    the trainer's sites pass the compile's provenance
    (:meth:`CompileProvenance.fields`) as ``extra``. Never raises."""
    try:
        rec = program_record(**kwargs)
    except Exception:
        return {}
    ledger = get_ledger()
    try:
        from .trace import get_tracer

        compiled = kwargs.get("compiled")
        if compiled is not None and ledger.enabled and get_tracer().enabled:
            # its own span: the part of the record only a traced run pays for
            with get_tracer().span("scope_table"):
                table = scope_table(compiled)
                if table:
                    rel = Path("scopes") / f"{rec['label']}.json"
                    path = ledger.path.parent / rel
                    path.parent.mkdir(parents=True, exist_ok=True)
                    path.write_text(json.dumps(table, sort_keys=True))
                    rec["scope_table"] = str(rel)
                for carried in ("kv_cache", "recurrent_state", "lm_head"):
                    shape = rec["geometry"].get(f"{carried}_shape")
                    if shape:
                        rec[f"{carried}_whole_ops"] = kv_cache_whole_ops(compiled, shape)
    except Exception:
        pass
    ledger.write(rec)
    try:
        from .metrics import get_registry

        if rec.get("peak_bytes") is not None:
            get_registry().gauge("program_peak_bytes", rec["peak_bytes"])
    except Exception:
        pass
    return rec


def load_programs(path: Union[str, Path]) -> list:
    """Ledger records from ``programs.jsonl`` (or a run dir containing one),
    in file order; unparseable lines skipped, missing file → ``[]``."""
    p = Path(path)
    if p.is_dir():
        p = p / "programs.jsonl"
    if not p.exists():
        return []
    out = []
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "site" in rec:
            out.append(rec)
    return out
