"""In-program collective helpers: the TPU-native answer to the reference's
NCCL wrapper (``/root/reference/VAR_models/dist.py``, full inventory in
SURVEY.md §2.2/§5.8).

The reference exposes process-level ``allreduce`` / ``allgather`` /
``allgather_diff_shape`` / ``broadcast`` / ``barrier`` over NCCL. On TPU these
become *named-axis collectives inside a jitted program* — XLA lowers them to
ICI/DCN all-reduce/all-gather — plus a small set of host-level helpers
(process rank, master-only, cross-host barrier) for the bits that genuinely
live outside the compiled step (checkpoint writes, logging).
"""

from __future__ import annotations

import functools
import itertools
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, TypeVar

import jax
import jax.numpy as jnp

T = TypeVar("T")
Pytree = Any


# --------------------------------------------------------------------------
# In-graph collectives (use inside shard_map bodies, named axis in scope)
# --------------------------------------------------------------------------

def psum_tree(tree: Pytree, axis_name: str) -> Pytree:
    """All-reduce-sum every leaf over a named mesh axis (dist.py:97 allreduce)."""
    return jax.tree_util.tree_map(lambda x: jax.lax.psum(x, axis_name), tree)


def pmean_tree(tree: Pytree, axis_name: str) -> Pytree:
    """All-reduce-mean — the reference's ``dist_fmt_vals`` metric aggregation
    (dist.py:159-168) done in-graph instead of via host gathers."""
    return jax.tree_util.tree_map(lambda x: jax.lax.pmean(x, axis_name), tree)


def all_gather_tree(tree: Pytree, axis_name: str, *, axis: int = 0) -> Pytree:
    """Concatenating all-gather of every leaf (dist.py:109 allgather)."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.all_gather(x, axis_name, axis=axis, tiled=True), tree
    )


def all_gather_ragged(
    x: jax.Array, length: jax.Array, max_len: int, axis_name: str
) -> Tuple[jax.Array, jax.Array]:
    """Ragged all-gather: shards hold a variable-length prefix of a padded
    buffer; gather both data and true lengths.

    The reference pads CPU tensors to the max batch then slices back
    (``allgather_diff_shape``, dist.py:122-146). Under jit, shapes are static,
    so the idiom inverts: callers keep ``x`` padded to ``max_len`` along axis
    0 with ``length`` valid rows, and downstream consumers mask. Returns
    ``(gathered [n_shards, max_len, ...], lengths [n_shards])``.
    """
    if x.shape[0] != max_len:
        pad = [(0, max_len - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    data = jax.lax.all_gather(x, axis_name)  # [n_shards, max_len, ...]
    lens = jax.lax.all_gather(length, axis_name)  # [n_shards]
    return data, lens


def ppermute_ring(x: jax.Array, axis_name: str, *, shift: int = 1) -> jax.Array:
    """Ring shift along a named axis — the building block for ring attention
    and other neighbor-exchange schedules (used by ops/ring_attention)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


# --------------------------------------------------------------------------
# Host-level helpers (outside jit; multi-process runs)
# --------------------------------------------------------------------------

def process_rank() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_master() -> bool:
    """dist.py:66 ``is_master`` — process 0 owns logging/checkpoint writes."""
    return jax.process_index() == 0


def master_only(fn: Callable[..., T]) -> Callable[..., Optional[T]]:
    """Decorator: run only on process 0 (dist.py:171-184 ``master_only``)."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if is_master():
            return fn(*args, **kwargs)
        return None

    return wrapper


# --------------------------------------------------------------------------
# Host-gather transport selection
#
# multihost_utils' gathers/barriers run a *compiled* cross-process program,
# and XLA's CPU backend cannot build one ("Multiprocess computations aren't
# implemented on the CPU backend") — which would leave every
# host-level agreement path (metric means, the coordinated-commit vote, the
# desync fingerprint, preemption broadcast) untestable on the 2-proc CPU rig
# the chaos tests and CI run on. The jax.distributed coordination service's
# key-value store works on every backend with zero device involvement, so
# host gathers route through it on CPU (override: HYPERSCALEES_HOST_GATHER=
# {kv,xla}). Payloads here are tiny — scalars, 32-byte digests, [pop, B]
# float32 reward rows — so transport efficiency is irrelevant; correctness
# and availability are the whole game.
# --------------------------------------------------------------------------

_KV_SEQ = itertools.count()
_BARRIER_SEQ = itertools.count()

# Elastic membership (resilience/elastic.py): once a hard-failed host has
# been voted out, every later host gather is scoped to the surviving ranks.
# None = every process is live (the default, zero-cost path).
_LIVE_RANKS: "Optional[Tuple[int, ...]]" = None


class GatherTimeout(RuntimeError):
    """A host-level KV gather timed out waiting on peer rows — the signature
    of a hard-failed (or pathologically slow) host. Carries enough identity
    for the elastic roll-call (and a human reading stderr) to act on it:
    the gather ``seq`` (every process issues gathers in the same
    deterministic order, so all survivors observe the SAME seq), the waiting
    ``rank``, and ``missing`` — which ranks' keys never appeared. A dead
    host and a slow host look identical here; ``resilience/elastic.py``'s
    roll-call is what tells them apart."""

    def __init__(self, *, seq: int, rank: int, missing: "List[int]",
                 timeout_ms: int, cause: Optional[BaseException] = None):
        self.seq = int(seq)
        self.rank = int(rank)
        self.missing = sorted(int(r) for r in missing)
        self.timeout_ms = int(timeout_ms)
        super().__init__(
            f"host gather hg{self.seq} timed out on rank {self.rank}: no "
            f"key from rank(s) {self.missing} within {self.timeout_ms} ms — "
            "dead host or straggler beyond the KV deadline (elastic "
            "roll-call arbitrates)"
            + (f"; first error: {cause}" if cause is not None else "")
        )


def set_live_ranks(ranks: "Optional[Sequence[int]]") -> None:
    """Scope every later host gather to ``ranks`` (elastic survivor
    continuation). ``None`` restores all-processes. Must include this
    process's own rank; only meaningful on the KV transport — the XLA
    transport's ``process_allgather`` cannot address a rank subset."""
    global _LIVE_RANKS
    if ranks is None:
        _LIVE_RANKS = None
        return
    live = tuple(sorted(int(r) for r in ranks))
    if jax.process_index() not in live:
        raise ValueError(
            f"live rank set {list(live)} does not include this process "
            f"(rank {jax.process_index()})"
        )
    if len(live) < jax.process_count() and not _use_kv_transport():
        raise RuntimeError(
            "elastic membership (a live-rank subset) requires the KV host-"
            "gather transport; the XLA transport gathers over every process "
            "(set HYPERSCALEES_HOST_GATHER=kv, or use "
            "--elastic_action checkpoint_exit and relaunch)"
        )
    _LIVE_RANKS = live


def live_ranks() -> "List[int]":
    """Ranks participating in host gathers (all processes unless elastic
    continuation shrank the membership)."""
    if _LIVE_RANKS is not None:
        return list(_LIVE_RANKS)
    return list(range(jax.process_count()))


def live_count() -> int:
    return len(_LIVE_RANKS) if _LIVE_RANKS is not None else jax.process_count()


def _use_kv_transport() -> bool:
    mode = os.environ.get("HYPERSCALEES_HOST_GATHER", "").strip().lower()
    if mode in ("kv", "xla"):
        return mode == "kv"
    return jax.default_backend() == "cpu"


def _kv_client():
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "multi-process host gather requested but jax.distributed is not "
            "initialized (no coordination-service client) — launch through "
            "initialize_multihost/--coordinator"
        )
    return client


def kv_client():
    """The coordination-service KV client (public alias — the elastic
    roll-call posts its liveness/vote keys through the same store the
    gathers ride)."""
    return _kv_client()


# Compile-grace deadline: a gather issued in a COMPILE-BEARING epoch waits
# on peers that are legitimately still compiling the same program — with a
# short failure-detection deadline (chaos rigs / preemptible fleets set
# HYPERSCALEES_KV_TIMEOUT_MS to seconds), the fastest-compiling host would
# otherwise declare its slower peers dead at the very first gather. The
# trainer flips this on for epochs where it compiled (every host compiles
# the same geometry at the same epoch, so "I compiled" ⇒ "my peers are
# compiling") and off for steady-state epochs, where the short deadline is
# the whole point.
_GATHER_GRACE = False


def set_gather_grace(on: bool) -> None:
    global _GATHER_GRACE
    _GATHER_GRACE = bool(on)


def _kv_grace_ms() -> int:
    v = os.environ.get("HYPERSCALEES_KV_COMPILE_GRACE_MS", "").strip()
    try:
        return int(v) if v else 600_000
    except ValueError:
        return 600_000


def _kv_timeout_ms() -> int:
    v = os.environ.get("HYPERSCALEES_KV_TIMEOUT_MS", "").strip()
    try:
        base = int(v) if v else 600_000
    except ValueError:
        base = 600_000
    if _GATHER_GRACE:
        return max(base, _kv_grace_ms())
    return base


def _kv_probe_timeout_ms() -> int:
    """Short per-key probe after the first gather timeout: enumerate WHICH
    ranks' keys are missing (GatherTimeout's ``missing``) without paying the
    full deadline again per dead rank."""
    v = os.environ.get("HYPERSCALEES_KV_PROBE_MS", "").strip()
    try:
        return int(v) if v else 1_000
    except ValueError:
        return 1_000


def _kv_gather_rows(
    client, rank: int, ranks: "Sequence[int]", seq: int, data: bytes,
    length: int, timeout_ms: int,
) -> "List[bytes]":
    """The gather core (factored out of :func:`_kv_allgather_bytes` so the
    timeout→GatherTimeout path is unit-testable against a fake client):
    post this rank's row, read every rank's row in order. The first read
    that misses its deadline downgrades the remaining reads to the short
    probe timeout and the whole call raises :class:`GatherTimeout` naming
    every missing rank — a generic distributed-runtime error told an
    operator nothing about WHO is dead."""
    client.key_value_set(f"hyperscalees/hg{seq}/{rank}", data.hex())
    if seq >= 2:
        try:
            client.key_value_delete(f"hyperscalees/hg{seq - 2}/{rank}")
        except Exception:
            pass  # best-effort GC; stale rows are only a few bytes
    rows: Dict[int, bytes] = {}
    missing: List[int] = []
    first_err: Optional[BaseException] = None
    timeout = timeout_ms
    for r in ranks:
        try:
            rows[r] = bytes.fromhex(
                client.blocking_key_value_get(f"hyperscalees/hg{seq}/{r}", timeout)
            )
        except Exception as e:
            if first_err is None:
                first_err = e
                timeout = _kv_probe_timeout_ms()
            missing.append(r)
    if missing:
        raise GatherTimeout(
            seq=seq, rank=rank, missing=missing, timeout_ms=timeout_ms,
            cause=first_err,
        )
    out = [rows[r] for r in ranks]
    assert all(len(r) == length for r in out), "gather rows disagree on length"
    return out


def _kv_allgather_bytes(data: bytes, length: int) -> "List[bytes]":
    """Fixed-length byte gather over the coordination-service KV store.

    COLLECTIVE: every live process must call in the same order (the shared
    ``_KV_SEQ`` counter is what keys rendezvous on, exactly like XLA's
    launch-order contract). Each host deletes its own row from two rounds
    ago — by the time any host reaches round *s*, every peer has finished
    reading round *s−2* (reaching *s* requires reading all of *s−1*, whose
    rows peers only write after completing their *s−2* reads). Rows are
    read (and returned) for the LIVE ranks only — after an elastic
    membership shrink the dead ranks' keys would never appear. A read that
    exceeds the deadline raises :class:`GatherTimeout`."""
    return _kv_gather_rows(
        _kv_client(), jax.process_index(), live_ranks(), next(_KV_SEQ),
        data, length, _kv_timeout_ms(),
    )


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (dist.py:92 ``barrier``). No-op single-process.
    CPU multi-process uses the coordination-service barrier (unique id per
    call — the service rejects reuse) instead of the compiled
    ``sync_global_devices``, which XLA:CPU cannot build."""
    if live_count() > 1:
        if _use_kv_transport():
            if _LIVE_RANKS is not None and len(_LIVE_RANKS) < jax.process_count():
                # the coordination-service barrier waits for EVERY task —
                # with a shrunk membership the dead rank never arrives, so
                # survivors rendezvous through a tiny live-scoped gather
                _kv_allgather_bytes(b"\x01", 1)
                return
            _kv_client().wait_at_barrier(
                f"hyperscalees/{name}/{next(_BARRIER_SEQ)}", _kv_timeout_ms()
            )
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def host_scalar_allgather(scalars: Dict[str, float]) -> "Dict[str, Any]":
    """Cross-host gather of host-local scalars: every process gets
    ``{key: float32 ndarray[process_count]}`` (row *i* = process *i*'s
    value). Single-process: one-row arrays, no collective.

    This is THE per-epoch host reduction: the metric means, the cross-host
    θ-fingerprint agreement, and the preemption-flag broadcast all ride in
    one ``process_allgather`` rather than paying three. The wire dtype is
    float32 — NOT float64, which ``process_allgather`` would silently
    downcast under the default x32 mode — so a float32 device scalar
    (``theta_norm``, the desync fingerprint material) round-trips
    bit-exactly. Collective: every process must call it with the same key
    set (all processes run the identical training loop, so this holds by
    construction). Keys travel in sorted order so hosts agree on the gather
    layout.
    """
    import numpy as np

    keys = sorted(scalars)
    vec = np.asarray([float(scalars[k]) for k in keys], np.float32)
    if live_count() <= 1:
        gathered = vec[None]
    elif _use_kv_transport():
        rows = _kv_allgather_bytes(vec.tobytes(), vec.nbytes)
        gathered = np.stack([np.frombuffer(r, np.float32) for r in rows])
    else:
        from jax.experimental import multihost_utils

        gathered = np.asarray(multihost_utils.process_allgather(vec))
        gathered = gathered.reshape(jax.process_count(), len(keys))
    return {k: gathered[:, i] for i, k in enumerate(keys)}


def host_scalar_allmean(scalars: Dict[str, float]) -> Dict[str, float]:
    """Cross-host mean of host-local scalar metrics (no-op single-process).

    Logged numbers must be *global*, not whichever host happened to own the
    write: per-host wall-clock figures (``step_time_s``, ``images_per_sec``)
    genuinely differ across a pod, and reward stats are only global as long
    as the evaluator all-gathers scores in-graph — reducing them here makes
    that a guarantee of the logging layer instead of an accident of the
    current ``pop_eval`` design. Built on :func:`host_scalar_allgather`
    (same collective contract)."""
    if live_count() <= 1:
        return dict(scalars)
    return {k: float(v.mean()) for k, v in host_scalar_allgather(scalars).items()}


def host_allgather_bytes(data: bytes, length: int) -> "list[bytes]":
    """Gather one fixed-length byte blob per process (padded/truncated to
    ``length``); every process receives all blobs in rank order. The
    transport for the coordinated-commit digest vote (resilience/coord.py):
    a sha256 digest is 32 bytes — one tiny collective per checkpoint.
    Single-process: ``[data]`` unchanged semantics, no collective."""
    import numpy as np

    buf = np.zeros(length, np.uint8)
    raw = np.frombuffer(data[:length], np.uint8)
    buf[: raw.size] = raw
    if live_count() <= 1:
        rows = buf[None]
    elif _use_kv_transport():
        return _kv_allgather_bytes(buf.tobytes(), length)
    else:
        from jax.experimental import multihost_utils

        rows = np.asarray(multihost_utils.process_allgather(buf))
        rows = rows.reshape(jax.process_count(), length)
    return [bytes(rows[i].tobytes()) for i in range(rows.shape[0])]


def host_allgather_rows(arrays: Dict[str, Any]) -> Dict[str, Any]:
    """Cross-host row concatenation: every process passes a dict of
    same-dtype arrays whose leading axis is its local row slice (identical
    shapes on every host), and every process receives ``{key: [n_proc ·
    rows, ...]}`` concatenated in rank order, bit-exactly.

    This is THE pod fitness gather of host-sharded population evaluation
    (EGGROLL's "only fitness crosses hosts"): each host contributes its
    [lpop, B] reward rows, every host reassembles the identical full
    [pop, B] matrix, so every host computes the identical θ update from its
    own replicated program. Every key's bytes are packed into ONE blob per
    process (shapes/dtypes are identical everywhere and keys travel in
    sorted order, so every host agrees on the layout) and gathered in a
    single round — per-key gathers would put len(arrays) sequential
    cross-host round-trips on the epoch hot path. Bytes travel raw (KV
    transport) or as uint8 (XLA transport) — float32 rows round-trip
    bit-for-bit either way. Single-process: identity (no collective).
    Collective contract as above: same call order, same key set, same
    shapes on every process.
    """
    import numpy as np

    if live_count() <= 1 or not arrays:
        return {k: np.asarray(v) for k, v in arrays.items()}
    keys = sorted(arrays)
    local = {k: np.ascontiguousarray(np.asarray(arrays[k])) for k in keys}
    blob = b"".join(local[k].tobytes() for k in keys)
    if _use_kv_transport():
        rows = _kv_allgather_bytes(blob, len(blob))
    else:
        from jax.experimental import multihost_utils

        g = np.asarray(
            multihost_utils.process_allgather(np.frombuffer(blob, np.uint8))
        ).reshape(jax.process_count(), len(blob))
        rows = [g[i].tobytes() for i in range(jax.process_count())]
    out = {}
    offset = 0
    for k in keys:
        a = local[k]
        out[k] = np.concatenate([
            np.frombuffer(r[offset:offset + a.nbytes], a.dtype).reshape(a.shape)
            for r in rows
        ])
        offset += a.nbytes
    return out


def host_flag_any(flag: bool) -> bool:
    """True on every process iff ANY process passed True — the host-level
    OR underneath preemption broadcast when no scalar gather is already in
    flight to piggyback on. Collective when multi-process."""
    if live_count() <= 1:
        return bool(flag)
    return bool(host_scalar_allgather({"flag": 1.0 if flag else 0.0})["flag"].any())


def fmt_metric_vals(
    metrics: Dict[str, jax.Array], fmt: str = "%.4f"
) -> Dict[str, str]:
    """Host-side metric formatting after device_get — name kept close to the
    reference's ``dist_fmt_vals`` (dist.py:159-168) for discoverability."""
    import numpy as np

    return {k: fmt % float(np.mean(np.asarray(v))) for k, v in metrics.items()}
