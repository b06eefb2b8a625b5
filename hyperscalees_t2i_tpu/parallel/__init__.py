"""Distributed / parallel layer: meshes, collectives, population sharding.

TPU-native replacement for the reference's NCCL shim
(``/root/reference/VAR_models/dist.py`` — SURVEY.md §5.8) plus the
population/data/tensor parallelism the reference lacks (SURVEY.md §2.2).

Axis conventions (and deliberate omissions):

- ``pop`` — ES population members; this is the framework's data
  parallelism (each device evaluates whole models, only [pop, B] score
  rows cross ICI — ``pop_eval.py``).
- ``data`` — the intra-member image batch, so small populations still fill
  a slice.
- ``tp`` — tensor parallelism for serving/eval of one large model
  (``tp.py``, GSPMD weight shardings).
- sequence parallelism — ``ops/ring_attention.py`` (exact attention with
  the sequence sharded; K/V ring over ``ppermute``).
- pipeline and expert parallelism are deliberately NOT implemented:
  every supported generator fits on one chip (pp's bubble overhead buys
  nothing when pop-DP already scales perfectly at zero dependency depth),
  and no family has MoE layers for ep to shard.
"""

from .mesh import (
    DATA_AXIS,
    POP_AXIS,
    TP_AXIS,
    gcd_pop_data_mesh,
    initialize_multihost,
    local_pop,
    make_mesh,
    pop_sharding,
    replicated,
    shard_map,
)
from .collectives import (
    all_gather_ragged,
    all_gather_tree,
    barrier,
    fmt_metric_vals,
    host_allgather_rows,
    host_scalar_allgather,
    host_scalar_allmean,
    is_master,
    master_only,
    pmean_tree,
    ppermute_ring,
    process_count,
    process_rank,
    psum_tree,
)
from .pop_eval import make_population_evaluator
from .pop_update import make_sharded_es_update, pop_shard_update_plan
from .tp import (
    FAMILY_TP_RULES,
    count_tp_sharded,
    shard_params_tp,
    tp_sharding_tree,
)

__all__ = [
    "POP_AXIS",
    "DATA_AXIS",
    "TP_AXIS",
    "initialize_multihost",
    "make_mesh",
    "gcd_pop_data_mesh",
    "pop_sharding",
    "replicated",
    "local_pop",
    "psum_tree",
    "pmean_tree",
    "all_gather_tree",
    "all_gather_ragged",
    "ppermute_ring",
    "process_rank",
    "process_count",
    "is_master",
    "master_only",
    "barrier",
    "fmt_metric_vals",
    "host_allgather_rows",
    "host_scalar_allgather",
    "host_scalar_allmean",
    "make_population_evaluator",
    "make_sharded_es_update",
    "pop_shard_update_plan",
    "FAMILY_TP_RULES",
    "tp_sharding_tree",
    "shard_params_tp",
    "count_tp_sharded",
]
