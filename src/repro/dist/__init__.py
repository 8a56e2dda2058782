"""Distributed substrate: logical-axis sharding, mesh utilities, cross-process
timer reductions, and pipeline parallelism.

The package mirrors the paper's scaling story (Sec. 1: timing infrastructure
"for large-scale simulations ... distributed over many processors"): model code
annotates tensors with *logical* axis names, :mod:`repro.dist.sharding` maps
them onto physical mesh axes, and :mod:`repro.dist.stragglers` aggregates
per-host step walltimes from the timer database — the Cactus-style
cross-process timer reduction that lets a run profile itself and adapt.

Modules
-------
``sharding``   logical-axis rules -> ``PartitionSpec``/``NamedSharding``
``context``    ambient (mesh, rules) context + ``constrain`` annotations
``meshutil``   local/CI-friendly device-mesh construction + eviction rebuild
               and per-stage pipeline submeshes
``stragglers`` cross-host step-time reduction + slow-host detection
``pipeline``   GPipe forward + 1F1B training schedules, microbatch/stage plans

Acting on what the reduction finds — rebalancing microbatch plans, evicting
hosts, rebuilding meshes — is orchestrated by :mod:`repro.adapt`.
"""

from .context import constrain, current_sharding, use_sharding
from .meshutil import local_mesh, pipeline_submeshes, remove_host
from .pipeline import MicrobatchPlan, PipelineStep, StagePlan, phase_ticks, pipeline_step
from .sharding import DEFAULT_RULES, FSDP_RULES, Axes, ShardingRules, spec_for, tree_shardings
from .stragglers import LocalTransport, StragglerDetector, StragglerReport


__all__ = [
    "Axes",
    "ShardingRules",
    "DEFAULT_RULES",
    "FSDP_RULES",
    "spec_for",
    "tree_shardings",
    "use_sharding",
    "current_sharding",
    "constrain",
    "local_mesh",
    "pipeline_submeshes",
    "remove_host",
    "MicrobatchPlan",
    "PipelineStep",
    "StagePlan",
    "phase_ticks",
    "pipeline_step",
    "LocalTransport",
    "StragglerDetector",
    "StragglerReport",
]
