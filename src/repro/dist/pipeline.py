"""Pipeline parallelism over one mesh axis: forward-only GPipe and 1F1B.

``gpipe_forward`` places consecutive layer stages on consecutive devices along
a mesh axis and streams microbatches through them: at tick ``t`` stage 0
ingests microbatch ``t`` while every other stage works on the activation its
predecessor shipped via ``ppermute`` at tick ``t-1``.  After
``n_micro + n_stages - 1`` ticks the last stage has emitted every microbatch.
It is the forward-only schedule (no gradients); the
bubble fraction is ``(n_stages - 1) / (n_micro + n_stages - 1)``, so more
microbatches amortize the fill/drain cost exactly as in the GPipe paper.

:class:`PipelineStep` / :func:`pipeline_step` add the training schedule: a
**1F1B** (one-forward-one-backward) tick loop that returns the loss *and*
per-stage parameter gradients.  The schedule runs two counter-rotating
``ppermute`` rings — activations forward, activation-gradients backward —
driven by one global tick clock ``t``:

* stage ``d`` runs the *forward* of microbatch ``m`` at tick ``m + d``;
* stage ``d`` runs the *backward* of microbatch ``m`` at tick
  ``m + 2S - 1 - d`` (``S`` = pipeline depth), i.e. the loss gradient enters
  the last stage one tick after that microbatch's forward leaves it.

Ticks ``[0, S)`` are pure **warmup** (forward fill), ticks ``[S, M + S - 1)``
are **steady state** — every stage performs exactly one forward and one
backward micro-step per tick — and ticks ``[M + S - 1, M + 2S - 1)`` are
**cooldown** (backward drain).  :func:`phase_ticks` exposes these ranges and
:class:`PipelineStep` can execute them as three separately dispatched
segments so a launcher can time each phase (``phase_cb``).

Memory is the 1F1B win: each stage keeps only its *in-flight* stage-input
activations in a ring stash of ``min(2S, M)`` microbatch slots — sized by the
pipeline depth, **not** by ``n_micro`` (GPipe's forward-then-backward order
stashes all ``M``).  The backward recomputes the local stage group under
``jax.vjp`` from the stashed input (standard rematerialization), so the stash
holds one activation per in-flight microbatch and nothing else.

Fleet-level assignment objects sit above the schedules:

* :class:`MicrobatchPlan` — weighted largest-remainder split of the global
  microbatch count across data-parallel hosts (every active host >= 1).
* :class:`StagePlan` — the same apportionment over *pipeline stage depth*:
  ``n_layers`` contiguous layers split across stages by capacity weight
  (every stage >= 1 layer).  :meth:`StagePlan.pack` turns a flat per-layer
  parameter stack into the padded ``(n_stages * max_depth, ...)`` slot array
  (+ active mask) that :func:`pipeline_step` consumes, so the
  straggler-response controller can *move stage boundaries* at run time
  (``restage``) and the very next step executes the new split.
"""

from __future__ import annotations

import functools
from collections.abc import Callable, Iterable, Mapping
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "MicrobatchPlan",
    "PipelineStep",
    "StagePlan",
    "gpipe_forward",
    "phase_ticks",
    "pipeline_step",
]


# ---------------------------------------------------------------------------
# Weighted largest-remainder apportionment (shared by both plan types)
# ---------------------------------------------------------------------------

def _largest_remainder(weights: Mapping[int, float], total: int) -> dict[int, int]:
    """Apportion ``total`` indivisible units over ``weights`` proportionally.

    Every key receives at least one unit (one unit per key is reserved before
    the proportional split; the remainder is rounded largest-remainder with
    the key id as the deterministic tie-break).  The result satisfies the
    quota rule on the non-reserved part: each share is ``1 + floor(q)`` or
    ``1 + ceil(q)`` for quota ``q = extra * w / sum(w)`` — the invariant the
    property tests in ``tests/test_properties.py`` pin.
    """
    keys = sorted(weights)
    if not keys:
        raise ValueError("cannot apportion over an empty weight map")
    if total < len(keys):
        raise ValueError(
            f"total={total} cannot cover {len(keys)} entries with >= 1 each"
        )
    total_w = sum(weights.values())
    extra = total - len(keys)  # one reserved per key
    quotas = {k: extra * weights[k] / total_w for k in keys}
    counts = {k: int(quotas[k]) for k in keys}
    leftover = extra - sum(counts.values())
    by_remainder = sorted(keys, key=lambda k: (counts[k] - quotas[k], k))
    for k in by_remainder[:leftover]:
        counts[k] += 1
    return {k: counts[k] + 1 for k in keys}


@dataclass
class MicrobatchPlan:
    """Weighted assignment of ``n_micro`` microbatches to data-parallel hosts.

    ``weights`` maps each active host to a positive weight; :meth:`shares`
    apportions the global microbatch count proportionally (largest-remainder
    rounding) with every active host guaranteed at least one microbatch, so a
    rebalanced host still participates until it is explicitly evicted.
    """

    n_micro: int
    weights: dict[int, float] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.n_micro < max(len(self.weights), 1):
            raise ValueError(
                f"n_micro={self.n_micro} cannot cover {len(self.weights)} hosts "
                f"with at least one microbatch each"
            )
        for host, w in self.weights.items():
            if w <= 0.0:
                raise ValueError(f"host {host} weight must be positive, got {w}")

    @classmethod
    def equal(cls, hosts: Iterable[int], n_micro: int) -> MicrobatchPlan:
        """Uniform plan over ``hosts`` (the pre-adaptation default)."""
        return cls(n_micro=n_micro, weights={int(h): 1.0 for h in hosts})

    @property
    def hosts(self) -> list[int]:
        return sorted(self.weights)

    def set_weight(self, host: int, weight: float) -> None:
        if host not in self.weights:
            raise ValueError(f"host {host} is not in the plan {self.hosts}")
        if weight <= 0.0:
            raise ValueError(f"weight must be positive, got {weight}")
        self.weights[host] = float(weight)

    def evict(self, host: int) -> None:
        """Remove ``host``; its share is re-apportioned among survivors."""
        if host not in self.weights:
            raise ValueError(f"host {host} is not in the plan {self.hosts}")
        if len(self.weights) <= 1:
            raise ValueError("cannot evict the last host in the plan")
        del self.weights[host]

    def shares(self) -> dict[int, int]:
        """{host: microbatch count}; counts sum to ``n_micro``, each >= 1."""
        if not self.weights:
            raise ValueError("plan has no hosts")
        return _largest_remainder(self.weights, self.n_micro)

    def retarget(self, hosts: Iterable[int]) -> MicrobatchPlan:
        """Re-apportion onto a different host set (restore into an N→M
        topology).  Hosts present in both keep their learned capacity
        weights; new hosts enter at the carried mean weight, so a restored
        fleet neither punishes newcomers nor forgets which survivors were
        derated.  The largest-remainder :meth:`shares` then re-splits the
        same ``n_micro`` across the new set."""
        hosts = [int(h) for h in hosts]
        if not hosts:
            raise ValueError("cannot retarget onto an empty host set")
        mean = sum(self.weights.values()) / len(self.weights)
        return MicrobatchPlan(
            n_micro=self.n_micro,
            weights={h: float(self.weights.get(h, mean)) for h in hosts},
        )

    def share(self, host: int) -> int:
        return self.shares()[host]


@dataclass
class StagePlan:
    """Weighted split of ``n_layers`` contiguous layers across pipeline stages.

    The stage-depth analogue of :class:`MicrobatchPlan`: ``weights`` maps each
    pipeline stage (rank along the pipeline mesh axis) to a positive capacity
    weight, and :meth:`depths` apportions the layer count proportionally
    (largest-remainder, every stage >= 1 layer).  The straggler-response
    controller derates a slow stage-owner's weight (``restage`` action) so the
    stage boundary moves and the slow device runs fewer layers per microbatch.

    :meth:`pack` / :meth:`unpack` translate between the flat per-layer
    parameter stack and the padded per-stage slot layout
    (``n_stages * max_depth`` rows + active mask) that :func:`pipeline_step`
    executes, so a launcher applies a restage by simply re-packing before the
    next step.
    """

    n_layers: int
    weights: dict[int, float] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if not self.weights:
            raise ValueError("StagePlan needs at least one stage")
        if self.n_layers < len(self.weights):
            raise ValueError(
                f"n_layers={self.n_layers} cannot cover {len(self.weights)} "
                f"stages with at least one layer each"
            )
        for stage, w in self.weights.items():
            if w <= 0.0:
                raise ValueError(f"stage {stage} weight must be positive, got {w}")

    @classmethod
    def equal(cls, stages: Iterable[int], n_layers: int) -> StagePlan:
        """Uniform plan over ``stages`` (the pre-adaptation default)."""
        return cls(n_layers=n_layers, weights={int(s): 1.0 for s in stages})

    @property
    def stages(self) -> list[int]:
        return sorted(self.weights)

    @property
    def n_stages(self) -> int:
        return len(self.weights)

    def set_weight(self, stage: int, weight: float) -> None:
        if stage not in self.weights:
            raise ValueError(f"stage {stage} is not in the plan {self.stages}")
        if weight <= 0.0:
            raise ValueError(f"weight must be positive, got {weight}")
        self.weights[stage] = float(weight)

    def depths(self) -> dict[int, int]:
        """{stage: layer count}; counts sum to ``n_layers``, each >= 1."""
        return _largest_remainder(self.weights, self.n_layers)

    def retarget(self, stages: Iterable[int]) -> StagePlan:
        """Re-apportion the same ``n_layers`` onto a different stage set
        (restore into an N→M pipeline).  Stages present in both keep their
        learned capacity weights; new stages enter at the carried mean, and
        :meth:`depths` re-splits the layer stack — the flat per-layer
        parameter checkpoint then :meth:`pack`\\ s into the new topology
        without any tensor surgery."""
        stages = [int(s) for s in stages]
        if not stages:
            raise ValueError("cannot retarget onto an empty stage set")
        if self.n_layers < len(stages):
            raise ValueError(
                f"n_layers={self.n_layers} cannot cover {len(stages)} stages"
            )
        mean = sum(self.weights.values()) / len(self.weights)
        return StagePlan(
            n_layers=self.n_layers,
            weights={s: float(self.weights.get(s, mean)) for s in stages},
        )

    def boundaries(self) -> dict[int, tuple[int, int]]:
        """{stage: [start, stop) layer range} in stage order."""
        depths = self.depths()
        out: dict[int, tuple[int, int]] = {}
        start = 0
        for stage in self.stages:
            out[stage] = (start, start + depths[stage])
            start += depths[stage]
        return out

    def max_depth(self) -> int:
        return max(self.depths().values())

    def pack(self, layer_params) -> tuple[Any, jax.Array]:
        """Pad a flat per-layer parameter pytree into pipeline slots.

        ``layer_params`` is any pytree whose leaves share a leading
        ``n_layers`` dimension (a bare ``(n_layers, ...)`` array or a
        transformer block stack).  Returns ``(packed, mask)``: every packed
        leaf has leading ``n_stages * max_depth`` where stage ``s`` owns the
        contiguous slot block ``[s * max_depth, (s+1) * max_depth)`` holding
        its layers front-aligned; ``mask`` is the matching boolean
        slot-activity vector (inactive slots are identity in the pipeline and
        receive zero gradient).  Padding makes unequal stage depths executable
        under the SPMD schedule, whose per-device blocks must be equal-sized.
        """
        lmax, rows = self._slot_rows()
        n_slots = self.n_stages * lmax
        index = jnp.asarray(rows)

        def _pack_leaf(leaf):
            if int(leaf.shape[0]) != self.n_layers:
                raise ValueError(
                    f"layer_params leaf has {leaf.shape[0]} layers, plan "
                    f"covers {self.n_layers}"
                )
            out = jnp.zeros((n_slots,) + tuple(leaf.shape[1:]), leaf.dtype)
            return out.at[index].set(leaf)

        packed = jax.tree.map(_pack_leaf, layer_params)
        mask = jnp.zeros((n_slots,), bool).at[index].set(True)
        return packed, mask

    def unpack(self, packed):
        """Gather the active slots of a packed pytree (e.g. per-slot
        gradients) back into the flat ``(n_layers, ...)`` layer order."""
        lmax, rows = self._slot_rows()
        index = jnp.asarray(rows)

        def _unpack_leaf(leaf):
            if int(leaf.shape[0]) != self.n_stages * lmax:
                raise ValueError(
                    f"packed leaf has {leaf.shape[0]} slots, plan packs to "
                    f"{self.n_stages * lmax}"
                )
            return leaf[index]

        return jax.tree.map(_unpack_leaf, packed)

    def _slot_rows(self) -> tuple[int, list[int]]:
        """``(max_depth, slot index of each flat layer in layer order)`` —
        one apportionment pass serves both pack() and unpack(), which sit on
        the per-step hot path (the live-restage re-pack)."""
        depths = self.depths()
        lmax = max(depths.values())
        rows: list[int] = []
        for i, stage in enumerate(self.stages):
            rows.extend(i * lmax + j for j in range(depths[stage]))
        return lmax, rows


# ---------------------------------------------------------------------------
# Forward-only (GPipe) schedule
# ---------------------------------------------------------------------------

def gpipe_forward(
    layer_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params: jax.Array,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str,
    n_micro: int,
) -> jax.Array:
    """Run ``x`` through stacked stages pipelined over ``mesh`` axis ``axis``.

    ``stage_params`` has a leading stage dimension (``n_stages, ...``); stage
    ``i`` computes ``layer_fn(stage_params[i], activation)`` and must preserve
    the activation's shape and dtype (homogeneous pipeline).  ``n_stages`` must
    be a multiple of the mesh axis size (each device runs a contiguous group of
    stages) and ``x.shape[0]`` a multiple of ``n_micro``.
    """
    n_stages = int(stage_params.shape[0])
    axis_size = int(mesh.shape[axis])
    if n_stages % axis_size != 0:
        raise ValueError(
            f"n_stages={n_stages} must be a multiple of mesh axis {axis!r} "
            f"size {axis_size}"
        )
    batch = int(x.shape[0])
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by n_micro={n_micro}")
    micro_batch = batch // n_micro
    micro_shape = (micro_batch,) + x.shape[1:]

    out_abstract = jax.eval_shape(
        layer_fn,
        jax.ShapeDtypeStruct(stage_params.shape[1:], stage_params.dtype),
        jax.ShapeDtypeStruct(micro_shape, x.dtype),
    )
    if out_abstract.shape != micro_shape or out_abstract.dtype != x.dtype:
        raise ValueError(
            f"layer_fn must preserve activation shape/dtype for pipelining; "
            f"got {out_abstract.shape}/{out_abstract.dtype} from "
            f"{micro_shape}/{x.dtype}"
        )

    shift = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    n_ticks = n_micro + axis_size - 1

    def pipelined(stages_local: jax.Array, x_full: jax.Array) -> jax.Array:
        stage_index = jax.lax.axis_index(axis)
        micro = x_full.reshape((n_micro,) + micro_shape)

        def run_local_stages(activation: jax.Array) -> jax.Array:
            def one_stage(act, w):
                return layer_fn(w, act), None

            result, _ = jax.lax.scan(one_stage, activation, stages_local)
            return result

        def tick(t, carry):
            inflight, outputs = carry
            feed = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(t, 0, n_micro - 1), keepdims=False
            )
            activation = jnp.where(stage_index == 0, feed, inflight)
            produced = run_local_stages(activation)
            # the last device commits microbatch t-(axis_size-1); earlier
            # devices (and warm-up ticks) leave the zero buffer untouched
            out_index = jnp.clip(t - (axis_size - 1), 0, n_micro - 1)
            commit = jnp.logical_and(t >= axis_size - 1, stage_index == axis_size - 1)
            current = jax.lax.dynamic_index_in_dim(outputs, out_index, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(commit, produced, current), out_index, 0
            )
            inflight = jax.lax.ppermute(produced, axis, shift)
            return inflight, outputs

        inflight0 = jnp.zeros(micro_shape, x.dtype)
        outputs0 = jnp.zeros((n_micro,) + micro_shape, x.dtype)
        _, outputs = jax.lax.fori_loop(0, n_ticks, tick, (inflight0, outputs0))
        # only the last device holds non-zero outputs; psum replicates them
        outputs = jax.lax.psum(outputs, axis)
        return outputs.reshape((batch,) + x.shape[1:])

    # P(axis) on the stage dimension leaves each device a contiguous
    # (n_stages // axis_size, ...) block of consecutive stages
    fn = jax.shard_map(
        pipelined, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(), check_vma=False
    )
    return fn(stage_params, x)


# ---------------------------------------------------------------------------
# 1F1B training schedule
# ---------------------------------------------------------------------------

def phase_ticks(n_micro: int, axis_size: int) -> dict[str, tuple[int, int]]:
    """The 1F1B global tick ranges: ``{phase: [start, stop)}``.

    *warmup* is the forward fill (no backward active anywhere), *steady* the
    one-forward-one-backward regime, *cooldown* the backward drain (no forward
    active anywhere).  The full schedule is ``n_micro + 2 * axis_size - 1``
    ticks; ranges may be empty (e.g. steady when ``n_micro < axis_size``).
    """
    s, m = int(axis_size), int(n_micro)
    return {
        "warmup": (0, s),
        "steady": (s, max(m + s - 1, s)),
        "cooldown": (max(m + s - 1, s), m + 2 * s - 1),
    }


def _leaf_key(tree) -> tuple:
    """Hashable (structure, shapes, dtypes) signature of a pytree."""
    leaves, treedef = jax.tree.flatten(tree)
    return (
        str(treedef),
        tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves),
    )


class PipelineStep:
    """Reusable 1F1B pipeline train step over one mesh axis.

    Builds (and caches, per input shape/dtype signature) the jitted tick
    runner once; every ``__call__`` then executes the schedule and returns
    ``(loss, grads)`` where ``loss`` is the mean of the per-microbatch loss
    and ``grads`` matches ``stage_params``'s structure (per-slot parameter
    gradients of that mean loss).

    ``stage_params`` may be a bare ``(n_slots, ...)`` array or any pytree
    whose leaves share the leading slot dimension (e.g. a transformer block
    stack) — :meth:`StagePlan.pack` produces either.

    Parameters
    ----------
    layer_fn:
        ``layer_fn(slot_params, activation) -> activation`` — must preserve
        activation shape/dtype (homogeneous pipeline).  ``slot_params`` is
        one slot's slice of the ``stage_params`` pytree.
    loss_fn:
        ``loss_fn(final_activation, target_microbatch) -> scalar``; it is
        evaluated (and differentiated) on the last stage only.  ``None`` is
        allowed iff ``last_fn`` is given (the head then owns the loss).
    mesh / axis:
        The pipeline mesh axis.  The slot count must be a multiple of the
        axis size; each device runs a contiguous slot block.
    n_micro:
        Microbatch count ``M``; ``x.shape[0]`` must be divisible by it.
    first_fn / last_fn:
        Stage-pinning hooks for heterogeneous ends of the pipeline (both or
        neither).  ``first_fn(first_params, raw_microbatch) -> activation``
        runs pinned to stage 0 (the embedding: ``x`` then carries raw inputs,
        e.g. int32 tokens, and the activation shape/dtype is inferred from
        ``first_fn``); ``last_fn(last_params, activation, target_microbatch)
        -> scalar`` runs pinned to the final stage (norm + head + loss) and
        replaces ``loss_fn``.  ``__call__`` then takes ``first_params`` /
        ``last_params`` and returns ``(loss, (stage_grads, first_grads,
        last_grads))``.
    phase_cb:
        Optional ``phase_cb(name) -> context manager`` for
        ``warmup``/``steady``/``cooldown``.  When set, the schedule executes
        as three separately dispatched (and synchronized) segments with the
        callback's context open around each — the launcher hook that times
        phases as ``repro.timing`` scopes.  When unset the whole schedule is
        one fused dispatch.
    stage_spec:
        Optional ``PartitionSpec`` pytree (or prefix) for the packed stage
        parameters, composing per-stage tensor-parallel/FSDP sharding with
        the pipeline axis: every leaf spec's leading entry must be the
        pipeline ``axis`` (the slot dimension); trailing entries shard the
        parameter dimensions over the mesh's inner axes.  Defaults to
        ``P(axis)`` (stage-sharded, otherwise replicated).  Applied to both
        the stage params input and the gradient accumulator carry.
    """

    def __init__(
        self,
        layer_fn: Callable[[Any, jax.Array], jax.Array],
        loss_fn: Callable[[jax.Array, jax.Array], jax.Array] | None,
        *,
        mesh: Mesh,
        axis: str,
        n_micro: int,
        first_fn: Callable[[Any, jax.Array], jax.Array] | None = None,
        last_fn: Callable[[Any, jax.Array, jax.Array], jax.Array] | None = None,
        phase_cb: Callable[[str], object] | None = None,
        stage_spec: Any | None = None,
    ) -> None:
        if (first_fn is None) != (last_fn is None):
            raise ValueError(
                "first_fn and last_fn pin the pipeline's heterogeneous ends "
                "together: pass both or neither"
            )
        if loss_fn is None and last_fn is None:
            raise ValueError("loss_fn may only be None when last_fn is given")
        self.layer_fn = layer_fn
        self.loss_fn = loss_fn
        self.first_fn = first_fn
        self.last_fn = last_fn
        self.mesh = mesh
        self.axis = axis
        self.n_micro = int(n_micro)
        self.phase_cb = phase_cb
        self.stage_spec = stage_spec if stage_spec is not None else P(axis)
        self.axis_size = int(mesh.shape[axis])
        if self.n_micro < 1:
            raise ValueError(f"n_micro must be >= 1, got {n_micro}")
        self._runners: dict[tuple, Callable] = {}

    # -- public entry ---------------------------------------------------------
    def __call__(
        self,
        stage_params: Any,
        x: jax.Array,
        targets: jax.Array,
        stage_mask: jax.Array | None = None,
        *,
        first_params: Any = None,
        last_params: Any = None,
    ):
        s, m = self.axis_size, self.n_micro
        hooks = self.last_fn is not None
        if hooks and (first_params is None or last_params is None):
            raise ValueError(
                "first_params/last_params are required when first_fn/last_fn "
                "are set"
            )
        if not hooks and (first_params is not None or last_params is not None):
            raise ValueError(
                "first_params/last_params given but the step has no "
                "first_fn/last_fn"
            )
        leaves = jax.tree.leaves(stage_params)
        if not leaves:
            raise ValueError("stage_params has no array leaves")
        n_slots = int(leaves[0].shape[0])
        for leaf in leaves:
            if int(leaf.shape[0]) != n_slots:
                raise ValueError(
                    f"stage_params leaves disagree on the slot dimension: "
                    f"{leaf.shape[0]} != {n_slots}"
                )
        if n_slots % s != 0:
            raise ValueError(
                f"n_slots={n_slots} must be a multiple of mesh axis "
                f"{self.axis!r} size {s}"
            )
        batch = int(x.shape[0])
        if batch % m != 0:
            raise ValueError(f"batch {batch} not divisible by n_micro={m}")
        if int(targets.shape[0]) != batch:
            raise ValueError(
                f"targets leading dim {targets.shape[0]} != batch {batch}"
            )
        if stage_mask is None:
            stage_mask = jnp.ones((n_slots,), bool)
        elif stage_mask.shape != (n_slots,):
            raise ValueError(
                f"stage_mask shape {stage_mask.shape} != ({n_slots},)"
            )
        in_micro_shape = (batch // m,) + x.shape[1:]
        tmicro_shape = (batch // m,) + targets.shape[1:]
        if hooks:
            act_abs = jax.eval_shape(
                self.first_fn, first_params,
                jax.ShapeDtypeStruct(in_micro_shape, x.dtype),
            )
            if not hasattr(act_abs, "shape"):
                raise ValueError("first_fn must return a single array")
            micro_shape, act_dtype = tuple(act_abs.shape), act_abs.dtype
        else:
            micro_shape, act_dtype = in_micro_shape, x.dtype

        key = (
            _leaf_key(stage_params),
            _leaf_key(first_params), _leaf_key(last_params),
            x.shape, str(x.dtype), targets.shape, str(targets.dtype),
        )
        runner = self._runners.get(key)
        if runner is None:
            runner = self._build(
                n_slots, in_micro_shape, micro_shape, tmicro_shape,
                x.dtype, act_dtype, targets.dtype,
                stage_params, first_params, last_params,
            )
            self._runners[key] = runner

        micro = x.reshape((m,) + in_micro_shape)
        tmicro = targets.reshape((m,) + tmicro_shape)
        r = min(2 * s, m)
        zeros_like_stacked = (
            lambda tree, lead: jax.tree.map(
                lambda leaf: jnp.zeros(lead + tuple(leaf.shape), leaf.dtype), tree
            )
        )
        carry = (
            jnp.zeros((s,) + micro_shape, act_dtype),          # forward ring
            jnp.zeros((s,) + micro_shape, act_dtype),          # backward ring
            jnp.zeros((s, r) + micro_shape, act_dtype),        # input stash
            jnp.zeros((s, r) + micro_shape, act_dtype),        # loss-grad seeds
            jnp.zeros((s,), jnp.float32),                      # per-device loss
            jax.tree.map(                                      # per-slot grads
                lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), stage_params
            ),
            zeros_like_stacked(first_params, (s,)) if hooks else (),
            zeros_like_stacked(last_params, (s,)) if hooks else (),
        )
        if self.phase_cb is None:
            carry = runner(stage_params, stage_mask, first_params, last_params,
                           micro, tmicro, carry, 0, m + 2 * s - 1)
        else:
            for name, (t0, t1) in phase_ticks(m, s).items():
                if t1 <= t0:
                    continue
                with self.phase_cb(name):
                    carry = runner(stage_params, stage_mask, first_params,
                                   last_params, micro, tmicro, carry, t0, t1)
                    # synchronize inside the scope so the caliper window
                    # covers the phase's device work, not just its dispatch
                    jax.block_until_ready(carry[4])
        loss = jnp.sum(carry[4])  # only the last stage accumulated loss
        if not hooks:
            return loss, carry[5]
        # the pinned-stage accumulators are stacked over the pipeline axis;
        # only the pinned stage contributed non-zeros, so the sum extracts it
        first_grads = jax.tree.map(lambda a: jnp.sum(a, axis=0), carry[6])
        last_grads = jax.tree.map(lambda a: jnp.sum(a, axis=0), carry[7])
        return loss, (carry[5], first_grads, last_grads)

    # -- schedule construction -------------------------------------------------
    def _build(self, n_slots, in_micro_shape, micro_shape, tmicro_shape,
               x_dtype, act_dtype, t_dtype,
               stage_params, first_params, last_params):
        s, m = self.axis_size, self.n_micro
        r = min(2 * s, m)
        axis, layer_fn, loss_fn = self.axis, self.layer_fn, self.loss_fn
        first_fn, last_fn = self.first_fn, self.last_fn
        hooks = last_fn is not None
        fwd_ring = [(i, (i + 1) % s) for i in range(s)]
        bwd_ring = [(i, (i - 1) % s) for i in range(s)]

        slot_abs = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(tuple(leaf.shape[1:]), leaf.dtype),
            stage_params,
        )
        act_sds = jax.ShapeDtypeStruct(micro_shape, act_dtype)
        tgt_sds = jax.ShapeDtypeStruct(tmicro_shape, t_dtype)
        out_abstract = jax.eval_shape(layer_fn, slot_abs, act_sds)
        if out_abstract.shape != micro_shape or out_abstract.dtype != act_dtype:
            raise ValueError(
                f"layer_fn must preserve activation shape/dtype for "
                f"pipelining; got {out_abstract.shape}/{out_abstract.dtype} "
                f"from {micro_shape}/{act_dtype}"
            )
        if hooks:
            loss_abstract = jax.eval_shape(last_fn, last_params, act_sds, tgt_sds)
        else:
            loss_abstract = jax.eval_shape(loss_fn, act_sds, tgt_sds)
        if loss_abstract.shape != ():
            raise ValueError(
                f"loss_fn must return a scalar, got shape {loss_abstract.shape}"
            )

        def local(stages_local, mask_local, act):
            # inactive slots (StagePlan padding) are identity and therefore
            # contribute exactly zero gradient
            def one(a, wm):
                w, active = wm
                return jnp.where(active, layer_fn(w, a), a), None

            res, _ = jax.lax.scan(one, act, (stages_local, mask_local))
            return res

        def _masked_add(acc_tree, d_tree, flag):
            return jax.tree.map(
                lambda acc, d: acc + jnp.where(flag, d, jnp.zeros_like(d)),
                acc_tree, d_tree,
            )

        def shard_body(stage_params, stage_mask, first_params, last_params,
                       micro, tmicro, carry, t0, t1):
            d = jax.lax.axis_index(axis)
            is_first = d == 0
            is_last = d == s - 1

            def tick(t, c):
                recv_f, recv_b, stash, seed, loss_sum, gacc, fgacc, lgacc = c
                # ---- forward: microbatch t - d ----
                mf = t - d
                active_f = jnp.logical_and(mf >= 0, mf < m)
                mf_c = jnp.clip(mf, 0, m - 1)
                raw = jax.lax.dynamic_index_in_dim(micro, mf_c, keepdims=False)
                feed = first_fn(first_params, raw) if hooks else raw
                act_in = jnp.where(is_first, feed, recv_f)
                slot_f = jnp.mod(mf_c, r)
                cur = jax.lax.dynamic_index_in_dim(stash, slot_f, keepdims=False)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, jnp.where(active_f, act_in, cur), slot_f, 0
                )
                y = local(stage_params, stage_mask, act_in)
                # last stage: fold the loss in and stash its gradient seed for
                # the backward tick one step later
                tgt = jax.lax.dynamic_index_in_dim(tmicro, mf_c, keepdims=False)
                take_loss = jnp.logical_and(active_f, is_last)
                if hooks:
                    lm, (gm, glast) = jax.value_and_grad(
                        lambda yy, lp: last_fn(lp, yy, tgt), argnums=(0, 1)
                    )(y, last_params)
                    lgacc = _masked_add(
                        lgacc,
                        jax.tree.map(lambda g: g / m, glast),
                        take_loss,
                    )
                else:
                    lm, gm = jax.value_and_grad(lambda yy: loss_fn(yy, tgt))(y)
                loss_sum = loss_sum + jnp.where(take_loss, lm, 0.0) / m
                curs = jax.lax.dynamic_index_in_dim(seed, slot_f, keepdims=False)
                seed = jax.lax.dynamic_update_index_in_dim(
                    seed, jnp.where(take_loss, gm / m, curs), slot_f, 0
                )
                send_f = jax.lax.ppermute(y, axis, fwd_ring)
                # ---- backward: microbatch t - (2S - 1 - d) ----
                mb = t - (2 * s - 1 - d)
                active_b = jnp.logical_and(mb >= 0, mb < m)
                mb_c = jnp.clip(mb, 0, m - 1)
                slot_b = jnp.mod(mb_c, r)
                act_b = jax.lax.dynamic_index_in_dim(stash, slot_b, keepdims=False)
                g_seed = jax.lax.dynamic_index_in_dim(seed, slot_b, keepdims=False)
                g_in = jnp.where(is_last, g_seed, recv_b)
                # rematerialize the local stage group from the stashed input;
                # only the stage inputs are kept in-flight (the 1F1B stash)
                _, vjp = jax.vjp(
                    lambda w, a: local(w, stage_mask, a), stage_params, act_b
                )
                dw, dact = vjp(g_in)
                gacc = _masked_add(gacc, dw, active_b)
                if hooks:
                    # stage 0's activation gradient flows into the pinned
                    # first_fn (the embedding); recompute its vjp from the
                    # raw microbatch input
                    raw_b = jax.lax.dynamic_index_in_dim(
                        micro, mb_c, keepdims=False
                    )
                    _, vjp_first = jax.vjp(
                        lambda fp: first_fn(fp, raw_b), first_params
                    )
                    (dfp,) = vjp_first(dact)
                    fgacc = _masked_add(
                        fgacc, dfp, jnp.logical_and(active_b, is_first)
                    )
                send_b = jax.lax.ppermute(
                    jnp.where(active_b, dact, jnp.zeros_like(dact)),
                    axis, bwd_ring,
                )
                return (send_f, send_b, stash, seed, loss_sum, gacc,
                        fgacc, lgacc)

            (recv_f, recv_b, stash, seed, loss_sum, gacc, fgacc, lgacc) = carry
            head = lambda tree: jax.tree.map(lambda a: a[0], tree)
            c = (recv_f[0], recv_b[0], stash[0], seed[0], loss_sum[0], gacc,
                 head(fgacc), head(lgacc))
            c = jax.lax.fori_loop(t0, t1, tick, c)
            recv_f, recv_b, stash, seed, loss_sum, gacc, fgacc, lgacc = c
            unhead = lambda tree: jax.tree.map(lambda a: a[None], tree)
            return (recv_f[None], recv_b[None], stash[None], seed[None],
                    loss_sum[None], gacc, unhead(fgacc), unhead(lgacc))

        carry_specs = (P(axis), P(axis), P(axis), P(axis), P(axis),
                       self.stage_spec, P(axis), P(axis))
        smapped = jax.shard_map(
            shard_body,
            mesh=self.mesh,
            in_specs=(self.stage_spec, P(axis), P(), P(), P(), P(),
                      carry_specs, None, None),
            out_specs=carry_specs,
            check_vma=False,
        )

        @functools.partial(jax.jit, static_argnums=(7, 8))
        def run(stage_params, stage_mask, first_params, last_params,
                micro, tmicro, carry, t0, t1):
            return smapped(stage_params, stage_mask, first_params, last_params,
                           micro, tmicro, carry, t0, t1)

        return run


def pipeline_step(
    layer_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params: jax.Array,
    x: jax.Array,
    targets: jax.Array,
    *,
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str,
    n_micro: int,
    stage_mask: jax.Array | None = None,
    phase_cb: Callable[[str], object] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One-shot 1F1B step: ``(loss, per-slot grads)`` for ``x``/``targets``.

    Convenience wrapper over :class:`PipelineStep` (which hot loops should
    construct once and reuse — the jitted tick runner is cached on the
    instance, so a fresh ``pipeline_step`` call re-traces).
    """
    step = PipelineStep(
        layer_fn, loss_fn, mesh=mesh, axis=axis, n_micro=n_micro,
        phase_cb=phase_cb,
    )
    return step(stage_params, x, targets, stage_mask)
