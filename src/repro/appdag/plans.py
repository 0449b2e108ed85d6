"""Plan extractors: model config x parallelism axes -> per-step JobDAG.

Each extractor walks a ``ModelConfig`` plus a ``PlanAxes`` (DP/TP/PP/EP
sizes) and emits the communication DAG of one training step (or one
serving request) with compute nodes between the collectives, lowering
every logical collective through ``appdag.lowering``:

  ``dense_train_dag``    backward chain with TP activation-grad
                         all-reduces, inter-stage activation p2p, per-unit
                         DP gradient all-reduce, optimizer updates.
  ``moe_train_dag``      the dense skeleton plus, per MoE unit, the two
                         expert-parallel all-to-alls (combine-grad before
                         the unit's backward, dispatch-grad after) and the
                         expert-gradient all-reduce over the dp/ep replica
                         groups.
  ``pipeline_serve_dag`` GPipe-style pipelined prefill: the (stage x
                         microbatch) compute grid with per-boundary
                         activation p2p metaflows.
  ``ep_stage_dag``       one expert-parallel group's share of a pipeline
                         stage, per rank: routed, node-limited rail
                         all-to-alls whose every leg is its own metaflow.

Port-numbering convention (DESIGN.md §9): one fabric port per device,
``rank(pp_i, dp_i, tp_i) = port_base + (pp_i * dp + dp_i) * tp + tp_i``,
so a plan occupies the contiguous span ``[port_base, port_base + world)``
and the arrival mixer places jobs by choosing ``port_base``.  This is the
same "one contended port per participant" convention ``core/workload.py``
uses for mappers/reducers.

Sizes are in seconds-at-unit-capacity (flow size = transfer seconds at
full link rate, compute load = seconds), matching
``core/comm_schedule.py``.  All analytics are derived from the config
alone — no JAX import — so the extractors run anywhere the simulator does.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.appdag.lowering import add_lowered, lower_grouped, rail_all_to_all
from repro.appdag.routing import dispatch_bytes, route
from repro.configs.base import (ModelConfig, ShapeConfig, active_param_count,
                                n_units, param_count)
from repro.core.metaflow import JobDAG
from repro.roofline.analysis import HBM_BW, LINK_BW, PEAK_FLOPS


@dataclass(frozen=True)
class PlanAxes:
    """Parallelism degrees.  ``world = dp * tp * pp``; ``ep`` partitions
    each DP group into expert shards (``ep`` must divide ``dp``)."""

    dp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1

    def __post_init__(self) -> None:
        for ax, v in (("dp", self.dp), ("tp", self.tp), ("pp", self.pp),
                      ("ep", self.ep)):
            if v < 1:
                raise ValueError(f"{ax} must be >= 1, got {v}")
        if self.dp % self.ep:
            raise ValueError(f"ep={self.ep} must divide dp={self.dp}")

    @property
    def world(self) -> int:
        return self.dp * self.tp * self.pp

    # ----------------------------------------------------------- port maps
    def rank(self, pp_i: int, dp_i: int, tp_i: int, port_base: int = 0) -> int:
        return port_base + (pp_i * self.dp + dp_i) * self.tp + tp_i

    def dp_groups(self, pp_i: int, port_base: int = 0) -> list[tuple[int, ...]]:
        """One group per tp index at stage ``pp_i`` (gradient sync peers)."""
        return [tuple(self.rank(pp_i, d, t, port_base) for d in range(self.dp))
                for t in range(self.tp)]

    def tp_groups(self, pp_i: int, port_base: int = 0) -> list[tuple[int, ...]]:
        """One group per dp index at stage ``pp_i`` (activation sync peers)."""
        return [tuple(self.rank(pp_i, d, t, port_base) for t in range(self.tp))
                for d in range(self.dp)]

    def ep_groups(self, pp_i: int, port_base: int = 0) -> list[tuple[int, ...]]:
        """EP groups: each DP group split into ``dp/ep`` chunks of ``ep``."""
        out = []
        for g in self.dp_groups(pp_i, port_base):
            out.extend(tuple(g[c:c + self.ep])
                       for c in range(0, self.dp, self.ep))
        return out

    def ep_replica_groups(self, pp_i: int,
                          port_base: int = 0) -> list[tuple[int, ...]]:
        """Expert-gradient sync peers: same expert shard across the dp/ep
        EP chunks of one DP group."""
        reps = self.dp // self.ep
        out = []
        for g in self.dp_groups(pp_i, port_base):
            for j in range(self.ep):
                out.append(tuple(g[c * self.ep + j] for c in range(reps)))
        return out


# ------------------------------------------------------------ config math
def _embed_params(cfg: ModelConfig) -> int:
    return cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)


def unit_grad_bytes(cfg: ModelConfig) -> float:
    """bf16 gradient bytes of one scan unit (embeddings excluded)."""
    return 2.0 * (param_count(cfg) - _embed_params(cfg)) / n_units(cfg)


def unit_bwd_seconds(cfg: ModelConfig, tokens: float, world: int) -> float:
    """Roofline backward+recompute seconds for one unit's step share."""
    active = active_param_count(cfg) - _embed_params(cfg)
    flops = 6.0 * (active / n_units(cfg)) * tokens
    return flops / (world * PEAK_FLOPS)


def _stage_of(u: int, n_units_: int, pp: int) -> int:
    """Contiguous unit->stage assignment (stage s owns a block of units)."""
    return u * pp // n_units_


# ------------------------------------------------------------- extractors
def _train_dag(cfg: ModelConfig, shape: ShapeConfig, plan: PlanAxes,
               default_name: str, algorithm: str, max_units: int | None,
               link_bw: float, port_base: int, name: str | None,
               arrival: float, opt_ratio: float) -> JobDAG:
    """Shared training-step emitter (backward runs top unit first).

    Per unit ``u`` (stage ``s(u)``), in DAG order:
      * MoE unit: combine-grad all-to-all ``a2a_c{u}`` over the EP groups
        *before* the unit's backward (the backward of combine is a
        dispatch),
      * compute ``bwd{u}`` (deps: the previous unit's backward gate),
      * MoE unit: dispatch-grad all-to-all ``a2a_d{u}`` after it,
      * TP > 1: activation-grad all-reduce ``tpar{u}`` over the stage's TP
        groups (merged rounds — SPMD lockstep), gating the next unit,
      * stage boundary: activation-grad p2p ``act{u}`` to the stage below,
      * gradient sync consumed by ``opt{u}`` (memory-bound update):
        dense/shared grads ``g{u}`` all-reduced over the stage's DP
        groups; expert grads ``ge{u}`` over the dp/ep replica groups —
        independent buckets unlocking the same optimizer shard.

    Dense configs are the degenerate case: no MoE units, so only the
    ``bwd``/``tpar``/``act``/``g``/``opt`` skeleton is emitted.

    ``max_units`` truncates the emitted unit count (a model slab) while
    keeping per-unit sizes those of the full model — benchmark DAGs stay
    tractable without distorting per-bucket arithmetic.
    """
    U_full = n_units(cfg)
    U = min(U_full, max_units) if max_units else U_full
    tokens = shape.global_batch * shape.seq_len
    bwd = unit_bwd_seconds(cfg, tokens, plan.world)

    # Split the unit's grads into expert vs dense(shared) buckets; both
    # are zero-expert for dense configs.  TP shards every bucket
    # ``tp``-ways; experts additionally shard over EP.
    D, F = cfg.d_model, cfg.expert_ff
    moe_layers = sum(1 for i in range(cfg.n_layers) if cfg.is_moe_layer(i))
    # With ep == 1 experts are DP-replicated like any other param, so they
    # stay in the dense bucket (and the expert bucket is empty).
    expert_params_unit = ((moe_layers * cfg.n_experts * 3 * D * F) / U_full
                          if plan.ep > 1 else 0.0)
    dense_grad_bytes = max(unit_grad_bytes(cfg) - 2.0 * expert_params_unit,
                           0.0) / plan.tp
    expert_grad_bytes = 2.0 * expert_params_unit / (plan.ep * plan.tp)
    g_xfer = dense_grad_bytes / link_bw
    ge_xfer = expert_grad_bytes / link_bw
    opt_load = (opt_ratio * (g_xfer + ge_xfer)
                + (dense_grad_bytes + expert_grad_bytes) * 6 / HBM_BW)
    # Routed-token payload per rank for one unit's all-to-all, and the
    # activation(-grad) buffer of this rank's batch shard (bf16).
    a2a_xfer = (2.0 * (tokens / plan.dp) * D * cfg.experts_per_token
                / plan.tp / link_bw)
    act_xfer = 2.0 * (tokens / plan.dp) * D / plan.tp / link_bw

    job = JobDAG(name=name or default_name, arrival=arrival)
    gate: str | None = None          # what the next (lower) unit waits on
    for u in reversed(range(U)):
        s = _stage_of(u, U, plan.pp)
        moe_unit = plan.ep > 1 and cfg.is_moe_layer(
            (u + 1) * (cfg.n_layers // U_full) - 1)
        bwd_deps = [gate] if gate else []
        if moe_unit:
            a2a_c = lower_grouped("all_to_all", plan.ep_groups(s, port_base),
                                  a2a_xfer, algorithm)
            last = add_lowered(job, f"a2a_c{u}", a2a_c, deps=bwd_deps)
            bwd_deps = [last] if last else bwd_deps
        job.add_task(f"bwd{u}", load=bwd,
                     machine=plan.rank(s, 0, 0, port_base), deps=bwd_deps)
        gate = f"bwd{u}"
        if moe_unit:
            a2a_d = lower_grouped("all_to_all", plan.ep_groups(s, port_base),
                                  a2a_xfer, algorithm)
            last = add_lowered(job, f"a2a_d{u}", a2a_d, deps=[gate])
            gate = last or gate
        if plan.tp > 1:
            tpar = lower_grouped("all_reduce", plan.tp_groups(s, port_base),
                                 act_xfer, algorithm)
            last = add_lowered(job, f"tpar{u}", tpar, deps=[gate])
            gate = last or gate
        if u > 0:
            s_next = _stage_of(u - 1, U, plan.pp)
            if s_next != s:
                flows = [(plan.rank(s, d, t, port_base),
                          plan.rank(s_next, d, t, port_base), act_xfer)
                         for d in range(plan.dp) for t in range(plan.tp)]
                job.add_metaflow(f"act{u}", flows=flows, deps=[gate])
                gate = f"act{u}"
        opt_deps: list[str] = []
        if plan.dp > 1 and g_xfer > 0:
            g = lower_grouped("all_reduce", plan.dp_groups(s, port_base),
                              g_xfer, algorithm)
            last = add_lowered(job, f"g{u}", g, deps=[f"bwd{u}"])
            if last:
                opt_deps.append(last)
        if moe_unit and plan.dp // plan.ep > 1 and ge_xfer > 0:
            ge = lower_grouped("all_reduce",
                               plan.ep_replica_groups(s, port_base),
                               ge_xfer, algorithm)
            last = add_lowered(job, f"ge{u}", ge, deps=[f"bwd{u}"])
            if last:
                opt_deps.append(last)
        job.add_task(f"opt{u}", load=opt_load,
                     machine=plan.rank(s, 0, 0, port_base),
                     deps=opt_deps or [f"bwd{u}"])
    job.validate()
    return job


def dense_train_dag(cfg: ModelConfig, shape: ShapeConfig, plan: PlanAxes,
                    *, algorithm: str = "ring", max_units: int | None = None,
                    link_bw: float = LINK_BW, port_base: int = 0,
                    name: str | None = None, arrival: float = 0.0,
                    opt_ratio: float = 0.15) -> JobDAG:
    """One training step of a dense model under ``plan`` (see
    ``_train_dag`` for the emitted structure)."""
    return _train_dag(cfg, shape, plan,
                      f"{cfg.name}-{shape.name}-"
                      f"dp{plan.dp}tp{plan.tp}pp{plan.pp}",
                      algorithm, max_units, link_bw, port_base, name,
                      arrival, opt_ratio)


def moe_train_dag(cfg: ModelConfig, shape: ShapeConfig, plan: PlanAxes,
                  *, algorithm: str = "ring", max_units: int | None = None,
                  link_bw: float = LINK_BW, port_base: int = 0,
                  name: str | None = None, arrival: float = 0.0,
                  opt_ratio: float = 0.15) -> JobDAG:
    """One training step of an MoE model with expert parallelism: the
    dense skeleton plus per-MoE-unit all-to-alls and the split
    dense/expert gradient buckets (see ``_train_dag``)."""
    if not cfg.is_moe:
        raise ValueError(f"{cfg.name} is not an MoE config")
    return _train_dag(cfg, shape, plan,
                      f"{cfg.name}-{shape.name}-dp{plan.dp}ep{plan.ep}",
                      algorithm, max_units, link_bw, port_base, name,
                      arrival, opt_ratio)


def pipeline_serve_dag(cfg: ModelConfig, plan: PlanAxes, *,
                       n_microbatches: int = 4, tokens_per_mb: float = 2048,
                       link_bw: float = LINK_BW, port_base: int = 0,
                       name: str | None = None,
                       arrival: float = 0.0) -> JobDAG:
    """One pipelined prefill request: the GPipe (stage x microbatch) grid.

    Compute ``c{s}m{m}`` (stage s, microbatch m) depends on the stage's
    previous microbatch (the stage is busy) and on the activation p2p
    metaflow ``x{s}m{m}`` from stage s-1 (one flow per TP rank pair; DP in
    serving means independent replicas, so use ``dp=1`` per request).
    Intra-stage TP all-reduces are folded into the compute load — they ride
    the stage-internal mesh, not the inter-stage fabric this DAG contends
    for.
    """
    if plan.pp < 1:
        raise ValueError("pipeline_serve_dag needs pp >= 1")
    active = active_param_count(cfg)
    # Forward-only: ~2 flops/param/token, stage share, TP split.
    stage_load = (2.0 * (active / plan.pp) * tokens_per_mb
                  / (plan.tp * PEAK_FLOPS))
    act_xfer = 2.0 * tokens_per_mb * cfg.d_model / plan.tp / link_bw

    job = JobDAG(name=name or f"{cfg.name}-serve-pp{plan.pp}",
                 arrival=arrival)
    for m in range(n_microbatches):
        for s in range(plan.pp):
            deps: list[str] = []
            if m > 0:
                deps.append(f"c{s}m{m - 1}")
            if s > 0:
                flows = [(plan.rank(s - 1, d, t, port_base),
                          plan.rank(s, d, t, port_base), act_xfer)
                         for d in range(plan.dp) for t in range(plan.tp)]
                job.add_metaflow(f"x{s}m{m}", flows=flows,
                                 deps=[f"c{s - 1}m{m}"])
                deps.append(f"x{s}m{m}")
            job.add_task(f"c{s}m{m}", load=stage_load,
                         machine=plan.rank(s, 0, 0, port_base), deps=deps)
    job.validate()
    return job


# ------------------------------------------------- expert-parallel stage
#: NVIDIA H800 SXM, as DeepSeek-V3 was trained on (arXiv:2412.19437
#: §3.1): the H100 SXM's dense peaks, FLOP/s, and one 400 Gb/s
#: InfiniBand NIC, bytes/s.
H800_BF16_FLOPS = 989.5e12
H800_FP8_FLOPS = 1979e12
H800_NIC_BW = 50e9
#: Share of the dense peak a training GEMM reaches (assumed).
GEMM_EFFICIENCY = 0.5


def mla_flops_per_token(cfg: ModelConfig, seq_len: int) -> float:
    """Forward FLOPs of one token through an MLA block: the low-rank
    query and key/value projections, the output projection, and causal
    attention over a ``seq_len`` sequence (the mean token sees
    ``(seq_len + 1) / 2`` keys)."""
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    proj = (d * cfg.q_lora_rank + cfg.q_lora_rank * h * qk
            + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + h * cfg.v_head_dim * d)
    keys = (seq_len + 1) / 2
    return 2.0 * (proj + h * (qk + cfg.v_head_dim) * keys)


def expert_flops_per_token(cfg: ModelConfig) -> float:
    """Forward FLOPs of one token through one expert's gated FFN."""
    return 2.0 * 3 * cfg.d_model * cfg.expert_ff


def ep_stage_dag(cfg: ModelConfig, rng: np.random.Generator, *,
                 moe_layers: int, tokens_per_rank: int, ep: int,
                 gpus_per_node: int, bias_sigma: float,
                 sample: int) -> JobDAG:
    """One EP group's share of a pipeline stage: ``moe_layers`` MoE
    layers forward, then backward, for one microbatch of
    ``tokens_per_rank`` tokens (one sequence) on each of ``ep`` ranks.

    Each layer is routed once (``routing.route``; the backward reuses
    the forward's routing).  Rank ``r`` is port ``r``, on node
    ``r // gpus_per_node``, and each all-to-all is a rail all-to-all
    (``lowering.rail_all_to_all``): every leg is a one-flow metaflow
    gated only by its producer, so a node's experts wait on their own
    incoming legs and not on the whole exchange.  Forward layer ``l``,
    rank ``r``, node ``n``:

      * ``f{l}/A{r}``: MLA, the shared experts and the router on rank
        ``r``; needs ``f{l-1}/E{node(r)}`` and the legs ``f{l-1}/C*>{r}``
        (the stage's input is there at the start);
      * ``f{l}/D{r}>{n}``: dispatch, ``r``'s tokens for node ``n`` in
        FP8 (``routing.dispatch_bytes``); needs ``f{l}/A{r}``;
      * ``f{l}/E{n}``: the node's routed experts, as long as its busiest
        rank; needs the legs ``f{l}/D*>{n}`` and the node's ``A`` tasks;
      * ``f{l}/C{n}>{r}``: combine, the same tokens back in BF16; needs
        ``f{l}/E{n}``.

    ``turn{r}`` (no load) is rank ``r``'s forward end, where its
    backward starts.  The backward ``b{l}/...`` has the same four kinds
    with layers in reverse: ``D`` carries the combine's gradient from
    rank to experts, ``C`` the dispatch's gradient back, both BF16; its
    ``D`` and ``E`` wait on ``b{l+1}/A`` (or ``turn``) where the forward
    waits on ``A``, and its compute is twice the forward's.

    Compute loads are FLOPs at the config's widths over an H800's
    dense peak times ``GEMM_EFFICIENCY``: BF16 for MLA, the shared
    experts and the router, FP8 for the routed experts.  Sizes are in
    the time one MB takes on a NIC (port capacity 1.0 moves one MB per
    unit).  ``job.meta["route_stats"]`` keeps each layer's
    ``RouteStats``."""
    mb = 1e6
    unit_s = mb / H800_NIC_BW
    n_nodes = ep // gpus_per_node
    d_bytes, c_bytes = dispatch_bytes(cfg), 2.0 * cfg.d_model
    dense = (mla_flops_per_token(cfg, tokens_per_rank)
             + cfg.n_shared_experts * expert_flops_per_token(cfg)
             + 2.0 * cfg.d_model * cfg.n_experts)
    a_load = (tokens_per_rank * dense
              / (H800_BF16_FLOPS * GEMM_EFFICIENCY) / unit_s)
    pair_load = (expert_flops_per_token(cfg)
                 / (H800_FP8_FLOPS * GEMM_EFFICIENCY) / unit_s)
    routes = [route(cfg, ep, gpus_per_node, tokens_per_rank, rng,
                    bias_sigma=bias_sigma, sample=sample)
              for _ in range(moe_layers)]
    node_ranks = [range(n * gpus_per_node, (n + 1) * gpus_per_node)
                  for n in range(n_nodes)]

    job = JobDAG(name=f"{cfg.name}-ep{ep}-stage")
    job.meta["route_stats"] = tuple(rt.stats for rt in routes)
    senders = [f"f0/A{r}" for r in range(ep)]
    for r in range(ep):
        job.add_task(senders[r], load=a_load, machine=r)
    # The exchanges in the order they run: forward by layer, backward in
    # reverse; each one's receivers are the next one's senders.
    order = ([("f", k) for k in range(moe_layers)]
             + [("b", k) for k in reversed(range(moe_layers))])
    for phase, k in order:
        p, rt = f"{phase}{k}", routes[k]
        grow, d_size = (1.0, d_bytes) if phase == "f" else (2.0, c_bytes)
        legs = rail_all_to_all(rt.node_tokens, gpus_per_node)
        into: list[list[str]] = [[] for _ in range(n_nodes)]
        for r, n, (src, dst, tok) in legs:
            into[n].append(f"{p}/D{r}>{n}")
            job.add_metaflow(into[n][-1], [(src, dst, tok * d_size / mb)],
                             deps=[senders[r]])
        for n in range(n_nodes):
            busiest = max(rt.pairs[r] for r in node_ranks[n])
            job.add_task(f"{p}/E{n}", load=grow * busiest * pair_load,
                         machine=n * gpus_per_node,
                         deps=into[n] + [senders[r] for r in node_ranks[n]])
        back: list[list[str]] = [[] for _ in range(ep)]
        for r, n, (src, dst, tok) in legs:
            back[r].append(f"{p}/C{n}>{r}")
            job.add_metaflow(back[r][-1], [(dst, src, tok * c_bytes / mb)],
                             deps=[f"{p}/E{n}"])
        if phase == "b":
            receivers, load = [f"b{k}/A{r}" for r in range(ep)], 2 * a_load
        elif k + 1 < moe_layers:
            receivers, load = [f"f{k + 1}/A{r}" for r in range(ep)], a_load
        else:
            receivers, load = [f"turn{r}" for r in range(ep)], 0.0
        for r in range(ep):
            job.add_task(receivers[r], load=load, machine=r,
                         deps=[f"{p}/E{r // gpus_per_node}"] + back[r])
        senders = receivers
    job.validate()
    return job
